"""chipbench: horovod_tpu's on-chip benchmark. See chipbench/README.md."""
