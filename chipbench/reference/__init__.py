"""Plain references the benchmark compares the program with. Nothing
here imports horovod_tpu's optimizer, mesh or collective code."""
