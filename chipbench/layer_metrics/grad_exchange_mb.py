"""grad_exchange_mb: megabytes (1e6 bytes) the gradient exchange hands to
collectives per step and per chip (counter ``collective_bytes``, noted in
horovod_tpu/opt/ while the step is traced). Program counter."""

from chipbench import step_split


def read(trace, host, cell):
    nbytes = step_split.counter(trace, "collective_bytes")
    return None if nbytes is None else nbytes / 1e6
