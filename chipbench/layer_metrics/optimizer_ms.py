"""optimizer_ms: per step, the device time under the scope
``hvd.optimizer`` (the inner optax update, set in horovod_tpu/opt/); mean over
the cell's devices. Program span."""

from chipbench import step_split


def read(trace, host, cell):
    return step_split.ms(trace, ["optimizer"])
