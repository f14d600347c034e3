"""backward_ms: per step, the device time of the instructions whose ``op_name``
carries ``transpose(`` outside a recomputation (phase ``backward`` of
horovod_tpu/utils/scopes.py); mean over the cell's devices. Program span."""

from chipbench import step_split


def read(trace, host, cell):
    return step_split.ms(trace, ["backward"])
