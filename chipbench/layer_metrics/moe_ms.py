"""moe_ms: per step, the device time under the scope ``hvd.model/moe``
(the expert layer of horovod_tpu/parallel/moe.py: norm, sort, gathers,
grouped matmuls, the weighted sum), forward, recompute and backward together;
mean over the cell's devices. Program span."""

from chipbench import step_split


def read(trace, host, cell):
    return step_split.ms(trace, ["hvd.model/moe"], by="part")
