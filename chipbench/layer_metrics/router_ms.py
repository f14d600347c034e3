"""router_ms: per step, the device time under the scope
``hvd.model/router`` (a sparse-expert block's router: the float32 scores
from the block's input, the top k, their weights; set in
horovod_tpu/models/transformer.py), forward, recompute and backward together;
mean over the cell's devices. Program span."""

from chipbench import step_split


def read(trace, host, cell):
    return step_split.ms(trace, ["hvd.model/router"], by="part")
