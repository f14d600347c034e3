"""mlp_ms: per step, the device time under the scope ``hvd.model/mlp``
(a block's dense feed-forward with its norm or norms; in a looped
decoder every application of every block; set in
horovod_tpu/models/transformer.py), forward, recompute and backward
together; mean over the cell's devices. None on a program without the
scope's table. Program span."""

from chipbench import step_split


def read(trace, host, cell):
    return step_split.ms(trace, ["hvd.model/mlp"], by="part")
