"""shared_expert_ms: per step, the device time under the scope
``hvd.model/shared_expert`` (the gated MLP every token takes beside its
routed experts; set in horovod_tpu/models/transformer.py), forward,
recompute and backward together; mean over the cell's devices. Program
span."""

from chipbench import step_split


def read(trace, host, cell):
    return step_split.ms(trace, ["hvd.model/shared_expert"], by="part")
