"""latent_ms: per step, the device time under the scope
``hvd.model/latent`` (a latent-attention block's down-projection to the
key/value latent and the shared rotary key, the latent's norm, the
rotary turn of that key and the up-projection to every head's keys and
values; set in horovod_tpu/models/transformer.py), forward, recompute
and backward together; mean over the cell's devices. Program span."""

from chipbench import step_split


def read(trace, host, cell):
    return step_split.ms(trace, ["hvd.model/latent"], by="part")
