"""grad_pack_ms: per step, the device time under
``hvd.grad_exchange/pack`` and ``hvd.grad_exchange/unpack``: the copies into
and out of the flat buffer, without the collective; mean over the cell's
devices. Program span."""

from chipbench import step_split


def read(trace, host, cell):
    return step_split.ms(trace, ["hvd.grad_exchange/pack",
                                 "hvd.grad_exchange/unpack"], by="part")
