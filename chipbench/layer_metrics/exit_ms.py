"""exit_ms: per step, the device time under the scope ``hvd.model/exit``
(a looped decoder's exits: each pass's gate on its normed state, then
the exit distribution, its entropy and the expected loss; set in
horovod_tpu/models/transformer.py), forward, recompute and backward
together; mean over the cell's devices. The final norm, classifier and
cross-entropy of every exit are ``head_ms``. None on a program without
the scope's table. Program span."""

from chipbench import step_split


def read(trace, host, cell):
    return step_split.ms(trace, ["hvd.model/exit"], by="part")
