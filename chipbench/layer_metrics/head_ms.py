"""head_ms: per step, the device time under the scope ``hvd.model/head``
(the final norm, the classifier and the cross-entropy; in a looped
decoder those of every pass's exit, the final norm being part of the
recurrence; set in horovod_tpu/models/transformer.py), forward,
recompute and backward together; mean over the cell's devices. None on a
program without the scope's table. Program span."""

from chipbench import step_split


def read(trace, host, cell):
    return step_split.ms(trace, ["hvd.model/head"], by="part")
