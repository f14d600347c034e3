"""moe_buffer_rows: rows the expert layer's buffers are sized for, per
layer: tokens a step and chip times the most experts one token can have
among those held (counter ``moe_buffer_rows``, noted in
horovod_tpu/models/transformer.py while the step is traced). The bound a
dropless layer must hold; the rows really routed are fewer. Program
counter."""

from chipbench import step_split


def read(trace, host, cell):
    return step_split.counter(trace, "moe_buffer_rows")
