"""grad_exchange_calls: collectives the gradient exchange issues per
step (counter ``collectives``, noted in horovod_tpu/opt/ while the step is
traced). Program counter."""

from chipbench import step_split


def read(trace, host, cell):
    return step_split.counter(trace, "collectives")
