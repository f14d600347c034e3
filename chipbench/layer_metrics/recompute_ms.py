"""recompute_ms: per step, the device time of the instructions under
``rematted_computation``: the forward work ``jax.checkpoint`` does again in
the backward pass (phase ``recompute`` of horovod_tpu/utils/scopes.py); mean
over the cell's devices. Program span."""

from chipbench import step_split


def read(trace, host, cell):
    return step_split.ms(trace, ["recompute"])
