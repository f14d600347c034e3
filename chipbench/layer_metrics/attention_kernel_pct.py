"""attention_kernel_pct: of the attention calls the decoder's default
path traced in the step, the share it routed to the fused Pallas kernels
(``hvd_flash_fwd``, ``hvd_flash_bwd_dq``, ``hvd_flash_bwd_dkv``) rather
than to the einsum path (counters ``attention_kernel_calls`` over
``attention_calls``, noted in horovod_tpu/models/transformer.py while the
step is traced; a share, because ``jax.checkpoint`` retraces a block).
Program counter."""

from chipbench import step_split


def read(trace, host, cell):
    calls = step_split.counter(trace, "attention_calls")
    kernel = step_split.counter(trace, "attention_kernel_calls")
    return 100.0 * kernel / calls if calls else None
