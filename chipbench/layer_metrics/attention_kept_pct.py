"""attention_kept_pct: of the attention calls the decoder's default path
traced in the step, the share that ran in a checkpointed block whose
policy keeps what the fused backward kernels read (``q``, ``k``, ``v``,
``o``, ``lse``), so that ``hvd_flash_fwd`` runs once a layer and not a
second time in the recomputation (counters ``attention_kept_calls`` over
``attention_calls``, noted in horovod_tpu/models/transformer.py while the
step is traced; a share, because ``jax.checkpoint`` traces a block once
or several times). None on a program that notes no such counter. Program
counter."""

from chipbench import step_split


def read(trace, host, cell):
    calls = step_split.counter(trace, "attention_calls")
    kept = step_split.counter(trace, "attention_kept_calls")
    return 100.0 * kept / calls if calls and kept is not None else None
