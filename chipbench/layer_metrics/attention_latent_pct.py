"""attention_latent_pct: of the attention calls the decoder's default
path traced in the step, the share over latent (MLA) heads (counters
``attention_latent_calls`` over ``attention_calls``, noted in
horovod_tpu/models/transformer.py while the step is traced; a share,
because ``jax.checkpoint`` traces a block once or several times). None
on a program that notes no such counter. Program counter."""

from chipbench import step_split


def read(trace, host, cell):
    calls = step_split.counter(trace, "attention_calls")
    latent = step_split.counter(trace, "attention_latent_calls")
    return 100.0 * latent / calls if calls and latent is not None else None
