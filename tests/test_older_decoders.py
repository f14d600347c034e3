"""The older decoders trace the programs they did before the gated
short-convolution layers, the kernels' narrow heads and ``norm_eps``:
the seeded weights' bytes and the traced loss-and-gradient program's
text, hashed. The dense, windowed-sparse, latent and looped decoders on
the einsum path are pinned in ``tests/test_looped_decoder.py`` and
``tests/test_sparse_attention.py``, whose pins hold with the new
settings at their defaults (the same configuration); these are the
ones no test pinned: learned sparse attention, and the fused kernels'
path, where the decoder answers for a TPU."""

import hashlib
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import transformer as T

F = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")

#: sha256 (16 hex digits) of older decoders' seeded weights and traced
#: loss-and-gradient programs as they were before the conv layers, the
#: narrow heads and ``norm_eps``: learned sparse attention at
#: ``tests/test_sparse_attention.py``'s toy sizes on the einsum path,
#: and on the fused kernels (the decoder answering for a TPU, 128-tiles
#: over 256 positions) a dense decoder of two heads of 128, a grouped
#: one with windowed layers, QK-norm and sigmoid-routed experts, and
#: learned sparse attention over heads of 128. The programs of the three
#: with experts are taken anew since their sorted rows are walked in
#: chunks of the rows the held experts expect, not of the tokens, and
#: the learned-sparse one on the kernels again since they unpack a
#: selection tile once for the query heads that share it
PINNED = {
    "learned_sparse": (dict(
        vocab_size=48, d_model=32, n_heads=4, n_kv_heads=2, d_head=8,
        n_layers=2, d_ff=0, max_seq=64, dtype=jnp.float32,
        positions="layout", rope_layout=(1,), rope_theta=1e7, n_experts=8,
        experts_per_token=2, d_expert=16, experts_held=(0, 4),
        tie_embeddings=False, router_input="normed",
        expert_activation="silu", qk_norm=True, sparse_topk=8,
        index_heads=4, index_dim=8, index_rope=4, index_chunk=32,
        remat=True), (1, 65), "d1614ed2211cd3d7", "de6c4a387d583d6f"),
    "dense_kernels": (dict(
        vocab_size=64, d_model=256, n_heads=2, n_layers=2, d_ff=64,
        max_seq=256, remat=True), (1, 257),
        "2d9ff0ba897081be", "6a36cd7822bdc339"),
    "window_kernels": (dict(
        vocab_size=64, d_model=64, n_heads=4, n_kv_heads=2, d_head=128,
        n_layers=2, d_ff=0, max_seq=256, remat=True, positions="layout",
        rope_layout=(0, 1), window=128, window_layout=(0, 1), n_experts=8,
        experts_per_token=2, d_expert=16, experts_held=(2, 4),
        tie_embeddings=False, qk_norm=True, mlp="gated", n_dense_layers=1,
        router_scoring="sigmoid", router_input="normed",
        expert_activation="silu"), (1, 257),
        "565b63542507d0e6", "71c6f477df1574d5"),
    "sparse_kernels": (dict(
        vocab_size=48, d_model=64, n_heads=4, n_kv_heads=2, d_head=128,
        n_layers=2, d_ff=0, max_seq=256, dtype=jnp.float32,
        positions="layout", rope_layout=(1,), rope_theta=1e7, n_experts=8,
        experts_per_token=2, d_expert=16, experts_held=(0, 4),
        tie_embeddings=False, router_input="normed",
        expert_activation="silu", qk_norm=True, sparse_topk=40,
        index_heads=4, index_dim=8, index_rope=4, index_chunk=64,
        remat=True), (1, 257), "3489d1c5737f0def", "d5a1b54ff1d7664b"),
}


def digest(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


def traced(cfg, params, shape) -> str:
    tokens = jnp.zeros(shape, jnp.int32)
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda p: T.lm_loss(p, tokens, cfg, use_constraints=False)))(params))
    return digest(re.sub(r"0x[0-9a-f]+", "0x", text).encode())


@pytest.mark.parametrize("name", list(PINNED))
def test_an_older_decoder_is_what_it_was(name, monkeypatch):
    """The older decoders with the new settings spelled out at their
    defaults (``norm_eps`` 1e-6, no conv layers, ``expert_chunk``, which
    nothing reads) draw the weights and
    trace the program they did, instruction for instruction: learned
    sparse attention on the einsum path, and three decoders on the fused
    kernels, whose heads of 128 still come side by side."""
    settings, shape, weights, program = PINNED[name]
    if shape[1] == 257:  # on the kernels
        monkeypatch.setattr(T, "FUSED_ATTENTION_MIN_SEQ", 256)
        monkeypatch.setattr(F, "BLOCKS", (128,))
        monkeypatch.setattr(T, "_on_tpu", lambda: True)
        monkeypatch.setattr(T, "_partitioned_by_xla", lambda: False)
    cfg = T.TransformerConfig(**{**settings, "norm_eps": 1e-6,
                                 "layer_types": (), "conv_taps": 3,
                                 "expert_chunk": 1})
    params = T.init(jax.random.PRNGKey(7), cfg)
    assert digest(b"".join(np.asarray(leaf).tobytes()
                           for leaf in jax.tree.leaves(params))) == weights
    assert traced(cfg, params, shape) == program
