"""The decoder's gated short-convolution layers (``layer_types``: LFM2-MoE's
conv layers beside grouped-query attention with QK-norm, a leading dense
layer, experts under a sigmoid bias-corrected router, a tied head,
``norm_eps``) against the plain reference of ``chipbench/reference/
lfm2.py`` at a toy size on the CPU; the filter's causality; the share of
the experts one chip holds; what a checkpointed conv layer keeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax.sharding import PartitionSpec as P

from chipbench.reference import kanana2
from chipbench.reference import lfm2 as reference
from horovod_tpu.models import transformer as T
from horovod_tpu.parallel import moe

KINDS = ("conv", "full_attention", "conv", "conv", "conv")
ARCH = {"rope_theta": 1e6, "num_experts_per_tok": 4,
        "routed_scaling_factor": 1, "first_held": 0}


def toy(**kw):
    """The cut's five layers at d 64: four heads of 16 over two, a dense
    layer, then 8 of 32 experts held, 4 a token (as the cell's)."""
    return T.TransformerConfig(**{**dict(
        vocab_size=64, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        n_layers=5, d_ff=96, max_seq=256, dtype=jnp.float32,
        positions="layout", rope_layout=(1,), rope_theta=1e6, n_experts=32,
        experts_per_token=4, d_expert=24, experts_held=(0, 8),
        tie_embeddings=True, mlp="gated", n_dense_layers=1,
        router_scoring="sigmoid", router_input="normed",
        expert_activation="silu", qk_norm=True, norm_eps=1e-5,
        layer_types=KINDS), **kw})


def seeded_params(cfg, key=0):
    """Seeded weights of `T.init`'s shapes, drawn by NumPy (no program to
    compile): the matrices at `T.init`'s spread of 0.02, the routers at
    four times it, the embedding at 1 (as the cell), every norm's scale
    away from 1, the conv taps and the routers' biases at a spread of
    their own, so that where each norm sits, which tap reads which
    position and what the bias does all matter."""
    rng = np.random.RandomState(key)

    def draw(path, leaf):
        name = jax.tree_util.keystr(path)
        noise = rng.randn(*leaf.shape).astype(np.float32)
        if "scale" in name:
            return jnp.asarray(1 + 0.3 * noise)
        spread = (0.5 if "conv" in name else 0.3 if "router_bias" in name
                  else 0.08 if "router" in name else 1.0 if "embed" in name
                  else 0.02)
        return jnp.asarray(spread * noise)

    return jax.tree_util.tree_map_with_path(draw, jax.eval_shape(
        lambda: T.init(jax.random.PRNGKey(0), cfg)))


def close(got, want, rtol=1e-4):
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        w = np.asarray(w)
        np.testing.assert_allclose(
            np.asarray(g), w, rtol=rtol, atol=rtol * np.abs(w).max() + 1e-9,
            err_msg=jax.tree_util.keystr(path))


def test_decoder_matches_the_reference():
    """(a) Loss and every leaf's gradient, the tied embedding's two uses
    summed, against the reference routed as the program routed; each
    block checkpointed, as the cell runs it."""
    cfg = toy(remat=True)
    params = seeded_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 17), 0, 64)
    (loss, routing), grads = jax.jit(jax.value_and_grad(
        lambda p: T.lm_loss(p, tokens, cfg, use_constraints=False,
                            return_routing=True), has_aux=True))(params)
    masks = [jnp.zeros((16, 32), bool).at[jnp.arange(16)[:, None],
                                          chosen].set(True)
             for chosen in routing]
    (want, own), want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens[0], ARCH, masks), has_aux=True))(
            params)
    assert len(routing) == 4 and "head" not in params
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    for mask, (chosen, short) in zip(masks, own):  # no near tie here
        assert (np.asarray(mask) == np.asarray(chosen)).all() and short == 0
    close(grads, want_grads)


def test_the_filter_is_causal_and_three_taps_long():
    """(b) A conv layer's output at position t reads positions t - 2 to
    t of its input and nothing later; positions 0 and 1 see zeros before
    the sequence, as the reference's explicit sum of shifted products."""
    cfg = toy()
    blk = seeded_params(cfg)["blocks"][2]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 64))
    mixer = jax.jit(lambda x: T._short_conv(x, blk, cfg) - x)
    reads = jnp.abs(jax.jacrev(lambda x: mixer(x)[0].sum(-1))(x)).sum((1, 3))
    t, earlier = np.indices((12, 12))
    assert bool(jnp.all((reads > 0) == ((earlier <= t) & (earlier >= t - 2))))
    want = reference.short_conv(reference.rmsnorm(x[0], blk["ln1"]["scale"]),
                                blk)
    np.testing.assert_allclose(np.asarray(mixer(x)[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_a_checkpointed_conv_layer_keeps_its_input_alone():
    """(c) Under the decoder's checkpoint policy the conv operator names
    nothing it computes: what its recomputation starts from is its
    input and its weights, and `_kept_bytes` counts nothing for it."""
    cfg = toy(dtype=jnp.bfloat16)
    blk = seeded_params(cfg)["blocks"][0]
    x = jnp.ones((2, 16, 64), jnp.bfloat16)
    kept = saved_residuals(jax.checkpoint(
        lambda x, blk: T._short_conv(x, blk, cfg),
        policy=T._KEEP_KERNEL_RESIDUALS), x, blk)
    assert kept and all("argument" in why for _, why in kept)
    assert T._kept_bytes((2, 16), cfg, False) == 0


def test_the_held_shares_add_up_under_this_router():
    """(d) 32 experts cut four ways, eight held a chip, four chosen a
    token by the largest ``sigmoid(u W_r) + b``, weights the chosen
    sigmoids normalised, SiLU experts, the sorted rows in chunks of the
    rows the held experts expect (the router's width given) or in one
    chunk of the bound (not given): the four shares' outputs add up to
    the uncut reference layer's."""
    rng = np.random.RandomState(0)
    t, d, f, experts = 64, 32, 16, 32
    u = jnp.asarray(rng.randn(t, d), jnp.float32)
    router = jnp.asarray(rng.randn(d, experts), jnp.float32)
    bias = jnp.asarray(0.3 * rng.randn(experts), jnp.float32)
    params = {w: jnp.asarray(0.3 * rng.randn(experts, *shape), jnp.float32)
              for w, shape in (("gate", (d, f)), ("up", (d, f)),
                               ("down", (f, d)))}

    @jax.jit
    def both(u, params):
        weights, _, _ = kanana2.router_weights(u, router, bias, 4, 1.0)
        chosen, w = moe.route(u @ router, 4, scoring="sigmoid", bias=bias)
        shares = [moe.expert_layer(
            u, chosen, w, jax.tree.map(lambda x: x[first:first + 8], params),
            (first, 8), activation="silu",
            experts=experts if first % 16 else None)
            for first in range(0, experts, 8)]
        return sum(shares), kanana2.experts(u, params, weights, 0)

    total, whole = both(u, params)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=5e-5)


def test_the_conv_settings_and_what_they_refuse():
    """(e) A conv layer's leaves and specs; a list of kinds that is not
    one a layer, or that names another kind, or conv layers beside
    another attention, is refused."""
    cfg = toy()
    params = jax.eval_shape(lambda: T.init(jax.random.PRNGKey(7), cfg))
    conv, attention = params["blocks"][0], params["blocks"][1]
    assert set(conv) == {"ln1", "ln2", "in_proj", "conv", "out_proj", "mlp"}
    assert (conv["in_proj"].shape, conv["conv"].shape,
            conv["out_proj"].shape) == ((64, 192), (3, 64), (64, 64))
    assert {"wq", "wk", "wv", "wo", "q_norm", "k_norm"} <= set(attention)
    assert jax.tree.structure(T.param_specs(cfg)) == jax.tree.structure(
        jax.tree.map(lambda _: P(), params))
    for settings in (dict(layer_types=KINDS[:4]),
                     dict(layer_types=("conv",) * 4 + ("mamba",)),
                     dict(kv_latent=8, d_rope=4),
                     dict(sparse_topk=8, index_heads=4, index_dim=8)):
        with pytest.raises(ValueError, match="layer_types"):
            jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0),
                                          toy(**settings)))
