"""The latent-attention sparse-expert decoder (models/transformer.py's one
block with its further settings, parallel/moe.py's sigmoid scoring and
SiLU experts, the ``hvd_mla_*`` kernels) against the plain float32
reference the benchmark checks it with on the chip
(chipbench/reference/kanana2.py), at a toy size on the CPU: latent heads
with a shared rotary key, a leading dense gated layer, then expert
layers whose router reads the normed input, scores by sigmoid and
chooses by ``s + b`` with a nonzero ``b``, beside a shared expert; an
untied head over a slice of the vocabulary."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from chipbench.reference import kanana2 as reference
from horovod_tpu.models import transformer as T
from horovod_tpu.parallel import data_parallel_step, dp, moe
from horovod_tpu.utils import scopes

ARCH = {"qk_nope_head_dim": 8, "kv_lora_rank": 12, "rope_theta": 1e6,
        "num_experts_per_tok": 2, "routed_scaling_factor": 2.448}


def toy(held=None, **kw):
    kw = {"d_head": 8, "d_rope": 4, "kv_latent": 12, "n_layers": 3,
          "max_seq": 64, "vocab_size": 48, "d_model": 32, "n_heads": 4,
          "dtype": jnp.float32, **kw}
    return T.TransformerConfig(
        d_ff=40, positions="layout", rope_theta=1e6, n_experts=8,
        experts_per_token=2, d_expert=16, experts_held=held,
        tie_embeddings=False, mlp="gated", n_dense_layers=1,
        n_shared_experts=2, router_scoring="sigmoid", router_input="normed",
        routed_scale=2.448, expert_activation="silu", **kw)


def seeded_params(cfg, key=0):
    """Seeded weights with routers and attention far from their 0.02
    spread, so that the choice and the keys matter, and a bias that
    changes the choice."""
    params = jax.jit(lambda k: T.init(k, cfg))(jax.random.PRNGKey(key))
    blocks = []
    for i, b in enumerate(params["blocks"]):
        b = {**b, "wq": 4 * b["wq"], "wkva": 4 * b["wkva"]}
        if "router" in b:
            b["router"] = 20.0 * b["router"]
        if "router_bias" in b:  # the sigmoid scoring's
            b["router_bias"] = 0.2 * jax.random.normal(
                jax.random.PRNGKey(10 + i), b["router_bias"].shape)
        blocks.append(b)
    return {**params, "blocks": blocks}


def share_of(params, first, count):
    """The parameters a chip holding experts ``first ..`` would have."""
    return {**params, "blocks": [
        {**b, "experts": jax.tree.map(lambda x: x[first:first + count],
                                      b["experts"])} if "experts" in b else b
        for b in params["blocks"]]}


@pytest.fixture(scope="module")
def seeded():
    return (seeded_params(toy()),
            jax.random.randint(jax.random.PRNGKey(1), (1, 25), 0, 48))


@pytest.mark.parametrize("held,remat", [(None, False), ((4, 4), True)],
                         ids=["all-experts", "a-share-remat"])
def test_decoder_matches_the_reference(seeded, held, remat, monkeypatch):
    """(a) Loss and every leaf's gradient of the dense layer and two
    expert layers, with all the experts and with the share 4..7, against
    the reference given the same share; the reference's attention in
    three blocks of queries. No gradient reaches the bias, on either
    side."""
    monkeypatch.setattr(reference, "QUERY_BLOCK", 8)
    params, tokens = seeded
    cfg = toy(held, remat=remat)
    if held:
        params = share_of(params, *held)
    arch = {**ARCH, "first_held": held[0] if held else 0}

    (loss, routing), grads = jax.jit(jax.value_and_grad(
        lambda p: T.lm_loss(p, tokens, cfg, use_constraints=False,
                            return_routing=True), has_aux=True))(params)
    (want, ref_routing), want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens[0], arch), has_aux=True))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert len(routing) == 2 and routing[0].shape == (24, 2)
    for chosen, (own, _) in zip(routing, ref_routing):  # the same choice
        mask = jnp.zeros((24, 8), bool).at[
            jnp.arange(24)[:, None], chosen].set(True)
        assert bool(jnp.all(mask == own))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.asarray(got).any() and not np.asarray(ref).any()
            continue
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-3,
            atol=2e-3 * float(jnp.abs(ref).max()) + 1e-9, err_msg=name)


def layer_inputs(t=96, d=32, f=16, experts=16, seed=0):
    rng = np.random.RandomState(seed)
    u = jnp.asarray(rng.randn(t, d), jnp.float32)
    router = jnp.asarray(0.5 * rng.randn(d, experts), jnp.float32)
    bias = jnp.asarray(0.2 * rng.randn(experts), jnp.float32)

    def gated(*lead):
        return {"gate": jnp.asarray(0.3 * rng.randn(*lead, d, f), jnp.float32),
                "up": jnp.asarray(0.3 * rng.randn(*lead, d, f), jnp.float32),
                "down": jnp.asarray(0.3 * rng.randn(*lead, f, d),
                                    jnp.float32)}

    return u, router, bias, gated(experts), gated()


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """(b) 16 experts cut four ways, six chosen a token: the four shares'
    routed parts, plus the shared experts' part counted once (every chip
    computes it alike), add up to the uncut reference's layer output.
    Each share routes over all 16 and normalises over all six chosen."""
    u, router, bias, params, shared = layer_inputs()
    weights, _, _ = reference.router_weights(u, router, bias, 6, 2.448)
    whole = (jax.jit(reference.experts, static_argnums=3)(
        u, params, weights, 0) + reference.gated_mlp(u, shared))
    chosen, w = moe.route(u @ router, 6, scoring="sigmoid", bias=bias,
                          scale=2.448)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.448, rtol=1e-6)
    total = T._gated_mlp(u[None], shared, jnp.float32)[0]
    for first in (0, 4, 8, 12):
        held = jax.tree.map(lambda x: x[first:first + 4], params)
        part = moe.expert_layer(u, chosen, w, held, (first, 4),
                                activation="silu")
        assert float(jnp.abs(part).max()) > 0
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=5e-5)


def test_sigmoid_route_chooses_by_the_biased_score_and_weighs_by_the_plain():
    """(c) `route` with the sigmoid scoring against a brute-force choice:
    the k largest of ``s + b``, the weights ``scale * s / sum of s over
    the chosen``. A bias that changes the choice changes no weight of an
    expert both choices hold beyond the normalisation, and a bias that
    leaves the choice alone changes nothing at all; no gradient reaches
    the bias. The default scoring is the softmax it was."""
    rng = np.random.RandomState(3)
    logits = jnp.asarray(rng.randn(40, 12), jnp.float32)
    bias = jnp.asarray(0.3 * rng.randn(12), jnp.float32)
    chosen, w = moe.route(logits.astype(jnp.bfloat16).astype(jnp.float32), 3,
                          scoring="sigmoid", bias=bias, scale=2.0)
    s = 1 / (1 + np.exp(-np.asarray(
        logits.astype(jnp.bfloat16).astype(jnp.float32), np.float64)))
    for tok in range(40):
        want = np.argsort(-(s[tok] + np.asarray(bias)))[:3]
        assert sorted(chosen[tok].tolist()) == sorted(want.tolist())
        picked = s[tok][np.asarray(chosen[tok])]
        np.testing.assert_allclose(np.asarray(w[tok]),
                                   2.0 * picked / picked.sum(), rtol=1e-5)
    none = jnp.zeros((12,))
    plain, _ = moe.route(logits, 3, scoring="sigmoid", bias=none)
    assert bool(jnp.any(jnp.sort(plain, -1) != jnp.sort(chosen, -1)))
    # a bias that lifts every expert alike changes neither choice nor weight
    same, w_same = moe.route(logits, 3, scoring="sigmoid",
                             bias=jnp.full((12,), 0.7))
    _, w_plain = moe.route(logits, 3, scoring="sigmoid", bias=none)
    assert bool(jnp.all(same == plain))
    np.testing.assert_array_equal(np.asarray(w_same), np.asarray(w_plain))
    grad = jax.grad(lambda b: jnp.sum(moe.route(
        logits, 3, scoring="sigmoid", bias=b)[1] ** 2))(bias)
    assert not np.asarray(grad).any()
    soft, w_soft = moe.route(logits, 3)
    np.testing.assert_allclose(np.asarray(w_soft.sum(-1)), 1.0, rtol=1e-6)
    assert soft.tolist() == jax.lax.top_k(logits, 3)[1].tolist()
    with pytest.raises(ValueError, match="scoring"):
        moe.route(logits, 3, scoring="tanh")
    with pytest.raises(ValueError, match="bias"):
        moe.route(logits, 3, scoring="sigmoid")


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("router_input", ["normed", "block"])
def test_a_checkpointed_block_keeps_its_routers_choice(
        scoring, router_input, capsys):
    """The expert layer keeps what it sorted out of the forward pass's
    routing, so a checkpointed block keeps its router's choice, [tokens,
    k] int32 an expert layer, whether the router reads the expert
    layer's normed input or the block's own, under either scoring, and
    takes the weights at the kept choice (the gradients are those of the
    block without remat). `route` itself: a named choice changes neither
    the choice nor the weights."""
    cfg = dataclasses.replace(toy(remat=True), router_scoring=scoring,
                              router_input=router_input)
    params = seeded_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 25), 0, 48)

    def loss(p):
        return T.lm_loss(p, tokens, cfg, use_constraints=False)

    jax.ad_checkpoint.print_saved_residuals(loss, params)
    # what `route` hands the backward pass: the index its weights were
    # read at the named choice (the expert layer keeps what it sorted out
    # of the routing, `scopes.KEPT_ROUTING`, and reads the choice no
    # more), one [tokens, k] int32 a layer
    kept = [line.split()[0] for line in capsys.readouterr().out.splitlines()
            if "(route)" in line]
    assert kept == 2 * ["i32[48,2]"]
    assert T._kept_bytes((2, 24), cfg, False, True) == 2 * 24 * 2 * 4
    plain = jax.grad(lambda p: T.lm_loss(
        p, tokens, dataclasses.replace(cfg, remat=False),
        use_constraints=False))(params)
    for a, b in zip(jax.tree.leaves(jax.grad(loss)(params)),
                    jax.tree.leaves(plain)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    logits = jnp.asarray(np.random.RandomState(5).randn(30, 8), jnp.float32)
    kw = {"scoring": scoring, "bias": jnp.linspace(-0.2, 0.2, 8)}
    for got, want in zip(moe.route(logits, 2, **kw, name="a-name"),
                         moe.route(logits, 2, **kw)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)


def test_expert_parallel_exchange_with_the_sigmoid_scoring():
    """(f) The 'ep' path on a 4-device mesh (4 SiLU experts a chip, a
    quarter of the tokens each) under the sigmoid scoring equals the
    one-chip layer over all 16, output and gradients."""
    from horovod_tpu.parallel import create_mesh

    u, router, bias, params, _ = layer_inputs()
    mesh = create_mesh({"ep": 4}, devices=jax.devices()[:4])

    def layer(axis_name):
        def fn(u, router, params):
            chosen, w = moe.route(u @ router, 6, scoring="sigmoid",
                                  bias=bias, scale=2.448)
            return moe.expert_layer(u, chosen, w, params,
                                    axis_name=axis_name, activation="silu")
        return fn

    exchanged = jax.jit(jax.shard_map(
        layer("ep"), mesh=mesh, in_specs=(P("ep"), P(), P("ep")),
        out_specs=P("ep"), check_vma=False))
    one_chip = layer(None)
    np.testing.assert_allclose(np.asarray(exchanged(u, router, params)),
                               np.asarray(one_chip(u, router, params)),
                               atol=5e-5)
    g1, g2 = (jax.grad(lambda *a: jnp.sum(f(*a) ** 2), (0, 1, 2))(
        u, router, params) for f in (exchanged, one_chip))
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3,
                                   rtol=2e-3)


@pytest.fixture
def on_the_kernels(monkeypatch):
    """The decoder as a chip traces it: the latent kernels (interpret
    mode here) at 128-blocks from 256 positions on."""
    F = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(T, "FUSED_ATTENTION_MIN_SEQ", 256)
    monkeypatch.setattr(F, "BLOCKS", (128,))
    monkeypatch.setattr(T, "_on_tpu", lambda: True)
    return F


def wide(held=None, **kw):
    """A toy the kernels take: two heads of 128 + 16 over a latent of 32,
    the dense layer and one expert layer."""
    return toy(held, n_layers=2, d_head=128, d_rope=16, kv_latent=32, n_heads=2, d_model=64,
               max_seq=256, vocab_size=64, **kw)


def test_fused_path_agrees_with_the_reference(on_the_kernels, monkeypatch):
    """The latent decoder through the kernels (two tiles a side), remat
    on, as ``data_parallel_step`` runs it: loss and every gradient
    against the reference."""
    monkeypatch.setattr(reference, "QUERY_BLOCK", 64)
    cfg = wide(remat=True)
    params = seeded_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 257), 0, 64)
    one_chip = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    loss, grads = jax.jit(jax.shard_map(
        lambda p, t: jax.value_and_grad(T.lm_loss)(
            p, t, cfg, use_constraints=False),
        mesh=one_chip, in_specs=P(), out_specs=P(), check_vma=False))(
            params, tokens)
    arch = {**ARCH, "qk_nope_head_dim": 128, "kv_lora_rank": 32,
            "first_held": 0}
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens[0], arch)[0]))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-3,
            atol=2e-3 * float(jnp.abs(ref).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


def test_remat_keeps_what_the_latent_kernels_read(on_the_kernels):
    """(g) A checkpointed latent block keeps `latent_attention`'s seven
    residuals: the lowered step calls the forward kernel's entry once a
    layer; the counters say every call was a latent one, on the kernels
    and kept, count the dense layer and the shared experts, and
    ``remat_kept_mb`` is the bytes of the arrays the forward rule really
    hands on; the new parts carry scopes of their own."""
    F = on_the_kernels
    cfg = wide(held=(0, 4), remat=True)
    step = data_parallel_step(
        lambda p, t: (p, jax.grad(T.lm_loss)(p, t, cfg,
                                             use_constraints=False)),
        mesh=Mesh(jax.devices()[:1], ("hvd",)), batch_argnums=(1,),
        donate_argnums=())
    lowered = step.lower(
        jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), cfg)),
        jax.ShapeDtypeStruct((2, 257), jnp.int32))
    text = lowered.as_text(debug_info=True)
    assert text.count("call @_latent_fwd_lse") == cfg.n_layers
    assert text.count("call @_latent_bwd") == cfg.n_layers
    assert "call @_flash_fwd_lse" not in text
    for part in (scopes.LATENT, scopes.SHARED_EXPERT, scopes.ATTENTION,
                 scopes.MLP, scopes.ROUTER, scopes.MOE):
        assert part in text, part
    counters = dp.step_counters(step)
    calls = counters["attention_calls"]
    assert calls > 0 and all(counters[c] == calls for c in (
        "attention_latent_calls", "attention_kernel_calls",
        "attention_kept_calls"))
    assert "attention_window_calls" not in counters
    assert (counters["dense_layers"], counters["shared_experts"],
            counters["moe_layers"]) == (1, 1, 1)
    assert (counters["experts_held"], counters["experts_total"],
            counters["experts_per_token"]) == (4, 8, 2)
    assert counters["moe_buffer_rows"] == 2 * 256 * 2
    x = jax.ShapeDtypeStruct((2, 256, 2 * 128), cfg.dtype)
    residuals = jax.eval_shape(
        lambda q, qr, k, kr, v: F._latent_fwd(q, qr, k, kr, v, 128, 128,
                                              2)[1],
        x, jax.ShapeDtypeStruct((2, 2, 256, 16), cfg.dtype), x,
        jax.ShapeDtypeStruct((2, 256, 16), cfg.dtype), x)
    a_block = sum(r.size * r.dtype.itemsize for r in residuals)
    assert a_block == T._kept_bytes((2, 256), cfg)
    # and the expert layer's router, on the normed input, its choice
    choice = 2 * 256 * 2 * 4
    assert T._kept_bytes((2, 256), cfg, True, True) == a_block + choice
    # and what the expert layer sorted out of its routing
    indices = sum(a.size * a.dtype.itemsize for a in jax.eval_shape(
        lambda key, w: moe._indices(moe.chunk_rows(512, 2, 4, cfg.n_experts),
                                    1024, 4, key, w),
        jax.ShapeDtypeStruct((1024,), jnp.int32),
        jax.ShapeDtypeStruct((512, 2), jnp.float32)).values())
    assert T._kept_bytes((2, 256), cfg, True, True, True) == \
        a_block + choice + indices
    assert counters["remat_kept_mb"] == pytest.approx(
        (2 * a_block + choice + indices) / 1e6)


def test_the_first_decoders_are_settings_of_the_same_block():
    """The GPT-2-style and the SmallThinker-style configurations leave
    the further settings at their defaults and get the parameters they
    got: no latent, gated or shared leaf, no bias."""
    def init(cfg):  # shapes only
        return jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), cfg))

    dense = init(T.TransformerConfig(
        vocab_size=32, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_seq=8))
    assert set(dense["blocks"][0]) == {"ln1", "ln2", "wq", "wk", "wv", "wo",
                                       "w1", "w2"}
    sparse = init(T.TransformerConfig(
        vocab_size=32, d_model=16, n_heads=2, n_layers=1, d_ff=0, max_seq=8,
        n_experts=4, experts_per_token=2, d_expert=8, tie_embeddings=False))
    assert set(sparse["blocks"][0]) == {"ln1", "ln2", "wq", "wk", "wv", "wo",
                                        "router", "experts"}
    cfg = toy()
    latent = init(cfg)
    assert set(latent["blocks"][0]) == {"ln1", "ln2", "wq", "wkva", "ln_kv",
                                        "wkvb", "wo", "mlp"}
    assert set(latent["blocks"][1]) == {
        "ln1", "ln2", "wq", "wkva", "ln_kv", "wkvb", "wo", "router",
        "router_bias", "experts", "shared"}
    assert latent["blocks"][1]["shared"]["gate"].shape == (32, 2 * 16)
    assert jax.tree.structure(T.param_specs(cfg)) == jax.tree.structure(
        jax.tree.map(lambda _: P(), latent))
    with pytest.raises(ValueError, match="window"):
        jax.eval_shape(lambda p: T.apply(
            p, jnp.zeros((1, 8), jnp.int32),
            toy(window=4, window_layout=(1,)), use_constraints=False), latent)
