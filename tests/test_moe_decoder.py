"""The sparse-expert decoder (models/transformer.py composed per layer,
parallel/moe.py) against the plain float32 reference the benchmark
checks it with on the chip (chipbench/reference/smallthinker.py), at a
toy size on the CPU: global-NoPE and window-rotary layers, grouped-query
heads, a router on the block's input, ReGLU experts of which a share is
held, an untied head over a slice of the vocabulary."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chipbench.reference import smallthinker as reference
from horovod_tpu.models import transformer as T
from horovod_tpu.parallel import moe
from horovod_tpu.utils import scopes

LAYOUT = (0, 1)
ARCH = {"sliding_window_size": 8, "sliding_window_layout": LAYOUT,
        "rope_layout": LAYOUT, "rope_theta": 1.5e6,
        "moe_num_active_primary_experts": 2}


def toy(held=None, **kw):
    return T.TransformerConfig(
        vocab_size=48, d_model=32, n_heads=4, n_layers=2, d_ff=0, max_seq=64,
        dtype=jnp.float32, n_kv_heads=2, d_head=8, positions="layout",
        rope_layout=LAYOUT, rope_theta=1.5e6, window=8, window_layout=LAYOUT,
        n_experts=8, experts_per_token=2, d_expert=16, experts_held=held,
        tie_embeddings=False, **kw)


def share_of(params, first, count):
    """The parameters a chip holding experts ``first ..`` would have."""
    blocks = [{**b, "experts": jax.tree.map(
        lambda x: x[first:first + count], b["experts"])}
        for b in params["blocks"]]
    return {**params, "blocks": blocks}


@pytest.fixture(scope="module")
def seeded():
    params = jax.jit(lambda key: T.init(key, toy()))(jax.random.PRNGKey(0))
    # routers far from their 0.02 initial spread: the choice must matter
    params["blocks"] = [{**b, "router": 20.0 * b["router"]}
                        for b in params["blocks"]]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 25), 0, 48)
    return params, tokens


@pytest.mark.parametrize("held,remat", [(None, False), ((4, 4), True)],
                         ids=["all-experts", "a-share-remat"])
def test_decoder_matches_the_reference(seeded, held, remat, monkeypatch):
    """(a) Loss and every leaf's gradient of a global-NoPE and a
    window-rotary layer, with all the experts and with the share 4..7,
    against the reference given the same share; the reference's
    attention in three blocks of queries."""
    monkeypatch.setattr(reference, "QUERY_BLOCK", 8)
    params, tokens = seeded
    cfg = toy(held, remat=remat)
    if held:
        params = share_of(params, *held)
    arch = {**ARCH, "first_held": held[0] if held else 0}

    (loss, routing), grads = jax.jit(jax.value_and_grad(
        lambda p: T.lm_loss(p, tokens, cfg, use_constraints=False,
                            return_routing=True), has_aux=True))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens[0], arch)[0]))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert len(routing) == 2 and routing[0].shape == (24, 2)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-3,
            atol=2e-3 * float(jnp.abs(ref).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


def layer_inputs(t=96, d=32, f=16, experts=64, seed=0):
    rng = np.random.RandomState(seed)
    u = jnp.asarray(rng.randn(t, d), jnp.float32)
    router = jnp.asarray(rng.randn(d, experts), jnp.float32)
    params = {"gate": jnp.asarray(0.3 * rng.randn(experts, d, f), jnp.float32),
              "up": jnp.asarray(0.3 * rng.randn(experts, d, f), jnp.float32),
              "down": jnp.asarray(0.3 * rng.randn(experts, f, d), jnp.float32)}
    return u, router, params


def test_the_shares_add_up_to_the_uncut_layer():
    """(b) 64 experts cut four ways, six chosen a token: the four shares'
    expert-layer outputs add up to the uncut reference's layer output
    (nothing here is computed by every chip alike: no shared expert).
    Each share routes over all 64 and normalises over all six chosen."""
    u, router, params = layer_inputs()
    weights, _, _ = reference.router_weights(u, router, 6)
    whole = jax.jit(reference.experts, static_argnums=3)(u, params, weights, 0)
    chosen, w = moe.route(u @ router, 6)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    total = 0.0
    for first in (0, 16, 32, 48):
        held = jax.tree.map(lambda x: x[first:first + 16], params)
        part = moe.expert_layer(u, chosen, w, held, (first, 16))
        assert float(jnp.abs(part).max()) > 0
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=5e-5)


@pytest.mark.parametrize("count", [8, 1], ids=["8-held", "1-held"])
def test_no_row_is_dropped_under_skew(count):
    """(c) Router scores biased so that expert 3 is every token's first
    choice (a capacity-factor layer would drop most of its rows): the
    output equals the reference's, row for row, and its gradient too;
    with one expert held the buffers' bound is one row a token."""
    u, router, params = layer_inputs(t=64, experts=8)
    held = jax.tree.map(lambda x: x[:count] if count == 8 else x[3:4],
                        params)
    first = 0 if count == 8 else 3
    bias = jnp.zeros((8,)).at[3].set(50.0)

    def ours(u, held):
        chosen, w = moe.route(u @ router + bias, 2)
        return moe.expert_layer(u, chosen, w, held, (first, count)), chosen

    def ref(u, held):
        r = u @ router + bias
        kth = jnp.sort(r, axis=-1)[:, -2][:, None]
        e = jnp.where(r >= kth, jnp.exp(r - r.max(-1, keepdims=True)), 0.0)
        return reference.experts(u, held, e / e.sum(-1, keepdims=True),
                                 first)

    got, chosen = jax.jit(ours)(u, held)
    assert bool(jnp.all(chosen[:, 0] == 3))       # 64 rows on one expert
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.jit(ref)(u, held)), atol=2e-5)
    assert float(jnp.abs(got).sum(-1).min()) > 0  # no token's row is lost
    g1 = jax.jit(jax.grad(lambda u, p: jnp.sum(ours(u, p)[0] ** 2),
                          (0, 1)))(u, held)
    g2 = jax.jit(jax.grad(lambda u, p: jnp.sum(ref(u, p) ** 2),
                          (0, 1)))(u, held)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3,
                                   rtol=2e-3)


@pytest.mark.parametrize("shape,rows", [
    ((16384, 4, 8, 32), 20480), ((16384, 8, 8, 128), 10240),
    ((16384, 6, 8, 64), 15360), ((16384, 6, 16, 128), 15360),
    ((16384, 6, 8, 8), 16384 * 6), ((24, 2, 4, 8), 32),
    ((64, 4, 8, 9), 64 * 4), ((100, 1, 3, 3), 100)],
    ids=["lfm2", "keye", "smallthinker", "kanana", "all-held",
         "a-tile-up", "past-the-bound", "the-exchange"])
def test_a_chunk_holds_the_expected_rows_and_a_quarter(shape, rows):
    """(h) A chunk is the rows the held experts expect (``n * k * held /
    experts``) times 1.25, rounded up to a whole number of 16, and at
    most the bound ``n * min(k, held)``: 20,480, 10,240, 15,360 and
    15,360 at the four expert cells' layers; the bound, one chunk, where
    every expert is held, where the expected rows reach it, and behind
    the exchange (a buffer of rows of one choice each)."""
    assert moe.chunk_rows(*shape) == rows


#: 8 experts, 4 a token, experts 4..7 held, 32 tokens: 64 rows expected,
#: chunks of 80 over the bound of 128, the second padded to 160. Each
#: case: how many of a token's four choices are held, token by token,
#: and the chunks the rows routed reach.
LIVE_CHUNKS = {
    "none": ([0] * 32, 0),           # no token chooses a held expert
    "one": ([1] * 32, 1),            # one held expert a token
    "a-group-split": ([3] * 32, 2),  # 96 rows: chunk 0 ends in a group
    "at-a-chunk-end": ([4] * 16 + [1] * 16, 1),  # 80 rows: chunk 0, full
    "all": ([4] * 32, 2),            # every token chooses all four held
}


@pytest.mark.parametrize("case", LIVE_CHUNKS)
def test_chunks_follow_the_rows_routed(case, monkeypatch):
    """(g) The sorted side is walked in chunks of the rows the held
    experts expect and a quarter, and a chunk no routed row reaches does
    not run: with none, one, two (an expert's rows split over the
    boundary), exactly one full chunk and all the rows routed (the last
    chunk padded past the bound), the layer equals the reference row for
    row and gradient for gradient, and the same layer evaluated as one
    chunk of all the rows."""
    counts, live_chunks = LIVE_CHUNKS[case]
    t, first, count, k, experts = 32, 4, 4, 4, 8
    u, _, params = layer_inputs(t=t, experts=experts, seed=3)
    held = jax.tree.map(lambda x: x[first:first + count], params)
    rng = np.random.RandomState(4)
    chosen = jnp.asarray(np.stack([rng.permutation(np.concatenate([
        first + rng.permutation(count)[:h], rng.permutation(first)[:k - h]]))
        for h in counts]), jnp.int32)
    w = jnp.asarray(rng.rand(t, k), jnp.float32)
    w = w / w.sum(-1, keepdims=True)
    chunk, bound = moe.chunk_rows(t, k, count, experts), t * k
    assert (chunk, -(-bound // chunk) * chunk) == (80, 160)
    sizes = np.bincount(np.asarray(chosen).ravel(), minlength=8)[first:]
    live = int(sizes.sum())
    assert -(-live // chunk) == live_chunks
    if case == "a-group-split":   # the boundary lies inside a group
        assert chunk not in np.cumsum(sizes)
    if case == "at-a-chunk-end":
        assert live == chunk

    def ours(u, held, w):
        return moe.expert_layer(u, chosen, w, held, (first, count),
                                experts=experts)

    def ref(u, held, w):
        dense = jnp.zeros((t, 8)).at[jnp.arange(t)[:, None], chosen].set(w)
        return reference.experts(u, held, dense, first)

    def value_and_grads(f):
        cot = jnp.cos(jnp.arange(t * 32, dtype=jnp.float32)).reshape(t, 32)
        return jax.jit(lambda *a: (f(*a), jax.grad(
            lambda *a: jnp.sum(f(*a) * cot), (0, 1, 2))(*a)))(u, held, w)

    got, want = value_and_grads(ours), value_and_grads(ref)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)
    if case == "none":
        assert all(not np.asarray(a).any() for a in jax.tree.leaves(got))
    monkeypatch.setattr(moe, "chunk_rows",
                        lambda n, k, held, experts: n * min(k, held))
    for a, b in zip(jax.tree.leaves(got),
                    jax.tree.leaves(value_and_grads(ours))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6,
                                   rtol=2e-6)


def test_a_cotangent_at_a_token_without_a_held_expert_reaches_nothing():
    """A token none of whose pairs a held expert takes comes out zero;
    whatever cotangent it is handed there reaches no gradient (not of
    the rows, the routing weights or the experts), masked or not: its
    rows of the cotangent are read only at sorted positions past the
    rows routed, which are in no expert's group and no token's sum."""
    t, k, count = 32, 4, 4
    u, _, params = layer_inputs(t=t, experts=count, seed=5)
    rng = np.random.RandomState(6)
    key = rng.randint(0, 2 * count, (t, k)).clip(0, count)
    key[: t // 4] = count                              # none held
    w = jnp.asarray(rng.rand(t, k), jnp.float32)
    out, back = jax.vjp(lambda u, w, p: moe._experts_on_tokens(
        u, jnp.asarray(key.T.reshape(-1), jnp.int32), w, p), u, w, params)
    g = jnp.asarray(rng.randn(*out.shape), jnp.float32)
    held = (key < count).any(1)[:, None]
    assert 0 < int(held.sum()) < t
    assert not np.asarray(out)[~held[:, 0]].any()
    for a, b in zip(jax.tree.leaves(back(g)),
                    jax.tree.leaves(back(jnp.where(held, g, 0.0)))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_token_without_a_held_expert_is_exactly_zero():
    """A quarter of the tokens choose only experts held elsewhere: their
    rows of the layer, of ``d_u`` and of the routing weights' gradient
    are exactly zero, and every other row equals the reference's."""
    t, k, first, count = 32, 4, 4, 4
    u, _, params = layer_inputs(t=t, experts=8, seed=7)
    held = jax.tree.map(lambda x: x[first:first + count], params)
    rng = np.random.RandomState(8)
    chosen = np.stack([rng.permutation(8)[:k] for _ in range(t)])
    chosen[: t // 4] = rng.permutation(4)              # experts 0..3 alone
    chosen = jnp.asarray(chosen, jnp.int32)
    w = jnp.asarray(rng.rand(t, k), jnp.float32)
    none = np.arange(t) < t // 4

    def ours(u, w):
        return moe.expert_layer(u, chosen, w, held, (first, count))

    def ref(u, w):
        dense = jnp.zeros((t, 8)).at[jnp.arange(t)[:, None], chosen].set(w)
        return reference.experts(u, held, dense, first)

    cot = jnp.asarray(rng.randn(t, 32), jnp.float32)
    got, (d_u, d_w) = jax.jit(lambda u, w: (ours(u, w), jax.grad(
        lambda u, w: jnp.sum(ours(u, w) * cot), (0, 1))(u, w)))(u, w)
    want, (r_u, r_w) = jax.jit(lambda u, w: (ref(u, w), jax.grad(
        lambda u, w: jnp.sum(ref(u, w) * cot), (0, 1))(u, w)))(u, w)
    for a in (got, d_u, d_w):
        assert not np.asarray(a)[none].any()
        assert np.asarray(a)[~none].any()
    for a, b in ((got, want), (d_u, r_u), (d_w, r_w)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)


#: the parent's count (PR 35's tree) of the equations in the gradient of
#: `expert_layer` and everything nested in it, at t=256, d=64, f=32, 8 of
#: 64 experts held, k=6: what tracing and lowering a layer cost grows
#: with it, once a layer, direction and program (PERF.md §6, PR 37)
PARENT_EQUATIONS = 602


def test_the_layer_traces_no_more_than_its_parent():
    """The set-up guard: the expert layer's gradient jaxpr, nested bodies
    (the chunk loops, the custom rules, jnp's own jits) included, counts
    no more equations than the parent's at the same shape."""
    from jax.extend import core

    def count(jaxpr):
        n = len(jaxpr.eqns)
        for eqn in jaxpr.eqns:
            for v in eqn.params.values():
                for j in v if isinstance(v, (list, tuple)) else [v]:
                    if isinstance(j, core.ClosedJaxpr):
                        n += count(j.jaxpr)
                    elif isinstance(j, core.Jaxpr):
                        n += count(j)
        return n

    u, router, params = layer_inputs(t=256, d=64, f=32, experts=64)
    held = jax.tree.map(lambda x: x[:8], params)
    chosen, w = moe.route(u @ router, 6)
    grad = jax.make_jaxpr(jax.grad(lambda u, w, p: jnp.sum(moe.expert_layer(
        u, chosen, w, p, (0, 8), experts=64) ** 2), (0, 1, 2)))(u, w, held)
    assert count(grad.jaxpr) <= PARENT_EQUATIONS


def test_expert_parallel_exchange_equals_the_one_chip_layer():
    """(f) The 'ep' path on a 4-device mesh (16 experts a chip, a
    quarter of the tokens each, two all-to-alls sized for the bound)
    equals the one-chip layer over all 64, output and gradients."""
    from horovod_tpu.parallel import create_mesh

    u, router, params = layer_inputs()
    mesh = create_mesh({"ep": 4}, devices=jax.devices()[:4])

    def per_chip(u, router, params):
        chosen, w = moe.route(u @ router, 6)
        return moe.expert_layer(u, chosen, w, params, axis_name="ep")

    def one_chip(u, router, params):
        chosen, w = moe.route(u @ router, 6)
        return moe.expert_layer(u, chosen, w, params)

    exchanged = jax.jit(jax.shard_map(
        per_chip, mesh=mesh, in_specs=(P("ep"), P(), P("ep")),
        out_specs=P("ep"), check_vma=False))
    np.testing.assert_allclose(np.asarray(exchanged(u, router, params)),
                               np.asarray(one_chip(u, router, params)),
                               atol=5e-5)
    g1, g2 = (jax.grad(lambda *a: jnp.sum(f(*a) ** 2), (0, 1, 2))(
        u, router, params) for f in (exchanged, one_chip))
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3,
                                   rtol=2e-3)
    with pytest.raises(ValueError, match="axis_name"):
        moe.expert_layer(u, *moe.route(u @ router, 6), params, (0, 64),
                         axis_name="ep")


def test_route_normalises_over_the_chosen():
    logits = jnp.asarray([[0.0, 3.0, 1.0, 2.0, -1.0]])
    chosen, w = moe.route(logits.astype(jnp.bfloat16), 2)
    assert chosen.tolist() == [[1, 3]] and w.dtype == jnp.float32
    e = np.exp([3.0, 2.0])
    np.testing.assert_allclose(np.asarray(w)[0], e / e.sum(), rtol=1e-6)


def test_step_scopes_and_counters(seeded):
    """A traced step notes what was held, routed for and windowed; the
    router and the expert layer carry scopes of their own."""
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.parallel import create_mesh, data_parallel_step, dp

    params, tokens = seeded
    cfg = toy((0, 4), remat=True)
    params = share_of(params, 0, 4)
    opt = hvd.DistributedOptimizer(optax.sgd(0.1), axis_name="hvd")

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(T.lm_loss)(
            params, tokens, cfg, use_constraints=False)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = data_parallel_step(
        step, mesh=create_mesh({"hvd": 1}, devices=jax.devices()[:1]),
        donate_argnums=())
    text = step.lower(params, opt.init(params), tokens).as_text(
        debug_info=True)
    counters = dp.step_counters(step)
    assert counters["moe_layers"] == 2
    assert (counters["experts_held"], counters["experts_total"],
            counters["experts_per_token"]) == (4, 8, 2)
    assert counters["moe_buffer_rows"] == 24 * 2    # 24 tokens a chip, k=2
    # 24 rows expected (2 of 8 a token, 4 held), and a quarter: 30 → 32
    assert (counters["moe_chunks"], counters["moe_chunk_rows"]) == (2, 32)
    assert 0 < counters["attention_window_calls"] < counters["attention_calls"]
    assert counters["attention_window_calls"] * 2 == \
        counters["attention_calls"]
    assert scopes.ROUTER in text and scopes.MOE in text
    assert scopes.part_of(f"jit(f)/{scopes.STEP}/{scopes.MOE}/x") == scopes.MOE


def test_fused_path_agrees_with_the_einsum_path(monkeypatch):
    """The sparse decoder as a chip traces it (the fused kernels in
    interpret mode here: two query heads over one key/value head of 128,
    a global layer without positions and a windowed one with rotary
    positions applied to the heads side by side) against the same
    decoder on the einsum path: loss and every gradient."""
    import importlib

    from jax.sharding import Mesh

    F = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(T, "FUSED_ATTENTION_MIN_SEQ", 256)
    monkeypatch.setattr(F, "BLOCKS", (128,))
    cfg = T.TransformerConfig(
        vocab_size=64, d_model=64, n_heads=2, n_layers=2, d_ff=0, max_seq=256,
        dtype=jnp.float32, remat=True, n_kv_heads=1, d_head=128,
        positions="layout", rope_layout=LAYOUT, rope_theta=1.5e6, window=128,
        window_layout=LAYOUT, n_experts=4, experts_per_token=2, d_expert=16,
        tie_embeddings=False)
    params = jax.jit(lambda k: T.init(k, cfg))(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 257), 0, 64)
    one_chip = Mesh(np.array(jax.devices()[:1]), ("hvd",))

    def loss_and_grads(on_tpu):
        monkeypatch.setattr(T, "_on_tpu", lambda: on_tpu)
        return jax.jit(jax.shard_map(
            lambda p, t: jax.value_and_grad(T.lm_loss)(
                p, t, cfg, use_constraints=False),
            mesh=one_chip, in_specs=P(), out_specs=P(),
            check_vma=False))(params, tokens)

    assert T.fused_attention_blocks(256, 128, False) is None  # CPU: einsum
    (loss, grads), (want, want_grads) = loss_and_grads(True), \
        loss_and_grads(False)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-6, rtol=2e-4)
