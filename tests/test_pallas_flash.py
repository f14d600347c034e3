"""Pallas flash-attention kernel numerics vs the plain-XLA oracle
(SURVEY.md §5.7 pallas splash-attention; runs in interpret mode on the CPU
test mesh, compiled on a real TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.pallas.flash_attention import (
    _lax_stats,
    _reference_attention,
    attention_stats,
    block_sizes,
    flash_attention,
    latent_attention,
    scan_stats,
)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    B, s, d = 2, 256, 64
    mk = lambda: jnp.asarray(rng.randn(B, s, d), jnp.float32)  # noqa: E731
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(qkv, causal):
    q, k, v = qkv
    o = flash_attention(q, k, v, causal, 128, 128)
    ref = _reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=1e-4)


def test_flash_gradients_match_reference(qkv):
    q, k, v = qkv

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, True, 128, 128) ** 2).sum()

    def loss_ref(q, k, v):
        return (_reference_attention(q, k, v, True) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


@pytest.mark.parametrize("wrt", [0, 1, 2], ids=["dq", "dk", "dv"])
@pytest.mark.parametrize("block_q,block_k", [(128, 64), (64, 256)])
@pytest.mark.parametrize("causal", [True, False])
def test_backward_kernels_match_autodiff_of_reference(qkv, causal, block_q,
                                                      block_k, wrt):
    """``hvd_flash_bwd_dq`` / ``hvd_flash_bwd_dkv`` (interpret mode)
    against autodiff of the dense oracle, with two heads side by side as
    the decoder hands them over: blocks wider than tall and taller than
    wide, so tiles are skipped, cut by the diagonal and left whole."""
    B, s, d, heads = 2, 256, 32, 2
    q, k, v = (x.reshape(B, s, heads * d) for x in qkv)
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)

    def fold(x):    # [B, s, heads*d] -> the oracle's [B*heads, s, d]
        return x.reshape(B, s, heads, d).swapaxes(1, 2).reshape(-1, s, d)

    def loss_kernels(*x):
        return (flash_attention(*x, causal, block_q, block_k, heads)
                * w).sum()

    def loss_ref(*x):
        return (_reference_attention(*map(fold, x), causal)
                * fold(w)).sum()

    got = jax.grad(loss_kernels, argnums=wrt)(q, k, v)
    want = jax.grad(loss_ref, argnums=wrt)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("blocks", [2, 4])
@pytest.mark.parametrize("block,heads", [(128, 4), (256, 1), (128, 1),
                                         (256, 4)])
@pytest.mark.parametrize("causal", [True, False])
def test_grad_matches_reference_and_scan_stats_vjp(causal, block, heads,
                                                   blocks):
    """``jax.grad`` through `flash_attention` (all three kernels,
    interpret mode) against autodiff of the dense oracle and against the
    VJP it replaced, `scan_stats`', over square blocks as `block_sizes`
    gives them: a sequence of two and of four, heads side by side."""
    B, s, d = 1, block * blocks, 16
    rng = np.random.RandomState(block + blocks + heads)
    q, k, v, w = (jnp.asarray(rng.randn(B, s, heads * d), jnp.float32)
                  for _ in range(4))

    def fold(x):    # [B, s, heads*d] -> the oracles' [B*heads, s, d]
        return x.reshape(B, s, heads, d).swapaxes(1, 2).reshape(-1, s, d)

    def grads(attention):
        return jax.jit(jax.grad(lambda *x: (attention(*x) * w).sum(),
                                argnums=(0, 1, 2)))(q, k, v)

    def folded(attention):
        return lambda *x: attention(*map(fold, x)).reshape(
            B, heads, s, d).swapaxes(1, 2).reshape(B, s, heads * d)

    got = grads(lambda *x: flash_attention(*x, causal, block, block, heads))
    dense = grads(folded(lambda *x: _reference_attention(*x, causal)))
    scanned = grads(folded(lambda *x: scan_stats(*x, causal, 0, block)[0]))
    for name, g, a, b in zip(("dq", "dk", "dv"), got, dense, scanned):
        np.testing.assert_allclose(np.asarray(g), np.asarray(a), atol=5e-5,
                                   err_msg=f"{name} against the oracle")
        np.testing.assert_allclose(np.asarray(g), np.asarray(b), atol=5e-5,
                                   err_msg=f"{name} against scan_stats")


@pytest.mark.parametrize("s,head_dim,want", [
    (2048, 128, (1024, 1024)), (1536, 128, (512, 512)),
    (512, 128, (512, 512)), (768, 256, (768, 768)),   # one whole block
    (2050, 128, None),            # none of 1024, 512, 256 divides it
    (2048, 64, None), (2048, 192, None),   # a head that is no lane multiple
])
def test_block_sizes_takes_the_largest_square_that_tiles(s, head_dim, want):
    assert block_sizes(s, head_dim) == want


def _inside_shard_map(mesh, axes, ask):
    """``ask()`` as it answers inside a shard_map manual over ``axes``."""
    from jax.sharding import PartitionSpec as P

    seen = []

    def body(x):
        seen.append(ask())
        return x

    jax.eval_shape(jax.shard_map(
        body, mesh=mesh, in_specs=P(), out_specs=P(),
        axis_names=frozenset(axes), check_vma=False), jnp.zeros(()))
    return seen[0]


@pytest.mark.parametrize("on_tpu,devices,manual,s,head_dim,constraints,want", [
    (True, 1, None, 2048, 128, False, (1024, 1024)),   # the LM cell's check
    (True, 8, ("dp", "tp"), 2048, 128, False, (1024, 1024)),  # its step
    (True, 1, None, 512, 128, False, (512, 512)),      # the shortest taken
    (False, 1, None, 2048, 128, False, None),          # off the TPU
    (True, 1, None, 2048, 128, True, None),            # GSPMD constraints
    (True, 1, None, 256, 128, False, None),            # the kernels lose
    (True, 1, None, 2050, 128, False, None),           # nothing tiles it
    (True, 1, None, 2048, 64, False, None),            # half a lane a head
    (True, 8, None, 2048, 128, False, None),   # a bare jit may be GSPMD's
    (True, 8, ("dp",), 2048, 128, False, None),        # 'tp' left to XLA
], ids=["one-device", "all-axes-manual", "s512", "cpu", "use-constraints",
        "s256", "s2050", "head64", "several-devices-no-mesh",
        "one-axis-manual"])
def test_fused_attention_blocks_at_each_exit_of_the_rule(
        monkeypatch, on_tpu, devices, manual, s, head_dim, constraints, want):
    """``models/transformer.py`` takes the fused kernels exactly where
    its rule says: a TPU, nothing for XLA to partition (every mesh axis
    manual, or one device), no GSPMD constraints, a sequence of 512 or
    more that the blocks tile, heads a lane multiple wide."""
    from jax.sharding import Mesh

    from horovod_tpu.models import transformer as T

    monkeypatch.setattr(T, "_on_tpu", lambda: on_tpu)
    monkeypatch.setattr(jax, "device_count", lambda: devices)

    def ask():
        return T.fused_attention_blocks(s, head_dim, constraints)

    if manual is None:
        assert ask() == want
    else:
        mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "tp"))
        assert _inside_shard_map(mesh, manual, ask) == want


def test_decoder_on_the_kernels_agrees_with_causal_attention(monkeypatch):
    """A toy decoder whose 256 positions take two 128-blocks, as
    ``data_parallel_step`` runs it (per chip, inside a shard_map): routed
    to the kernels, loss and gradients are `causal_attention`'s, and the
    two counters say where each call went. A caller's own ``attn_fn`` is
    the caller's: the rule stays out of it."""
    import importlib

    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.models import transformer as T
    from horovod_tpu.utils import scopes

    F = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(T, "FUSED_ATTENTION_MIN_SEQ", 256)
    monkeypatch.setattr(F, "BLOCKS", (128,))
    cfg = T.TransformerConfig(vocab_size=64, d_model=256, n_heads=2,
                              n_layers=2, d_ff=64, max_seq=256, remat=True,
                              dtype=jnp.float32)
    params = T.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 257), 0, 64)
    one_chip = Mesh(np.array(jax.devices()[:1]), ("hvd",))

    def loss_and_grads(on_tpu, run=lambda f, *x: jax.jit(f)(*x), **kw):
        monkeypatch.setattr(T, "_on_tpu", lambda: on_tpu)
        record = scopes.StepRecord()
        with scopes.recording(record):
            out = run(jax.shard_map(
                lambda p, t: jax.value_and_grad(T.lm_loss)(
                    p, t, cfg, use_constraints=False, **kw),
                mesh=one_chip, in_specs=P(), out_specs=P(),
                check_vma=False), params, tokens)
        calls = record.counters.get("attention_calls", 0)
        return out, calls and record.counters["attention_kernel_calls"] / calls

    (loss, grads), share = loss_and_grads(True)
    (want, want_grads), want_share = loss_and_grads(False)
    assert (share, want_share) == (1.0, 0.0)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-6, rtol=1e-4)
    _, share = loss_and_grads(True, jax.eval_shape,
                              attn_fn=T.causal_attention)
    assert share == 0


def test_checkpoint_that_keeps_the_residuals_runs_the_forward_once():
    """A decoder-block-shaped function (norm, three projections, the
    kernels, the output projection, a residual) under ``jax.checkpoint``
    with the policy that saves what `flash_attention`'s forward rule
    names: its gradients are, bit for bit, those under a bare checkpoint
    and under none (the kept ``o`` and ``lse`` are the arrays a second
    run would make), and its lowered text calls the forward kernel's
    entry once where the bare checkpoint's calls it twice."""
    from horovod_tpu.utils import scopes

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 256, 256), jnp.float32)
    w = {n: jnp.asarray(rng.randn(256, 256) * 0.05, jnp.float32)
         for n in "qkvo"}

    def block(x, w):
        h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        q, k, v = (h @ w[n] for n in "qkv")
        return x + flash_attention(q, k, v, True, 128, 128, 2) @ w["o"]

    policy = jax.checkpoint_policies.save_only_these_names(
        *scopes.KEPT_BY_REMAT)
    grads, forwards = {}, {}
    for name, fn in (("none", block), ("bare", jax.checkpoint(block)),
                     ("kept", jax.checkpoint(block, policy=policy))):
        grad = jax.jit(jax.grad(lambda x, w, fn=fn: jnp.sum(fn(x, w) ** 2),
                                argnums=(0, 1)))
        forwards[name] = grad.lower(x, w).as_text().count(
            "call @_flash_fwd_lse")
        grads[name] = jax.tree.leaves(grad(x, w))
    assert forwards == {"none": 1, "bare": 2, "kept": 1}
    for name in ("bare", "kept"):
        for got, want in zip(grads[name], grads["none"]):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_attention_stats_contract(qkv):
    """(o, m, l) stats: o normalized, exp-renormalization reconstructs the
    unnormalized accumulator (the ring-combination contract)."""
    q, k, v = qkv
    o, m, l = attention_stats(q, k, v, False, 128, 128)
    o2, m2, l2 = _lax_stats(q, k, v, False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l2), rtol=1e-5)


def test_attention_stats_differentiable(qkv):
    """Cotangents flow through o, m and l (ring combine uses all three)."""
    q, k, v = qkv

    def loss(q, k, v):
        o, m, l = attention_stats(q, k, v, True, 128, 128)
        return (o ** 2).sum() + (m * 0.1).sum() + (l * 0.01).sum()

    def loss_ref(q, k, v):
        o, m, l = _lax_stats(q, k, v, True)
        return (o ** 2).sum() + (m * 0.1).sum() + (l * 0.01).sum()

    g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_flash_bf16():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 128, 64), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, 128, 64), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 128, 64), jnp.bfloat16)
    o = flash_attention(q, k, v, True, 128, 128)
    ref = _reference_attention(q, k, v, True)
    assert o.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


def test_strict_causal_offset_kernel_matches_oracle(qkv):
    """causal_offset=1 (strict: row > col) — the mask striped ring
    attention's j>i rounds select on TPU. Kernel (interpret mode here,
    compiled on a real chip) vs the XLA stats fallback vs the dense
    oracle with the diagonal excluded. Row 0 is fully masked: the stats
    contract there is m = NEG_INF (o and l are unconstrained garbage,
    exactly annihilated in the ring combine by beta = exp(NEG_INF - m)
    = 0 — asserted in test_parallel.py's striped equivalence)."""
    from horovod_tpu.ops.pallas.flash_attention import NEG_INF

    q, k, v = qkv
    o_k, m_k, l_k = attention_stats(q, k, v, True, 128, 128, 1)
    o_x, m_x, l_x = _lax_stats(q, k, v, True, 1)
    np.testing.assert_allclose(np.asarray(o_k)[:, 1:], np.asarray(o_x)[:, 1:],
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_x), atol=1e-4)
    np.testing.assert_allclose(np.asarray(l_k)[:, 1:],
                               np.asarray(l_x)[:, 1:], rtol=1e-5, atol=1e-5)
    # empty first row: annihilation marker on both paths
    assert np.all(np.asarray(m_k)[:, 0] == NEG_INF)
    assert np.all(np.asarray(m_x)[:, 0] == NEG_INF)
    # against the dense strict oracle
    ref = _reference_attention(q, k, v, True, 1)
    np.testing.assert_allclose(np.asarray(o_k)[:, 1:], np.asarray(ref)[:, 1:],
                               atol=1e-4)


def test_scan_stats_matches_lax_stats(qkv):
    """Blockwise scan_stats == the dense oracle for both mask variants,
    forward and gradients (multiple block widths)."""
    from horovod_tpu.ops.pallas.flash_attention import scan_stats

    q, k, v = qkv
    for offset in (0, 1):
        for bk in (64, 128, 256):
            o_s, m_s, l_s = scan_stats(q, k, v, True, offset, bk)
            o_d, m_d, l_d = _lax_stats(q, k, v, True, offset)
            np.testing.assert_allclose(np.asarray(o_s)[:, offset:],
                                       np.asarray(o_d)[:, offset:],
                                       atol=1e-4)
            np.testing.assert_allclose(np.asarray(m_s), np.asarray(m_d),
                                       atol=1e-4)
            np.testing.assert_allclose(np.asarray(l_s)[:, offset:],
                                       np.asarray(l_d)[:, offset:],
                                       rtol=1e-4, atol=1e-4)

    # non-divisible length: block shrinks to a divisor, never the dense path
    qs, ks, vs = q[:, :96], k[:, :96], v[:, :96]
    o_s, m_s, l_s = scan_stats(qs, ks, vs, True, 0, 64)
    o_d, m_d, l_d = _lax_stats(qs, ks, vs, True, 0)
    np.testing.assert_allclose(np.asarray(o_s), np.asarray(o_d), atol=1e-4)
    np.testing.assert_allclose(np.asarray(l_s), np.asarray(l_d),
                               rtol=1e-4, atol=1e-4)

    def loss_s(q, k, v):
        o, m, l = scan_stats(q, k, v, True, 0, 64)
        return (o.astype(jnp.float32) ** 2).sum() + (m * l).sum()

    def loss_d(q, k, v):
        o, m, l = _lax_stats(q, k, v, True, 0)
        return (o.astype(jnp.float32) ** 2).sum() + (m * l).sum()

    gs = jax.grad(loss_s, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_flash_backward_is_blockwise_in_memory():
    """The blockwise VJP's compiled temp memory shrinks with the block
    size — the [B, sq, sk] score matrix is gone from the backward
    executable (it was the dense VJP's dominant buffer). Held on
    `attention_stats`, whose VJP `scan_stats` still is (ring attention).
    Needs a length where the score matrix dominates the scan
    bookkeeping."""
    rng = np.random.RandomState(7)
    B, s, d = 1, 1024, 32
    q = jnp.asarray(rng.randn(B, s, d), jnp.float32)

    def temp_mb(bk):
        f = jax.jit(jax.grad(
            lambda q, k, v: (attention_stats(q, k, v, True, 256, bk)[0]
                             .astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2)))
        c = f.lower(q, q, q).compile()
        return c.memory_analysis().temp_size_in_bytes / 2**20

    small, full = temp_mb(64), temp_mb(1024)
    assert small < full * 0.6, (small, full)


def test_fused_backward_stays_under_the_dense_oracle_in_memory():
    """`flash_attention`'s backward kernels keep the compiled temp
    memory far under the dense oracle's at every block size: the score
    matrix is in neither direction's executable."""
    rng = np.random.RandomState(7)
    B, s, d = 1, 1024, 32
    q = jnp.asarray(rng.randn(B, s, d), jnp.float32)

    def temp_mb(attention):
        f = jax.jit(jax.grad(
            lambda q, k, v: (attention(q, k, v).astype(jnp.float32) ** 2)
            .sum(), argnums=(0, 1, 2)))
        c = f.lower(q, q, q).compile()
        return c.memory_analysis().temp_size_in_bytes / 2**20

    dense = temp_mb(lambda q, k, v: _reference_attention(q, k, v, True))
    for bk in (64, 1024):
        fused = temp_mb(lambda q, k, v: flash_attention(q, k, v, True, 256,
                                                        bk))
        assert fused < dense * 0.4, (bk, fused, dense)


# -- a sliding window and grouped-query heads --------------------------------

@pytest.mark.parametrize("s,window,wrt", [
    (512, 200, None), (512, 200, 0), (512, 200, 1), (512, 200, 2),
    (256, 256, None), (256, 256, 1), (512, 128, None), (512, 128, 0)],
    ids=["cuts-tiles-o", "cuts-tiles-dq", "cuts-tiles-dk", "cuts-tiles-dv",
         "cuts-none-o", "cuts-none-dk", "whole-tiles-o", "whole-tiles-dq"])
def test_window_and_grouped_heads_match_the_einsum_path(s, window, wrt):
    """The three kernels (interpret mode) with a ``window`` and four
    query heads over two key/value heads, against `causal_attention`
    with the same two arguments: at 512 positions and 128-blocks a
    window of 200 empties whole tiles and cuts two per row, one of 128
    cuts exactly at tile edges, and at 256 = the length it cuts none
    (the mask is there and changes nothing). Forward, and each of the
    three gradients through the custom VJP: the dK/dV kernel sums over
    the two query heads of a group."""
    from horovod_tpu.models.transformer import causal_attention

    b, heads, kv, d, block = 1, 4, 2, 32, 128
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(b, s, heads * d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, kv * d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, kv * d), jnp.float32)
    t = jnp.asarray(rng.randn(b, s, heads * d), jnp.float32)

    def kernels(q, k, v):
        return flash_attention(q, k, v, True, block, block, heads, window,
                               kv)

    def einsum(q, k, v):
        o = causal_attention(q.reshape(b, s, heads, d),
                             k.reshape(b, s, kv, d), v.reshape(b, s, kv, d),
                             window, kv)
        return o.reshape(b, s, heads * d)

    if wrt is None:
        got, want = kernels(q, k, v), einsum(q, k, v)
    else:
        got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * t), argnums=wrt)(
            q, k, v) for f in (kernels, einsum))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=2e-4)


def test_window_against_scan_stats_oracle_by_hand_mask():
    """One head, a window of 96 at 64-blocks (not a multiple of the
    block: both edge tiles are cut inside), against a dense softmax with
    the mask written out: ``j <= i`` and ``i - j < window``."""
    s, d, window = 256, 32, 96
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(1, s, d), jnp.float32)
               for _ in range(3))
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    scores = np.einsum("bqd,bkd->bqk", q, k) * d ** -0.5
    scores = np.where((j <= i) & (i - j < window), scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    want = np.einsum("bqk,bkd->bqd", p / p.sum(-1, keepdims=True), v)
    got = flash_attention(q, k, v, True, 64, 64, 1, window)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)
    # no window: the global kernel, and the same entry as before
    full = flash_attention(q, k, v, True, 64, 64)
    ref = _reference_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(full), np.asarray(ref), atol=1e-4)


def test_window_and_head_arguments_are_checked():
    q = jnp.zeros((1, 128, 4 * 32))
    kv3 = jnp.zeros((1, 128, 3 * 32))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, False, 64, 64, 4, 32)
    with pytest.raises(ValueError, match="key/value heads"):
        flash_attention(q, kv3, kv3, True, 64, 64, 4, None, 3)


# -- latent heads: a score of two parts, one rotary key for all heads --------

@pytest.mark.parametrize("wrt", [None, 0, 1, 2, 3, 4],
                         ids=["o", "dq", "dq_rope", "dk", "dk_rope", "dv"])
def test_latent_kernels_match_the_einsum_path_and_scan_stats(wrt):
    """The three ``hvd_mla_*`` kernels (interpret mode) at four
    128-tiles a side, three heads of 32 + 16 columns: against
    `causal_attention` on heads put together (the rotary key copied per
    head there, never in the kernels) and, forward, against `scan_stats`
    head by head. Forward, and each of the five gradients through the
    custom VJP: the shared key's is the sum over the heads."""
    from horovod_tpu.models.transformer import causal_attention

    b, s, heads, d, r, block = 2, 512, 3, 32, 16, 128
    rng = np.random.RandomState(11)
    q, k, v, t = (jnp.asarray(rng.randn(b, s, heads * d), jnp.float32)
                  for _ in range(4))
    q_rope = jnp.asarray(rng.randn(b, heads, s, r), jnp.float32)
    k_rope = jnp.asarray(rng.randn(b, s, r), jnp.float32)

    def kernels(q, q_rope, k, k_rope, v):
        return latent_attention(q, q_rope, k, k_rope, v, block, block, heads)

    def together(q, q_rope, k, k_rope):
        """[b, s, heads, d + r] queries and keys."""
        return (jnp.concatenate([q.reshape(b, s, heads, d),
                                 q_rope.transpose(0, 2, 1, 3)], -1),
                jnp.concatenate([k.reshape(b, s, heads, d), jnp.broadcast_to(
                    k_rope[:, :, None], (b, s, heads, r))], -1))

    def einsum(q, q_rope, k, k_rope, v):
        qq, kk = together(q, q_rope, k, k_rope)
        return causal_attention(qq, kk, v.reshape(b, s, heads, d)).reshape(
            b, s, heads * d)

    args = (q, q_rope, k, k_rope, v)
    if wrt is None:
        got, want = kernels(*args), einsum(*args)
        qq, kk = together(q, q_rope, k, k_rope)
        for h in range(heads):  # scan_stats pads v to the score's width
            vv = jnp.pad(v.reshape(b, s, heads, d)[:, :, h],
                         ((0, 0), (0, 0), (0, r)))
            o, _, _ = scan_stats(qq[:, :, h], kk[:, :, h], vv, True, 0, 128)
            np.testing.assert_allclose(
                np.asarray(got.reshape(b, s, heads, d)[:, :, h]),
                np.asarray(o[..., :d]), atol=2e-4, rtol=2e-4)
    else:
        got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * t), argnums=wrt)(
            *args) for f in (kernels, einsum))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=2e-4)


def test_latent_shapes_are_checked_and_plain_heads_tile_as_before():
    x = jnp.zeros((1, 128, 2 * 32))
    with pytest.raises(ValueError, match="latent heads"):
        latent_attention(x, jnp.zeros((1, 128, 2, 16)), x,
                         jnp.zeros((1, 128, 16)), x, 64, 64, 2)
    # a latent head's head_dim is its no-rope columns: the same rule
    assert block_sizes(8192, 128) == (1024, 1024)
    assert block_sizes(8192, 192) is None
