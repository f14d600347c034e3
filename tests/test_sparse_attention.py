"""Learned sparse attention in the decoder of ``models/transformer.py``
(``sparse_topk``: an indexer scores every earlier key, each query
attends over the keys of its largest scores, the indexer learns from a
KL term of its own; QK-norm; the ``hvd_dsa_*`` kernels) against the
plain reference of ``chipbench/reference/keye_vl2.py``, at a toy size on
the CPU; the selection, the detach, the kept selection; and the older
decoders left as they were."""

import hashlib
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from chipbench.reference import kanana2, smallthinker
from chipbench.reference import keye_vl2 as reference
from horovod_tpu.models import transformer as T
from horovod_tpu.ops import selection
from horovod_tpu.parallel import data_parallel_step, dp, moe
from horovod_tpu.utils import scopes

F = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")

ARCH = {"rope_theta": 1e7, "topk": 8, "index_rope": 4,
        "num_experts_per_tok": 2, "first_held": 0}


def toy(**kw):
    """Two expert layers of four query heads over two key/value heads of
    8, QK-norm, an indexer of four heads of 8 picking 8 keys a query."""
    return T.TransformerConfig(**{**dict(
        vocab_size=48, d_model=32, n_heads=4, n_kv_heads=2, d_head=8,
        n_layers=2, d_ff=0, max_seq=64, dtype=jnp.float32,
        positions="layout", rope_layout=(1,), rope_theta=1e7, n_experts=8,
        experts_per_token=2, d_expert=16, experts_held=(0, 4),
        tie_embeddings=False, router_input="normed",
        expert_activation="silu", qk_norm=True, sparse_topk=8,
        index_heads=4, index_dim=8, index_rope=4, index_chunk=32), **kw})


def seeded_params(cfg, key=0):
    """Seeded weights, the matrices four times their spread and every
    norm's scale away from 1, so that the selection, the routing and
    where each norm sits matter; the embedding at a spread of 1."""
    params = T.init(jax.random.PRNGKey(key), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(key + 100), 128))
    params = jax.tree.map(
        lambda a: a * (1 + 0.5 * jax.random.normal(next(keys), a.shape))
        if a.ndim == 1 else 4 * a, params)
    params["embed"] = jax.random.normal(next(keys), params["embed"].shape)
    return params


def close(got, want, rtol=1e-4):
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=rtol,
            atol=rtol * float(jnp.abs(w).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


@pytest.fixture(autouse=True)
def blocks_of_32(monkeypatch):
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_decoder_matches_the_reference(remat):
    """(a) Loss (the LM loss and the indexers' KL terms), logits and every
    leaf's gradient, the indexers' among them, against the reference
    selecting by ``lax.top_k``: 8 keys of up to 64 a query."""
    cfg = toy(remat=remat)
    params = seeded_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 65), 0, 48)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: T.lm_loss(
        p, tokens, cfg, use_constraints=False)))(params)
    (want, (_, picked)), want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens[0], ARCH), has_aux=True))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    close(grads, want_grads)
    for name in ("wq", "wk", "w"):
        assert float(jnp.abs(grads["blocks"][1]["indexer"][name]).max()) > 0
    logits = T.apply(params, tokens[:, :-1], cfg, use_constraints=False)
    want_logits, kl, _ = jax.jit(lambda p: reference.forward(
        p, tokens[0], ARCH))(params)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want_logits),
                               rtol=1e-4, atol=1e-4)
    assert float(kl) > 0.01       # the untrained indexer is far from p
    # the reference selected on its own: the same sets as the program
    differs, extra, missing, short = picked
    assert float(differs.max()) == 0 and float(extra.max()) == 0
    assert float(missing.max()) == 0


def test_the_selection_is_lax_top_k():
    """(b) The exact threshold per query: the keys ``lax.top_k`` gives,
    all of a query's keys while it has fewer than ``topk``, none after
    it; its selection packed and unpacked again is itself."""
    scores = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 96))
    for first, topk in ((32, 8), (0, 40), (0, 200)):
        keep = T._select(scores, first, topk)
        causal = (jnp.arange(96)[None, :]
                  <= first + jnp.arange(64)[:, None])[None]
        masked = jnp.where(causal, scores, -jnp.inf)
        _, top = jax.lax.top_k(masked, min(topk, 96))
        want = jnp.zeros_like(keep).at[
            jnp.arange(2)[:, None, None], jnp.arange(64)[None, :, None],
            top].set(True) & causal
        assert bool(jnp.all(keep == want)), (first, topk)
        np.testing.assert_array_equal(keep.sum(-1), np.broadcast_to(
            np.minimum(np.arange(64) + first + 1, topk), (2, 64)))
        np.testing.assert_array_equal(
            selection.unpack(selection.pack(keep)), keep)
    # negative scores and scores of one sign order as floats do
    mixed = jnp.array([[[-3.0, 2.0, -0.5, 0.25, -1e-30, 7.0, -7.0, 1e-30]]])
    keep = T._select(mixed, 7, 3)
    assert keep[0, 0].tolist() == [False, True, False, True, False, True,
                                   False, False]


def test_a_selection_of_every_key_is_plain_attention():
    """(c) With ``sparse_topk`` at least the sequence, the decoder's
    logits are the plain decoder's on the same weights; the sparse
    kernels over a full causal selection give the fused kernels' output
    and gradients."""
    cfg = toy(sparse_topk=64)
    params = seeded_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 64), 0, 48)
    plain_cfg = toy(sparse_topk=0)
    plain = {**params, "blocks": [{k: v for k, v in b.items()
                                   if k != "indexer"}
                                  for b in params["blocks"]]}
    np.testing.assert_allclose(
        np.asarray(T.apply(params, tokens, cfg, use_constraints=False)),
        np.asarray(T.apply(plain, tokens, plain_cfg, use_constraints=False)),
        rtol=1e-5, atol=1e-5)

    B, s, heads, kv, d, block = 2, 256, 4, 2, 32, 128
    q, k, v, do = (jax.random.normal(jax.random.PRNGKey(i), (B, s, w * d))
                   for i, w in ((0, heads), (1, kv), (2, kv), (3, heads)))
    every = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), bool)), (B, s, s))
    by_query = selection.pack(every)
    chosen = (by_query, selection.pack(jnp.swapaxes(every, 1, 2)),
              selection.tiles(by_query, block, block))

    def sparse(q, k, v):
        return jnp.sum(F.sparse_attention(q, k, v, chosen, block, block,
                                          heads, kv, s)[0] * do)

    def fused(q, k, v):
        return jnp.sum(F.flash_attention(q, k, v, True, block, block, heads,
                                         None, kv) * do)

    got = jax.value_and_grad(sparse, (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(fused, (0, 1, 2))(q, k, v)
    close(got, want, 1e-5)


def test_the_indexer_learns_from_its_kl_term_alone():
    """(d) The detach: the LM loss gives every indexer leaf a zero
    gradient (the selection it makes is integers, its input a constant),
    and the KL term gives every other leaf none (its target is a
    constant): the whole loss's gradient is the LM loss's outside the
    indexers and the KL term's inside them."""
    cfg = toy()
    params = seeded_params(cfg, key=4)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 65), 0, 48)

    def lm_only(p):
        logits = T.apply(p, tokens[:, :-1], cfg, use_constraints=False)
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), tokens[:, 1:, None], axis=-1))

    whole = jax.jit(jax.grad(lambda p: T.lm_loss(
        p, tokens, cfg, use_constraints=False)))(params)
    lm = jax.jit(jax.grad(lm_only))(params)
    for got, want in zip(whole["blocks"], lm["blocks"]):
        index_lm = want.pop("indexer")
        assert all(float(jnp.abs(g).max()) == 0
                   for g in jax.tree.leaves(index_lm))
        index_kl = got.pop("indexer")
        assert all(float(jnp.abs(g).max()) > 0
                   for g in jax.tree.leaves(index_kl))
        close(got, want, 1e-5)
    close({w: whole[w] for w in ("embed", "head", "ln_f")},
          {w: lm[w] for w in ("embed", "head", "ln_f")}, 1e-5)


def masked_attention(q, k, v, keep, heads):
    """Each query over the keys ``keep`` [B, s, s] marks, as an einsum."""
    B, s, width = q.shape
    d = width // heads
    group = heads // (k.shape[-1] // d)
    qh = q.reshape(B, s, heads, d)
    kh, vh = (jnp.repeat(a.reshape(B, s, -1, d), group, 2) for a in (k, v))
    logits = jnp.einsum("bshd,bthd->bhst", qh, kh) * d ** -0.5
    probs = jax.nn.softmax(jnp.where(keep[:, None], logits, -jnp.inf), -1)
    return jnp.einsum("bhst,bthd->bshd", probs, vh).reshape(B, s, width)


@pytest.mark.parametrize("heads,kv,hs", [
    (4, 2, 2), (2, 2, 1), (8, 2, 4), (8, 2, 2)],
    ids=["grouped", "mha", "group4", "group4_split"])
def test_sparse_kernels_against_a_masked_einsum(heads, kv, hs, monkeypatch,
                                                request):
    """(e) ``hvd_dsa_fwd``, ``hvd_dsa_bwd_dq``, ``hvd_dsa_bwd_dkv`` and
    ``hvd_dsa_target`` in interpret mode: 40 keys of each query's past by
    a random score, a tile emptied by hand (skipped), forward and
    backward against the masked einsum, the target against the einsum
    path's. The forward kernel takes ``hs`` query heads a step, one
    unpacked tile for them all (`F.mask_heads`): the whole group where
    its blocks fit the budget, two of four where the budget holds two."""
    B, s, d, block, topk = 1, 256, 32, 128, 40
    if hs < heads // kv:   # a budget that holds ``hs`` heads' blocks
        monkeypatch.setattr(F, "DSA_HEADS_VMEM",
                            hs * F._head_vmem(block, d, jnp.float32))
        jax.clear_caches()   # the kernels traced at the default budget
        request.addfinalizer(jax.clear_caches)   # and those at this one
    assert F.mask_heads(heads, kv, block, d, jnp.float32) == hs
    q, k, v, do = (jax.random.normal(jax.random.PRNGKey(i), (B, s, w * d))
                   for i, w in ((10, heads), (11, kv), (12, kv), (13, heads)))
    score = jax.random.normal(jax.random.PRNGKey(14), (B, s, s))
    keep = T._select(score, 0, topk)
    keep = keep.at[:, 128:, :128].set(False).at[:, 128:, 128].set(True)
    by_query = selection.pack(keep)
    tiles = selection.tiles(by_query, block, block)
    assert tiles.tolist() == [1, 0, 0, 1]
    chosen = (by_query, selection.pack(jnp.swapaxes(keep, 1, 2)), tiles)

    def ours(q, k, v):
        return jnp.sum(F.sparse_attention(q, k, v, chosen, block, block,
                                          heads, kv, topk)[0] * do)

    def einsum(q, k, v):
        return jnp.sum(masked_attention(q, k, v, keep, heads) * do)

    close(jax.value_and_grad(ours, (0, 1, 2))(q, k, v),
          jax.value_and_grad(einsum, (0, 1, 2))(q, k, v), 1e-5)
    # the log-sum-exps it returns, and the target kernel's mean
    # probabilities of a chunk of 128 queries from them (the chunk at 128
    # fetches no key block past its last query), against the einsum's
    o, lse = F.sparse_attention(q, k, v, chosen, block, block, heads, kv,
                                topk)
    _, want_lse = T._selected_attention(q, k, v, keep, heads)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=1e-5, atol=1e-5)
    for first in (0, 128):
        rows = slice(first, first + 128)
        target = F.sparse_target(q[:, rows], k, lse[..., rows],
                                 by_query[:, first // 32:(first + 128) // 32],
                                 first, block, block, heads, kv, topk)
        want = T._target(q[:, rows], k, lse[..., rows], keep[:, rows], heads)
        np.testing.assert_allclose(np.asarray(target), np.asarray(want),
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(np.asarray(target.sum(-1)), 1.0,
                                   rtol=1e-5)


def on_the_kernels(monkeypatch):
    """The decoder as a chip traces it: the fused kernels, in interpret
    mode, at 256 positions of 128-tiles."""
    monkeypatch.setattr(T, "FUSED_ATTENTION_MIN_SEQ", 256)
    monkeypatch.setattr(F, "BLOCKS", (128,))
    monkeypatch.setattr(T, "_on_tpu", lambda: True)
    cfg = toy(d_model=64, n_heads=4, d_head=128, max_seq=256, sparse_topk=40,
              index_chunk=64, remat=True)
    return cfg, seeded_params(cfg, key=6)


def scans(jaxpr, out):
    """The trip counts of every ``scan`` in a jaxpr, nested ones too."""
    for e in jaxpr.eqns:
        if e.primitive.name == "scan":
            out.append(e.params["length"])
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    scans(inner, out)
    return out


def test_the_kept_selection_is_what_the_backward_pass_reads(monkeypatch):
    """(f) On the kernels, under remat, through ``data_parallel_step``:
    loss and gradients against the reference; the indexer's two scans
    of four chunks (the selection, then the KL term) run once a layer,
    never again in the recomputation, because the checkpoint keeps the
    selection and the KL gradients (`scopes.KEPT_SELECTION`): without
    those names in the policy the recomputation runs both again. One
    forward and one backward kernel entry a layer; the counters say
    so."""
    cfg, params = on_the_kernels(monkeypatch)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 64)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (1, 257), 0, 48)
    step = data_parallel_step(
        lambda p, t: jax.value_and_grad(T.lm_loss)(
            p, t, cfg, use_constraints=False),
        mesh=Mesh(np.array(jax.devices()[:1]), ("hvd",)), batch_argnums=(1,),
        donate_argnums=())
    loss, grads = step(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens[0], {**ARCH, "topk": 40})[0]))(
            params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    close(grads, want_grads, 1e-3)
    text = step.lower(params, tokens).as_text(debug_info=True)
    assert text.count("call @_dsa_fwd_lse") == cfg.n_layers
    assert text.count("call @_dsa_bwd") == cfg.n_layers
    assert scopes.INDEXER in text and scopes.ATTENTION in text

    def indexer_scans():
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: T.lm_loss(
            p, tokens, cfg, use_constraints=False)))(params)
        return scans(jaxpr.jaxpr, []).count(256 // cfg.index_chunk)

    assert indexer_scans() == 2 * cfg.n_layers  # select, then the KL term
    monkeypatch.setattr(T, "_KEEP_KERNEL_RESIDUALS",
                        jax.checkpoint_policies.save_only_these_names(
                            *scopes.KEPT_BY_REMAT, scopes.KEPT_CHOICE,
                            scopes.KEPT_ROUTING))
    assert indexer_scans() == 4 * cfg.n_layers
    counters = dp.step_counters(step)
    assert counters["attention_calls"] == cfg.n_layers
    assert counters["attention_sparse_calls"] == cfg.n_layers
    assert counters["indexer_kernel_calls"] == cfg.n_layers
    assert counters["attention_kept_calls"] == cfg.n_layers
    kept = (T._kept_bytes((1, 256), cfg, True, True, True)
            + T._kept_selection_bytes((1, 256), cfg, True))
    assert counters["remat_kept_mb"] == pytest.approx(
        cfg.n_layers * kept / 1e6)


@pytest.mark.parametrize("first,operands", [
    (0, jnp.float32), (1024, jnp.float32), (1536, jnp.float32),
    (1024, jnp.bfloat16)], ids=["first", "middle", "last", "bf16"])
def test_the_indexer_kernels_against_the_einsum(first, operands,
                                                monkeypatch):
    """(k) ``hvd_dsa_index_fwd`` and ``hvd_dsa_index_bwd`` in interpret
    mode at the cell's widths (16 heads of 64, chunks of 512) over 2048
    keys, for the first, a middle and the last chunk: the scores are
    `_index_scores`' where a key is causal and 0 after it, whole tiles
    past the chunk's last query included; the VJP is ``jax.vjp`` of that,
    and a cotangent on a query's later keys moves no gradient. With the
    MXU's bf16 operands (the TPU's), against the einsum of the operands
    so rounded: the scores as tight, the gradients within the rounding of
    the cotangent the kernel hands the MXU."""
    monkeypatch.setattr(F, "_index_operands", lambda: operands)
    B, c, s, heads, dim = 1, 512, 2048, 16, 64
    keys = jax.random.split(jax.random.PRNGKey(first), 4)
    iq = jax.random.normal(keys[0], (B, c, heads, dim))
    iw = jax.random.normal(keys[1], (B, c, heads))
    ik = jax.random.normal(keys[2], (B, s, dim))
    g = jax.random.normal(keys[3], (B, c, s))
    group, block_k = F.index_blocks(c, s, heads, dim)
    assert (group, block_k) == (2, 512)
    causal = jnp.arange(s)[None, :] <= first + jnp.arange(c)[:, None]

    def rounded(a):  # as the MXU takes it, the gradient passing through
        return a + jax.lax.stop_gradient(a.astype(operands) - a)

    def einsum(q, w, k):
        return jnp.where(causal, T._index_scores(rounded(q), w, rounded(k)),
                         0.0)

    def kernels(q, w, k):
        return F.index_scores(q, w, k, first, group, block_k)

    got, back = jax.vjp(kernels, iq, iw, ik)
    want, want_back = jax.vjp(einsum, iq, iw, ik)
    close(got, want, 1e-5)
    assert float(jnp.abs(jnp.where(causal, 0.0, got)).max()) == 0
    past = -(-(first + c) // block_k) * block_k
    assert past == s or float(jnp.abs(got[..., past:]).max()) == 0
    grads = back(g)
    close(grads, want_back(g), 1e-5 if operands == jnp.float32 else 1e-2)
    for a, b in zip(grads, back(jnp.where(causal, g, 0.0))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("path", ["kernels", "einsum"])
def test_the_indexer_kernel_counter(path, monkeypatch):
    """(l) ``indexer_kernel_calls`` counts the layers whose indexer takes
    its kernels: all four where the attention kernels are taken and the
    chunks tile, none on the einsum path (a number there, not absent)."""
    cfg, _ = on_the_kernels(monkeypatch)
    cfg = T.dataclasses.replace(cfg, n_layers=4)
    monkeypatch.setattr(T, "_on_tpu", lambda: path == "kernels")
    monkeypatch.setattr(T, "_partitioned_by_xla", lambda: False)  # one chip
    record = scopes.StepRecord()
    with scopes.recording(record):
        jax.eval_shape(lambda p, t: T.lm_loss(p, t, cfg, use_constraints=False),
                       jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0),
                                                     cfg)),
                       jax.ShapeDtypeStruct((1, 257), jnp.int32))
    assert record.counters["attention_sparse_calls"] == 4
    assert record.counters["indexer_kernel_calls"] == (
        4 if path == "kernels" else 0)


@pytest.mark.parametrize("sparse", [True, False], ids=["sparse", "dense"])
def test_the_mask_heads_counter(sparse, monkeypatch):
    """(m) ``sparse_mask_heads`` is the query heads one unpacked selection
    tile serves in the forward kernel (`F.mask_heads`): at toy widths the
    whole group of two; a program with no sparse layer notes none."""
    cfg, _ = on_the_kernels(monkeypatch)
    if not sparse:
        cfg = T.dataclasses.replace(cfg, sparse_topk=0)
    monkeypatch.setattr(T, "_partitioned_by_xla", lambda: False)  # one chip
    record = scopes.StepRecord()
    with scopes.recording(record):
        jax.eval_shape(lambda p, t: T.lm_loss(p, t, cfg, use_constraints=False),
                       jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0),
                                                     cfg)),
                       jax.ShapeDtypeStruct((1, 257), jnp.int32))
    assert record.counters["attention_kernel_calls"] == cfg.n_layers
    if sparse:
        assert record.counters["sparse_mask_heads"] == F.mask_heads(
            cfg.n_heads, cfg.kv_heads, 128, cfg.head_dim, cfg.dtype) == 2
    else:
        assert "sparse_mask_heads" not in record.counters


def test_the_held_shares_add_up_under_this_router():
    """(g) 128 experts cut sixteen ways, eight held a chip, eight chosen a
    token by a softmax over all 128 on the normed input, weights
    normalised over the eight, SiLU experts: the sixteen shares' outputs
    add up to the uncut reference layer's."""
    rng = np.random.RandomState(0)
    t, d, f, experts = 64, 32, 16, 128
    u = jnp.asarray(rng.randn(t, d), jnp.float32)
    router = jnp.asarray(rng.randn(d, experts), jnp.float32)
    params = {w: jnp.asarray(0.3 * rng.randn(experts, *shape), jnp.float32)
              for w, shape in (("gate", (d, f)), ("up", (d, f)),
                               ("down", (f, d)))}
    weights, _, _ = smallthinker.router_weights(u, router, 8)
    whole = jax.jit(kanana2.experts, static_argnums=3)(u, params, weights, 0)
    chosen, w = moe.route(u @ router, 8)
    total = 0.0
    for first in range(0, experts, 8):
        held = jax.tree.map(lambda x: x[first:first + 8], params)
        total = total + moe.expert_layer(u, chosen, w, held, (first, 8),
                                         activation="silu")
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=5e-5)


#: sha256 (16 hex digits) of a looped decoder's seeded weights and
#: traced loss-and-gradient program at ``tests/test_looped_decoder.py``'s
#: toy sizes, remat on, as they were before learned sparse attention
LOOPED = ("7c36d371e6f86f33", "d6b04f2a133ac301")


def digest(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


@pytest.mark.parametrize("name", ["dense", "sparse", "latent", "looped"])
def test_an_older_decoder_is_what_it_was(name):
    """(h) The four older decoders with the new settings spelled out at
    their defaults (no QK-norm, no selection) draw the weights and trace
    the program they did, instruction for instruction."""
    looped = importlib.import_module("test_looped_decoder")
    if name == "looped":
        settings = {f.name: getattr(looped.toy(remat=True), f.name)
                    for f in T.dataclasses.fields(T.TransformerConfig)}
        weights, program = LOOPED
    else:
        settings, weights, program = looped.OLDER[name]
    cfg = T.TransformerConfig(**{**settings, "qk_norm": False,
                                 "sparse_topk": 0, "index_heads": 0,
                                 "index_dim": 0, "index_rope": 0})
    params = T.init(jax.random.PRNGKey(7), cfg)
    assert "indexer" not in params["blocks"][0]
    assert "q_norm" not in params["blocks"][0]
    assert digest(b"".join(np.asarray(leaf).tobytes()
                           for leaf in jax.tree.leaves(params))) == weights
    tokens = jnp.zeros((2, 17), jnp.int32)
    traced = str(jax.make_jaxpr(jax.value_and_grad(
        lambda p: T.lm_loss(p, tokens, cfg, use_constraints=False)))(params))
    assert digest(re.sub(r"0x[0-9a-f]+", "0x", traced).encode()) == program


def test_the_sparse_settings_and_what_they_refuse():
    """(i) The new leaves and their specs; the indexer's weights come
    from a key of their own, so every other leaf is the one the same
    settings draw without it; what no cell needs is refused."""
    cfg = toy()
    params = T.init(jax.random.PRNGKey(7), cfg)
    blk = params["blocks"][0]
    assert {"q_norm", "k_norm", "indexer"} <= set(blk)
    assert blk["indexer"]["wq"].shape == (32, 4, 8)
    assert blk["indexer"]["wk"].shape == (32, 8)
    assert blk["indexer"]["w"].shape == (32, 4)
    assert float(jnp.abs(blk["indexer"]["k_norm"]["bias"]).max()) == 0
    assert jax.tree.structure(T.param_specs(cfg)) == jax.tree.structure(
        jax.tree.map(lambda _: P(), params))
    plain = T.init(jax.random.PRNGKey(7), toy(sparse_topk=0, qk_norm=False))
    for got, want in zip(params["blocks"], plain["blocks"]):
        for w in want:
            close(got[w], want[w], 0)
    tokens = jnp.zeros((1, 33), jnp.int32)
    for settings, call in (
            (dict(window=8, window_layout=(1,)), None),
            (dict(kv_latent=8, d_rope=4), None),
            (dict(n_loops=2, n_experts=0), None),
            (dict(index_rope=3), None),
            (dict(index_heads=0), None),
            ({}, lambda p, c: T.apply(p, tokens, c, use_constraints=False,
                                      attn_fn=lambda q, k, v: q))):
        with pytest.raises(ValueError, match="sparse|attn_fn"):
            bad = toy(**settings)
            (call or (lambda p, c: T.init(jax.random.PRNGKey(0), c)))(
                params, bad)


def test_the_selection_comes_back_packed_with_the_routing():
    """(j) ``return_selection`` gives each layer's selection packed by
    query, beside the routing where both are asked for."""
    cfg = toy()
    params = seeded_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 65), 0, 48)
    loss, (routing, selections) = T.lm_loss(
        params, tokens, cfg, use_constraints=False, return_routing=True,
        return_selection=True)
    assert len(routing) == len(selections) == 2
    assert selections[0].shape == (2, 2, 64)
    # at least 8 keys a query once it has 8, more only where scores tie
    # at the threshold (a score is exactly 0 where every head's is)
    taken = selection.unpack(selections[1]).sum(-1)
    least = np.minimum(np.arange(64) + 1, 8)[None]
    assert bool(np.all(taken >= least)) and np.mean(taken == least) > 0.9
    _, alone = T.apply(params, tokens[:, :-1], cfg, use_constraints=False,
                       return_selection=True)
    for a, b in zip(alone, selections):
        np.testing.assert_array_equal(a, b)
