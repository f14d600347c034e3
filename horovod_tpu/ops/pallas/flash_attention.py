"""Fused block (flash) attention — the Pallas TPU kernel behind
`horovod_tpu.parallel.sp.ring_attention`'s inner step (SURVEY.md §5.7
"pallas splash-attention kernels"; greenfield — the reference has no
attention kernels at all).

Forward is a single Pallas kernel: for each Q block the K/V blocks stream
through VMEM while an online softmax (running max ``m``, running sum ``l``,
rescaled accumulator) lives in VMEM scratch — logits never round-trip to
HBM, which is the whole point on a bandwidth-bound chip. The kernel also
returns ``(m, l)`` so ring attention can combine partial results from
other chips' K/V shards exactly.

Backward is a rematerialized BLOCKWISE VJP: autodiff through
``scan_stats`` — a ``lax.scan`` over K/V blocks with a checkpointed
body — so both directions hold one [B, sq, block_k] score block, never
the full matrix. Only q/k/v are residuals. A fused backward kernel is
a later optimization.

On non-TPU backends the kernel runs in Pallas interpret mode (tests on the
virtual CPU mesh), so one code path serves everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils import scopes

NEG_INF = -1e30
# lane width of a TPU vector register: the last dimension of every block
# the TPU compiler accepts is a multiple of it (or the whole array's)
LANES = 128


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                      acc_scr, m_scr, l_scr, *, scale: float, causal: bool,
                      causal_offset: int, block_q: int, block_k: int,
                      num_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _block():
        q = q_ref[0]                      # [bq, d]
        k = k_ref[0]                      # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if causal:
            # causal_offset=0: standard (row >= col); =1: STRICT (row > col)
            # — striped ring attention's j>i rounds exclude the diagonal
            rows = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols + causal_offset, s, NEG_INF)
        # running stats stay [bq, 1] columns (one per score row): the
        # TPU has no 1-D vector layout
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    if causal:
        # skip blocks whose mask is entirely empty
        @pl.when(ki * block_k + causal_offset < (qi + 1) * block_q)
        def _():
            _block()
    else:
        _block()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        # guard fully-masked rows (l == 0 never happens when causal includes
        # the diagonal, but ring callers may pass degenerate blocks)
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)

        def row(col):
            # [bq, 1] column -> [1, bq] lane-major row for the [B, 1, sq]
            # outputs: spread over a lane tile, transpose, keep one row
            return jnp.broadcast_to(col, (block_q, LANES)).T[:1]

        m_ref[0] = row(m_scr[:])
        l_ref[0] = row(l)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def kernel_tiles(sq: int, sk: int, block_q: int, block_k: int,
                 lane_aligned: bool = True) -> bool:
    """Can the kernel take these shapes? Blocks must divide the sequences;
    the TPU compiler (``lane_aligned``) also wants every block a multiple
    of the lane width, or the whole sequence — the [1, bq] rows of m/l and
    the [bq, bk] score tile. Interpret mode needs only the first."""
    bq, bk = min(block_q, sq), min(block_k, sk)
    if sq % bq or sk % bk:
        return False
    return not lane_aligned or all(
        b % LANES == 0 or b == n for b, n in ((bq, sq), (bk, sk)))


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "causal_offset"))
def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               causal_offset: int = 0):
    """q: [B, sq, d], k/v: [B, sk, d] → (o [B, sq, d], m [B, sq], l [B, sq]).

    o is *normalized* (already divided by l); combining across ring steps
    uses (m, l) to undo/redo normalization exactly.
    """
    B, sq, d = q.shape
    sk = k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    interpret = _interpret()
    if not kernel_tiles(sq, sk, bq, bk, lane_aligned=not interpret):
        raise ValueError(
            f"sequence lengths ({sq}, {sk}) must be divisible by the block "
            f"sizes ({bq}, {bk}), and on TPU each block a multiple of "
            f"{LANES} or the whole sequence; pick block_q/block_k that "
            "tile the sequence or use the blockwise XLA fallback "
            "(scan_stats / use_flash=False)")
    nq, nk = sq // bq, sk // bk
    scale = d ** -0.5

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal,
        causal_offset=causal_offset, block_q=bq, block_k=bk,
        num_k_blocks=nk)
    # inside a vma-checked shard_map (ring attention) the outputs vary
    # over the mesh axes the inputs vary over; frozenset() elsewhere
    vma = frozenset().union(*(jax.typeof(x).vma for x in (q, k, v)))
    # m/l leave the kernel as [B, 1, sq]: a (1, 1, bq) block is legal on
    # TPU (second-to-last dim = the whole array's, last a lane multiple),
    # a (1, bq) block of [B, sq] is not
    o, m, l = pl.pallas_call(
        kernel,
        grid=(B, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, sq, d), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((B, 1, sq), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((B, 1, sq), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="hvd_flash_fwd",  # a trace's kernel events are found by it
    )(q, k, v)
    return o, m[:, 0], l[:, 0]


def _reference_attention(q, k, v, causal: bool, causal_offset: int = 0):
    """Plain XLA attention used by the backward rematerialization and as
    the numerics oracle in tests. q/k/v: [B, s, d]."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool),
                        k=-causal_offset)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512):
    """Fused attention: q [B, sq, d] × k/v [B, sk, d] → [B, sq, d]."""
    o, _, _ = _flash_fwd(q, k, v, causal, block_q, block_k)
    return o


def flash_attention_stats(q, k, v, causal: bool = True, block_q: int = 512,
                          block_k: int = 512):
    """Forward returning (o, m, l) for cross-chip (ring) combination."""
    return _flash_fwd(q, k, v, causal, block_q, block_k)


def _lax_stats(q, k, v, causal: bool, causal_offset: int = 0):
    """Pure-XLA stats attention: (normalized o, running max m, sum l) in the
    same contract as the Pallas kernel. Serves as the differentiable
    fallback (non-TPU backends) and the autodiff oracle for the kernel's
    rematerialized VJP."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool),
                        k=-causal_offset)
        s = jnp.where(mask, s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v).astype(jnp.float32)
    o = (o / jnp.where(l == 0.0, 1.0, l)[..., None]).astype(q.dtype)
    return o, m, l


def scan_stats(q, k, v, causal: bool = True, causal_offset: int = 0,
               block_k: int = 512):
    """Blockwise stats attention: same (normalized o, m, l) contract as
    the Pallas kernel and ``_lax_stats``, computed as a ``lax.scan`` over
    K/V blocks with a rematerialized body — so BOTH autodiff directions
    hold only one [B, sq, block_k] score block, never the full
    [B, sq, sk] matrix. This is the memory-honest backward for the
    flash forward (the dense VJP it replaces materialized the full
    score matrix, defeating the kernel's point for long shards)."""
    B, sq, d = q.shape
    sk = k.shape[1]
    bk = min(block_k, sk)
    if sk % bk:
        # largest divisor of sk that is <= block_k: stays blockwise for
        # any length without degenerating to tiny blocks (a decrement
        # loop could land on bk=1 for near-prime lengths)
        bk = max(d_ for d_ in range(1, bk + 1) if sk % d_ == 0)
    n = sk // bk
    scale = d ** -0.5
    qf = q.astype(jnp.float32)
    kb = k.reshape(B, n, bk, d).swapaxes(0, 1)
    vb = v.reshape(B, n, bk, d).swapaxes(0, 1)
    rows = lax.broadcasted_iota(jnp.int32, (sq, bk), 0)
    cols0 = lax.broadcasted_iota(jnp.int32, (sq, bk), 1)

    def body(carry, inp):
        m, l, acc = carry
        kj, vj, j = inp
        sblk = jnp.einsum("bqd,bkd->bqk", qf,
                          kj.astype(jnp.float32)) * scale
        if causal:
            mask = rows >= (j * bk + cols0) + causal_offset
            sblk = jnp.where(mask[None], sblk, NEG_INF)
        m_new = jnp.maximum(m, sblk.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sblk - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = (acc * alpha[..., None]
               + jnp.einsum("bqk,bkd->bqd", p, vj.astype(jnp.float32)))
        return (m_new, l, acc), None

    # init derives from the data so its device-varying (vma) type matches
    # the body outputs when traced inside a shard_map (constants are
    # replication-typed and lax.scan demands equal carry types)
    zrow = qf[..., 0] * 0.0                       # [B, sq], varies like q
    init = (zrow + NEG_INF, zrow, qf * 0.0)
    (m, l, acc), _ = lax.scan(jax.checkpoint(body), init,
                              (kb, vb, jnp.arange(n)))
    o = (acc / jnp.where(l == 0.0, 1.0, l)[..., None]).astype(q.dtype)
    return o, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def attention_stats(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, causal_offset: int = 0):
    """Differentiable stats attention: Pallas kernel on TPU for the primal,
    rematerialized XLA VJP for the backward (cotangents of o, m, l all
    handled — ring combination makes m and l real outputs, not residuals).
    """
    return _flash_fwd(q, k, v, causal, block_q, block_k, causal_offset)


def _stats_fwd(q, k, v, causal, block_q, block_k, causal_offset):
    out = _flash_fwd(q, k, v, causal, block_q, block_k, causal_offset)
    return out, (q, k, v)


def _stats_bwd(causal, block_q, block_k, causal_offset, res, cts):
    q, k, v = res
    # blockwise recompute: never materializes [B, sq, sk]
    with jax.named_scope(scopes.ATTENTION):
        _, vjp = jax.vjp(
            lambda a, b, c: scan_stats(a, b, c, causal, causal_offset,
                                       block_k),
            q, k, v)
        return vjp(cts)


attention_stats.defvjp(_stats_fwd, _stats_bwd)


def _fwd(q, k, v, causal, block_q, block_k):
    o, m, l = _flash_fwd(q, k, v, causal, block_q, block_k)
    # only the inputs are residuals: the blockwise VJP recomputes its
    # own stats, so o/lse must not stay live across fwd->bwd
    return o, (q, k, v)


def _bwd(causal, block_q, block_k, res, do):
    q, k, v = res
    with jax.named_scope(scopes.ATTENTION):
        _, vjp = jax.vjp(
            lambda a, b, c: scan_stats(a, b, c, causal, 0, block_k)[0],
            q, k, v)
        return vjp(do)


flash_attention.defvjp(_fwd, _bwd)
