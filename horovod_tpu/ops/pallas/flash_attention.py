"""Fused block (flash) attention — the Pallas TPU kernels behind the dense
decoder's attention (``models/transformer.py`` picks them from the
sequence length) and `horovod_tpu.parallel.sp.ring_attention`'s inner
step (SURVEY.md §5.7 "pallas splash-attention kernels"; greenfield — the
reference has no attention kernels at all).

Forward is a single Pallas kernel (``hvd_flash_fwd``): for each Q block
the K/V blocks stream through VMEM while an online softmax (running max
``m``, running sum ``l``, rescaled accumulator) lives in VMEM scratch —
logits never round-trip to HBM, which is the whole point on a
bandwidth-bound chip. The kernel also returns ``(m, l)`` so ring
attention can combine partial results from other chips' K/V shards
exactly.

Backward has two paths, by what the caller differentiates:

- ``flash_attention`` (cotangent of ``o`` alone; the decoder's path):
  two Pallas kernels. Residuals are ``(q, k, v, o, lse)`` with ``lse = m
  + log l`` from the forward's own outputs, each under its name of
  ``scopes.KEPT_BY_REMAT``: a caller's ``jax.checkpoint`` that saves
  those names runs the forward kernel once. ``hvd_flash_bwd_dq`` walks
  Q blocks and accumulates ``dq`` over the K/V blocks; it holds ``do``
  and ``o`` of its block, so it also makes ``delta = rowsum(do * o)``
  and hands it on. ``hvd_flash_bwd_dkv`` walks K/V blocks and
  accumulates ``dk``, ``dv`` over the Q blocks. Each recomputes ``p =
  exp(s - lse)`` per tile in float32, so the score matrix goes to HBM
  in neither direction.
- ``attention_stats`` (cotangents of ``o``, ``m`` and ``l``; ring
  attention): a rematerialized BLOCKWISE VJP in XLA, autodiff through
  ``scan_stats`` — a ``lax.scan`` over K/V blocks with a checkpointed
  body — which holds one [B, sq, block_k] score block. Only q/k/v are
  residuals. ``scan_stats`` is also the kernels' oracle in tests.

All three kernels skip the tiles the causal mask empties (no compute,
and the block index is held so no DMA either) and mask only the tiles
the diagonal crosses. `block_sizes` is the one place the tile shape is
chosen, from ``(s, head_dim)``.

Two kernel families of the same machinery beside them: `latent_attention`
(``hvd_mla_*``: a score of two products, one rotary key for all heads)
and `sparse_attention` (``hvd_dsa_*``: learned sparse attention, each
query over the keys an indexer selected, the selection unpacked from
int32 words in the tile once for the query heads that read it and a
tile that holds none skipped), with `sparse_target`, the indexer's
training target, from the same tiles.

On non-TPU backends the kernels run in Pallas interpret mode (tests on
the virtual CPU mesh), so one code path serves everywhere.

What the kernels cost a job's start: importing this module imports
JAX's Pallas (about a second), so it is imported only where a kernel is
taken; each kernel's call sits under ``jax.jit`` with static block
sizes, so a program traces and lowers it once however many layers,
recomputations and directions call it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
from jax import ad_checkpoint, lax

from ...utils import scopes
from ..selection import WORD

#: what ``jax._src.pallas.pallas_call`` imports for ``interpret=`` of GPU
#: kernels, inside a ``try``/``except ImportError`` of its own
_GPU_INTERPRETER = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"


def _on_a_started_tpu() -> bool:
    """Is JAX's backend up, and a TPU? Asked without starting one: an
    import must not (a job may yet have to call
    ``jax.distributed.initialize``). The decoder imports this module at
    its first trace, when the backend is long up."""
    from jax._src import xla_bridge

    started = getattr(xla_bridge, "backends_are_initialized", None)
    return bool(started and started()) and jax.default_backend() == "tpu"


@contextlib.contextmanager
def _without_gpu_interpreter():
    """Import Pallas as an installation without its GPU interpreter
    does. ``import jax.experimental.pallas`` spends two thirds of its
    second on that interpreter and the Mosaic GPU dialects behind it,
    and a job pays it at its first trace; ``pallas_call`` treats the
    module as optional, so on a TPU, which never interprets a GPU
    kernel, it is left out (PERF.md, PR 27: 0.7 s of ``setup_s``). A
    None in ``sys.modules`` is Python's own way to say "not here"; it is
    taken away again, so a later import of that module works."""
    blocked = _on_a_started_tpu() and _GPU_INTERPRETER not in sys.modules
    if blocked:
        sys.modules[_GPU_INTERPRETER] = None
    try:
        yield
    finally:
        if blocked:
            del sys.modules[_GPU_INTERPRETER]


try:
    with _without_gpu_interpreter():
        from jax.experimental import pallas as pl
except ImportError:  # a JAX that insists on it
    from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

NEG_INF = -1e30
# lane width of a TPU vector register: the last dimension of every block
# the TPU compiler accepts is a multiple of it (or the whole array's)
LANES = 128
#: the tile edges `block_sizes` tries, best first (PERF.md, PR 26: the
#: sweep on the v5e that ordered them)
BLOCKS = (1024, 512, 256)
#: the one head narrower than a lane tile the kernels take, heads first:
#: a block of one head's rows then spans its array's last dimension
NARROW_HEAD = 64


_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b

# The kernel bodies below are written with ``lax`` functions, not with
# operators or ``jnp``: on a tracer ``a * b`` and ``jnp.where`` each go
# through a jitted numpy wrapper, and a body has sixty of them. The
# jaxprs are the same; a program traces them in half the time (PERF.md,
# PR 27).


def _on_visible_tiles(block, causal: bool, q_block, k_block, block_q: int,
                      block_k: int, causal_offset: int = 0, window=None):
    """Run ``block(masked)`` on the score tile of Q block ``q_block`` and
    K block ``k_block`` as the mask ``row >= col + causal_offset`` (and,
    with a ``window``, ``row - col < window``) leaves it: not at all
    where the mask empties it, with the mask where the diagonal or the
    window's far edge cuts it, else plain."""
    if not causal:
        block(False)
        return
    first_col = lax.add(lax.mul(k_block, block_k), causal_offset)
    visible = lax.lt(first_col, lax.mul(lax.add(q_block, 1), block_q))
    cut = lax.lt(lax.mul(q_block, block_q),
                 lax.add(first_col, block_k - 1))
    if window is not None:
        # the tile's smallest and largest ``row - col``
        nearest = lax.sub(lax.mul(q_block, block_q),
                          lax.add(first_col, block_k - 1))
        visible = lax.bitwise_and(visible, lax.lt(nearest, window))
        cut = lax.bitwise_or(cut, lax.ge(
            lax.add(nearest, block_q + block_k - 2), window))
    pl.when(lax.bitwise_and(visible, cut))(lambda: block(True))
    pl.when(lax.bitwise_and(visible, lax.bitwise_not(cut)))(
        lambda: block(False))


def _causal(s, q_block, k_block, block_q: int, block_k: int,
            causal_offset: int = 0, q_axis: int = 0, window=None):
    """The score tile ``s`` of Q block ``q_block`` and K block
    ``k_block`` with NEG_INF wherever ``row < col + causal_offset`` or,
    with a ``window``, ``row - col >= window``; Q rows run along
    ``q_axis`` of the tile."""
    rows = lax.add(lax.mul(q_block, block_q),
                   lax.broadcasted_iota(jnp.int32, s.shape, q_axis))
    cols = lax.add(lax.add(lax.mul(k_block, block_k), causal_offset),
                   lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis))
    keep = lax.ge(rows, cols)
    if window is not None:
        keep = lax.bitwise_and(keep, lax.lt(lax.sub(rows, cols), window))
    return lax.select(keep, s, lax.full_like(s, NEG_INF))


def _zeros(ref):
    return lax.full(ref.shape, 0, ref.dtype)


def _row_max(x):
    """[n, m] -> [n, 1]."""
    return lax.broadcast_in_dim(lax.reduce_max(x, (1,)), (x.shape[0], 1),
                                (0,))


def _row_sum(x):
    """[n, m] -> [n, 1]."""
    return lax.broadcast_in_dim(lax.reduce_sum(x, (1,)), (x.shape[0], 1),
                                (0,))


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _as_row(col):
    """[n, 1] column -> [1, n] lane-major row, as the [.., 1, s] row
    statistics are stored: spread over a lane tile, transpose, keep one
    row. The TPU has no 1-D vector layout, and per-row statistics are
    columns wherever they meet a [rows, cols] score tile."""
    n = col.shape[0]
    wide = lax.broadcast_in_dim(col, (n, LANES), (0, 1))
    return lax.slice(lax.transpose(wide, (1, 0)), (0, 0), (1, n))


def _as_col(row):
    """The reverse of `_as_row`: [1, n] -> [n, 1]."""
    n = row.shape[1]
    wide = lax.broadcast_in_dim(row, (LANES, n), (0, 1))
    return lax.slice(lax.transpose(wide, (1, 0)), (0, 0), (n, 1))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                      acc_scr, m_scr, l_scr, *, scale: float, causal: bool,
                      causal_offset: int, block_q: int, block_k: int,
                      num_k_blocks: int, window=None, rotary=None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(lax.eq(ki, 0))
    def _init():
        m_scr[...] = lax.full(m_scr.shape, NEG_INF, m_scr.dtype)
        l_scr[...] = _zeros(l_scr)
        acc_scr[...] = _zeros(acc_scr)

    def _block(masked: bool):
        # q [bq, d] x k [bk, d] -> [bq, bk]
        s = _dot(q_ref[0], k_ref[0], _NT)
        if rotary is not None:  # + q_rope [bq, r] x the shared k_rope [bk, r]
            qr_ref, kr_ref = rotary
            s = lax.add(s, _dot(qr_ref[0, 0], kr_ref[0], _NT))
        s = lax.mul(s, scale)
        if masked:
            # causal_offset=0: standard (row >= col); =1: STRICT (row > col)
            # — striped ring attention's j>i rounds exclude the diagonal
            s = _causal(s, qi, ki, block_q, block_k, causal_offset,
                        window=window)
        # running stats stay [bq, 1] columns (one per score row): the
        # TPU has no 1-D vector layout
        m_prev = m_scr[...]
        m_new = lax.max(m_prev, _row_max(s))
        p = lax.exp(lax.sub(s, m_new))
        alpha = lax.exp(lax.sub(m_prev, m_new))
        l_scr[...] = lax.add(lax.mul(l_scr[...], alpha), _row_sum(p))
        v = v_ref[0]
        acc_scr[...] = lax.add(
            lax.mul(acc_scr[...], alpha),
            _dot(lax.convert_element_type(p, v.dtype), v, _NN))
        m_scr[...] = m_new

    _on_visible_tiles(_block, causal, qi, ki, block_q, block_k,
                      causal_offset, window)

    @pl.when(lax.eq(ki, num_k_blocks - 1))
    def _finalize():
        # guard fully-masked rows (l == 0 never happens when causal includes
        # the diagonal, but ring callers may pass degenerate blocks)
        l = l_scr[...]
        safe = lax.select(lax.eq(l, 0.0), lax.full_like(l, 1.0), l)
        o_ref[0] = lax.convert_element_type(lax.div(acc_scr[...], safe),
                                            o_ref.dtype)
        m_ref[0] = _as_row(m_scr[...])
        l_ref[0] = _as_row(l)


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


def block_sizes(s: int, head_dim: int):
    """``(block_q, block_k)`` for a decoder's self-attention over ``s``
    positions with its heads ``head_dim`` wide each, or None where the
    TPU kernels cannot take that (a head that is neither a lane multiple
    nor `NARROW_HEAD`, a length none of `BLOCKS` tiles). Heads of a lane
    multiple come side by side; `NARROW_HEAD`-wide ones heads first
    (`flash_attention`). A latent head's ``head_dim`` is its no-rope
    columns (and its value's): the rotary columns come as arrays of
    their own (`latent_attention`), whatever their width. The one place
    a caller with no reason of its own gets its tile shape (ring
    attention passes its own). Square, and the largest that tiles: on
    the v5e a larger tile beat a finer causal skip at every length tried
    (PERF.md, PR 26; the sweep ran at ``head_dim`` 128 only)."""
    if head_dim % LANES and head_dim != NARROW_HEAD:
        return None
    for block in BLOCKS:
        if kernel_tiles(s, s, block, block):
            return min(block, s), min(block, s)
    return None


def _kv_heads(heads: int, kv_heads, q_width: int, k_width: int) -> int:
    """``kv_heads`` (None: as many as ``heads``), checked against the
    arrays' widths."""
    kv_heads = heads if kv_heads is None else kv_heads
    if heads % kv_heads or q_width * kv_heads != k_width * heads:
        raise ValueError(
            f"{heads} query heads over {kv_heads} key/value heads need q "
            f"and k/v widths in that ratio, not {q_width} and {k_width}")
    return kv_heads


def _layout(q, k, heads: int, kv_heads):
    """``(B, sq, sk, d, kv_heads, first)`` of a call whose q [B, sq,
    heads*d] and k/v [B, sk, kv_heads*d] come with the heads side by
    side, ``first`` None; or heads first, q [B, heads, sq, d] and k/v
    [B, kv_heads, sk, d], ``first`` q's shape (the kernels then take
    them as `_slabs`). Checked against ``heads`` and ``kv_heads`` (None:
    as many as the arrays say)."""
    if q.ndim == 3:
        B, sq, width = q.shape
        return (B, sq, k.shape[1], width // heads,
                _kv_heads(heads, kv_heads, width, k.shape[2]), None)
    kv_heads = k.shape[1] if kv_heads is None else kv_heads
    if (q.shape[1] != heads or k.shape[1] != kv_heads or heads % kv_heads
            or q.shape[-1] != k.shape[-1]):
        raise ValueError(
            f"heads first, {heads} query heads over {kv_heads} key/value "
            f"heads take q [B, {heads}, s, d] and k/v [B, {kv_heads}, s, "
            f"d], not {q.shape} and {k.shape}")
    return q.shape[0], q.shape[2], k.shape[2], q.shape[3], kv_heads, q.shape


def _slabs(*arrays):
    """Heads-first arrays [B, h, s, d] as [B*h, s, d], each head's rows a
    slab of its own (a free reshape: the heads lead)."""
    return [a.reshape(-1, *a.shape[2:]) for a in arrays]


def _blocks(sq: int, sk: int, block_q: int, block_k: int, d: int,
            heads: int):
    """The blocks as the kernels take them (no longer than the
    sequences) and whether they run interpreted; raises where the
    shapes do not tile."""
    bq, bk = min(block_q, sq), min(block_k, sk)
    interpret = _interpret()
    if not kernel_tiles(sq, sk, bq, bk, lane_aligned=not interpret):
        raise ValueError(
            f"sequence lengths ({sq}, {sk}) must be divisible by the block "
            f"sizes ({bq}, {bk}), and on TPU each block a multiple of "
            f"{LANES} or the whole sequence; pick block_q/block_k that "
            "tile the sequence or use the blockwise XLA fallback "
            "(scan_stats / use_flash=False)")
    if heads > 1 and d % LANES and not interpret:
        raise ValueError(
            f"heads side by side must each be a multiple of {LANES} wide "
            f"on TPU, not {d}: give them heads first, [B, heads, s, d]")
    return bq, bk, interpret


#: every grid is (batch, head, outer block, inner block): the inner one
#: accumulates into scratch, the rest are independent
_GRID_SEMANTICS = pltpu.CompilerParams(dimension_semantics=(
    "parallel", "parallel", "parallel", "arbitrary"))


def _specs(bq: int, bk: int, d: int, heads: int, q_block, k_block,
           q_head=None, kv_head=None, slabs=None):
    """BlockSpecs over a grid ``(b, head, x, y)``: one head's ``d``
    columns of a block of rows of a [b, s, heads*d] array, for Q-like and
    K-like arrays, and a block of a [b*heads, 1, sq] row statistic.
    ``q_block(x, y)`` and ``k_block(x, y)`` give the row blocks. With
    grouped-query heads the grid's head is not every array's:
    ``q_head(h, x, y)`` and ``kv_head(h, x, y)`` give the Q-like and the
    K-like arrays' (default: ``h`` for both). ``slabs`` (the key/value
    heads): the arrays come heads first, [b*heads, s, d] and
    [b*kv_heads, s, d] (`_slabs`), and a block is a block of rows of its
    head's slab.

    The statistics are [.., 1, sq] because a (1, 1, bq) block is legal
    on TPU (second-to-last dim = the whole array's, last a lane
    multiple) and a (1, bq) block of [.., sq] is not."""
    q_head = q_head or (lambda h, x, y: h)
    kv_head = kv_head or (lambda h, x, y: h)
    if slabs is None:
        q_at = lambda b, h, x, y: (b, q_block(x, y), q_head(h, x, y))  # noqa: E731
        k_at = lambda b, h, x, y: (b, k_block(x, y), kv_head(h, x, y))  # noqa: E731
    else:
        def q_at(b, h, x, y):
            return (lax.add(lax.mul(b, heads), q_head(h, x, y)),
                    q_block(x, y), 0)

        def k_at(b, h, x, y):
            return (lax.add(lax.mul(b, slabs), kv_head(h, x, y)),
                    k_block(x, y), 0)
    return (
        pl.BlockSpec((1, bq, d), q_at),
        pl.BlockSpec((1, bk, d), k_at),
        pl.BlockSpec((1, 1, bq), lambda b, h, x, y: (
            lax.add(lax.mul(b, heads), q_head(h, x, y)), 0, q_block(x, y))))


def _visible_k_block(causal: bool, bq: int, bk: int, causal_offset: int,
                     i, j, window=None):
    """K block ``j`` of Q block ``i``'s row of tiles, or, where the mask
    empties that tile, the nearest one it leaves: a tile that does
    nothing holds its neighbour's block index and asks for no DMA
    either."""
    if causal:
        last = lax.div(lax.sub(lax.mul(lax.add(i, 1), bq),
                               causal_offset + 1), bk)
        j = lax.min(j, lax.max(last, 0))
    if window is not None:
        # the block of the oldest key the block's first row still sees
        first = lax.div(lax.max(lax.sub(lax.mul(i, bq), window - 1), 0), bk)
        j = lax.max(j, first)
    return j


def _kv_head_of(heads: int, kv_heads: int):
    """`_specs`' ``kv_head`` for ``heads`` query heads over ``kv_heads``
    key/value heads: query head ``h`` reads head ``h // (heads //
    kv_heads)``; None (the identity) where they are as many."""
    if kv_heads == heads:
        return None
    group = heads // kv_heads
    return lambda h, x, y: lax.div(h, group)


def _kernel_name(stem: str, window) -> str:
    """A trace's kernel events are found by it; the windowed kernels
    carry the window in theirs, so that a reader tells them from the
    global ones and knows which tiles they computed."""
    return stem if window is None else f"{stem}_w{window}"


def _vma(*arrays):
    """Inside a vma-checked shard_map (ring attention) a kernel's outputs
    vary over the mesh axes its inputs vary over; frozenset() elsewhere."""
    return frozenset().union(*(jax.typeof(x).vma for x in arrays))


def _fwd_call(q, k, v, causal: bool, block_q: int, block_k: int,
              causal_offset: int, heads: int, window=None, kv_heads=None):
    """The forward kernel's call: q [B, sq, heads*d], k/v [B, sk,
    kv_heads*d] -> (o [B, sq, heads*d], m, l [B*heads, 1, sq] float32);
    or heads first (`_layout`), o as q."""
    B, sq, sk, d, kv_heads, first = _layout(q, k, heads, kv_heads)
    if first:
        q, k, v = _slabs(q, k, v)
    bq, bk, interpret = _blocks(sq, sk, block_q, block_k, d,
                                1 if first else heads)
    nq, nk = sq // bq, sk // bk
    static = {} if window is None else {"window": window}
    kernel = functools.partial(
        _flash_fwd_kernel, scale=d ** -0.5, causal=causal,
        causal_offset=causal_offset, block_q=bq, block_k=bk,
        num_k_blocks=nk, **static)
    vma = _vma(q, k, v)
    k_block = functools.partial(_visible_k_block, causal, bq, bk,
                                causal_offset, **static)
    q_spec, k_spec, row_spec = _specs(bq, bk, d, heads, lambda i, j: i,
                                      k_block,
                                      kv_head=_kv_head_of(heads, kv_heads),
                                      slabs=kv_heads if first else None)
    row = jax.ShapeDtypeStruct((B * heads, 1, sq), jnp.float32, vma=vma)
    o, m, l = pl.pallas_call(
        kernel,
        grid=(B, heads, nq, nk),
        in_specs=[q_spec, k_spec, k_spec],
        out_specs=[q_spec, row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma), row, row],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=_GRID_SEMANTICS, interpret=interpret,
        name=_kernel_name("hvd_flash_fwd", window),
    )(q, k, v)
    return (o if first is None else o.reshape(first)), m, l


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "causal_offset", "heads"))
def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               causal_offset: int = 0, heads: int = 1):
    """q: [B, sq, heads*d], k/v: [B, sk, heads*d] → (o [B, sq, heads*d],
    m [B*heads, sq], l [B*heads, sq]).

    o is *normalized* (already divided by l); combining across ring steps
    uses (m, l) to undo/redo normalization exactly. With ``heads`` > 1
    each head is ``d`` adjacent columns — a decoder's [b, s, h, hd]
    activations as its projections write them, no transpose — and the
    kernel takes them head by head through its block index.
    """
    o, m, l = _fwd_call(q, k, v, causal, block_q, block_k, causal_offset,
                        heads)
    return o, m[:, 0], l[:, 0]


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "heads", "window", "kv_heads"))
def _flash_fwd_lse(q, k, v, causal: bool, block_q: int, block_k: int,
                   heads: int = 1, window=None, kv_heads=None):
    """`flash_attention`'s forward, for its primal and for its VJP alike:
    (o, lse [B*heads, 1, sq]) with ``lse = m + log l``, all the backward
    kernels need of the softmax (a row the mask empties, l == 0, cannot
    occur at causal_offset 0). One jitted entry for both, so a program
    traces and lowers the forward kernel once however many blocks call
    it, differentiated, recomputed or plain."""
    o, m, l = _fwd_call(q, k, v, causal, block_q, block_k, 0, heads, window,
                        kv_heads)
    return o, lax.add(m, lax.log(l))


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


def _pinned_mesh():
    """JAX keys a jit's trace on the abstract-mesh context, which reads
    None where a ``custom_vjp`` traces its primal and an empty mesh under
    ``jax.checkpoint``'s JVP; pinned to what it is, both find the one
    trace of each kernel's entry (PERF.md, PR 26: 0.2 s of a job's
    set-up)."""
    return jax.sharding.use_abstract_mesh(jax.sharding.get_abstract_mesh())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, heads: int = 1, window=None,
                    kv_heads=None):
    """Fused attention: q [B, sq, heads*d] × k/v [B, sk, kv_heads*d] →
    [B, sq, heads*d], each head ``d`` adjacent columns (``d`` a lane
    multiple on a TPU); or heads first, q [B, heads, sq, d] × k/v [B,
    kv_heads, sk, d] → [B, heads, sq, d] (any ``d``: a head
    `NARROW_HEAD` wide is taken so).

    ``window`` (causal only): query ``i`` sees the keys ``i - window < j
    <= i``; the tiles wholly older than that are skipped like those
    above the diagonal. ``kv_heads`` (default ``heads``): grouped-query
    heads, query head ``h`` reads key/value head ``h // (heads //
    kv_heads)``, and the dK/dV kernel sums over a head's queries."""
    if window is not None and not causal:
        raise ValueError("a window needs causal=True")
    with _pinned_mesh():
        return _flash_fwd_lse(q, k, v, causal, block_q, block_k, heads,
                              window, kv_heads)[0]


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


def _p_and_ds(q, k, v, do, lse, delta, qi, ki, *, scale: float, masked: bool,
              block_q: int, block_k: int, transposed: bool, window=None,
              rotary=None):
    """One tile of the backward pass, recomputed from the forward's
    ``lse``: ``p = exp(s - lse)`` and ``ds = p * (dp - delta)`` (without
    the ``scale`` factor, which the caller applies once to its sum), both
    float32, [bq, bk] — or, ``transposed``, [bk, bq], a K row per
    sublane and a Q row per lane. ``lse``/``delta`` are per Q row:
    [bq, 1] columns, or [1, bq] rows when transposed. ``rotary``: a
    latent head's ``(q_rope [bq, r], k_rope [bk, r])``, the second part
    of its score."""
    a, b, c, e = (k, q, v, do) if transposed else (q, k, do, v)
    s = _dot(a, b, _NT)
    if rotary is not None:
        s = lax.add(s, _dot(*(rotary[::-1] if transposed else rotary), _NT))
    s = lax.mul(s, scale)
    if masked:
        s = _causal(s, qi, ki, block_q, block_k, q_axis=int(transposed),
                    window=window)
    p = lax.exp(lax.sub(s, lse))
    return p, lax.mul(p, lax.sub(_dot(c, e, _NT), delta))


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                          causal: bool, block_q: int, block_k: int,
                          num_q_blocks: int, window=None, group: int = 1,
                          rotary=None):
    if rotary is not None:  # a latent head's rotary parts
        qr_ref, kr_ref, dkr_ref, dkr_scr = rotary
    ki = pl.program_id(2)
    # the inner axis walks the Q blocks of each of the ``group`` query
    # heads that read this key/value head, one head after the other
    step = pl.program_id(3)
    qi = step if group == 1 else lax.rem(step, num_q_blocks)

    @pl.when(lax.eq(step, 0))
    def _init():
        dk_scr[...] = _zeros(dk_scr)
        dv_scr[...] = _zeros(dv_scr)
        if rotary is not None:
            dkr_scr[...] = _zeros(dkr_scr)

    def _block(masked: bool):
        # the tile is held TRANSPOSED: the per-Q-row statistics are
        # [1, bq] rows as they come from HBM, and both accumulations are
        # plain [bk, bq] x [bq, d] products, with nothing to transpose
        q, do = q_ref[0], do_ref[0]       # [bq, d]
        pt, dst = _p_and_ds(
            q, k_ref[0], v_ref[0], do, lse_ref[0], delta_ref[0], qi, ki,
            scale=scale, masked=masked, block_q=block_q, block_k=block_k,
            transposed=True, **({} if window is None else {"window": window}),
            **({} if rotary is None else
               {"rotary": (qr_ref[0, 0], kr_ref[0])}))
        dv_scr[...] = lax.add(dv_scr[...], _dot(
            lax.convert_element_type(pt, do.dtype), do, _NN))
        dk = dk_scr[...]
        dst = lax.convert_element_type(dst, q.dtype)
        dk_scr[...] = lax.add(dk, _dot(dst, q, _NN))
        if rotary is not None:
            dkr_scr[...] = lax.add(dkr_scr[...], _dot(dst, qr_ref[0, 0], _NN))

    _on_visible_tiles(_block, causal, qi, ki, block_q, block_k,
                      window=window)

    @pl.when(lax.eq(step, group * num_q_blocks - 1))
    def _finalize():
        dk_ref[0] = lax.convert_element_type(lax.mul(dk_scr[...], scale),
                                             dk_ref.dtype)
        dv_ref[0] = lax.convert_element_type(dv_scr[...], dv_ref.dtype)
        if rotary is not None:
            dkr_ref[0, 0] = lax.convert_element_type(
                lax.mul(dkr_scr[...], scale), dkr_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                         dq_ref, delta_ref, dq_scr, lse_scr, delta_scr, *,
                         scale: float, causal: bool, block_q: int,
                         block_k: int, num_k_blocks: int, window=None,
                         rotary=None):
    if rotary is not None:  # a latent head's rotary parts
        qr_ref, kr_ref, dqr_ref, dqr_scr = rotary
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(lax.eq(ki, 0))
    def _init():
        dq_scr[...] = _zeros(dq_scr)
        if rotary is not None:
            dqr_scr[...] = _zeros(dqr_scr)
        lse_scr[...] = _as_col(lse_ref[0])
        # delta = rowsum(do * o), once per Q block while both are here;
        # it leaves as a row too, for the dK/dV kernel
        delta = _row_sum(lax.mul(
            lax.convert_element_type(do_ref[0], jnp.float32),
            lax.convert_element_type(o_ref[0], jnp.float32)))   # [bq, 1]
        delta_scr[...] = delta
        delta_ref[0] = _as_row(delta)

    def _block(masked: bool):
        k = k_ref[0]                      # [bk, d]
        _, ds = _p_and_ds(
            q_ref[0], k, v_ref[0], do_ref[0], lse_scr[...], delta_scr[...],
            qi, ki, scale=scale, masked=masked, block_q=block_q,
            block_k=block_k, transposed=False,
            **({} if window is None else {"window": window}),
            **({} if rotary is None else
               {"rotary": (qr_ref[0, 0], kr_ref[0])}))
        dq = dq_scr[...]
        ds = lax.convert_element_type(ds, k.dtype)
        dq_scr[...] = lax.add(dq, _dot(ds, k, _NN))
        if rotary is not None:
            dqr_scr[...] = lax.add(dqr_scr[...], _dot(ds, kr_ref[0], _NN))

    _on_visible_tiles(_block, causal, qi, ki, block_q, block_k,
                      window=window)

    @pl.when(lax.eq(ki, num_k_blocks - 1))
    def _finalize():
        dq_ref[0] = lax.convert_element_type(lax.mul(dq_scr[...], scale),
                                             dq_ref.dtype)
        if rotary is not None:
            dqr_ref[0, 0] = lax.convert_element_type(
                lax.mul(dqr_scr[...], scale), dqr_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "heads", "window", "kv_heads"))
def _flash_bwd(q, k, v, do, o, lse, causal: bool, block_q: int,
               block_k: int, heads: int = 1, window=None, kv_heads=None):
    """q/do/o: [B, sq, heads*d], k/v: [B, sk, kv_heads*d], lse:
    [B*heads, 1, sq] float32 → (dq, dk, dv): the VJP of
    `flash_attention` as two kernels; or heads first (`_layout`), each
    gradient as its array. The dQ kernel runs first: it has ``do`` and
    ``o`` of a Q block together, so ``delta = rowsum(do * o)`` is made
    there and handed to the dK/dV kernel. With grouped-query heads the
    dK/dV kernel's grid runs over the key/value heads, and its inner
    axis over every Q block of every query head of the group: the sum
    over a head's queries is made in its scratch."""
    B, sq, sk, d, kv_heads, first = _layout(q, k, heads, kv_heads)
    if first:
        shapes = q.shape, k.shape
        q, k, v, do, o = _slabs(q, k, v, do, o)
    group = heads // kv_heads
    bq, bk, interpret = _blocks(sq, sk, block_q, block_k, d,
                                1 if first else heads)
    nq, nk = sq // bq, sk // bk
    windowed = {} if window is None else {"window": window}
    static = dict(scale=d ** -0.5, causal=causal, block_q=bq, block_k=bk,
                  **windowed)
    vma = _vma(q, k, v, do)
    params = dict(compiler_params=_GRID_SEMANTICS, interpret=interpret)

    # as in the forward, a tile the mask empties holds the block index
    # of the nearest visible one
    def q_block(j, i):   # dkv: Q blocks above K block j's diagonal
        if group > 1:
            i = lax.rem(i, nq)
        if causal:
            i = lax.min(lax.max(i, lax.div(lax.mul(j, bk), bq)), nq - 1)
        if window is not None:
            # the block of the last query that still sees this K block
            i = lax.min(i, lax.div(
                lax.add(lax.mul(lax.add(j, 1), bk), window - 2), bq))
        return i

    slabs = kv_heads if first else None
    q_spec, k_spec, row_spec = _specs(
        bq, bk, d, heads, lambda i, j: i,
        functools.partial(_visible_k_block, causal, bq, bk, 0, **windowed),
        kv_head=_kv_head_of(heads, kv_heads), slabs=slabs)
    row = jax.ShapeDtypeStruct(lse.shape, jnp.float32, vma=vma)
    dq, delta = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, num_k_blocks=nk, **static),
        grid=(B, heads, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, q_spec, row_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma), row],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        name=_kernel_name("hvd_flash_bwd_dq", window), **params,
    )(q, k, v, do, o, lse)
    grouped = {} if group == 1 else dict(
        group=group,
        # grid head = the key/value head; the inner step's query head
        q_head=lambda h, j, i: lax.add(lax.mul(h, group), lax.div(i, nq)))
    q_spec, k_spec, row_spec = _specs(bq, bk, d, heads, q_block,
                                      lambda j, i: j,
                                      q_head=grouped.pop("q_head", None),
                                      slabs=slabs)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, num_q_blocks=nq, **static,
                          **grouped),
        grid=(B, kv_heads, nk, group * nq),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
            jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        name=_kernel_name("hvd_flash_bwd_dkv", window), **params,
    )(q, k, v, do, lse, delta)
    if first:
        return dq.reshape(shapes[0]), dk.reshape(shapes[1]), dv.reshape(
            shapes[1])
    return dq, dk, dv


def _fwd(q, k, v, causal, block_q, block_k, heads, window, kv_heads):
    with _pinned_mesh():
        o, lse = _flash_fwd_lse(q, k, v, causal, block_q, block_k, heads,
                                window, kv_heads)
    # named, so that a ``jax.checkpoint`` around the caller can keep what
    # the backward kernels read (``save_only_these_names``) and not run
    # this forward a second time; the identity outside a checkpoint. The
    # caller gets the named ``o``: what it does with the output (its
    # projection's weight gradient) then reads the kept array too
    q, k, v, o, lse = map(ad_checkpoint.checkpoint_name, (q, k, v, o, lse),
                          scopes.KEPT_BY_REMAT)
    return o, (q, k, v, o, lse)


def _bwd(causal, block_q, block_k, heads, window, kv_heads, res, do):
    q, k, v, o, lse = res
    with jax.named_scope(scopes.ATTENTION):
        return _flash_bwd(q, k, v, do, o, lse, causal, block_q, block_k,
                          heads, window, kv_heads)


flash_attention.defvjp(_fwd, _bwd)


# -- latent heads: a score of two parts, one rotary key for all heads -------

def _rope_specs(bq: int, bk: int, r: int, q_block, k_block):
    """BlockSpecs, over `_specs`' grid, of a latent head's rotary parts:
    a block of one head's rows of q_rope [b, heads, sq, r] (the heads
    lead: ``r`` is no lane multiple, and a block's last dimension is one
    or the whole array's) and of the one k_rope [b, sk, r] every head
    reads. dk_rope, per head, is laid out as q_rope is."""
    return (pl.BlockSpec((1, 1, bq, r), lambda b, h, x, y: (
                b, h, q_block(x, y), 0)),
            pl.BlockSpec((1, bk, r), lambda b, h, x, y: (
                b, k_block(x, y), 0)),
            pl.BlockSpec((1, 1, bk, r), lambda b, h, x, y: (
                b, h, k_block(x, y), 0)))


def _latent_fwd_kernel(q_ref, qr_ref, k_ref, kr_ref, v_ref, *rest, **static):
    _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, rotary=(qr_ref, kr_ref),
                      **static)


def _latent_bwd_dq_kernel(q_ref, qr_ref, k_ref, kr_ref, v_ref, do_ref, o_ref,
                          lse_ref, dq_ref, dqr_ref, delta_ref, dq_scr,
                          dqr_scr, lse_scr, delta_scr, **static):
    _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
                         delta_ref, dq_scr, lse_scr, delta_scr,
                         rotary=(qr_ref, kr_ref, dqr_ref, dqr_scr), **static)


def _latent_bwd_dkv_kernel(q_ref, qr_ref, k_ref, kr_ref, v_ref, do_ref,
                           lse_ref, delta_ref, dk_ref, dkr_ref, dv_ref,
                           dk_scr, dkr_scr, dv_scr, **static):
    _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr,
                          rotary=(qr_ref, kr_ref, dkr_ref, dkr_scr), **static)


def _latent_shapes(q, q_rope, k, k_rope, v, heads: int):
    """(B, s, d, r) of a latent call, checked: q, k, v [B, s, heads*d],
    q_rope [B, heads, s, r], k_rope [B, s, r]."""
    B, s, width = q.shape
    r = k_rope.shape[-1]
    if (width % heads or k.shape != q.shape or v.shape != q.shape
            or q_rope.shape != (B, heads, s, r) or k_rope.shape != (B, s, r)):
        raise ValueError(
            f"latent heads take q, k, v [B, s, {heads}*d], q_rope [B, "
            f"{heads}, s, r] and k_rope [B, s, r], not {q.shape}, {k.shape}, "
            f"{v.shape}, {q_rope.shape}, {k_rope.shape}")
    return B, s, width // heads, r


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "heads"))
def _latent_fwd_lse(q, q_rope, k, k_rope, v, block_q: int, block_k: int,
                    heads: int = 1):
    """`latent_attention`'s forward, for its primal and its VJP alike
    (as `_flash_fwd_lse`): (o [B, s, heads*d], lse [B*heads, 1, s])."""
    B, s, d, r = _latent_shapes(q, q_rope, k, k_rope, v, heads)
    bq, bk, interpret = _blocks(s, s, block_q, block_k, d, heads)
    vma = _vma(q, q_rope, k, k_rope, v)
    k_block = functools.partial(_visible_k_block, True, bq, bk, 0)
    q_spec, k_spec, row_spec = _specs(bq, bk, d, heads, lambda i, j: i,
                                      k_block)
    qr_spec, kr_spec, _ = _rope_specs(bq, bk, r, lambda i, j: i, k_block)
    row = jax.ShapeDtypeStruct((B * heads, 1, s), jnp.float32, vma=vma)
    o, m, l = pl.pallas_call(
        functools.partial(_latent_fwd_kernel, scale=(d + r) ** -0.5,
                          causal=True, causal_offset=0, block_q=bq,
                          block_k=bk, num_k_blocks=s // bk),
        grid=(B, heads, s // bq, s // bk),
        in_specs=[q_spec, qr_spec, k_spec, kr_spec, k_spec],
        out_specs=[q_spec, row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma), row, row],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=_GRID_SEMANTICS, interpret=interpret,
        name="hvd_mla_fwd",
    )(q, q_rope, k, k_rope, v)
    return o, lax.add(m, lax.log(l))


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "heads"))
def _latent_bwd(q, q_rope, k, k_rope, v, do, o, lse, block_q: int,
                block_k: int, heads: int = 1):
    """The VJP of `latent_attention` as two kernels, as `_flash_bwd`:
    → (dq, dq_rope, dk, dk_rope, dv). The dK/dV kernel writes each
    head's part of dk_rope, [B, heads, s, r]; their sum over the heads,
    in float32, is the one rotary key's gradient."""
    B, s, d, r = _latent_shapes(q, q_rope, k, k_rope, v, heads)
    bq, bk, interpret = _blocks(s, s, block_q, block_k, d, heads)
    nq, nk = s // bq, s // bk
    static = dict(scale=(d + r) ** -0.5, causal=True, block_q=bq, block_k=bk)
    vma = _vma(q, q_rope, k, k_rope, v, do)
    params = dict(compiler_params=_GRID_SEMANTICS, interpret=interpret)

    def like(x, shape=None):
        return jax.ShapeDtypeStruct(shape or x.shape, x.dtype, vma=vma)

    k_block = functools.partial(_visible_k_block, True, bq, bk, 0)
    q_spec, k_spec, row_spec = _specs(bq, bk, d, heads, lambda i, j: i,
                                      k_block)
    qr_spec, kr_spec, _ = _rope_specs(bq, bk, r, lambda i, j: i, k_block)
    dq, dq_rope, delta = pl.pallas_call(
        functools.partial(_latent_bwd_dq_kernel, num_k_blocks=nk, **static),
        grid=(B, heads, nq, nk),
        in_specs=[q_spec, qr_spec, k_spec, kr_spec, k_spec, q_spec, q_spec,
                  row_spec],
        out_specs=[q_spec, qr_spec, row_spec],
        out_shape=[like(q), like(q_rope),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32, vma=vma)],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, r), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        name="hvd_mla_bwd_dq", **params,
    )(q, q_rope, k, k_rope, v, do, o, lse)

    def q_block(j, i):   # Q blocks above K block j's diagonal
        return lax.min(lax.max(i, lax.div(lax.mul(j, bk), bq)), nq - 1)

    q_spec, k_spec, row_spec = _specs(bq, bk, d, heads, q_block,
                                      lambda j, i: j)
    qr_spec, kr_spec, dkr_spec = _rope_specs(bq, bk, r, q_block,
                                             lambda j, i: j)
    dk, dk_rope, dv = pl.pallas_call(
        functools.partial(_latent_bwd_dkv_kernel, num_q_blocks=nq, **static),
        grid=(B, heads, nk, nq),
        in_specs=[q_spec, qr_spec, k_spec, kr_spec, k_spec, q_spec, row_spec,
                  row_spec],
        out_specs=[k_spec, dkr_spec, k_spec],
        out_shape=[like(k), like(k_rope, (B, heads, s, r)), like(v)],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, r), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        name="hvd_mla_bwd_dkv", **params,
    )(q, q_rope, k, k_rope, v, do, lse, delta)
    dk_rope = jnp.sum(dk_rope, axis=1, dtype=jnp.float32).astype(k_rope.dtype)
    return dq, dq_rope, dk, dk_rope, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def latent_attention(q, q_rope, k, k_rope, v, block_q: int = 512,
                     block_k: int = 512, heads: int = 1):
    """Fused causal attention over latent (MLA) heads, whose query/key
    width differs from their value's: head ``a``'s score of query ``i``
    against key ``j <= i`` is ``(q[i, a] . k[j, a] + q_rope[i, a] .
    k_rope[j]) / sqrt(d + r)``, the second part against one rotary key
    that all heads share (never copied per head). q, k, v: [B, s,
    heads*d], each head ``d`` adjacent columns; q_rope: [B, heads, s, r];
    k_rope: [B, s, r] → [B, s, heads*d]. Three kernels of their own
    names (``hvd_mla_fwd``, ``hvd_mla_bwd_dq``, ``hvd_mla_bwd_dkv``):
    `flash_attention`'s bodies with the second product in each tile's
    score and ``ds``'s two further products."""
    with _pinned_mesh():
        return _latent_fwd_lse(q, q_rope, k, k_rope, v, block_q, block_k,
                               heads)[0]


def _latent_fwd(q, q_rope, k, k_rope, v, block_q, block_k, heads):
    with _pinned_mesh():
        o, lse = _latent_fwd_lse(q, q_rope, k, k_rope, v, block_q, block_k,
                                 heads)
    # named as `_fwd` names its own, and for its reason
    res = tuple(map(ad_checkpoint.checkpoint_name,
                    (q, q_rope, k, k_rope, v, o, lse),
                    scopes.KEPT_BY_REMAT_LATENT))
    return res[5], res


def _latent_bwd_rule(block_q, block_k, heads, res, do):
    with jax.named_scope(scopes.ATTENTION):
        return _latent_bwd(*res[:5], do, *res[5:], block_q, block_k, heads)


latent_attention.defvjp(_latent_fwd, _latent_bwd_rule)


# -- learned sparse attention: each query over the keys it selected --------

#: VMEM the sparse kernels ask for besides what their heads or their
#: unpacked mask add: the compiler's default scoped limit on a v5e, in
#: which the one-head kernels and the [bq, bk] float32 tiles a head's
#: body makes fit at 1024-blocks
DSA_TILE_VMEM = 16 * 2 ** 20
#: VMEM the sparse forward kernel gives the query heads of one grid step
#: (`mask_heads`): eight heads of 128 at 1024-blocks in bf16, a key/value
#: head's group in the learned-sparse cell, take 20.5 MiB of it, and the
#: call then asks for 36.5 MiB of the v5e's 128
DSA_HEADS_VMEM = 24 * 2 ** 20


def _unpack(words):
    """[n, cols] int32 words → [32 n, cols] bool: row ``32 r + i`` is bit
    ``i`` of word row ``r``."""
    n, cols = words.shape
    shape = (n, WORD, cols)
    wide = lax.broadcast_in_dim(words, shape, (0, 2))
    bit = lax.shift_right_logical(
        wide, lax.broadcasted_iota(jnp.int32, shape, 1))
    return lax.ne(lax.reshape(lax.bitwise_and(bit, 1), (n * WORD, cols)), 0)


def _head_columns(h, d: int):
    """Head ``h``'s ``d`` columns of a block that holds a step's heads
    side by side: a static slice, or in a loop over the heads a dynamic
    one a lane multiple apart."""
    if isinstance(h, int):
        return slice(h * d, (h + 1) * d)
    return pl.ds(pl.multiple_of(lax.mul(h, d), d), d)


def _dsa_fwd_kernel(tiles_ref, q_ref, k_ref, v_ref, sel_ref, o_ref, lse_ref,
                    acc_scr, m_scr, l_scr, *, scale: float, nq: int, nk: int):
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    hs, _, d = acc_scr.shape   # the step's query heads, one K/V head's

    @pl.when(lax.eq(ki, 0))
    def _init():
        m_scr[...] = lax.full(m_scr.shape, NEG_INF, m_scr.dtype)
        l_scr[...] = _zeros(l_scr)
        acc_scr[...] = _zeros(acc_scr)

    @pl.when(lax.ne(tiles_ref[(b * nq + qi) * nk + ki], 0))
    def _block():
        keep = _unpack(sel_ref[0])            # [bq, bk], once for the heads
        k, v = k_ref[0], v_ref[0]

        def head(h):
            s = lax.mul(_dot(q_ref[0, :, _head_columns(h, d)], k, _NT), scale)
            s = lax.select(keep, s, lax.full_like(s, NEG_INF))
            m_prev = m_scr[h]
            m_new = lax.max(m_prev, _row_max(s))
            # a row with no selected key in this tile adds nothing, also
            # while its running max is still NEG_INF
            p = lax.select(keep, lax.exp(lax.sub(s, m_new)),
                           lax.full_like(s, 0))
            alpha = lax.exp(lax.sub(m_prev, m_new))
            l_scr[h] = lax.add(lax.mul(l_scr[h], alpha), _row_sum(p))
            acc_scr[h] = lax.add(
                lax.mul(acc_scr[h], alpha),
                _dot(lax.convert_element_type(p, v.dtype), v, _NN))
            m_scr[h] = m_new

        # a loop the compiler schedules once; unrolled, every head's
        # [bq, bk] tiles would be live at once
        lax.fori_loop(0, hs, lambda h, carry: (head(h), carry)[1], 0)

    @pl.when(lax.eq(ki, nk - 1))
    def _finalize():
        for h in range(hs):
            l = l_scr[h]
            safe = lax.select(lax.eq(l, 0.0), lax.full_like(l, 1.0), l)
            o_ref[0, :, _head_columns(h, d)] = lax.convert_element_type(
                lax.div(acc_scr[h], safe), o_ref.dtype)
            lse_ref[h] = _as_row(lax.add(m_scr[h], lax.log(safe)))


def _dsa_bwd_dq_kernel(tiles_ref, q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                       sel_ref, dq_ref, delta_ref, dq_scr, lse_scr, delta_scr,
                       *, scale: float, nq: int, nk: int):
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(lax.eq(ki, 0))
    def _init():
        dq_scr[...] = _zeros(dq_scr)
        lse_scr[...] = _as_col(lse_ref[0])
        delta = _row_sum(lax.mul(
            lax.convert_element_type(do_ref[0], jnp.float32),
            lax.convert_element_type(o_ref[0], jnp.float32)))   # [bq, 1]
        delta_scr[...] = delta
        delta_ref[0] = _as_row(delta)

    @pl.when(lax.ne(tiles_ref[(b * nq + qi) * nk + ki], 0))
    def _block():
        k = k_ref[0]
        s = lax.mul(_dot(q_ref[0], k, _NT), scale)
        p = lax.exp(lax.sub(s, lse_scr[...]))
        p = lax.select(_unpack(sel_ref[0]), p, lax.full_like(p, 0))
        ds = lax.mul(p, lax.sub(_dot(do_ref[0], v_ref[0], _NT),
                                delta_scr[...]))
        dq_scr[...] = lax.add(dq_scr[...], _dot(
            lax.convert_element_type(ds, k.dtype), k, _NN))

    @pl.when(lax.eq(ki, nk - 1))
    def _finalize():
        dq_ref[0] = lax.convert_element_type(lax.mul(dq_scr[...], scale),
                                             dq_ref.dtype)


def _dsa_bwd_dkv_kernel(tiles_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, sel_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                        mask_scr, *, scale: float, nq: int, nk: int,
                        group: int):
    b, ki, step = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    # the inner axis walks the Q blocks, and for each the ``group`` query
    # heads of this key/value head one after the other: they read one
    # selection tile, unpacked at the first of them into ``mask_scr``
    qi = lax.div(step, group)
    live = lax.ne(tiles_ref[(b * nq + qi) * nk + ki], 0)

    @pl.when(lax.eq(step, 0))
    def _init():
        dk_scr[...] = _zeros(dk_scr)
        dv_scr[...] = _zeros(dv_scr)

    @pl.when(lax.bitwise_and(live, lax.eq(lax.rem(step, group), 0)))
    def _unpack_once():
        mask_scr[...] = lax.convert_element_type(_unpack(sel_ref[0]),
                                                 jnp.int32)

    @pl.when(live)
    def _block():
        # held transposed, a K row per sublane, as `_flash_bwd_dkv_kernel`
        q, do = q_ref[0], do_ref[0]
        st = lax.mul(_dot(k_ref[0], q, _NT), scale)      # [bk, bq]
        pt = lax.exp(lax.sub(st, lse_ref[0]))
        pt = lax.select(lax.ne(mask_scr[...], 0), pt, lax.full_like(pt, 0))
        dst = lax.mul(pt, lax.sub(_dot(v_ref[0], do, _NT), delta_ref[0]))
        dv_scr[...] = lax.add(dv_scr[...], _dot(
            lax.convert_element_type(pt, do.dtype), do, _NN))
        dk_scr[...] = lax.add(dk_scr[...], _dot(
            lax.convert_element_type(dst, q.dtype), q, _NN))

    @pl.when(lax.eq(step, group * nq - 1))
    def _finalize():
        dk_ref[0] = lax.convert_element_type(lax.mul(dk_scr[...], scale),
                                             dk_ref.dtype)
        dv_ref[0] = lax.convert_element_type(dv_scr[...], dv_ref.dtype)


def _after_prefetch(spec):
    """``spec`` for a grid with scalar prefetch: its index map is called
    with the prefetched array after the grid's indices, and reads none."""
    return pl.BlockSpec(spec.block_shape,
                        lambda b, h, x, y, _: spec.index_map(b, h, x, y))


def _dsa_name(stem: str, topk: int) -> str:
    """A trace's reader counts the operations of the selected pairs, for
    which it needs the keys a query selects: the name carries them."""
    return f"{stem}_k{topk}"


def _dsa_call(kernel, name: str, grid, in_specs, out_specs, out_shape,
              scratch, interpret: bool, tiles, *args, vmem=None):
    """``vmem``: the kernel's VMEM limit in bytes (None: the compiler's
    default)."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[_after_prefetch(s) for s in in_specs],
            out_specs=[_after_prefetch(s) for s in out_specs],
            scratch_shapes=scratch),
        out_shape=out_shape, compiler_params=_GRID_SEMANTICS if vmem is None
        else dataclasses.replace(_GRID_SEMANTICS, vmem_limit_bytes=vmem),
        interpret=interpret, name=name,
    )(tiles, *args)


def _head_vmem(block_q: int, d: int, dtype) -> int:
    """The VMEM one query head adds to a step of the sparse forward
    kernel at Q blocks of ``block_q`` rows and heads ``d`` wide in
    ``dtype``: its Q and O blocks, each double-buffered, its float32
    accumulator, its running max and sum ([bq, 1] columns a lane tile
    wide) and its lse block (a row eight sublanes high, twice)."""
    return block_q * (4 * d * jnp.dtype(dtype).itemsize
                      + 4 * d + 2 * 4 * LANES + 2 * 4 * 8)


def mask_heads(heads: int, kv_heads: int, block_q: int, d: int,
               dtype) -> int:
    """The query heads one grid step of the sparse forward kernel takes,
    all of one key/value head's: the largest divisor of the group whose
    `_head_vmem` fits `DSA_HEADS_VMEM`, one at least. The step unpacks
    its selection tile once for them all."""
    group = heads // kv_heads
    return max(n for n in range(1, group + 1) if group % n == 0 and (
        n == 1 or n * _head_vmem(block_q, d, dtype) <= DSA_HEADS_VMEM))


def _head_block_specs(bq: int, bk: int, d: int, hs: int, heads: int,
                      kv_heads: int, q_block, k_block):
    """BlockSpecs over a grid ``(b, g, x, y)`` whose ``g`` walks the query
    heads ``hs`` at a time, all ``hs`` of one key/value head's: their
    ``hs*d`` adjacent columns of a block of rows of a [b, s, heads*d]
    array (Q-like), their key/value head's ``d`` columns of a [b, s,
    kv_heads*d] array (K-like), and their (hs, 1, bq) block of a
    [b*heads, 1, s] row statistic. With ``hs`` 1, `_specs`' blocks for
    grouped heads."""
    steps = heads // kv_heads // hs   # grid steps a key/value head
    return (
        pl.BlockSpec((1, bq, hs * d), lambda b, g, x, y: (
            b, q_block(x, y), g)),
        pl.BlockSpec((1, bk, d), lambda b, g, x, y: (
            b, k_block(x, y), lax.div(g, steps))),
        pl.BlockSpec((hs, 1, bq), lambda b, g, x, y: (
            lax.add(lax.mul(b, heads // hs), g), 0, q_block(x, y))))


def _dsa_blocks(q, k, selection, block_q: int, block_k: int, heads: int,
                kv_heads: int):
    """(B, s, d, bq, bk, interpret) of a sparse call, checked: q [B, s,
    heads*d], k/v [B, s, kv_heads*d], the selection's words [B, s/32, s]
    each way and its tiles at these blocks."""
    B, s, width = q.shape
    _kv_heads(heads, kv_heads, width, k.shape[2])
    by_query, by_key, tiles = selection
    bq, bk, interpret = _blocks(s, s, block_q, block_k, width // heads, heads)
    if (by_query.shape != (B, s // WORD, s) or by_key.shape != by_query.shape
            or tiles.shape != (B * (s // bq) * (s // bk),)
            or bq % WORD or bk % WORD):
        raise ValueError(
            f"a selection of [{B}, {s}, {s}] at blocks ({bq}, {bk}) is words "
            f"[B, s/32, s] each way and [B * nq * nk] tiles, not "
            f"{by_query.shape}, {by_key.shape}, {tiles.shape}")
    return B, s, width // heads, bq, bk, interpret


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "heads",
                                             "kv_heads", "topk"))
def _dsa_fwd_lse(q, k, v, selection, block_q: int, block_k: int, heads: int,
                 kv_heads: int, topk: int):
    """`sparse_attention`'s forward, for its primal and its VJP alike (as
    `_flash_fwd_lse`): (o [B, s, heads*d], lse [B*heads, 1, s])."""
    B, s, d, bq, bk, interpret = _dsa_blocks(q, k, selection, block_q,
                                             block_k, heads, kv_heads)
    by_query, _, tiles = selection
    nq, nk = s // bq, s // bk
    hs = mask_heads(heads, kv_heads, bq, d, q.dtype)
    vma = _vma(q, k, v)
    k_block = functools.partial(_visible_k_block, True, bq, bk, 0)
    q_spec, k_spec, row_spec = _head_block_specs(
        bq, bk, d, hs, heads, kv_heads, lambda i, j: i, k_block)
    sel_spec = pl.BlockSpec((1, bq // WORD, bk), lambda b, g, i, j: (
        b, i, k_block(i, j)))
    row = jax.ShapeDtypeStruct((B * heads, 1, s), jnp.float32, vma=vma)
    return _dsa_call(
        functools.partial(_dsa_fwd_kernel, scale=d ** -0.5, nq=nq, nk=nk),
        _dsa_name("hvd_dsa_fwd", topk), (B, heads // hs, nq, nk),
        [q_spec, k_spec, k_spec, sel_spec], [q_spec, row_spec],
        [jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma), row],
        [pltpu.VMEM((hs, bq, d), jnp.float32),
         pltpu.VMEM((hs, bq, 1), jnp.float32),
         pltpu.VMEM((hs, bq, 1), jnp.float32)],
        interpret, tiles, q, k, v, by_query,
        vmem=DSA_TILE_VMEM + hs * _head_vmem(bq, d, q.dtype))


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "heads",
                                             "kv_heads", "topk"))
def _dsa_bwd(q, k, v, selection, do, o, lse, block_q: int, block_k: int,
             heads: int, kv_heads: int, topk: int):
    """The VJP of `sparse_attention` as two kernels, as `_flash_bwd`:
    (dq, dk, dv). The dK/dV kernel's inner axis walks the Q blocks and,
    for each, the group's query heads, so that they read one unpacked
    selection tile."""
    B, s, d, bq, bk, interpret = _dsa_blocks(q, k, selection, block_q,
                                             block_k, heads, kv_heads)
    by_query, by_key, tiles = selection
    group = heads // kv_heads
    nq, nk = s // bq, s // bk
    static = dict(scale=d ** -0.5, nq=nq, nk=nk)
    vma = _vma(q, k, v, do)
    k_block = functools.partial(_visible_k_block, True, bq, bk, 0)
    q_spec, k_spec, row_spec = _specs(bq, bk, d, heads, lambda i, j: i,
                                      k_block,
                                      kv_head=_kv_head_of(heads, kv_heads))
    sel_spec = pl.BlockSpec((1, bq // WORD, bk), lambda b, h, i, j: (
        b, i, k_block(i, j)))
    row = jax.ShapeDtypeStruct(lse.shape, jnp.float32, vma=vma)
    dq, delta = _dsa_call(
        functools.partial(_dsa_bwd_dq_kernel, **static),
        _dsa_name("hvd_dsa_bwd_dq", topk), (B, heads, nq, nk),
        [q_spec, k_spec, k_spec, q_spec, q_spec, row_spec, sel_spec],
        [q_spec, row_spec],
        [jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma), row],
        [pltpu.VMEM((bq, d), jnp.float32), pltpu.VMEM((bq, 1), jnp.float32),
         pltpu.VMEM((bq, 1), jnp.float32)],
        interpret, tiles, q, k, v, do, o, lse, by_query)

    def first_q(j):   # the first Q block that sees K block j
        return lax.div(lax.mul(j, bk), bq)

    def q_block(j, i):   # Q blocks above K block j's diagonal hold
        return lax.min(lax.max(lax.div(i, group), first_q(j)), nq - 1)

    def q_head(h, j, i):  # and there the group's first head: no DMA
        held = lax.lt(lax.div(i, group), first_q(j))
        return lax.add(lax.mul(h, group),
                       lax.select(held, 0, lax.rem(i, group)))

    q_spec, k_spec, row_spec = _specs(
        bq, bk, d, heads, q_block, lambda j, i: j,
        q_head=q_head)
    sel_spec = pl.BlockSpec((1, bk // WORD, bq), lambda b, h, j, i: (
        b, j, q_block(j, i)))
    dk, dv = _dsa_call(
        functools.partial(_dsa_bwd_dkv_kernel, group=group, **static),
        _dsa_name("hvd_dsa_bwd_dkv", topk), (B, kv_heads, nk, group * nq),
        [q_spec, k_spec, k_spec, q_spec, row_spec, row_spec, sel_spec],
        [k_spec, k_spec],
        [jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma),
         jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma)],
        [pltpu.VMEM((bk, d), jnp.float32), pltpu.VMEM((bk, d), jnp.float32),
         pltpu.VMEM((bk, bq), jnp.int32)],
        interpret, tiles, q, k, v, do, lse, delta, by_key,
        vmem=DSA_TILE_VMEM + bk * bq * 4)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def sparse_attention(q, k, v, selection, block_q: int = 512,
                     block_k: int = 512, heads: int = 1, kv_heads=None,
                     topk: int = 0):
    """Fused causal attention of each query over the keys it selected
    (learned sparse attention): q [B, s, heads*d] × k/v [B, s,
    kv_heads*d] → (o [B, s, heads*d], lse [B*heads, 1, s]), grouped
    heads as `flash_attention`; ``lse``, each head's log-sum-exp of its
    scaled scores over the selection, takes no gradient (a caller reads
    it as a constant: `sparse_target`).
    ``selection`` is ``(by_query, by_key, tiles)``: the selection packed
    by query and by key (``ops/selection.py`` ``pack``, [B, s/32, s]
    int32 each) and which tiles at these blocks hold a selected key
    (``selection.tiles``); it holds no key after its query, and takes no
    gradient. Three kernels of their own names (``hvd_dsa_fwd``,
    ``hvd_dsa_bwd_dq``, ``hvd_dsa_bwd_dkv``, each ``_k<topk>``:
    ``topk``, the keys a query selects, names them and changes nothing
    else): `flash_attention`'s bodies with the selection as the mask of
    every tile, and a tile that holds no selected key skipped. The
    selection is one per query, whatever the head: the forward kernel
    takes `mask_heads` query heads of one key/value head a grid step and
    the dK/dV kernel walks a group's heads one after the other on a
    tile, each unpacking the tile once for them."""
    with _pinned_mesh():
        return _dsa_fwd_lse(q, k, v, selection, block_q, block_k, heads,
                            kv_heads or heads, topk)


def _sparse_fwd(q, k, v, selection, block_q, block_k, heads, kv_heads,
                topk):
    with _pinned_mesh():
        o, lse = _dsa_fwd_lse(q, k, v, selection, block_q, block_k, heads,
                              kv_heads or heads, topk)
    # named as `_fwd` names its own, and for its reason; the selection is
    # named where it is made (models/transformer.py)
    q, k, v, o, lse = map(ad_checkpoint.checkpoint_name, (q, k, v, o, lse),
                          scopes.KEPT_BY_REMAT)
    return (o, lse), (q, k, v, selection, o, lse)


def _sparse_bwd(block_q, block_k, heads, kv_heads, topk, res, cts):
    q, k, v, selection, o, lse = res
    do = cts[0]
    with jax.named_scope(scopes.ATTENTION):
        dq, dk, dv = _dsa_bwd(q, k, v, selection, do, o, lse, block_q,
                              block_k, heads, kv_heads or heads, topk)
    return dq, dk, dv, None


sparse_attention.defvjp(_sparse_fwd, _sparse_bwd)


def _dsa_target_kernel(tiles_ref, first_ref, q_ref, k_ref, lse_ref, sel_ref,
                       p_ref, *, scale: float, heads: int, nq: int, nk: int):
    b, qi, ki, h = (pl.program_id(a) for a in range(4))

    @pl.when(lax.eq(h, 0))
    def _init():
        p_ref[...] = _zeros(p_ref)

    live = lax.ne(tiles_ref[(b * nq + qi) * nk + ki], 0)

    @pl.when(live)
    def _block():
        s = lax.mul(_dot(q_ref[0], k_ref[0], _NT), scale)
        p_ref[0] = lax.add(p_ref[0], lax.exp(lax.sub(s, _as_col(lse_ref[0]))))

    @pl.when(lax.bitwise_and(live, lax.eq(h, heads - 1)))
    def _finalize():
        # the selection masks the heads' sum once, not each head's term:
        # a key off it summed at most to inf (never NaN), and goes here
        p = p_ref[0]
        p_ref[0] = lax.mul(lax.select(_unpack(sel_ref[0]), p,
                                      lax.full_like(p, 0)), 1.0 / heads)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "heads",
                                             "kv_heads", "topk"))
def sparse_target(q, k, lse, by_query, first, block_q: int, block_k: int,
                  heads: int, kv_heads: int, topk: int):
    """The probabilities `sparse_attention` gave each selected key, the
    mean over the heads, [B, c, s] float32 (0 off the selection), for the
    ``c`` queries ``first ..`` of a chunk: q [B, c, heads*d] of those
    queries, k [B, s, kv_heads*d], their ``lse`` [B*heads, 1, c] as
    `sparse_attention` returned it, their selection packed by query
    [B, c/32, s]. One kernel (``hvd_dsa_target_k<topk>``) whose grid
    walks the heads innermost and sums them in its output tile; a tile
    that holds no selected key is skipped, and one past the chunk's last
    query is not fetched; the selection masks the heads' sum once, at
    the last head. The indexer's KL target, without the scores' logits
    ever in HBM."""
    from ..selection import tiles as tiles_of

    B, c, width = q.shape
    s, d = k.shape[1], width // heads
    _kv_heads(heads, kv_heads, width, k.shape[2])
    bq, bk, interpret = _blocks(c, s, block_q, block_k, d, heads)
    if by_query.shape != (B, c // WORD, s) or lse.shape != (B * heads, 1, c):
        raise ValueError(f"a chunk of {c} queries over {s} keys takes words "
                         f"[B, c/32, s] and lse [B*heads, 1, c], not "
                         f"{by_query.shape} and {lse.shape}")
    nq, nk = c // bq, s // bk
    group = heads // kv_heads

    def k_block(i, j, first_ref):  # no further than the block's last query
        last = lax.div(lax.add(first_ref[0], (i + 1) * bq - 1), bk)
        return lax.min(j, last)

    return pl.pallas_call(
        functools.partial(_dsa_target_kernel, scale=d ** -0.5, heads=heads,
                          nq=nq, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, nq, nk, heads),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda b, i, j, h, t, f: (b, i, h)),
                pl.BlockSpec((1, bk, d), lambda b, i, j, h, t, f: (
                    b, k_block(i, j, f), lax.div(h, group))),
                pl.BlockSpec((1, 1, bq), lambda b, i, j, h, t, f: (
                    lax.add(lax.mul(b, heads), h), 0, i)),
                pl.BlockSpec((1, bq // WORD, bk), lambda b, i, j, h, t, f: (
                    b, i, k_block(i, j, f)))],
            out_specs=pl.BlockSpec((1, bq, bk),
                                   lambda b, i, j, h, t, f: (b, i, j))),
        out_shape=jax.ShapeDtypeStruct((B, c, s), jnp.float32,
                                       vma=_vma(q, k, lse)),
        compiler_params=_GRID_SEMANTICS, interpret=interpret,
        name=_dsa_name("hvd_dsa_target", topk),
    )(tiles_of(by_query, bq, bk), jnp.reshape(first, (1,)).astype(jnp.int32),
      q, k, lse, by_query)


# -- the learned-sparse indexer's scores, its heads summed in the tile -------

#: the key blocks `index_blocks` tries, largest first
INDEX_BLOCKS = (512, 256, 128)
#: the largest chunk of queries the indexer kernels hold as one block
INDEX_MAX_CHUNK = 1024

#: the indexer kernels' grid is (batch, key block); the backward kernel
#: accumulates the query side over the key blocks
_INDEX_GRID = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def index_blocks(c: int, s: int, heads: int, dim: int):
    """``(group, block_k)`` for the indexer kernels over chunks of ``c``
    queries and ``s`` keys, ``heads`` heads ``dim`` wide each, or None
    where they cannot take that. A chunk is one query block, of at most
    `INDEX_MAX_CHUNK` queries. ``group`` heads share a lane tile of the
    queries (their ``dim`` columns side by side, as the projection wrote
    them): a ``dim`` that divides `LANES`, whose groups the heads fill.
    ``block_k``: the largest of `INDEX_BLOCKS` that tiles ``s``."""
    if LANES % dim:
        return None
    group = min(heads, LANES // dim)
    block_k = next((bk for bk in INDEX_BLOCKS if s % bk == 0), None)
    if heads % group or c > INDEX_MAX_CHUNK or block_k is None:
        return None
    return group, block_k


def _index_operands():
    """The dtype the indexer kernels hand the MXU: bf16 on the TPU, as
    XLA's default precision rounds the float32 einsum they replace
    (``models/transformer.py`` ``_index_scores``), and float32 in
    interpret mode, as the CPU computes that einsum."""
    return jnp.float32 if _interpret() else jnp.bfloat16


def _placed(k, group: int, dim: int):
    """A key tile [bk, dim] as ``group`` tiles [bk, group*dim], the
    ``r``-th holding it in head ``r``'s lanes and zeros in the others:
    its product with a group's queries [c, group*dim] is head ``r``'s
    alone, and no lane is sliced. Placed by the MXU (a product with a
    0/1 matrix, exact)."""
    if group == 1:
        return [k]
    shape = (dim, group * dim)
    rows = lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = lax.broadcasted_iota(jnp.int32, shape, 1)
    return [lax.convert_element_type(_dot(k, lax.convert_element_type(
        lax.eq(cols, lax.add(rows, r * dim)), k.dtype), _NN), k.dtype)
        for r in range(group)]


def _past_the_chunk(first, c: int, j, block_k: int):
    """Does key tile ``j`` lie wholly after the last query of the chunk of
    ``c`` queries from ``first``? Such a tile is neither fetched nor
    computed."""
    return lax.gt(lax.mul(j, block_k), lax.add(first, c - 1))


def _chunk_causal(x, first, j, block_k: int):
    """The tile ``x`` [c, bk] of key block ``j`` with 0 wherever the key
    comes after the query (query ``i`` of the chunk is ``first + i``)."""
    rows = lax.add(first, lax.broadcasted_iota(jnp.int32, x.shape, 0))
    cols = lax.add(lax.mul(j, block_k),
                   lax.broadcasted_iota(jnp.int32, x.shape, 1))
    return lax.select(lax.ge(rows, cols), x, lax.full_like(x, 0))


def _group_columns(p, width: int):
    """Head group ``p``'s lanes of the queries [c, heads*dim]."""
    return pl.ds(pl.multiple_of(lax.mul(p, width), width), width)


def _index_fwd_kernel(first_ref, q_ref, w_ref, k_ref, i_ref, *, heads: int,
                      group: int, dim: int, scale: float, block_k: int):
    j, first = pl.program_id(1), first_ref[0]
    width = group * dim
    past = _past_the_chunk(first, q_ref.shape[1], j, block_k)

    @pl.when(lax.bitwise_not(past))
    def _tile():
        placed = _placed(lax.convert_element_type(k_ref[0], q_ref.dtype),
                         group, dim)
        w = w_ref[0]                                       # [c, heads]
        lane = lax.broadcasted_iota(jnp.int32, w.shape, 1)
        i_ref[...] = _zeros(i_ref)

        def one_group(p, carry):   # the group's heads, summed in the tile
            q = q_ref[0, :, _group_columns(p, width)]
            total = i_ref[0]
            for r in range(group):
                weight = _row_sum(lax.select(
                    lax.eq(lane, lax.add(lax.mul(p, group), r)), w,
                    lax.full_like(w, 0)))                  # [c, 1]
                total = lax.add(total, lax.mul(
                    lax.max(_dot(q, placed[r], _NT), 0.0), weight))
            i_ref[0] = total
            return carry

        lax.fori_loop(0, heads // group, one_group, 0)
        i_ref[0] = _chunk_causal(lax.mul(i_ref[0], scale), first, j, block_k)

    @pl.when(past)
    def _past():
        i_ref[...] = _zeros(i_ref)


def _index_bwd_kernel(first_ref, q_ref, wq_ref, k_ref, g_ref, u_ref, dk_ref,
                      *, heads: int, group: int, dim: int, block_k: int):
    j, first = pl.program_id(1), first_ref[0]
    width = group * dim
    past = _past_the_chunk(first, q_ref.shape[1], j, block_k)

    @pl.when(lax.eq(j, 0))
    def _init():
        u_ref[...] = _zeros(u_ref)

    @pl.when(lax.bitwise_not(past))
    def _tile():
        placed = _placed(lax.convert_element_type(k_ref[0], q_ref.dtype),
                         group, dim)
        g = _chunk_causal(g_ref[0], first, j, block_k)     # [c, bk]
        lane_head = lax.div(lax.broadcasted_iota(
            jnp.int32, (g.shape[0], width), 1), dim)
        dk_ref[...] = _zeros(dk_ref)

        def one_group(p, carry):
            cols = _group_columns(p, width)
            q, wq = q_ref[0, :, cols], wq_ref[0, :, cols]
            u, dk = u_ref[0, :, cols], dk_ref[0]
            for r in range(group):
                # the cotangent where head r's product is positive, as
                # the MXU takes it
                ds = lax.convert_element_type(lax.select(
                    lax.gt(_dot(q, placed[r], _NT), 0.0), g,
                    lax.full_like(g, 0)), q.dtype)
                u = lax.add(u, _dot(ds, placed[r], _NN))   # [c, width]
                if group > 1:   # head r's columns of the weighted queries
                    wq_r = lax.select(lax.eq(lane_head, r), wq,
                                      lax.full_like(wq, 0))
                else:
                    wq_r = wq
                dk = lax.add(dk, _dot(ds, wq_r, _TN))      # [bk, width]
            u_ref[0, :, cols] = u
            dk_ref[0] = dk
            return carry

        lax.fori_loop(0, heads // group, one_group, 0)

    @pl.when(past)
    def _past():
        dk_ref[...] = _zeros(dk_ref)


def _index_call(kernel, name: str, first, in_specs, out_specs, out_shape,
                grid, *args):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape, compiler_params=_INDEX_GRID,
        interpret=_interpret(), name=name,
    )(jnp.reshape(first, (1,)).astype(jnp.int32), *args)


def _index_specs(c: int, width: int, dim: int, block_k: int):
    """BlockSpecs over the grid ``(b, j)``: a chunk's queries [b, c,
    width] whole, the key block ``j`` of [b, s, dim] (held at the chunk's
    last where ``j`` is past it: no DMA), and a [b, c, s] tile."""
    def k_block(j, first_ref):
        return lax.min(j, lax.div(lax.add(first_ref[0], c - 1), block_k))

    return (pl.BlockSpec((1, c, width), lambda b, j, f: (b, 0, 0)),
            pl.BlockSpec((1, block_k, dim), lambda b, j, f: (
                b, k_block(j, f), 0)),
            pl.BlockSpec((1, c, block_k), lambda b, j, f: (
                b, 0, k_block(j, f))))


@functools.partial(jax.jit, static_argnames=("group", "block_k", "operands"))
def _index_fwd(iq, iw, ik, first, group: int, block_k: int, operands):
    """`index_scores`' forward kernel (``hvd_dsa_index_fwd``), the MXU
    taking ``operands`` (`_index_operands`)."""
    B, c, heads, dim = iq.shape
    s = ik.shape[1]
    q_spec, k_spec, _ = _index_specs(c, heads * dim, dim, block_k)
    w_spec = pl.BlockSpec((1, c, heads), lambda b, j, f: (b, 0, 0))
    kernel = functools.partial(
        _index_fwd_kernel, heads=heads, group=group, dim=dim,
        scale=(heads * dim) ** -0.5, block_k=block_k)
    return _index_call(
        kernel, "hvd_dsa_index_fwd", first,
        [q_spec, w_spec, k_spec],
        pl.BlockSpec((1, c, block_k), lambda b, j, f: (b, 0, j)),
        jax.ShapeDtypeStruct((B, c, s), jnp.float32, vma=_vma(iq, iw, ik)),
        (B, s // block_k),
        iq.reshape(B, c, heads * dim).astype(operands), iw, ik)


@functools.partial(jax.jit, static_argnames=("group", "block_k", "operands"))
def _index_bwd(iq, iw, ik, first, g, group: int, block_k: int, operands):
    """`index_scores`' VJP: one kernel (``hvd_dsa_index_bwd``) recomputes
    each head's product in a tile and takes, with the cotangent ``g``
    where it is positive, ``U = g·mask @ k`` (summed over the key tiles)
    and each key tile's ``Σ (g·mask)ᵀ @ (w·scale·q)``; the queries' and
    the weights' gradients follow from ``U`` here, ``w·scale·U`` and
    ``scale·Σ q·U`` (``relu(q·k) = mask·(q·k)``), a [c, heads, dim]
    pass."""
    B, c, heads, dim = iq.shape
    s, width = ik.shape[1], group * dim
    scale = (heads * dim) ** -0.5
    q = iq.reshape(B, c, heads * dim).astype(operands)
    wq = (iq * (iw * scale)[..., None]).reshape(q.shape).astype(operands)
    q_spec, k_spec, tile_spec = _index_specs(c, heads * dim, dim, block_k)
    vma = _vma(iq, iw, ik, g)
    u, dk = _index_call(
        functools.partial(_index_bwd_kernel, heads=heads, group=group,
                          dim=dim, block_k=block_k),
        "hvd_dsa_index_bwd", first, [q_spec, q_spec, k_spec, tile_spec],
        [q_spec, pl.BlockSpec((1, block_k, width),
                              lambda b, j, f: (b, j, 0))],
        [jax.ShapeDtypeStruct(q.shape, jnp.float32, vma=vma),
         jax.ShapeDtypeStruct((B, s, width), jnp.float32, vma=vma)],
        (B, s // block_k), q, wq, ik, g)
    u = u.reshape(iq.shape)
    d_iw = jnp.sum(q.astype(jnp.float32).reshape(iq.shape) * u, -1) * scale
    return (u * (iw * scale)[..., None], d_iw,
            dk.reshape(B, s, group, dim).sum(2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def index_scores(iq, iw, ik, first, group: int, block_k: int):
    """The learned-sparse indexer's scores of a chunk of queries, ``I(t,
    s) = Σ_j w_j(t) relu(q_j(t)·k(s)) (heads·dim)^-½`` for the keys ``s
    <= t`` and 0 for the later ones, [B, c, s] float32: queries ``iq``
    [B, c, heads, dim] ``first ..`` with weights ``iw`` [B, c, heads]
    against keys ``ik`` [B, s, dim] (``models/transformer.py``
    ``_index_scores`` where a key is causal, its precision too); ``group``
    and ``block_k`` as `index_blocks` gives them.

    Two kernels (``hvd_dsa_index_fwd``, ``hvd_dsa_index_bwd``) over the
    chunk's key tiles: each head's [c, dim] × [dim, bk] product, its ReLU
    and weight and the sum over the heads stay in VMEM, only ``I`` leaves;
    a tile past the chunk's last query is neither fetched nor computed
    (it reads 0, and its keys take no gradient), and the one the diagonal
    cuts is masked. The gradient recomputes the products once: three
    products a head and tile, where a flash backward's two kernels take
    four."""
    with _pinned_mesh():
        return _index_fwd(iq, iw, ik, first, group, block_k,
                          _index_operands())


def _index_scores_fwd(iq, iw, ik, first, group, block_k):
    return index_scores(iq, iw, ik, first, group, block_k), (iq, iw, ik,
                                                             first)


def _index_scores_bwd(group, block_k, res, g):
    iq, iw, ik, first = res
    with _pinned_mesh():
        return (*_index_bwd(iq, iw, ik, first, g, group, block_k,
                            _index_operands()), None)


index_scores.defvjp(_index_scores_fwd, _index_scores_bwd)
