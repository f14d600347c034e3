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
import functools
import sys

import jax
import jax.numpy as jnp
from jax import ad_checkpoint, lax

from ...utils import scopes

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


_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b

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
    positions with its heads side by side, ``head_dim`` wide each, or
    None where the TPU kernels cannot take that (a head that is no lane
    multiple, a length none of `BLOCKS` tiles). A latent head's
    ``head_dim`` is its no-rope columns (and its value's): the rotary
    columns come as arrays of their own (`latent_attention`), whatever
    their width. The one place a caller
    with no reason of its own gets its tile shape (ring attention passes
    its own). Square, and the largest that tiles: on the v5e a larger
    tile beat a finer causal skip at every length tried (PERF.md, PR 26;
    the sweep ran at ``head_dim`` 128 only)."""
    if head_dim % LANES:
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
            f"on TPU, not {d}: fold them into the batch (heads=1)")
    return bq, bk, interpret


#: every grid is (batch, head, outer block, inner block): the inner one
#: accumulates into scratch, the rest are independent
_GRID_SEMANTICS = pltpu.CompilerParams(dimension_semantics=(
    "parallel", "parallel", "parallel", "arbitrary"))


def _specs(bq: int, bk: int, d: int, heads: int, q_block, k_block,
           q_head=None, kv_head=None):
    """BlockSpecs over a grid ``(b, head, x, y)``: one head's ``d``
    columns of a block of rows of a [b, s, heads*d] array, for Q-like and
    K-like arrays, and a block of a [b*heads, 1, sq] row statistic.
    ``q_block(x, y)`` and ``k_block(x, y)`` give the row blocks. With
    grouped-query heads the grid's head is not every array's:
    ``q_head(h, x, y)`` and ``kv_head(h, x, y)`` give the Q-like and the
    K-like arrays' (default: ``h`` for both).

    The statistics are [.., 1, sq] because a (1, 1, bq) block is legal
    on TPU (second-to-last dim = the whole array's, last a lane
    multiple) and a (1, bq) block of [.., sq] is not."""
    q_head = q_head or (lambda h, x, y: h)
    kv_head = kv_head or (lambda h, x, y: h)
    return (
        pl.BlockSpec((1, bq, d), lambda b, h, x, y: (
            b, q_block(x, y), q_head(h, x, y))),
        pl.BlockSpec((1, bk, d), lambda b, h, x, y: (
            b, k_block(x, y), kv_head(h, x, y))),
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
    kv_heads*d] -> (o [B, sq, heads*d], m, l [B*heads, 1, sq] float32)."""
    B, sq, width = q.shape
    sk, d = k.shape[1], width // heads
    kv_heads = _kv_heads(heads, kv_heads, width, k.shape[2])
    bq, bk, interpret = _blocks(sq, sk, block_q, block_k, d, heads)
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
                                      kv_head=_kv_head_of(heads, kv_heads))
    row = jax.ShapeDtypeStruct((B * heads, 1, sq), jnp.float32, vma=vma)
    return pl.pallas_call(
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
    [B, sq, heads*d], each head ``d`` adjacent columns.

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
    `flash_attention` as two kernels. The dQ kernel runs first: it has
    ``do`` and ``o`` of a Q block together, so ``delta = rowsum(do * o)``
    is made there and handed to the dK/dV kernel. With grouped-query
    heads the dK/dV kernel's grid runs over the key/value heads, and
    its inner axis over every Q block of every query head of the group:
    the sum over a head's queries is made in its scratch."""
    B, sq, width = q.shape
    sk, d = k.shape[1], width // heads
    kv_heads = _kv_heads(heads, kv_heads, width, k.shape[2])
    group = heads // kv_heads
    bq, bk, interpret = _blocks(sq, sk, block_q, block_k, d, heads)
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

    q_spec, k_spec, row_spec = _specs(
        bq, bk, d, heads, lambda i, j: i,
        functools.partial(_visible_k_block, causal, bq, bk, 0, **windowed),
        kv_head=_kv_head_of(heads, kv_heads))
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
                                      q_head=grouped.pop("q_head", None))
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
