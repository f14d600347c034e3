"""Sequence/context parallelism: ring attention and Ulysses.

Greenfield per SURVEY.md §5.7 — the reference has no long-context support;
its only adjacent machinery is the alltoall primitive. Here both standard
SP schemes are first-class, built on the mesh 'sp' axis:

- **Ring attention** (`ring_attention`): K/V blocks rotate around the ring
  via ``lax.ppermute`` (ICI neighbor exchange) under a single
  ``lax.scan`` — program size and compile time are O(1) in ring size (a
  rolled loop, not n unrolled copies), and the K/V permute for step r+1
  overlaps with step r's block compute under XLA's latency-hiding
  scheduler. The inner step is the fused Pallas flash-attention kernel
  (`horovod_tpu.ops.pallas.attention_stats`) on TPU, with a pure-XLA
  fallback elsewhere; both return (o, m, l) online-softmax stats that the
  ring combines exactly.
- **Ulysses** (`ulysses_attention`): two ``all_to_all`` reshuffles trade
  the sequence sharding for a head sharding around the attention core
  (DeepSpeed-Ulysses style, built on the same primitive the reference
  exposes as hvd.alltoall).

Inputs are per-chip blocks [batch, seq_local, heads, head_dim] inside a
shard_map over the 'sp' axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import scopes

NEG_INF = -1e30


def _ring_scan(q, k, v, axis_name, round_stats):
    """Shared ring-attention scaffold: K/V rotate via ``lax.ppermute``
    under one ``lax.scan`` while an online softmax combines each round's
    normalized (o, m, l) block stats exactly. ``round_stats(qf, kf, vf,
    r, i, j)`` produces the current round's stats (layout [b*h, s, ...]);
    layout variants (block-sharded vs striped) differ only there."""
    n = lax.axis_size(axis_name)
    i = lax.axis_index(axis_name)
    b, s, h, d = q.shape

    def to_flat(x):  # kernel layout: [B=b*h, s, d]
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    qf = to_flat(q)
    perm = [(x, (x + 1) % n) for x in range(n)]

    def round_fn(carry, r):
        kf, vf, m_acc, l_acc, o_acc = carry
        j = (i - r) % n  # source shard of the resident K/V
        o_r, m_r, l_r = round_stats(qf, kf, vf, r, i, j)
        m_new = jnp.maximum(m_acc, m_r)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m_r - m_new)
        l_new = l_acc * alpha + l_r * beta
        # o_r is normalized by l_r: un-normalize before combining
        o_acc = (o_acc * alpha[..., None]
                 + o_r.astype(jnp.float32) * (l_r * beta)[..., None])
        kf = lax.ppermute(kf, axis_name, perm)
        vf = lax.ppermute(vf, axis_name, perm)
        return (kf, vf, m_new, l_new, o_acc), None

    init = (to_flat(k), to_flat(v),
            _varying(jnp.full((b * h, s), NEG_INF, jnp.float32), axis_name),
            _varying(jnp.zeros((b * h, s), jnp.float32), axis_name),
            _varying(jnp.zeros((b * h, s, d), jnp.float32), axis_name))
    (_, _, _, l_acc, o_acc), _ = lax.scan(round_fn, init, jnp.arange(n))
    out = o_acc / jnp.where(l_acc == 0.0, 1.0, l_acc)[..., None]
    return (out.reshape(b, h, s, d).transpose(0, 2, 1, 3)).astype(q.dtype)


def _varying(x, axis_name):
    """Type a constant as device-varying over ``axis_name``: constants are
    replication-typed, and scan carries / switch branches demand the same
    type as the per-chip values they sit beside."""
    return lax.pcast(x, axis_name, to="varying")


def _auto_flash(s, block_q, block_k, use_flash):
    if use_flash is not None:
        return use_flash
    from ..ops.pallas.flash_attention import kernel_tiles

    # on TPU the kernel answers for every local sequence its blocks tile;
    # only shapes the TPU compiler would refuse take the XLA stats path
    return (jax.default_backend() == "tpu"
            and kernel_tiles(s, s, block_q, block_k))


def ring_attention(q, k, v, axis_name: str = "sp", use_flash=None,
                   block_q: int = 512, block_k: int = 512):
    """Causal ring attention over the 'sp' axis.

    Sequence is block-sharded: chip i holds tokens [i*s_loc, (i+1)*s_loc).
    Returns the attention output for the local Q block, same shape/dtype
    as q ([batch, s_loc, heads, head_dim]).

    ``use_flash=None`` auto-selects the Pallas kernel on TPU and the
    differentiable XLA fallback elsewhere.
    """
    from ..ops.pallas.flash_attention import attention_stats, scan_stats

    use_flash = _auto_flash(q.shape[1], block_q, block_k, use_flash)
    axis = axis_name

    def stats(qf, kf, vf, causal):
        if use_flash:
            return attention_stats(qf, kf, vf, causal, block_q, block_k)
        # blockwise fallback: same [*, block_k]-bounded memory as the
        # kernel path, both autodiff directions
        return scan_stats(qf, kf, vf, causal, 0, block_k)

    def round_stats(qf, kf, vf, r, i, j):
        # causal block cases: diagonal (r==0) → triangular; j<i → full;
        # j>i → skip (entirely masked). Round 0 is the diagonal, so every
        # row sees ≥1 real entry before any skip round — the online
        # softmax stays finite.
        B, sq = qf.shape[0], qf.shape[1]
        branch = jnp.where(r == 0, 0, jnp.where(j < i, 1, 2))
        return lax.switch(branch, [
            lambda kv: stats(qf, kv[0], kv[1], True),
            lambda kv: stats(qf, kv[0], kv[1], False),
            lambda kv: (jnp.zeros_like(qf),
                        _varying(jnp.full((B, sq), NEG_INF, jnp.float32),
                                 axis),
                        _varying(jnp.zeros((B, sq), jnp.float32), axis)),
        ], (kf, vf))

    with jax.named_scope(scopes.ATTENTION):
        return _ring_scan(q, k, v, axis_name, round_stats)


def striped_ring_attention(q, k, v, axis_name: str = "sp", use_flash=None,
                           block_q: int = 512, block_k: int = 512):
    """Causal ring attention with STRIPED token layout — load-balanced.

    Block-sharded causal ring attention wastes ~half the machine: in
    round r only the chips with source index ≤ their own compute a real
    block, yet every chip waits out the round (the wall-clock is
    max-over-chips). Striping the sequence round-robin — chip i holds
    global tokens i, i+n, i+2n, … (`stripe_tokens`) — makes every
    (Q-shard, K-shard) pair a triangular block: for resident source
    j = (i−r) mod n the causal condition k_global ≤ q_global reduces to
    t_k ≤ t_q when j ≤ i and t_k < t_q when j > i (t = position within
    the shard). Every chip computes equal work every round — ~2×
    steady-state utilization for long causal sequences (Striped
    Attention, arXiv:2311.09431; same primitive family the reference
    exposes only as hvd.alltoall).

    Inputs are striped per-chip blocks [batch, s_loc, heads, head_dim]
    inside a shard_map over ``axis_name``; outputs stay striped (invert
    with `unstripe_tokens` after gathering).
    """
    from ..ops.pallas.flash_attention import attention_stats, scan_stats

    use_flash = _auto_flash(q.shape[1], block_q, block_k, use_flash)

    def stats(qf, kf, vf, offset):
        if use_flash:
            return attention_stats(qf, kf, vf, True, block_q, block_k,
                                   offset)
        return scan_stats(qf, kf, vf, True, offset, block_k)

    def round_stats(qf, kf, vf, r, i, j):
        # j <= i: inclusive diagonal; j > i: strict. Both are real
        # triangular work — no skip branch, no idle chips.
        return lax.switch(
            jnp.where(j <= i, 0, 1),
            [lambda kv: stats(qf, kv[0], kv[1], 0),
             lambda kv: stats(qf, kv[0], kv[1], 1)],
            (kf, vf))

    with jax.named_scope(scopes.ATTENTION):
        return _ring_scan(q, k, v, axis_name, round_stats)


def stripe_tokens(x, n: int, axis: int = 1):
    """Reorder a GLOBAL sequence so block-sharding over ``n`` chips gives
    the striped layout: chip i receives global tokens i, i+n, i+2n, …
    Closed form: gather with arange(S).reshape(S//n, n).T.ravel()."""
    S = x.shape[axis]
    if S % n:
        raise ValueError(f"sequence length {S} must divide by {n}")
    idx = jnp.arange(S).reshape(S // n, n).T.reshape(-1)
    return jnp.take(x, idx, axis=axis)


def unstripe_tokens(x, n: int, axis: int = 1):
    """Inverse of `stripe_tokens`: gather with the transposed reshape."""
    S = x.shape[axis]
    if S % n:
        raise ValueError(f"sequence length {S} must divide by {n}")
    idx = jnp.arange(S).reshape(n, S // n).T.reshape(-1)
    return jnp.take(x, idx, axis=axis)


def ulysses_attention(q, k, v, axis_name: str = "sp", attn_fn=None):
    """Ulysses SP: all_to_all seq⇄heads around a full attention core.

    Requires heads % axis_size == 0. Each chip computes full-sequence
    attention for its head shard — good when seq is long but heads are
    plentiful; ring attention covers the opposite regime.
    """
    n = lax.axis_size(axis_name)
    if q.shape[2] % n:
        raise ValueError(f"heads ({q.shape[2]}) must divide by sp={n}")
    if attn_fn is None:
        from ..models.transformer import causal_attention

        attn_fn = causal_attention

    def scatter_heads(x):  # [b, s_loc, h, hd] -> [b, s, h/n, hd]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def gather_heads(x):  # [b, s, h/n, hd] -> [b, s_loc, h, hd]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    return gather_heads(attn_fn(scatter_heads(q), scatter_heads(k),
                                scatter_heads(v)))
