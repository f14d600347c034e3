"""Sparse experts: one dropless router and an expert layer that is told
which experts it holds.

The reference supports this only as a primitive — alltoall with uneven
splits + received_splits (SURVEY.md §2.3, operations.cc:1131-1193). Here
the layer is whole, around two functions:

- `route` picks each token's ``k`` experts of all the router scores and
  normalises their weights over the ``k`` chosen.
- `expert_layer` computes, for the experts it ``held``, their part of
  ``sum_e w_e * E_e(u)``. No token is dropped: the (token, expert) pairs
  are sorted by expert, the pairs of held experts come first, and one
  grouped matmul per weight (``jax.lax.ragged_dot``: on a TPU XLA's own
  grouped-matmul kernel, whose work follows the rows really routed)
  runs over them. The bound on those pairs is ``tokens * min(k, held)``,
  and nothing is sized for less: there is no capacity to choose and
  nothing a skewed step can overflow. What the experts held elsewhere
  would add is left out, here and in whatever this is compared with.

The cost follows the rows routed, chunk by chunk (`_sorted_side`). The
sorted positions are cut into chunks of as many rows as the layer has
tokens (`chunk_rows`: ``min(k, held)`` chunks on one chip; one, the
whole, behind the exchange), and everything whose leading dimension is
the sorted position (the gather of the tokens' rows, both grouped
matmuls, the activation, and in the backward pass the gather of the
cotangent out of pair order and all their transposes) is computed for
one chunk at a time. Chunk 0 always runs; one loop a direction walks
the further chunks that a routed row reaches, so a step in which the
held experts take no more rows than there are tokens pays for one chunk
and a loop of no trips, and a skewed one for as many chunks as its rows
fill: more trips, never a dropped row, and one path whatever the
routing. The pair side (the gather back to pair order, the weighted sum
over a token's choices, and their transposes) runs once a layer at the
bound, over one array assembled in place per direction.

On one chip nothing is exchanged. With ``axis_name`` (inside a
``shard_map`` over the expert-parallel axis, each chip holding
``experts / axis_size`` of them and a shard of the tokens) the pairs go
to their experts' chips and back through two ``all_to_all``s sized for
the same bound; compiled programs need static shapes, so the uneven
split is padding, never a dropped token (SURVEY.md §7 hard part 6).

Both directions of every row movement are gathers: the transpose of a
gather is a scatter-add, which a TPU serialises row by row, but the
sort that made the gather's indices also gives the indices of its
inverse (`_take_rows`; `_sorted_side` has its own VJP for the same
reason, and recomputes a chunk's hidden activation inside the backward
loop, so that nothing of a chunk's size is handed out of a loop).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import ad_checkpoint, lax

from ..utils import scopes


def route(logits, k: int, *, scoring: str = "softmax", bias=None,
          scale: float = 1.0, name=None):
    """Each token's ``k`` experts and their weights: ``logits`` [t, e] →
    (``chosen`` [t, k] int32, ``weights`` [t, k] float32). In float32
    whatever comes in: a choice between near-equal scores should not
    hang on the activations' precision more than it must.

    ``scoring="softmax"``: the ``k`` largest logits, ``weights = exp(r) /
    sum over the k chosen of exp(r)``, which is a softmax over all ``e``
    renormalised over the chosen. ``scoring="sigmoid"``: ``s =
    sigmoid(r)``; the ``k`` largest of ``s + bias`` (``bias`` [e], a
    correction that enters the choice and nothing else: no gradient
    reaches it); ``weights = scale * s / (sum over the k chosen of s +
    1e-20)``.

    ``name``: the choice is given this ``checkpoint_name`` and the
    weights are read off the scores at the *named* choice, so that a
    caller under ``jax.checkpoint`` whose policy saves the name routes
    its backward pass as its forward pass. It has to where the logits
    come from activations the backward pass recomputes, not bit for
    bit: a token whose k-th and next scores nearly tie would be routed
    otherwise there."""
    logits = logits.astype(jnp.float32)
    if scoring == "softmax":
        scores = logits
        top, chosen = lax.top_k(logits, k)
    elif scoring == "sigmoid":
        if bias is None:
            raise ValueError("scoring='sigmoid' chooses by s + bias: give "
                             "the bias [e] (zeros where there is none)")
        scores = jax.nn.sigmoid(logits)
        _, chosen = lax.top_k(lax.stop_gradient(scores + bias), k)
        top = None  # the chosen's scores without the bias: read below
    else:
        raise ValueError(f"scoring={scoring!r}: softmax or sigmoid")
    chosen = chosen.astype(jnp.int32)
    if name is not None:
        chosen = ad_checkpoint.checkpoint_name(chosen, name)
    if top is None or name is not None:
        top = jnp.take_along_axis(scores, chosen, axis=-1)
    if scoring == "softmax":
        return chosen, jax.nn.softmax(top, axis=-1)
    return chosen, scale * top / (jnp.sum(top, axis=-1, keepdims=True)
                                  + 1e-20)


@jax.custom_vjp
def _take_rows(x, idx, back_idx, back_mask):
    """``x[idx]`` ([n, d] → [len(idx), d]) whose transpose is a gather
    too: row ``i`` of ``x`` is read by the result's rows ``back_idx[i]``
    where ``back_mask[i]`` ([n, m] each), and by no other."""
    return x.at[idx].get(mode="promise_in_bounds")


def _take_rows_fwd(x, idx, back_idx, back_mask):
    return _take_rows(x, idx, back_idx, back_mask), (back_idx, back_mask)


def _rows_back(g, n: int, back_idx, back_mask):
    """`_take_rows`' transpose, [n, d]: row ``i`` is the sum, in
    float32, of ``g``'s rows ``back_idx[j*n + i]`` over the ``j`` where
    ``back_mask[j*n + i]`` (reader-major vectors of ``n * m``)."""
    # one gather, then reader by reader in slices (no reshape to
    # [m, n, d]: see `_choice`)
    rows = g.at[back_idx].get(mode="promise_in_bounds")
    dx = sum(jnp.where(_choice(back_mask, n, j)[:, None],
                       _choice(rows, n, j).astype(jnp.float32), 0.0)
             for j in range(back_idx.shape[0] // n))
    return dx.astype(g.dtype)


def _take_rows_bwd(res, g):
    back_idx, back_mask = res
    # a custom VJP's backward pass is traced outside the scope its call
    # stood in: name it again, or a trace files it under nothing
    with jax.named_scope(scopes.MOE):
        return (_rows_back(g, back_idx.shape[0], back_idx.T.reshape(-1),
                           back_mask.T.reshape(-1)), None, None, None)


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


#: the routed experts' gate: ``(act(u G) * (u U)) D``
ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def _chunk(c, chunk: int, x, sizes, order):
    """Chunk ``c`` of the sorted positions: its first position, the
    pairs at its ``chunk`` positions, their rows of ``x`` (pair ``p``
    reads row ``p % n``) and the part of each expert's group that lies in
    it (the groups follow one another from position 0)."""
    lo = c * chunk
    pairs = lax.dynamic_slice_in_dim(order, lo, chunk)
    ends = jnp.cumsum(sizes)
    inside = (jnp.clip(ends, lo, lo + chunk)
              - jnp.clip(ends - sizes, lo, lo + chunk)).astype(jnp.int32)
    return (lo, pairs, x.at[pairs % x.shape[0]].get(mode="promise_in_bounds"),
            inside)


def _experts_on_rows(xs, gate_up, down, sizes, activation: str):
    """``xs`` [r, d] sorted by expert, ``sizes`` rows to each: every
    row through its expert. Rows past the last group are no expert's,
    and come out as whatever the kernel left there."""
    width = gate_up.shape[-1] // 2
    h = lax.ragged_dot(xs, gate_up, sizes)
    h = ACTIVATIONS[activation](h[:, :width]) * h[:, width:]
    return lax.ragged_dot(h, down, sizes)


def _in_chunks(live, chunk: int, chunks: int, on_chunk):
    """A carry through the chunks in turn: ``on_chunk(0, None)`` makes
    it, then one loop takes it through ``on_chunk(c, carry)`` for the
    further chunks ``c`` that a routed row reaches (``c * chunk <
    live``). Its trip count is the data's: with no such chunk the loop
    costs the test of a scalar, and there is one copy of a chunk's code
    however many there are."""
    carry = on_chunk(0, None)
    if chunks == 1:
        return carry
    return lax.fori_loop(1, (live + chunk - 1) // chunk, on_chunk, carry)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sorted_side(chunk: int, activation: str, x, weights, sizes, order,
                 place):
    """Everything of the expert layer whose leading dimension is the
    sorted position, chunk by chunk: ``x`` [n, d] → [pairs, d], pair
    ``p``'s row (row ``p % n`` of ``x``) through its expert.

    ``weights``: ``gate``, ``up``, ``down``; ``sizes`` [held]: rows to
    each expert, which fill the sorted positions from 0; ``order``
    [rows]: sorted position → pair; ``place`` [pairs]: pair → sorted
    position.

    Its own VJP (`_sorted_side_fwd`, `_sorted_side_bwd`), so that a
    chunk's gathers stay gathers in both directions and nothing of a
    chunk's size leaves the loop but its rows of the one assembled
    array."""
    return _sorted_side_fwd(chunk, activation, x, weights, sizes, order,
                            place)[0]


def _cast(weights, dtype):
    # gate and up as one matmul: the rows are read once, and their
    # gradient is one array, not the sum of two at the buffers' size
    return (jnp.concatenate([weights["gate"], weights["up"]],
                            axis=-1).astype(dtype),
            weights["down"].astype(dtype))


def _grown(rows, chunks: int):
    """Chunk 0's rows at the head of the array the further chunks write
    theirs into, zero where none does."""
    return jnp.pad(rows, ((0, (chunks - 1) * rows.shape[0]), (0, 0)))


def _sorted_side_fwd(chunk, activation, x, weights, sizes, order, place):
    chunks = order.shape[0] // chunk
    gate_up, down = _cast(weights, x.dtype)

    def on_chunk(c, out):
        lo, _, xs, inside = _chunk(c, chunk, x, sizes, order)
        rows = _experts_on_rows(xs, gate_up, down, inside, activation)
        return (_grown(rows, chunks) if out is None
                else lax.dynamic_update_slice_in_dim(out, rows, lo, 0))

    out = _in_chunks(jnp.sum(sizes), chunk, chunks, on_chunk)
    # the pair side, once a layer however many chunks ran. Rows past the
    # last group are junk or, in a chunk that did not run, zero: only
    # pairs without a held expert read them (clipped), and `_combine`
    # selects those away; no pass over the buffer here
    return (out.at[jnp.minimum(place, order.shape[0] - 1)].get(
        mode="promise_in_bounds"), (x, weights, sizes, order, place))


def _sorted_side_bwd(chunk, activation, res, g):
    x, weights, sizes, order, place = res
    n, chunks, live = x.shape[0], order.shape[0] // chunk, jnp.sum(sizes)
    with jax.named_scope(scopes.MOE):  # as in `_take_rows_bwd`
        gate_up, down = _cast(weights, x.dtype)

        def on_chunk(c, carry):
            lo, pairs, xs, inside = _chunk(c, chunk, x, sizes, order)
            # recomputed here from `x` and the indices, not kept
            _, back = jax.vjp(functools.partial(
                _experts_on_rows, sizes=inside, activation=activation),
                xs, gate_up, down)
            # positions past `live` are in no expert's group: what their
            # pairs' cotangent holds reaches no weight's gradient, and
            # their rows of `d_xs` are selected away below
            d_xs, *d_w = back(g.at[pairs].get(mode="promise_in_bounds"))
            d_w = [d.astype(jnp.float32) for d in d_w]
            if carry is None:
                return _grown(d_xs, chunks), d_w
            return (lax.dynamic_update_slice_in_dim(carry[0], d_xs, lo, 0),
                    [a + d for a, d in zip(carry[1], d_w)])

        d_rows, (d_gate_up, d_down) = _in_chunks(live, chunk, chunks,
                                                 on_chunk)
        width = weights["gate"].shape[-1]
        d_weights = {"gate": d_gate_up[..., :width],
                     "up": d_gate_up[..., width:], "down": d_down}
        # the token side, once a layer: row i of `x` was read at the
        # sorted positions of the pairs j*n + i that have a held expert
        d_x = _rows_back(d_rows, n, jnp.minimum(place, order.shape[0] - 1),
                         place < live)
        return (d_x, jax.tree.map(lambda d, w: d.astype(w.dtype), d_weights,
                                  weights), None, None, None)


_sorted_side.defvjp(_sorted_side_fwd, _sorted_side_bwd)


def chunk_rows(n: int, rows: int) -> int:
    """Rows of one of the chunks a layer's ``rows`` sorted positions are
    walked in, ``n`` being the rows of what the layer gathers from: ``n``
    where that cuts ``rows`` into whole chunks. On one chip it does
    (``rows`` is the tokens times the most held experts one token can
    have); behind the exchange ``n`` is ``rows``, the received buffer:
    one chunk."""
    return n if rows % n == 0 else rows


def _experts_on_pairs(x, key, params, rows: int, activation: str = "relu"):
    """Each pair's expert applied to its row: ``x`` [n, d]; pair ``p``
    takes row ``p % n`` to local expert ``key[p]`` (``count`` = none
    held here). → [pairs, d], junk where ``key == count``. ``rows`` is
    the bound on the pairs with a held expert: the buffers' size.

    The sorted positions are walked in chunks of ``n`` rows, ``x``'s
    own count (`chunk_rows`, `_sorted_side`). Every index comes of the
    two sorts and of arithmetic on them: an integer gather of ``pairs``
    elements costs a TPU as much as a sixth of a row gather."""
    count = params["gate"].shape[0]
    order = jnp.argsort(key, stable=True).astype(jnp.int32)  # held first
    place = jnp.argsort(order).astype(jnp.int32)   # pair -> sorted position
    group_sizes = jnp.sum(
        key[:, None] == jnp.arange(count, dtype=key.dtype)[None],
        axis=0, dtype=jnp.int32)
    weights = {name: params[name] for name in ("gate", "up", "down")}
    return _sorted_side(chunk_rows(x.shape[0], rows), activation, x, weights,
                        group_sizes, order[:rows], place)


def _pair_ids(t: int, k: int):
    """The (token, choice) pairs are numbered choice by choice: pair
    ``j*t + token`` is token's ``j``-th choice. [t, k] of them. (Token
    by token, ``[pairs, d] -> [t, k, d]`` would put ``k`` on a tiled
    dimension of the TPU's layout: a copy of the whole buffer, padded.)"""
    return (jnp.arange(k, dtype=jnp.int32)[None] * t
            + jnp.arange(t, dtype=jnp.int32)[:, None])


def _choice(out_pairs, t: int, j: int):
    """The ``t`` rows of every token's ``j``-th choice. Slices, never a
    reshape to [k, t, d]: the TPU compiler moves a reshape ahead of the
    arithmetic around it and then writes the operands out at the
    buffer's size."""
    return out_pairs[j * t:(j + 1) * t]


@jax.custom_vjp
def _combine(out_pairs, weights, mask):
    """``sum_j weights[tok, j] * out_pairs[j*t + tok]`` over the pairs
    ``mask`` keeps, accumulated in float32 → [t, d] float32. A masked
    pair's row is junk: selected away, not multiplied by zero. Its own
    VJP, so that what is kept for the backward pass is ``out_pairs`` as
    it came and not a float32 copy of a buffer sized for a bound."""
    t, k = weights.shape
    return sum(jnp.where(mask[:, j, None], weights[:, j, None]
                         * _choice(out_pairs, t, j).astype(jnp.float32), 0.0)
               for j in range(k))


def _combine_fwd(out_pairs, weights, mask):
    return _combine(out_pairs, weights, mask), (out_pairs, weights, mask)


def _combine_bwd(res, dy):
    out_pairs, weights, mask = res
    t, k = weights.shape
    with jax.named_scope(scopes.MOE):  # as in `_take_rows_bwd`
        d_rows = jnp.concatenate([
            jnp.where(mask[:, j, None], weights[:, j, None] * dy,
                      0.0).astype(out_pairs.dtype) for j in range(k)])
        d_weights = jnp.stack([
            jnp.sum(jnp.where(mask[:, j, None], _choice(out_pairs, t, j)
                              .astype(jnp.float32) * dy, 0.0), axis=-1)
            for j in range(k)], axis=1)
        return d_rows, d_weights, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def expert_layer(u, chosen, weights, expert_params, held=None, *,
                 axis_name=None, activation: str = "relu"):
    """The held experts' part of a gated sparse feed-forward.

    Args:
      u: [t, d] tokens (the local shard under ``axis_name``).
      chosen, weights: `route`'s, [t, k]; ``chosen`` counts over all the
        router's experts.
      expert_params: ``gate``, ``up`` [h, d, f] and ``down`` [h, f, d] of
        the ``h`` experts held here; expert ``e`` computes
        ``(act(u @ gate_e) * (u @ up_e)) @ down_e``.
      held: ``(first, count)``: this call holds experts ``first ..
        first + count - 1`` (default: ``0 .. h - 1``). Not with
        ``axis_name``, where chip ``i`` of the axis holds ``i*h ..``.
      axis_name: exchange the pairs over this mesh axis, so that every
        chosen expert is somebody's.
      activation: ``act``: ``"relu"`` (ReGLU experts) or ``"silu"``.

    Returns ``sum over the chosen e that are held of w_e * E_e(u)``,
    [t, d] in ``u``'s dtype. The weights stay normalised over all the
    chosen, held or not.
    """
    count = expert_params["gate"].shape[0]
    t, _ = u.shape
    k = chosen.shape[1]
    per_token = min(k, count)   # a token's chosen experts are distinct
    if axis_name is not None:
        if held is not None:
            raise ValueError("under axis_name a chip's experts follow from "
                             "its place on the axis, not from held=")
        return _exchanged(u, chosen, weights, expert_params, axis_name,
                          t * per_token, activation)
    first = 0 if held is None else held[0]
    if held is not None and held[1] != count:
        raise ValueError(f"held={held} but the parameters are of {count} "
                         "experts")
    local = chosen - first
    is_held = (local >= 0) & (local < count)
    key = jnp.where(is_held, local, count).T.reshape(-1)
    out_pairs = _experts_on_pairs(u, key, expert_params, t * per_token,
                                  activation)
    return _combine(out_pairs, weights, is_held).astype(u.dtype)


def _exchanged(u, chosen, weights, params, axis_name, bound: int,
               activation: str = "relu"):
    """`expert_layer` over the expert-parallel axis. A chip sends each
    peer at most ``bound`` rows (all of its pairs may go to one chip), so
    the exchange is ``[axis_size, bound, d]`` each way, padded."""
    n = lax.axis_size(axis_name)
    count = params["gate"].shape[0]
    t, d = u.shape
    k = chosen.shape[1]
    pairs = t * k
    expert = chosen.T.reshape(-1)
    order = jnp.argsort(expert, stable=True).astype(jnp.int32)
    place = jnp.argsort(order).astype(jnp.int32)
    dest = expert // count                                   # [pairs]
    to_peer = jnp.sum(dest[:, None] == jnp.arange(n)[None], axis=0,
                      dtype=jnp.int32)
    starts = jnp.cumsum(to_peer) - to_peer
    slot = dest * bound + (place - starts[dest])             # pair -> slot
    at = jnp.arange(bound, dtype=jnp.int32)
    filled = at[None] < to_peer[:, None]                     # [n, bound]
    sent = order[jnp.minimum(starts[:, None] + at[None], pairs - 1)]
    send_x = _take_rows(u, (sent % t).reshape(-1), slot[_pair_ids(t, k)],
                        jnp.ones((t, k), bool))
    send_key = jnp.where(filled, expert[sent] % count, count)
    recv_x = lax.all_to_all(send_x.reshape(n, bound, d), axis_name, 0, 0)
    recv_key = lax.all_to_all(send_key.astype(jnp.int32), axis_name, 0, 0)
    rows = n * bound
    out = _experts_on_pairs(recv_x.reshape(rows, d), recv_key.reshape(-1),
                            params, rows, activation)
    back = lax.all_to_all(out.reshape(n, bound, d), axis_name, 0, 0)
    out_pairs = _take_rows(back.reshape(rows, d), slot,
                           sent.reshape(-1, 1), filled.reshape(-1, 1))
    return _combine(out_pairs, weights,
                    jnp.ones((t, k), bool)).astype(u.dtype)
