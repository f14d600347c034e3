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
the sorted position is computed for one chunk at a time: the gather of
the tokens' rows, both grouped matmuls, the activation and the routing
weight, and the token side, which sums each token's rows of the chunk in
token order and brings the sums home with one gather of ``tokens`` rows
(`_token_sums`); in the backward pass the gather of the cotangent to the
rows and all their transposes. Chunk 0 always runs; one loop a direction
walks the further chunks that a routed row reaches, so a step in which
the held experts take no more rows than there are tokens pays for one
chunk and a loop of no trips, and a skewed one for as many chunks as its
rows fill: more trips, never a dropped row, and one path whatever the
routing. Nothing is sized for the (token, expert) pairs but vectors of
indices (`_indices`).

On one chip nothing is exchanged. With ``axis_name`` (inside a
``shard_map`` over the expert-parallel axis, each chip holding
``experts / axis_size`` of them and a shard of the tokens) the pairs go
to their experts' chips and back through two ``all_to_all``s sized for
the same bound; compiled programs need static shapes, so the uneven
split is padding, never a dropped token (SURVEY.md §7 hard part 6).

Both directions of every row movement are gathers: the transpose of a
gather is a scatter-add, which a TPU serialises row by row, but the
sort that made the gather's indices also gives the indices of its
inverse (`_sorted_side` has its own VJP for that reason, and recomputes
a chunk's hidden activation inside the backward loop, so that nothing
of a chunk's size is handed out of a loop; the exchange's `_take_rows`
likewise).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
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
    where ``back_mask[i]`` ([n, m] each), and by no other. The `'ep'`
    exchange's (`_exchanged`) only, as are `_combine` and its helpers."""
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


def _chunk(c, chunk: int, x, ix):
    """Chunk ``c`` of the sorted positions: its first position, the
    pairs at its ``chunk`` positions, their rows of ``x`` (pair ``p``
    reads row ``p % n``) and the part of each expert's group that lies in
    it (the groups follow one another from position 0)."""
    lo = c * chunk
    pairs = lax.dynamic_slice_in_dim(ix["order"], lo, chunk)
    inside = (lax.clamp(lo, ix["ends"], lo + chunk)
              - lax.clamp(lo, ix["starts"], lo + chunk))
    return (lo, pairs, x.at[lax.rem(pairs, x.shape[0])].get(
        mode="promise_in_bounds"), inside)


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


def _shifted(a, s: int, fill):
    """``a`` moved ``s`` rows on, ``fill`` in the first ``s``: one pad."""
    return lax.pad(a, np.array(fill, a.dtype),
                   [(s, -s, 0)] + [(0, 0, 0)] * (a.ndim - 1))


def _as(a, dtype):
    return lax.bitcast_convert_type(a, dtype)


#: every sort of the layer: two int32 operands, by the first, unstable.
#: The TPU's compiler builds a sort of 98,304 elements in 5 s and 1.5 MB
#: of code, a stable one in 11 s and 1.8 MB, and it makes a stable sort
#: whose second operand is no iota a sort of three (17 s, 2.4 MB). The
#: keys are unique but for the token sort's, where a tie is two rows of
#: one token, summed in either order.
_UNSTABLE = {"num_keys": 1, "is_stable": False}


def _indices(chunk: int, rows: int, count: int, key, route_w) -> dict:
    """What both directions of `_sorted_side` read off the routing,
    made outside it by three sorts and arithmetic, all of them sorts of
    two int32 operands by the first, and each named `scopes.KEPT_ROUTING`
    so that a checkpointed block keeps them and does not sort again (an
    integer gather of ``pairs`` elements costs a TPU as much as a sixth
    of a row gather; a sort of them costs its compiler seconds and
    megabytes of code).

    ``order`` [pairs]: sorted position → pair, the held experts' pairs
    first and grouped by expert (a routed row lies in the first
    ``rows``); ``starts``, ``ends`` [count]: each group's bounds (the
    last ``ends`` is the rows routed); ``weight`` [pairs]: the pair's
    routing weight, by sorted position (moved by the sort as its bits:
    ``route_w``'s gradient is `_sorted_side`'s). The token side, by
    chunk of ``chunk`` positions: ``back`` [rows], the chunk's positions
    in token order; ``token`` [rows], the token of that slot (``n``
    where no routed row is: last); ``end`` [chunks, n], the slot that
    ends each token's rows in the chunk, or ``chunk`` where the token has
    none there."""
    n, k = route_w.shape
    chunks = rows // chunk
    pairs = lax.iota(jnp.int32, n * k)
    order, weight = lax.sort((key * (n * k) + pairs, _as(
        lax.stop_gradient(route_w).T.reshape(-1), jnp.int32)), **_UNSTABLE)
    order = lax.rem(order, n * k)
    ends = jnp.cumsum(jnp.sum(
        key[:, None] == lax.iota(key.dtype, count)[None], axis=0,
        dtype=jnp.int32))
    live = ends[-1]
    at = pairs[:rows]
    slot, back = lax.sort(
        (lax.div(at, chunk) * (n + 1)
         + lax.select(at < live, lax.rem(order[:rows], n),
                      lax.full_like(at, n)), at), **_UNSTABLE)
    place = lax.sort((order, pairs), **_UNSTABLE)[1]    # pair → position
    of = lax.select(place < live, lax.div(place, chunk),
                    lax.full_like(place, chunks)).reshape(k, 1, n)
    held = jnp.sum(of == lax.iota(jnp.int32, chunks)[:, None], axis=0,
                   dtype=jnp.int32)                  # [chunks, n]
    ix = {"order": order, "weight": _as(weight, jnp.float32), "ends": ends,
          "starts": jnp.concatenate([jnp.zeros(1, jnp.int32), ends[:-1]]),
          "back": lax.rem(back, chunk), "token": lax.rem(slot, n + 1),
          "end": lax.select(held > 0, jnp.cumsum(held, 1) - 1,
                            lax.full_like(held, chunk))}
    return {name: ad_checkpoint.checkpoint_name(a, scopes.KEPT_ROUTING)
            for name, a in ix.items()}


def index_bytes(n: int, k: int, count: int) -> int:
    """Bytes of `_indices`' arrays for ``n`` tokens, ``k`` choices and
    ``count`` experts held, all int32 or float32: what a checkpointed
    block keeps of an expert layer."""
    rows = n * min(k, count)
    return 4 * (2 * n * k + 2 * count + 2 * rows
                + rows // chunk_rows(n, rows) * n)


def _token_sums(rows, c, chunk: int, span: int, ix, scale=None):
    """Chunk ``c``'s ``rows`` [chunk, d] (in sorted order), each token's
    summed → [n, d] in ``rows``' dtype: exactly zero for a token none of
    them is of.

    The rows are taken into the chunk's token order (``ix["back"]``),
    where a token's rows lie side by side, at most ``span`` of them, and
    times ``scale`` [chunk] (in that order) where given. One pass over
    the chunk then sums, in float32, each row with the up to ``span - 1``
    rows before it that are of its token, so that the last of a token's
    rows holds them all, and one gather of ``n`` rows brings those home
    (``ix["end"]``; a token with none there reads a zero, selected after
    the gather: a zero row padded on would copy the chunk)."""
    lo = c * chunk
    z = rows.at[lax.dynamic_slice_in_dim(ix["back"], lo, chunk)].get(
        mode="promise_in_bounds")
    token = lax.dynamic_slice_in_dim(ix["token"], lo, chunk)

    def row(s):  # the rows ``s`` slots back, read as they are
        z_s = z if s == 0 else _shifted(z, s, 0)
        z_s = z_s.astype(jnp.float32)
        if scale is None:
            return z_s
        return z_s * (scale if s == 0 else _shifted(scale, s, 0))[:, None]

    total = row(0)
    for s in range(1, span):
        total = lax.select(lax.broadcast_in_dim(
            token == _shifted(token, s, -1), z.shape, (0,)),
            total + row(s), total)
    end = lax.dynamic_index_in_dim(ix["end"], c, keepdims=False)
    home = total.astype(rows.dtype).at[jnp.minimum(end, chunk - 1)].get(
        mode="promise_in_bounds")
    return lax.select(lax.broadcast_in_dim(end < chunk, home.shape, (0,)),
                      home, lax.full_like(home, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _sorted_side(chunk: int, activation: str, x, route_w, weights, ix):
    """The expert layer on one chip's tokens, chunk by chunk: ``x``
    [n, d] → [n, d] in ``x``'s dtype, row ``i`` the sum over token
    ``i``'s pairs that a held expert takes of the pair's weight times
    the expert applied to ``x[i]``.

    ``route_w`` [n, k] float32, the weights by pair (`_indices` moved
    them to ``ix["weight"]``, which the forward pass reads: this is
    where their gradient goes); ``weights``: ``gate``, ``up``, ``down``
    of the experts held; ``ix``: `_indices`'. Everything whose leading
    dimension is the sorted position runs a chunk of ``chunk`` positions
    at a time (`_in_chunks`): the gather of the tokens' rows, both
    grouped matmuls and the activation, and the token side
    (`_token_sums`: a token's rows weighted and summed in token order and
    brought home by one gather of ``n`` rows), and in the backward pass
    the gather of the cotangent to the rows, each row's weight gradient
    and all their transposes.
    Chunks after the first add into the result: nothing is sized for
    the pairs.

    Its own VJP (`_sorted_side_fwd`, `_sorted_side_bwd`), so that a
    chunk's gathers stay gathers in both directions, what is kept for
    the backward pass is the inputs and the indices (the backward loop
    recomputes a chunk's hidden rows), and nothing of a chunk's size
    leaves a loop."""
    return _sorted_side_fwd(chunk, activation, x, route_w, weights, ix)[0]


def _cast(weights, dtype):
    # gate and up as one matmul: the rows are read once, and their
    # gradient is one array, not the sum of two at the buffers' size
    return (jnp.concatenate([weights["gate"], weights["up"]],
                            axis=-1).astype(dtype),
            weights["down"].astype(dtype))


def _span(pairs: int, n: int, weights) -> int:
    """The most held rows one token can have: its chosen are distinct."""
    return min(pairs // n, weights["gate"].shape[0])


def _sorted_side_fwd(chunk, activation, x, route_w, weights, ix):
    n, span = x.shape[0], _span(ix["order"].shape[0], x.shape[0], weights)
    gate_up, down = _cast(weights, x.dtype)

    def on_chunk(c, y):
        lo, _, xs, inside = _chunk(c, chunk, x, ix)
        # each row's routing weight, in the chunk's token order: applied in
        # float32 to the expert's bf16 row, as the parent's weighted sum did
        w = ix["weight"].at[lo + lax.dynamic_slice_in_dim(
            ix["back"], lo, chunk)].get(mode="promise_in_bounds")
        y_c = _token_sums(_experts_on_rows(xs, gate_up, down, inside,
                                           activation), c, chunk, span, ix, w)
        return y_c if y is None else y + y_c

    y = _in_chunks(ix["ends"][-1], chunk, n * span // chunk, on_chunk)
    return y, (x, route_w.shape, weights, ix)


def _sorted_side_bwd(chunk, activation, res, g):
    x, shape, weights, ix = res
    n, pairs, live = x.shape[0], ix["order"].shape[0], ix["ends"][-1]
    span = _span(pairs, n, weights)
    # a custom VJP's backward pass is traced outside the scope its call
    # stood in: name it again, or a trace files it under nothing
    with jax.named_scope(scopes.MOE):
        gate_up, down = _cast(weights, x.dtype)

        def on_chunk(c, carry):
            lo, at, xs, inside = _chunk(c, chunk, x, ix)
            # recomputed here from `x` and the indices, not kept
            out, back = jax.vjp(functools.partial(
                _experts_on_rows, sizes=inside, activation=activation), xs,
                gate_up, down)
            dy = g.at[lax.rem(at, n)].get(mode="promise_in_bounds").astype(
                jnp.float32)
            # the weight's gradient a dot product of the expert's row and
            # the cotangent, d wide in float32: taken from the cotangent of
            # the hidden row instead (f wide) it reads that row rounded to
            # bf16 by the grouped matmul, and the router's gradient, a
            # difference of these, read 2-3 times worse on the chip (PR 37).
            # Positions past `live` are in no expert's group: what they hold
            # reaches no weight's gradient, no token's sum (their slots are
            # the token ``n``'s) and no routing weight's
            d_w = jnp.sum(out.astype(jnp.float32) * dy, axis=-1)
            d_xs, *d_p = back((lax.dynamic_slice_in_dim(
                ix["weight"], lo, chunk)[:, None] * dy).astype(x.dtype))
            d_x = _token_sums(d_xs, c, chunk, span, ix)
            d_p = [d.astype(jnp.float32) for d in d_p]
            if carry is None:
                return d_x, lax.pad(d_w, np.array(0, d_w.dtype),
                                    [(0, pairs - chunk, 0)]), d_p
            return (carry[0] + d_x,
                    lax.dynamic_update_slice_in_dim(carry[1], d_w, lo, 0),
                    [a + d for a, d in zip(carry[2], d_p)])

        d_x, d_w, (d_gate_up, d_down) = _in_chunks(live, chunk,
                                                   n * span // chunk, on_chunk)
        width = weights["gate"].shape[-1]
        d_weights = {"gate": d_gate_up[..., :width],
                     "up": d_gate_up[..., width:], "down": d_down}
        # the routing weights' gradient back in pair order: one sort
        d_w = lax.select(lax.iota(jnp.int32, pairs) < live, d_w,
                         lax.full_like(d_w, 0))
        d_route = _as(lax.sort((ix["order"], _as(d_w, jnp.int32)),
                               **_UNSTABLE)[1], jnp.float32)
        return (d_x, d_route.reshape(shape[::-1]).T,
                jax.tree.map(lambda d, w: d.astype(w.dtype), d_weights,
                             weights), None)


_sorted_side.defvjp(_sorted_side_fwd, _sorted_side_bwd)


def chunk_rows(n: int, rows: int) -> int:
    """Rows of one of the chunks a layer's ``rows`` sorted positions are
    walked in, ``n`` being the rows of what the layer gathers from: ``n``
    where that cuts ``rows`` into whole chunks. On one chip it does
    (``rows`` is the tokens times the most held experts one token can
    have); behind the exchange ``n`` is ``rows``, the received buffer:
    one chunk."""
    return n if rows % n == 0 else rows


def _experts_on_tokens(x, key, route_w, params, activation: str = "relu"):
    """``x`` [n, d], ``key`` [n * k] (pair ``j*n + i`` → local expert,
    ``count`` = none held here), ``route_w`` [n, k] → [n, d]: each
    token's held pairs' weighted expert outputs, summed (`_sorted_side`),
    in chunks of ``n`` sorted positions (`chunk_rows`) up to the bound
    ``n * min(k, count)``."""
    n, k = route_w.shape
    count = params["gate"].shape[0]
    bound = n * min(k, count)
    chunk = chunk_rows(n, bound)
    route_w = route_w.astype(jnp.float32)
    weights = {name: params[name] for name in ("gate", "up", "down")}
    return _sorted_side(chunk, activation, x, route_w, weights,
                        _indices(chunk, bound, count, key, route_w))


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
    ``mask`` keeps, accumulated in float32 → [t, d] float32 (the `'ep'`
    exchange's weighted sum; one chip sums in `_token_sums`). A masked
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
    [t, d] in ``u``'s dtype, exactly zero for a token that chose none of
    them. The weights stay normalised over all the chosen, held or not.
    On one chip the pairs are sorted by expert and walked in chunks of
    ``t`` sorted rows (`_sorted_side`); each token's rows are summed in
    token order and brought home by one gather of ``t`` rows
    (`_token_sums`), and no array of the pairs' rows is made in either
    direction. Under ``axis_name`` each received row is a token of one
    choice of weight 1 to the same walk, and `_combine` weighs them.
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
    key = jnp.where((local >= 0) & (local < count), local, count)
    return _experts_on_tokens(u, key.T.reshape(-1), weights, expert_params,
                              activation)


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
    # each received row a token of one choice, of weight 1
    out = _experts_on_tokens(recv_x.reshape(rows, d), recv_key.reshape(-1),
                             jnp.ones((rows, 1), jnp.float32), params,
                             activation)
    back = lax.all_to_all(out.reshape(n, bound, d), axis_name, 0, 0)
    out_pairs = _take_rows(back.reshape(rows, d), slot,
                           sent.reshape(-1, 1), filled.reshape(-1, 1))
    return _combine(out_pairs, weights,
                    jnp.ones((t, k), bool)).astype(u.dtype)
