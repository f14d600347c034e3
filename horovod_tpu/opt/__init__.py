"""Distributed optimizer layer for JAX/optax.

Reference surface being reproduced (TPU-first, not ported):

- `DistributedOptimizer` — wraps an optimizer so every gradient is averaged
  across workers before the update (reference tensorflow/__init__.py:599,
  torch/optimizer.py:35, mxnet/__init__.py:40).
- `DistributedGradientTape` — tape wrapper allreducing gradients
  (tensorflow/__init__.py:743). JAX has no tape; the equivalent is
  `distributed_grad`, a drop-in for `jax.grad` whose output gradients are
  already averaged.
- local gradient aggregation / `backward_passes_per_step`
  (tensorflow/gradient_aggregation.py:16): accumulate N micro-batch
  gradients locally, allreduce once.

In optax terms the wrapper is itself a `GradientTransformation`, so it
composes with any optax chain — that is the idiomatic JAX shape of
"wrap your optimizer".

vma note (important): under ``jax.shard_map`` with the default
``check_vma=True``, differentiating a device-varying loss with respect to a
*replicated* parameter already inserts the cross-chip ``psum`` during
transposition — gradients arrive pre-summed and a manual allreduce would
double-count. The Horovod contract (local gradients, explicit allreduce —
what this module provides) corresponds to ``check_vma=False`` shard_map
regions, which is what `horovod_tpu.parallel.dp` train-step builders use.
In vma-typed code, either keep params varying
(``lax.pcast(..., to="varying")``) or skip the manual allreduce.

Fusion note: inside jit, per-tensor ``psum`` calls are fused by XLA; with
``fuse_buckets=True`` we additionally flatten the gradient pytree into one
flat buffer per dtype before a single ``psum`` — guaranteeing exactly one
collective per dtype per step (the tensor-fusion contract,
fusion_buffer_manager.h:40) regardless of compiler heuristics. Where the
axis has one member (a one-chip run of the same script) there is nothing
to reduce and no buffer is built: `_tree_allreduce` hands the gradients
straight to the update.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from ..common.context import DEFAULT_AXIS
from ..ops import collectives as C
from ..ops.collectives import ReduceOp
from ..utils import scopes


def _tree_allreduce(grads, op, axis_name, compression, prescale, postscale,
                    fuse_buckets: bool):
    """The replicated path's gradient exchange (`DistributedOptimizer`,
    `distributed_grad`, `distributed_value_and_grad`).

    Over an axis of one member the exchange is the identity, whatever
    ``op`` (the sum, mean, minimum, maximum, product and Adasum of one
    member are that member) and whatever ``compression`` (a wire format
    without a wire): traced gradients come back as they are, times
    ``prescale * postscale``, with no flat buffer, no collective and no
    rounding (``Compression.fp16`` and the stateless int8 wire round
    only where something is sent; upstream Horovod likewise skips the
    allreduce at ``size() == 1``). `scopes.note_exchange` still records
    the axis, with no collective. Not covered: eager calls (no axis to
    ask; the negotiated path decides for itself), the error-feedback
    quantized branch (its residual state is shaped like the flat
    buffer) and the ZeRO-1 wrappers (``opt/sharded.py``,
    `cross_replica_sharded_optimizer`)."""
    if (any(C._is_traced(g) for g in jax.tree.leaves(grads))
            and jax.lax.axis_size(axis_name) == 1):
        scopes.note_exchange([], axis_name)
        scale = prescale * postscale
        return (grads if scale == 1.0
                else jax.tree.map(lambda g: g * scale, grads))
    qspec = (getattr(compression, "quant_spec", None)
             if compression is not None else None)
    if qspec is not None:
        # stateless quantized reduce (no error-feedback carry across
        # calls — persistent EF lives in the optimizer wrapper's state)
        red, _ = quantized_tree_allreduce(
            grads, qspec, op=op, axis_name=axis_name,
            prescale_factor=prescale, postscale_factor=postscale)
        return red
    if fuse_buckets:
        return fused_tree_allreduce(grads, op=op, axis_name=axis_name,
                                    compression=compression,
                                    prescale_factor=prescale,
                                    postscale_factor=postscale)
    scopes.note_exchange(jax.tree.leaves(grads), axis_name)
    with jax.named_scope(scopes.REDUCE):
        return jax.tree.map(
            lambda g: C.allreduce(g, op=op, axis_name=axis_name,
                                  compression=compression,
                                  prescale_factor=prescale,
                                  postscale_factor=postscale),
            grads)


def _pack(leaves, idxs, axis_name):
    """The leaves ``idxs`` as the one flat buffer a collective takes."""
    with jax.named_scope(scopes.PACK):
        flats = [jnp.ravel(leaves[i]) for i in idxs]
        fused = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
    scopes.note_exchange([fused], axis_name, packed=len(flats) > 1)
    return fused


def _unpack(red, leaves, idxs, out) -> None:
    """`_pack`'s inverse on the reduced buffer, into ``out[i]``."""
    off = 0
    with jax.named_scope(scopes.UNPACK):
        for i in idxs:
            n = jnp.size(leaves[i])
            out[i] = jnp.reshape(red[off:off + n], jnp.shape(leaves[i]))
            off += n


def _quant_partition(tree):
    """Split a gradient pytree into quantization-eligible and fallback
    leaf indices per the convergence guardrails (ops/compression.py):
    name-pattern opt-outs (the tree path is the name), the small-leaf
    threshold, non-float dtypes. Pure Python over static metadata — runs
    at trace time, and the fallback counters tick once per (re)trace,
    matching their once-per-tensor semantics."""
    from ..ops import compression as compression_mod

    lwp, treedef = jax.tree_util.tree_flatten_with_path(tree)
    pats = compression_mod.quant_optout_patterns()
    mn = compression_mod.quant_min_elems()
    elig, plain = [], []
    for i, (path, leaf) in enumerate(lwp):
        name = jax.tree_util.keystr(path)
        reason = compression_mod.quant_fallback_reason(
            name, jnp.asarray(leaf).size, jnp.asarray(leaf).dtype,
            pats, mn)
        if reason is None:
            elig.append(i)
        else:
            compression_mod.quant_fallback_counter(reason).inc()
            plain.append(i)
    return [leaf for _, leaf in lwp], treedef, elig, plain


def quantized_tree_allreduce(tree, spec, *, op=ReduceOp.AVERAGE,
                             axis_name=DEFAULT_AXIS, prescale_factor=1.0,
                             postscale_factor=1.0, residuals=None):
    """Tensor-fused blockwise-quantized tree allreduce (traced path).

    Eligible leaves fuse into one flat buffer per dtype and go through
    ``collectives.quantized_allreduce`` — the EQuARX reduce-scatter/
    allgather with int8/int4 payloads compiled into the caller's
    program. Guardrail leaves (opt-outs, small leaves, non-floats) ride
    the plain fused psum. Returns ``(reduced_tree, new_residuals)``
    where ``new_residuals`` maps the per-dtype fused-buffer key to this
    rank's fresh quantization error; pass it back as ``residuals`` next
    step for error feedback (DistributedGradientTransformation stores it
    in optimizer state and does exactly that)."""
    from ..ops import compression as compression_mod

    leaves, treedef, elig, plain = _quant_partition(tree)
    if not leaves:
        return tree, {}
    out = [None] * len(leaves)
    new_res: dict = {}
    traced = any(C._is_traced(l) for l in leaves)

    def _by_dtype(idxs):
        groups: dict = {}
        for i in idxs:
            groups.setdefault(str(jnp.asarray(leaves[i]).dtype), []).append(i)
        return dict(sorted(groups.items()))

    for dt, idxs in _by_dtype(plain).items():
        fused = _pack(leaves, idxs, axis_name)
        with jax.named_scope(scopes.REDUCE):
            red = C.allreduce(fused, op=op, axis_name=axis_name,
                              prescale_factor=prescale_factor,
                              postscale_factor=postscale_factor)
        _unpack(red, leaves, idxs, out)
    for dt, idxs in _by_dtype(elig).items():
        fused = _pack(leaves, idxs, axis_name)
        if traced:
            res = residuals.get(dt) if residuals else None
            if res is not None and res.shape != fused.shape:
                res = None  # layout moved (resize/re-trace): clean reset
            with jax.named_scope(scopes.REDUCE):
                red, err = C.quantized_allreduce(
                    fused, axis_name, spec, op=op,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor, residual=res)
            new_res[dt] = err
        else:
            # eager call (no axis in scope): the quant marker routes the
            # fused buffer through the eager quantized chunk plan;
            # stateless — the queue runtime owns eager error feedback
            marker = compression_mod.QuantCompressor(
                spec.bits, spec.block, spec.error_feedback)
            red = C.allreduce(fused, op=op,
                              prescale_factor=prescale_factor,
                              postscale_factor=postscale_factor,
                              compression=marker)
        _unpack(red, leaves, idxs, out)
    return jax.tree.unflatten(treedef, out), new_res


def quant_residual_init(params, spec):
    """Zero error-feedback carries matching the fused-buffer layout
    ``quantized_tree_allreduce`` will use for this parameter tree — the
    init half of the optimizer-state EF contract."""
    leaves, _, elig, _ = _quant_partition(params)
    res: dict = {}
    for i in elig:
        dt = str(jnp.asarray(leaves[i]).dtype)
        res[dt] = res.get(dt, 0) + int(jnp.asarray(leaves[i]).size)
    return {dt: jnp.zeros((n,), jnp.float32) for dt, n in res.items()}


def fused_tree_allreduce(tree, *, op=ReduceOp.AVERAGE, axis_name=DEFAULT_AXIS,
                         compression=None, prescale_factor=1.0,
                         postscale_factor=1.0):
    """Flatten a pytree into one flat buffer per dtype and allreduce each
    with a single collective, then unflatten. This is tensor fusion on the
    compiled path."""
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    if compression is not None:
        comp = [compression.compress(l) for l in leaves]
        leaves = [c[0] for c in comp]
        dectxs = [c[1] for c in comp]
    by_dtype: dict = {}
    for i, l in enumerate(leaves):
        by_dtype.setdefault(jnp.asarray(l).dtype, []).append(i)
    out = [None] * len(leaves)
    for dt, idxs in by_dtype.items():
        fused = _pack(leaves, idxs, axis_name)
        with jax.named_scope(scopes.REDUCE):
            red = C.allreduce(fused, op=op, axis_name=axis_name,
                              prescale_factor=prescale_factor,
                              postscale_factor=postscale_factor)
        _unpack(red, leaves, idxs, out)
    if compression is not None:
        out = [compression.decompress(o, c) for o, c in zip(out, dectxs)]
    return jax.tree.unflatten(treedef, out)


class _AggState(NamedTuple):
    inner: optax.OptState
    acc: optax.Updates
    counter: jnp.ndarray


class _QuantEFState(NamedTuple):
    """Optimizer state wrapper carrying the error-feedback residuals for
    the quantized wire (per-dtype fused-buffer flat float32 arrays)."""

    inner: optax.OptState
    residuals: dict


def DistributedGradientTransformation(
    optimizer: optax.GradientTransformation,
    *,
    op: ReduceOp = ReduceOp.AVERAGE,
    axis_name: str = DEFAULT_AXIS,
    compression=None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    backward_passes_per_step: int = 1,
    fuse_buckets: bool = True,
    average_aggregated_gradients: bool = True,
    sharded_update: Optional[bool] = None,
    num_shards: Optional[int] = None,
    min_shard_elems: Optional[int] = None,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer so gradients are allreduced before update.

    Must be used inside a compiled per-chip context (shard_map / pjit with
    ``axis_name`` bound). With ``backward_passes_per_step > 1``, gradients
    are accumulated locally and only every Nth update triggers the
    collective + inner update (reference gradient_aggregation.py:16);
    intermediate steps return zero updates. Where ``axis_name`` has one
    member (the same script on one chip) nothing is exchanged, packed or
    rounded for a wire: the inner update gets the local gradients, times
    the two scale factors (`_tree_allreduce`; docs/tensor-fusion.md).

    ``sharded_update`` (ZeRO-1, docs/sharded_optimizer.md): replace
    allreduce + replicated step with reduce-scatter → sharded step →
    allgather — optimizer state 1/N per chip. ``None`` defers to the
    ``HOROVOD_SHARDED_UPDATE`` env knob; ``num_shards``/
    ``min_shard_elems`` parameterize the layout planner.
    """
    from . import sharded as sharded_mod

    if sharded_update is None:
        sharded_update = sharded_mod.sharded_update_enabled()
    if sharded_update:
        if backward_passes_per_step > 1:
            raise ValueError(
                "sharded_update does not compose with "
                "backward_passes_per_step > 1 — accumulate outside the "
                "optimizer (or run the replicated path)")
        if compression is not None:
            raise ValueError(
                "sharded_update does not compose with gradient "
                "compression (the reduce-scatter shard is never "
                "materialized as a full tensor to compress)")
        return sharded_mod.ShardedDistributedOptimizer(
            optimizer, num_shards=num_shards, axis_name=axis_name, op=op,
            min_shard_elems=min_shard_elems,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor)
    n = backward_passes_per_step
    qspec = (getattr(compression, "quant_spec", None)
             if compression is not None else None)
    if qspec is not None and qspec.error_feedback:
        # persistent error feedback: the residual carry lives in the
        # optimizer state so it survives across steps and checkpoints —
        # and resets naturally with a fresh init after an elastic resize
        if n > 1:
            raise ValueError(
                "quantized compression with error feedback does not "
                "compose with backward_passes_per_step > 1 — accumulate "
                "outside the optimizer, or disable error feedback "
                "(Compression.int8.with_options(error_feedback=False))")

        def q_init_fn(params):
            return _QuantEFState(optimizer.init(params),
                                 quant_residual_init(params, qspec))

        def q_update_fn(grads, state, params=None):
            reduced, new_res = quantized_tree_allreduce(
                grads, qspec, op=op, axis_name=axis_name,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
                residuals=state.residuals)
            with jax.named_scope(scopes.OPTIMIZER):
                updates, inner = optimizer.update(reduced, state.inner,
                                                  params)
            if not new_res:
                new_res = state.residuals  # eager call: carry unchanged
            return updates, _QuantEFState(inner, new_res)

        return optax.GradientTransformation(q_init_fn, q_update_fn)

    def init_fn(params):
        inner = optimizer.init(params)
        if n <= 1:
            return inner
        acc = jax.tree.map(jnp.zeros_like, params)
        return _AggState(inner, acc, jnp.zeros((), jnp.int32))

    def _reduce(grads):
        return _tree_allreduce(grads, op, axis_name, compression,
                               prescale_factor, postscale_factor, fuse_buckets)

    def _update(reduced, inner, params):
        with jax.named_scope(scopes.OPTIMIZER):
            return optimizer.update(reduced, inner, params)

    def update_fn(grads, state, params=None):
        if n <= 1:
            return _update(_reduce(grads), state, params)
        acc = jax.tree.map(lambda a, g: a + g, state.acc, grads)
        counter = state.counter + 1
        is_step = counter >= n

        def do_step(_):
            scale = 1.0 / n if average_aggregated_gradients else 1.0
            reduced = _reduce(jax.tree.map(lambda a: a * scale, acc))
            updates, inner = _update(reduced, state.inner, params)
            zeroed = jax.tree.map(jnp.zeros_like, acc)
            return updates, _AggState(inner, zeroed, jnp.zeros((), jnp.int32))

        def skip(_):
            zeros = jax.tree.map(jnp.zeros_like, acc)
            return zeros, _AggState(state.inner, acc, counter)

        return jax.lax.cond(is_step, do_step, skip, None)

    return optax.GradientTransformation(init_fn, update_fn)


# Horovod-style name
DistributedOptimizer = DistributedGradientTransformation


def distributed_grad(
    fun: Callable,
    *,
    op: ReduceOp = ReduceOp.AVERAGE,
    axis_name: str = DEFAULT_AXIS,
    compression=None,
    fuse_buckets: bool = True,
    has_aux: bool = False,
    argnums=0,
):
    """`jax.grad` whose gradients come back already allreduced — the JAX
    equivalent of DistributedGradientTape (tensorflow/__init__.py:743)."""
    gfun = jax.grad(fun, argnums=argnums, has_aux=has_aux)

    def wrapped(*args, **kwargs):
        if has_aux:
            g, aux = gfun(*args, **kwargs)
            return _tree_allreduce(g, op, axis_name, compression, 1.0, 1.0,
                                   fuse_buckets), aux
        g = gfun(*args, **kwargs)
        return _tree_allreduce(g, op, axis_name, compression, 1.0, 1.0,
                               fuse_buckets)

    return wrapped


def distributed_value_and_grad(
    fun: Callable,
    *,
    op: ReduceOp = ReduceOp.AVERAGE,
    axis_name: str = DEFAULT_AXIS,
    compression=None,
    fuse_buckets: bool = True,
    has_aux: bool = False,
    average_loss: bool = True,
    argnums=0,
):
    vgfun = jax.value_and_grad(fun, argnums=argnums, has_aux=has_aux)

    def wrapped(*args, **kwargs):
        val, g = vgfun(*args, **kwargs)
        g = _tree_allreduce(g, op, axis_name, compression, 1.0, 1.0, fuse_buckets)
        if average_loss:
            if has_aux:
                loss, aux = val
                val = (jax.lax.pmean(loss, axis_name), aux)
            else:
                val = jax.lax.pmean(val, axis_name)
        return val, g

    return wrapped


class _ShardedUpdate(NamedTuple):
    inner: object


def cross_replica_sharded_optimizer(inner: optax.GradientTransformation,
                                    num_shards: int,
                                    axis_name: str = DEFAULT_AXIS
                                    ) -> optax.GradientTransformation:
    """Shard the weight update across data-parallel replicas (ZeRO-1).

    The XLA "automatic cross-replica sharding of weight update"
    optimization (arXiv:2004.13336) as an explicit optax wrapper —
    greenfield vs the reference, which always runs the full update on
    every worker.

    Inside a ``shard_map`` DP region, each chip:

      1. reduce-scatters the gradients (``psum_scatter``) — same bytes on
         the wire as allreduce, split as RS+AG around the update;
      2. runs ``inner.update`` on its 1/num_shards slice of every leaf —
         optimizer state (e.g. Adam's m/v) is **num_shards× smaller per
         chip**, the classic ZeRO-1 memory win;
      3. all-gathers the update slices back to full updates for
         ``optax.apply_updates``.

    Exact for elementwise optimizers (SGD/momentum/Adam/AdamW/...): the
    sharded update equals the replicated update slice-for-slice. Not for
    optimizers whose update couples elements across a leaf or reads the
    tree structure (per-layer norms like LARS, Adafactor row factors,
    ``optax.masked``/``multi_transform``) — use the plain wrapper for
    those: the fused shard hands the inner optimizer ONE flat leaf per
    dtype (the module's tensor-fusion contract — exactly one
    reduce-scatter + all-gather pair per dtype per step).

    Use under ``data_parallel_step`` / shard_map with ``axis_name`` in
    scope; ``num_shards`` must equal the axis size (validated at trace
    time).
    """

    def _chunk(total: int) -> int:
        return -(-total // num_shards)

    def _dtype_totals(tree) -> dict:
        totals: dict = {}
        for l in jax.tree.leaves(tree):
            k = str(jnp.asarray(l).dtype)
            totals[k] = totals.get(k, 0) + l.size
        return dict(sorted(totals.items()))

    def init(params):
        shard_shaped = {dt: jnp.zeros((_chunk(total),), dtype=dt)
                        for dt, total in _dtype_totals(params).items()}
        return _ShardedUpdate(inner.init(shard_shaped))

    def update(grads, state, params=None):
        axis_n = jax.lax.axis_size(axis_name)
        if axis_n != num_shards:
            raise ValueError(
                f"cross_replica_sharded_optimizer(num_shards={num_shards}) "
                f"used under a {axis_n}-wide '{axis_name}' axis — gradient "
                "scaling would be silently wrong")
        idx = jax.lax.axis_index(axis_name)
        leaves, treedef = jax.tree.flatten(grads)
        p_leaves = (jax.tree.leaves(params) if params is not None else None)
        # group by the PARAM dtype when params are given (init keyed state
        # the same way): bf16 grads under fp32 params cast up before the
        # sharded update — master-weight semantics, and the state dict
        # keys always match init's
        ref_leaves = p_leaves if p_leaves is not None else leaves
        groups = {}  # dtype -> leaf indices, in flatten order
        for i, l in enumerate(ref_leaves):
            groups.setdefault(str(l.dtype), []).append(i)
        groups = dict(sorted(groups.items()))

        def fuse(ls, dt):
            with jax.named_scope(scopes.PACK):
                flats = [jnp.ravel(x).astype(dt) for x in ls]
                flat = (flats[0] if len(flats) == 1
                        else jnp.concatenate(flats))
                c = _chunk(flat.size)
                return jnp.pad(flat, (0, c * num_shards - flat.size)), c

        g_shard, p_shard = {}, {}
        for dt, idxs in groups.items():
            fused_g, c = fuse([leaves[i] for i in idxs], dt)
            scopes.note_exchange([fused_g], axis_name, packed=True)
            with jax.named_scope(scopes.REDUCE):
                g_shard[dt] = jax.lax.psum_scatter(
                    fused_g, axis_name, tiled=True) / num_shards
            if p_leaves is not None:
                fused_p, _ = fuse([p_leaves[i] for i in idxs], dt)
                p_shard[dt] = jax.lax.dynamic_slice(fused_p, (idx * c,), (c,))
        with jax.named_scope(scopes.OPTIMIZER):
            u_shard, new_inner = inner.update(
                g_shard, state.inner,
                p_shard if p_leaves is not None else None)

        out = list(leaves)
        for dt, idxs in groups.items():
            scopes.note_exchange([u_shard[dt]], axis_name)
            with jax.named_scope(scopes.REDUCE):
                full = jax.lax.all_gather(u_shard[dt], axis_name, tiled=True)
            off = 0
            for i in idxs:
                # dtype ref: the param leaf when given — casting updates to
                # a bf16 GRAD dtype under fp32 params would drift from the
                # replicated trajectory
                ref = p_leaves[i] if p_leaves is not None else leaves[i]
                n_el = leaves[i].size
                with jax.named_scope(scopes.UNPACK):
                    out[i] = jax.lax.slice(full, (off,), (off + n_el,)) \
                        .reshape(leaves[i].shape).astype(ref.dtype)
                off += n_el
        return jax.tree.unflatten(treedef, out), _ShardedUpdate(new_inner)

    return optax.GradientTransformation(init, update)


# ZeRO-1 sharded-update subsystem (docs/sharded_optimizer.md)
from .sharded import (  # noqa: E402  (re-export after the core wrappers)
    ShardGroup,
    ShardLayout,
    ShardedDistributedOptimizer,
    ShardedUpdateEngine,
    make_simulated_engines,
    plan_shard_layout,
    simulated_full_state,
    simulated_step,
)
