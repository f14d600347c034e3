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
to reduce and no buffer is built (`_one_member`): the gradients go
straight to the update. Every traced exchange of this package is made
of `_pack`, `_reduce` (the one place a collective is issued and noted)
and `_unpack`; `_exchange_by_dtype` is the three in a row.
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


def _one_member(leaves, axis_name) -> bool:
    """The one-member rule: traced ``leaves`` over an axis of one member.

    There an exchange is the identity, whatever the op (the sum, mean,
    minimum, maximum, product and Adasum of one member are that member)
    and whatever the wire format (without a wire nothing is rounded;
    upstream Horovod likewise skips the allreduce at ``size() == 1``).
    Eager leaves have no axis to ask: the negotiated path decides.

    Takes the shortcut: `_tree_allreduce`, that is `DistributedOptimizer`
    with and without accumulation under any ``compression`` (the
    stateless quantized wire included), `distributed_grad` and
    `distributed_value_and_grad`. Does not, and builds its exchange over
    one member as over many: the error-feedback quantized branch (its
    residuals are shaped like the flat buffer, and checkpoints hold
    them) and `ShardedDistributedOptimizer` (its state is shaped like
    the shard). Whether they should is a speed question (ROADMAP D14)."""
    return (any(C._is_traced(l) for l in leaves)
            and jax.lax.axis_size(axis_name) == 1)


def _tree_allreduce(grads, op, axis_name, compression, prescale, postscale,
                    fuse_buckets: bool):
    """The replicated path's gradient exchange. Over one member
    (`_one_member`) the gradients come back as they are, times
    ``prescale * postscale``: no flat buffer, no collective, no
    rounding; `scopes.note_exchange` still records the axis."""
    leaves = jax.tree.leaves(grads)
    if _one_member(leaves, axis_name):
        scopes.note_exchange([], axis_name)
        scale = prescale * postscale
        return (grads if scale == 1.0
                else jax.tree.map(lambda g: g * scale, grads))
    qspec = getattr(compression, "quant_spec", None)
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
    return jax.tree.unflatten(jax.tree.structure(grads), _reduce(
        leaves, axis_name,
        lambda g: C.allreduce(g, op=op, axis_name=axis_name,
                              compression=compression,
                              prescale_factor=prescale,
                              postscale_factor=postscale)))


def _pack(leaves, idxs, *, dtype=None, padded=None):
    """The leaves ``idxs`` as the one flat buffer a collective takes:
    each cast to ``dtype`` where one is given, the buffer zero-padded to
    ``padded`` elements where it is shorter."""
    with jax.named_scope(scopes.PACK):
        flats = [jnp.ravel(leaves[i]) for i in idxs]
        if dtype is not None:
            flats = [f.astype(dtype) for f in flats]
        fused = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
        if padded is not None and padded > fused.size:
            fused = jnp.pad(fused, (0, padded - fused.size))
    return fused


def _reduce(buffers, axis_name, collective, packed: bool = False) -> list:
    """The one reduce step: ``collective(buffer)`` for each of
    ``buffers`` under the ``reduce`` scope, noted as that many
    collectives over ``axis_name`` (``packed``: the buffers are copies
    `_pack` made of more than one leaf)."""
    scopes.note_exchange(buffers, axis_name, packed=packed)
    with jax.named_scope(scopes.REDUCE):
        return [collective(b) for b in buffers]


def _unpack(red, leaves, idxs, out, *, like=None) -> None:
    """`_pack`'s inverse on the reduced buffer, into ``out[i]``, each
    slice cast to the dtype of ``like[i]`` where ``like`` is given."""
    off = 0
    with jax.named_scope(scopes.UNPACK):
        for i in idxs:
            n = jnp.size(leaves[i])
            part = jnp.reshape(red[off:off + n], jnp.shape(leaves[i]))
            out[i] = part if like is None else part.astype(like[i].dtype)
            off += n


def _exchange_by_dtype(leaves, idxs, axis_name, collective, out) -> None:
    """Pack → ``collective`` → unpack of the leaves ``idxs``, one flat
    buffer per dtype in the order the dtypes first appear, into
    ``out[i]``: tensor fusion on the compiled path."""
    by_dtype: dict = {}
    for i in idxs:
        by_dtype.setdefault(jnp.asarray(leaves[i]).dtype, []).append(i)
    for group in by_dtype.values():
        red, = _reduce([_pack(leaves, group)], axis_name, collective,
                       packed=len(group) > 1)
        _unpack(red, leaves, group, out)


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
    out = [None] * len(leaves)
    new_res: dict = {}
    traced = any(C._is_traced(l) for l in leaves)
    scale = dict(prescale_factor=prescale_factor,
                 postscale_factor=postscale_factor)
    for i, red in zip(plain, fused_tree_allreduce(
            [leaves[i] for i in plain], op=op, axis_name=axis_name, **scale)):
        out[i] = red

    def quantized(fused):
        if not traced:
            # eager call (no axis in scope): the quant marker routes the
            # fused buffer through the eager quantized chunk plan;
            # stateless — the queue runtime owns eager error feedback
            marker = compression_mod.QuantCompressor(
                spec.bits, spec.block, spec.error_feedback)
            return C.allreduce(fused, op=op, compression=marker, **scale)
        dt = str(fused.dtype)
        res = residuals.get(dt) if residuals else None
        if res is not None and res.shape != fused.shape:
            res = None  # layout moved (resize/re-trace): clean reset
        red, new_res[dt] = C.quantized_allreduce(
            fused, axis_name, spec, op=op, residual=res, **scale)
        return red

    _exchange_by_dtype(leaves, elig, axis_name, quantized, out)
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
    if compression is not None:
        comp = [compression.compress(l) for l in leaves]
        leaves = [c[0] for c in comp]
        dectxs = [c[1] for c in comp]
    out = [None] * len(leaves)
    _exchange_by_dtype(
        leaves, range(len(leaves)), axis_name,
        lambda fused: C.allreduce(fused, op=op, axis_name=axis_name,
                                  prescale_factor=prescale_factor,
                                  postscale_factor=postscale_factor), out)
    if compression is not None:
        out = [compression.decompress(o, c) for o, c in zip(out, dectxs)]
    return jax.tree.unflatten(treedef, out)


def _update(optimizer, reduced, inner, params):
    """The inner optax update, under the ``hvd.optimizer`` scope."""
    with jax.named_scope(scopes.OPTIMIZER):
        return optimizer.update(reduced, inner, params)


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
    the two scale factors (`_one_member`; docs/tensor-fusion.md).

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
    qspec = getattr(compression, "quant_spec", None)
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
            updates, inner = _update(optimizer, reduced, state.inner, params)
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

    def _exchange(grads):
        return _tree_allreduce(grads, op, axis_name, compression,
                               prescale_factor, postscale_factor, fuse_buckets)

    def update_fn(grads, state, params=None):
        if n <= 1:
            return _update(optimizer, _exchange(grads), state, params)
        acc = jax.tree.map(lambda a, g: a + g, state.acc, grads)
        counter = state.counter + 1
        is_step = counter >= n

        def do_step(_):
            scale = 1.0 / n if average_aggregated_gradients else 1.0
            reduced = _exchange(jax.tree.map(lambda a: a * scale, acc))
            updates, inner = _update(optimizer, reduced, state.inner, params)
            zeroed = jax.tree.map(jnp.zeros_like, acc)
            return updates, _AggState(inner, zeroed, jnp.zeros((), jnp.int32))

        def skip(_):
            zeros = jax.tree.map(jnp.zeros_like, acc)
            return zeros, _AggState(state.inner, acc, counter)

        return jax.lax.cond(is_step, do_step, skip, None)

    return optax.GradientTransformation(init_fn, update_fn)


# Horovod-style name
DistributedOptimizer = DistributedGradientTransformation


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
    """`jax.value_and_grad` whose gradients come back already allreduced
    and, with ``average_loss``, whose loss is averaged over the axis."""
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
    equivalent of DistributedGradientTape (tensorflow/__init__.py:743):
    `distributed_value_and_grad` without the value."""
    vgfun = distributed_value_and_grad(
        fun, op=op, axis_name=axis_name, compression=compression,
        fuse_buckets=fuse_buckets, has_aux=has_aux, average_loss=False,
        argnums=argnums)

    def wrapped(*args, **kwargs):
        val, g = vgfun(*args, **kwargs)
        return (g, val[1]) if has_aux else g

    return wrapped


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
