"""Data-parallel training-step builder — the end-to-end Horovod loop shape.

Reference usage pattern being reproduced (examples/tensorflow2_mnist.py /
pytorch_mnist.py): wrap optimizer, broadcast initial params, feed per-worker
batch shards. Here the whole step compiles to one SPMD program: forward +
backward run per chip on the batch shard, the optimizer wrapper's fused
psum averages gradients over ICI, and XLA overlaps the collective with
remaining backward compute (the effect Horovod gets from its background
thread + fusion buffer, operations.cc:587 + fusion_buffer_manager.h).
"""

from __future__ import annotations

import weakref
from typing import Callable, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common import context as ctx_mod
from ..common.context import DEFAULT_AXIS
from ..utils import scopes

#: the step traced last in this process: what `scope_table` and
#: `step_counters` describe when given no step
_last_traced: Optional[weakref.ref] = None


def data_parallel_step(
    step_fn: Callable,
    *,
    mesh: Optional[Mesh] = None,
    axis_name: str = DEFAULT_AXIS,
    batch_argnums: tuple[int, ...] = (2,),
    donate_argnums: tuple[int, ...] = (0, 1),
    static_argnums: tuple[int, ...] = (),
) -> Callable:
    """Compile ``step_fn(params, opt_state, batch, ...)`` data-parallel.

    ``step_fn`` is written per-chip: it sees the local batch shard and may
    call any `horovod_tpu` collective with ``axis_name`` (Horovod
    semantics — ``check_vma=False``; see horovod_tpu.opt docstring).
    Non-batch args are replicated; batch args are sharded on dim 0 over
    ``axis_name``. Donation keeps params/opt-state in place in HBM
    (the donated-buffer equivalent of the persistent fusion buffer).

    The per-chip body runs under the scope ``hvd.step`` (utils/scopes.py)
    and the step remembers, each time it is traced, what it was traced
    with: `scope_table` and `step_counters` describe it afterwards.
    """
    if mesh is None:
        mesh = ctx_mod.global_process_set().mesh
    record = scopes.StepRecord()

    def make_specs(args):
        return tuple(
            P(axis_name) if i in batch_argnums else P()
            for i in range(len(args))
        )

    def per_chip(*args):
        with jax.named_scope(scopes.STEP):
            return step_fn(*args)

    def hvd_data_parallel_step(*args):
        # runs once per trace, never per call
        global _last_traced
        in_specs = make_specs(args)
        signature = tuple(
            arg if i in static_argnums else jax.tree.map(
                lambda x, s=NamedSharding(mesh, spec): jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=s,
                    weak_type=jax.typeof(x).weak_type), arg)
            for i, (arg, spec) in enumerate(zip(args, in_specs)))
        if signature != record.signature:
            record.signature, record.table = signature, None
        record.counters = {}
        _last_traced = weakref.ref(step)
        sharded = jax.shard_map(per_chip, mesh=mesh, in_specs=in_specs,
                                out_specs=P(), check_vma=False)
        with scopes.recording(record):
            return sharded(*args)

    step = jax.jit(hvd_data_parallel_step, donate_argnums=donate_argnums,
                   static_argnums=static_argnums)
    step.hvd_record = record
    return step


def _traced(step):
    """``(step, its record)``, the step traced last without one; None
    where that step was never traced."""
    if step is None and _last_traced is not None:
        step = _last_traced()
    record = getattr(step, "hvd_record", None)
    if record is None or record.signature is None:
        return None
    return step, record


def scope_table(step=None) -> Optional[dict]:
    """``{instruction name: op_name}`` of ``step``'s compiled module (of
    the step traced last, without one), for `scopes.seconds_by_phase`
    over a profile of it; None when no step was traced. The first call
    lowers and compiles the remembered signature (a load from the
    persistent cache where the step ran before); later calls reuse it."""
    traced = _traced(step)
    if traced is None:
        return None
    step, record = traced
    if record.table is None:
        record.table = scopes.instruction_scopes(
            step.lower(*record.signature).compile().as_text())
    return record.table


def step_counters(step=None) -> Optional[dict]:
    """What the gradient exchange noted while ``step`` was traced, per
    step and per chip (utils/scopes.py); None when no step was traced."""
    traced = _traced(step)
    return None if traced is None else dict(traced[1].counters)


def shard_batch(batch, mesh: Optional[Mesh] = None, axis_name: str = DEFAULT_AXIS):
    """Place a host batch (pytree, leading dim = global batch) onto the mesh
    sharded over ``axis_name`` — each process contributes its local shard
    (multi-host: pass only the local slice, as with Horovod's per-rank
    dataset sharding)."""
    if mesh is None:
        mesh = ctx_mod.global_process_set().mesh
    sharding = NamedSharding(mesh, P(axis_name))
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(sharding, x), batch)
