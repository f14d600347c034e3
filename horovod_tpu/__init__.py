"""horovod_tpu — a TPU-native distributed deep-learning training framework
with the capabilities of Horovod (reference at /root/reference).

    import horovod_tpu as hvd

    hvd.init()
    # compiled path (hot): inside shard_map/jit, per-chip semantics
    grads = jax.tree.map(lambda g: hvd.allreduce(g, axis_name="hvd"), grads)
    # eager path: per-process semantics, named + async if desired
    h = hvd.allreduce_async(np.ones(4), name="t0")
    out = hvd.synchronize(h)

Design (see SURVEY.md): the data plane is XLA collectives over a
`jax.sharding.Mesh` riding ICI/DCN — not a port of the reference's
NCCL/MPI rings. The reference's background thread, negotiation protocol,
fusion buffers and response cache survive only in the slim eager/async
runtime (`horovod_tpu.ops.queue`); the compiled path needs none of them.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .common.context import (  # noqa: F401
    DEFAULT_AXIS,
    ProcessSet,
    add_process_set,
    ccl_built,
    context,
    cross_rank,
    cross_size,
    cuda_built,
    ddl_built,
    global_process_set,
    gloo_built,
    gloo_enabled,
    init,
    is_homogeneous,
    is_initialized,
    local_rank,
    local_size,
    mpi_built,
    mpi_enabled,
    mpi_threads_supported,
    nccl_built,
    num_shards,
    rank,
    remove_process_set,
    rocm_built,
    shard_id,
    shutdown,
    size,
    start_timeline,
    stop_timeline,
    tpu_built,
    tpu_enabled,
)
from .common.exceptions import (  # noqa: F401
    HorovodInternalError,
    HostsUpdatedInterrupt,
)
from .ops.collectives import (  # noqa: F401
    Adasum,
    Average,
    Max,
    Min,
    Product,
    ReduceOp,
    Sum,
    allgather,
    allgather_object,
    allreduce,
    alltoall,
    barrier,
    broadcast,
    broadcast_object,
    grouped_allreduce,
    join,
    reducescatter,
)
from .ops.adasum import (  # noqa: F401
    adasum_allreduce,
    adasum_allreduce_hierarchical,
)
from .ops.compression import Compression  # noqa: F401
from .ops.queue import TensorEntry

__version__ = "0.1.0"


def metrics_snapshot() -> dict:
    """Structured snapshot of the process-global metrics registry
    (counters / gauges / histograms as JSON-able dicts) — the Python-side
    view of what ``GET /metrics`` on the rendezvous server exposes. Valid
    before init and after shutdown; the registry is process-lifetime."""
    from .utils import metrics as _metrics

    return _metrics.get_registry().snapshot()


def trace_report() -> dict:
    """Summary of this rank's collective-lifecycle spans (utils/tracing.py):
    per-phase p50/p95 latencies (queue/negotiate/fuse/dispatch/total),
    span and error counts, open spans, and straggler attribution when the
    coordinator computed any. ``{"enabled": False}`` unless HOROVOD_TRACE
    was set at init. The merged cross-rank view is ``GET /timeline`` on
    the launcher's rendezvous server (docs/timeline.md)."""
    from .utils import tracing as _tracing

    return _tracing.report()


def perf_report() -> dict:
    """This rank's per-step performance ledger (utils/perfledger.py):
    derived goodput stats (negotiate p50/p95, exposed-comm fraction,
    wire bytes per step, plan hit rate, effective allreduce GB/s), the
    five-phase step decomposition, and — when ``HOROVOD_SLO_SPEC`` armed
    the budget engine — each budget's bound and breach state.
    ``{"enabled": False}`` unless HOROVOD_PERFLEDGER was set at init.
    The merged cross-rank view is ``GET /perf`` on the launcher's
    rendezvous server (docs/observability.md)."""
    from .utils import perfledger as _perfledger

    return _perfledger.report()


def memory_report() -> dict:
    """This rank's device-memory & compile ledger (utils/memledger.py):
    live/peak device bytes, per-component attribution (plan_cache /
    staging_ring / ef_residuals / sharded_state), the dominant suspect
    component, recent samples, and compile accounting (per-kind compile
    seconds, serialized program bytes, persistent-cache hit/miss).
    ``{"enabled": False}`` unless HOROVOD_MEMLEDGER was set at init.
    The merged cross-rank view is ``GET /memory`` on the launcher's
    rendezvous server (docs/observability.md)."""
    from .utils import memledger as _memledger

    return _memledger.report()


def anatomy_report() -> dict:
    """This rank's step-anatomy profile (utils/anatomy.py): the
    per-entity aggregate table (named chunks, negotiation rounds, host
    gaps, compile events — each with span and exposed-comm seconds), the
    critical-path summary (which entity bounds the most steps), and the
    Amdahl-style headroom estimates — ``overlap_headroom_s`` (step
    seconds recoverable by fully overlapping dispatched collectives) and
    ``replay_headroom_s`` (step seconds recoverable by eliminating
    negotiation + host gap via plan replay). ``{"enabled": False}``
    unless HOROVOD_ANATOMY was set at init. The merged cross-rank view
    is ``GET /anatomy`` on the launcher's rendezvous server
    (docs/observability.md, "Step anatomy & headroom")."""
    from .utils import anatomy as _anatomy

    return _anatomy.report()


def megaplan_report() -> dict:
    """This rank's whole-step replay status (ops/megaplan.py): capture
    and replay counters, the replay hit rate over post-capture cycles,
    per-reason invalidation counts, the stability threshold, and the
    live plan's shape (tensors/chunks/bytes) while one is captured.
    ``{"enabled": False}`` unless HOROVOD_MEGAPLAN was set at init
    (docs/performance.md, "Whole-step replay")."""
    from .ops import megaplan as _megaplan

    return _megaplan.report()


def checkpoint_report() -> dict:
    """This rank's async-checkpoint status (utils/async_ckpt.py): the
    checkpoint directory, newest durably committed step, last
    snapshot-copy stall and background-write durations, committed shard
    bytes, and whether a snapshot is queued or in flight.
    ``{"enabled": False}`` unless HOROVOD_ASYNC_CKPT was set at init.
    The merged cross-rank view is ``GET /checkpoint`` on the launcher's
    rendezvous server (docs/fault_tolerance.md, "Surviving
    preemption")."""
    from .utils import async_ckpt as _async_ckpt

    return _async_ckpt.report()


def health_report() -> dict:
    """This rank's fleet-health status (utils/health.py): the local
    verdict (healthy/degraded/critical), active anomalies, total
    anomalies latched, learned per-series baselines, the newest value
    of each history series, and the suspect rank when anomalies are
    active and straggler attribution is fresh. ``{"enabled": False}``
    unless HOROVOD_HEALTH was set at init. The merged cross-rank views
    are ``GET /history`` and ``GET /health`` on the launcher's
    rendezvous server (docs/observability.md, "Fleet health &
    history")."""
    from .utils import health as _health

    return _health.report()


def diagnose() -> dict:
    """The local diagnostic bundle (utils/diag.py): all-thread stacks,
    lockcheck state, a metrics snapshot, open tracing spans, the flight
    recorder's last events, and live-state probes (background-cycle beat,
    coordinator gather state). This is what the wedge watchdog dumps on a
    hang and what ``GET /debug`` on the rendezvous server merges across
    ranks — callable any time, init or not, for on-demand inspection.
    See docs/observability.md, "Debugging a hung job"."""
    from .utils import diag as _diag

    return _diag.build_bundle("diagnose")


# ---------------------------------------------------------------------------
# Async handle-based API (reference torch/mpi_ops.py:843-879: *_async, poll,
# synchronize, wait_and_clear)
# ---------------------------------------------------------------------------

def _runtime():
    ctx = context()
    if ctx.runtime is None:
        raise ValueError("horovod_tpu runtime not running; call hvd.init()")
    return ctx.runtime


def _default_name(prefix: str, tensor) -> str:
    rt = _runtime()
    return f"{prefix}.noname.{rt.handles._next}"


def allreduce_async(tensor, average: Optional[bool] = None, name: Optional[str] = None,
                    *, op: Optional[ReduceOp] = None, prescale_factor: float = 1.0,
                    postscale_factor: float = 1.0, process_set: Optional[ProcessSet] = None,
                    compression=None) -> int:
    from .ops.collectives import _resolve_op
    from .ops.compression import NoneCompressor

    rt = _runtime()
    quant = None
    if compression is not None:
        quant = getattr(compression, "quant_spec", None)
        if quant is None and compression is not NoneCompressor \
                and not isinstance(compression, NoneCompressor):
            # cast compressors wrap the result synchronously — the async
            # handle path cannot carry the decompress context; quant
            # markers are a wire format the runtime owns, so they can
            raise ValueError(
                "allreduce_async supports Compression.none/int8/int4; "
                "use hvd.allreduce(...) for fp16/bf16 cast compression")
    return rt.enqueue(TensorEntry(
        name=name or _default_name("allreduce", tensor), op="allreduce",
        tensor=np.asarray(tensor), reduce_op=_resolve_op(op, average),
        prescale_factor=prescale_factor, postscale_factor=postscale_factor,
        process_set=process_set, quant=quant))


def allgather_async(tensor, name: Optional[str] = None,
                    process_set: Optional[ProcessSet] = None) -> int:
    rt = _runtime()
    return rt.enqueue(TensorEntry(
        name=name or _default_name("allgather", tensor), op="allgather",
        tensor=np.asarray(tensor), process_set=process_set))


def broadcast_async(tensor, root_rank: int, name: Optional[str] = None,
                    process_set: Optional[ProcessSet] = None) -> int:
    rt = _runtime()
    ps = process_set or global_process_set()
    if not 0 <= int(root_rank) < ps.size:
        # synchronous, like the reference's HorovodBasics rank check
        # (test_torch.py test_horovod_broadcast_rank_error)
        raise ValueError(
            f"root_rank {root_rank} out of range for process set of size "
            f"{ps.size}")
    return rt.enqueue(TensorEntry(
        name=name or _default_name("broadcast", tensor), op="broadcast",
        tensor=np.asarray(tensor), root_rank=root_rank, process_set=process_set))


def alltoall_async(tensor, splits=None, name: Optional[str] = None,
                   process_set: Optional[ProcessSet] = None) -> int:
    rt = _runtime()
    return rt.enqueue(TensorEntry(
        name=name or _default_name("alltoall", tensor), op="alltoall",
        tensor=np.asarray(tensor), splits=splits, process_set=process_set))


def reducescatter_async(tensor, name: Optional[str] = None, *,
                        op: Optional[ReduceOp] = None,
                        process_set: Optional[ProcessSet] = None) -> int:
    rt = _runtime()
    arr = np.asarray(tensor)
    nproc = (process_set or global_process_set()).cross_size
    if arr.ndim == 0 or arr.shape[0] % max(nproc, 1):
        # synchronous, like the broadcast rank check: the local shape and
        # process count fully determine the error — no need to surface it
        # from the cycle thread as HorovodInternalError
        raise ValueError("first dim must be divisible by the number of "
                         f"processes ({arr.shape} over {nproc})")
    return rt.enqueue(TensorEntry(
        name=name or _default_name("reducescatter", tensor), op="reducescatter",
        tensor=arr, reduce_op=op or ReduceOp.SUM,
        process_set=process_set))


def grouped_allreduce_async(tensors, average: Optional[bool] = None,
                            name: Optional[str] = None, *,
                            op: Optional[ReduceOp] = None,
                            prescale_factor: float = 1.0,
                            postscale_factor: float = 1.0,
                            process_set: Optional[ProcessSet] = None,
                            compression=None) -> list[int]:
    """Enqueue a group in one shot; the cycle loop fuses them into a single
    flat collective (reference grouped allreduce + GroupTable)."""
    # unnamed groups get a unique per-call base (reference
    # "grouped_allreduce.noname.<n>"): two concurrently pending unnamed
    # groups must not collide on the in-flight name guard
    base = name or _default_name("grouped_allreduce", tensors)
    return [allreduce_async(t, average, f"{base}.{i}", op=op,
                            prescale_factor=prescale_factor,
                            postscale_factor=postscale_factor,
                            process_set=process_set, compression=compression)
            for i, t in enumerate(tensors)]


def poll(handle: int) -> bool:
    return _runtime().handles.poll(handle)


def synchronize(handle: int):
    return _runtime().handles.wait(handle)


# alias matching torch naming
wait = synchronize


# ---------------------------------------------------------------------------
# Parameter broadcast helpers (reference tensorflow/functions.py:47
# broadcast_variables / torch broadcast_parameters)
# ---------------------------------------------------------------------------

def broadcast_parameters(params, root_rank: int = 0,
                         process_set: Optional[ProcessSet] = None):
    """Broadcast a pytree of arrays from ``root_rank`` — call once after
    init so all workers start from identical weights."""
    import jax

    return jax.tree.map(
        lambda p: broadcast(p, root_rank, process_set=process_set), params)


# optimizer layer re-exports (JAX-first API)
from .opt import (  # noqa: E402,F401
    DistributedOptimizer,
    DistributedGradientTransformation,
    ShardedDistributedOptimizer,
    ShardedUpdateEngine,
    distributed_grad,
    plan_shard_layout,
)
