"""Global runtime context: init/shutdown, process sets, device mesh.

TPU-native re-design of the reference's process-global state + background
runtime (`HorovodGlobalState`, /root/reference/horovod/common/global_state.h:43;
`InitializeHorovodOnce`, operations.cc:649). Key differences, by design:

- On GPU-Horovod, one process == one GPU == one rank, and every collective is
  negotiated between processes over MPI/Gloo and executed by NCCL.
- On TPU, one Python process drives ``local_size()`` chips and collectives are
  XLA programs over a `jax.sharding.Mesh` riding ICI (intra-slice) / DCN
  (cross-slice). SPMD programs are already symmetric across chips, so the
  per-tensor negotiation protocol (controller.cc:69 ComputeResponseList)
  collapses for the compiled path; it survives (slim, in
  `horovod_tpu.ops.queue`) only for the eager/dynamic path.

Rank/size vocabulary (documented contract):

- ``size()``   — total number of chips in the set (the data-parallel width a
                 Horovod user expects for LR scaling).
- ``rank()``   — global index of this process's first chip. ``rank() == 0``
                 is true exactly on the coordinator process, so rank-0
                 checkpoint/log idioms transfer unchanged.
- ``local_size()`` / ``local_rank()`` — under a launcher, worker processes
                 on this host / this worker's index among them (the
                 launcher-injected HOROVOD_LOCAL_* env wins); standalone,
                 chips driven by this process / 0.
- ``cross_size()`` / ``cross_rank()`` — number of processes / this process's
                 index (the reference's cross-communicator,
                 mpi_context.cc:147-156).

Per-chip rank only exists *inside* compiled programs, via
``jax.lax.axis_index(axis_name)`` — that is the TPU-native shape of the
reference's per-GPU rank.
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from . import env as env_schema
from .env import RuntimeConfig
from .exceptions import HorovodInternalError

LOG = logging.getLogger("horovod_tpu")

# Default axis name used by every collective when tracing inside shard_map.
DEFAULT_AXIS = "hvd"
# Process-level and local axes of the 2-D eager mesh.
PROC_AXIS = "hvd_proc"
LOCAL_AXIS = "hvd_local"


def _sorted_devices():
    """All addressable+global devices in (process_index, id) order."""
    return sorted(jax.devices(), key=lambda d: (d.process_index, d.id))


class ProcessSet:
    """A named subset of chips with its own meshes.

    TPU-native equivalent of an MPI (sub-)communicator
    (/root/reference/horovod/common/basics.py:33-65 accepts ``comm``/ranks;
    mpi_context.cc builds GLOBAL/LOCAL/CROSS comms). A ProcessSet owns:

    - ``mesh``      — 1-D mesh over all member chips, axis ``"hvd"``; the
                      data plane for flat collectives.
    - ``mesh_2d``   — (process, local-chip) mesh, axes ``("hvd_proc",
                      "hvd_local")``; used by eager process-level collectives
                      and by hierarchical (intra-host ICI / cross-host DCN)
                      strategies — the reference's LOCAL/CROSS communicator
                      triad (common.h:119-123).
    """

    def __init__(self, name: str, devices: Sequence[jax.Device]):
        self.name = name
        self.devices = list(devices)
        n = len(self.devices)
        if n == 0:
            raise ValueError("ProcessSet needs at least one device")
        dev_arr = np.array(self.devices, dtype=object)
        self.mesh = Mesh(dev_arr, (DEFAULT_AXIS,))
        # group by owning process
        procs = sorted({d.process_index for d in self.devices})
        self._proc_indices = procs
        by_proc = [[d for d in self.devices if d.process_index == p] for p in procs]
        local_counts = {len(g) for g in by_proc}
        if len(local_counts) == 1:
            self.is_homogeneous = True
            self.mesh_2d = Mesh(
                np.array(by_proc, dtype=object), (PROC_AXIS, LOCAL_AXIS)
            )
        else:
            # heterogeneous local counts: no rectangular 2-D mesh; eager path
            # falls back to the flat mesh
            self.is_homogeneous = False
            self.mesh_2d = None

    # --- sizes -------------------------------------------------------------
    @property
    def size(self) -> int:
        return len(self.devices)

    @property
    def local_devices(self):
        pid = jax.process_index()
        return [d for d in self.devices if d.process_index == pid]

    @property
    def local_size(self) -> int:
        return len(self.local_devices)

    @property
    def rank(self) -> int:
        """Global chip index of this process's first member device."""
        pid = jax.process_index()
        for i, d in enumerate(self.devices):
            if d.process_index == pid:
                return i
        raise HorovodInternalError(
            f"process {pid} owns no devices in process set {self.name!r}"
        )

    @property
    def cross_size(self) -> int:
        return len(self._proc_indices)

    @property
    def cross_rank(self) -> int:
        return self._proc_indices.index(jax.process_index())

    def included(self) -> bool:
        pid = jax.process_index()
        return any(d.process_index == pid for d in self.devices)

    def __repr__(self):
        return f"ProcessSet({self.name!r}, size={self.size})"


class _Context:
    """Process-global singleton (HorovodGlobalState equivalent)."""

    def __init__(self):
        self.lock = threading.RLock()
        self.initialized = False
        self.config: RuntimeConfig = RuntimeConfig()
        self.global_set: Optional[ProcessSet] = None
        self.process_sets: dict[str, ProcessSet] = {}
        self.runtime = None  # ops.queue.BackgroundRuntime, set by init()
        self.timeline = None  # utils.timeline.Timeline
        self.stall_inspector = None
        self.autotuner = None
        self.metrics_dumper = None  # utils.metrics.MetricsDumper
        self.joined = False  # reference global_state.h:107-111


_ctx = _Context()


def context() -> _Context:
    return _ctx


def _maybe_init_distributed():
    """Multi-host bootstrap: jax.distributed replaces MPI rendezvous.

    The launcher (horovod_tpu.runner) sets HOROVOD_TPU_COORDINATOR /
    NUM_PROCESSES / PROCESS_ID, the TPU-native equivalent of the env the
    reference's gloo launcher injects (gloo_run.py:65 create_slot_env_vars).
    """
    coord = os.environ.get(env_schema.HOROVOD_TPU_COORDINATOR)
    if not coord:
        return
    nproc = int(os.environ.get(env_schema.HOROVOD_TPU_NUM_PROCESSES, "1"))
    if nproc <= 1:
        return
    # IMPORTANT: do not touch jax.devices()/process_count() before this —
    # any backend-initializing call makes jax.distributed.initialize
    # impossible (it must run first in the process).
    from jax._src import distributed as _dist

    if getattr(_dist.global_state, "client", None) is not None:
        return  # already initialized
    # a failure raises: a launcher-spawned worker that carried on as a
    # world of one would train on its own and report success
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=nproc,
        process_id=int(os.environ[env_schema.HOROVOD_TPU_PROCESS_ID]),
    )
    LOG.info("jax.distributed initialized via %s", coord)
    _install_fatal_exit_hook()


def _install_fatal_exit_hook():
    """A distributed worker that dies of an unhandled exception must
    EXIT, not linger: interpreter teardown destroys the jax.distributed
    client, whose destructor blocks on the coordination-service shutdown
    barrier until the surviving peers also exit (measured: a failing rank
    stayed alive ~5 min while its healthy peer sat in a negotiation
    poll). The launcher's first-failure kill (reference gloo_run.py:
    263-271) can only fire once this process is actually gone — so after
    reporting the error we flush and hard-exit before teardown reaches
    that destructor. Normal completion and sys.exit() keep the clean
    path (the barrier is then bounded by real rank skew).

    Scope: only launcher-spawned workers (HOROVOD_RANK in the env) get
    the hook — a user-embedded driver that initializes jax.distributed
    itself keeps standard teardown (atexit handlers, coverage, tempfile
    cleanup). KeyboardInterrupt keeps its conventional 130 exit code.
    (Uncaught SystemExit never reaches sys.excepthook — the interpreter
    handles it first — so sys.exit() takes the normal teardown path,
    which is the desired behavior anyway.)"""
    import sys

    if os.environ.get(env_schema.HOROVOD_RANK) is None:
        return

    prev = sys.excepthook

    def hook(tp, val, tb):
        code = 1
        if issubclass(tp, KeyboardInterrupt):
            code = 130  # 128 + SIGINT, the shell convention
        try:
            # inside the try: a raising prev hook (or a torn-down stderr
            # pipe) must not skip the hard exit — lingering is the exact
            # failure this hook exists to prevent
            prev(tp, val, tb)
            sys.stdout.flush()
            sys.stderr.flush()
        finally:
            os._exit(code)

    sys.excepthook = hook


def init(ranks: Optional[Sequence[int]] = None, *, start_runtime: bool = True):
    """Initialize horovod_tpu (reference: hvd.init(), basics.py:33).

    ``ranks`` optionally restricts the global process set to a subset of chip
    indices — the moral equivalent of ``hvd.init(comm=ranks)``.

    Unlike the reference there is no background *communication* thread to
    spawn for the compiled path — XLA executes collectives inline in program
    order over ICI. ``start_runtime`` starts the slim background cycle loop
    that serves the *eager/async named-tensor* API
    (`horovod_tpu.ops.queue.BackgroundRuntime`, the TPU-shaped remnant of
    BackgroundThreadLoop, operations.cc:353).
    """
    with _ctx.lock:
        if _ctx.initialized:
            return
        _maybe_init_distributed()
        if os.environ.get(env_schema.HOROVOD_RANK) is not None:
            # launcher-spawned workers compile the same programs: they
            # share one persistent cache, placed by the one rule
            from ..utils.compile_cache import enable_compilation_cache

            enable_compilation_cache()
        _ctx.config = RuntimeConfig.from_env()
        devices = _sorted_devices()
        if ranks is not None:
            devices = [devices[i] for i in ranks]
        _ctx.global_set = ProcessSet("global", devices)
        _ctx.process_sets = {"global": _ctx.global_set}
        _ctx.joined = False

        # postmortem layer BEFORE the runtime/controller construct: both
        # resolve the recorder/watchdog handles once at build time
        _start_diag()

        # perf ledger BEFORE the runtime construct for the same reason;
        # the SLO engine attaches the stall inspector below once it exists
        from ..utils import perfledger as perfledger_mod

        perfledger_mod.init_ledger(rank=_ctx.global_set.cross_rank)

        # device-memory & compile ledger, same placement rationale: the
        # plan-build instrumentation in ops/collectives.py checks the
        # ledger handle at plan-cache-miss time
        from ..utils import memledger as memledger_mod

        memledger_mod.init_ledger(rank=_ctx.global_set.cross_rank)

        # step-anatomy profiler, same placement rationale: the queue's
        # dispatch hooks resolve the profiler handle once at build time
        from ..utils import anatomy as anatomy_mod

        anatomy_mod.init_profiler(rank=_ctx.global_set.cross_rank)

        # megaplan capture/replay manager, same placement rationale: the
        # runtime resolves the manager handle once at build time (and the
        # coordinator reads the same env gate in its own __init__)
        from ..ops import megaplan as megaplan_mod

        megaplan_mod.init_manager(rank=_ctx.global_set.cross_rank)

        # async shard checkpointer AFTER _start_diag(): its SIGTERM
        # handler must capture diag's as the chain target, so a
        # preemption flushes the in-flight snapshot first and dumps the
        # diagnostic bundle second
        from ..utils import async_ckpt as async_ckpt_mod

        async_ckpt_mod.init_checkpointer(
            rank=_ctx.global_set.cross_rank,
            world=_ctx.global_set.cross_size)

        # fleet health engine, same placement rationale as the ledgers:
        # the MetricsDumper flush hook checks the engine handle per pass
        from ..utils import health as health_mod

        health_mod.init_engine(rank=_ctx.global_set.cross_rank)

        if _ctx.config.trace_enabled:
            # before the runtime/controller construct: both resolve the
            # tracer once at build time (zero-cost None when off)
            from ..utils import tracing as tracing_mod

            tracing_mod.init_tracer(
                rank=_ctx.global_set.cross_rank,
                addr=os.environ.get(env_schema.HOROVOD_GLOO_RENDEZVOUS_ADDR),
                port=os.environ.get(env_schema.HOROVOD_GLOO_RENDEZVOUS_PORT))

        from ..utils.timeline import Timeline

        # the reference's timeline is recorded by the coordinator only
        # (operations.cc BackgroundThreadLoop gates on rank 0); same here —
        # also prevents same-host ranks clobbering one file
        tl_file = (_ctx.config.timeline_filename
                   if _ctx.global_set.cross_rank == 0 else "")
        _ctx.timeline = Timeline(tl_file,
                                 mark_cycles=_ctx.config.timeline_mark_cycles)

        if start_runtime:
            from ..ops.queue import BackgroundRuntime
            from ..utils.stall import StallInspector

            _ctx.stall_inspector = StallInspector(
                warning_time_s=_ctx.config.stall_warning_time_s,
                shutdown_time_s=_ctx.config.stall_shutdown_time_s,
                disabled=_ctx.config.stall_check_disable,
            )
            # idempotent: hands the inspector to an already-armed SLO
            # engine so breach escalations carry straggler attribution
            perfledger_mod.init_ledger(
                rank=_ctx.global_set.cross_rank,
                stall_inspector=_ctx.stall_inspector)
            # same handover for the health engine: anomaly escalations
            # carry straggler attribution once the inspector exists
            health_mod.init_engine(
                rank=_ctx.global_set.cross_rank,
                stall_inspector=_ctx.stall_inspector)
            _ctx.runtime = BackgroundRuntime(
                _ctx.global_set,
                config=_ctx.config,
                timeline=_ctx.timeline,
                stall_inspector=_ctx.stall_inspector,
            )
            _ctx.runtime.start()
            from ..utils import flightrec as flightrec_mod

            flightrec_mod.note("init_phase", phase="runtime_started")
            if _ctx.config.autotune:
                from ..utils.autotune import Autotuner

                _ctx.autotuner = Autotuner(
                    _ctx.runtime, log_path=_ctx.config.autotune_log,
                    warmup_samples=_ctx.config.autotune_warmup_samples,
                    max_samples=_ctx.config.autotune_max_samples,
                    config=_ctx.config)
                _ctx.runtime.autotuner = _ctx.autotuner
                _ctx.runtime.autotune_steps_per_sample = (
                    _ctx.config.autotune_steps_per_sample)
                # hand the tuner to the health engine so a latched
                # goodput drift feeds the workload-shift re-tune path
                health_mod.init_engine(
                    rank=_ctx.global_set.cross_rank,
                    autotuner=_ctx.autotuner)
        _start_metrics_dumper()
        _ctx.initialized = True
        from ..utils import flightrec as flightrec_mod

        flightrec_mod.note("init_phase", phase="initialized")
        LOG.info("horovod_tpu initialized: %s", _ctx.global_set)


def _start_diag():
    """Arm the postmortem layer (utils/flightrec.py + utils/diag.py):
    the flight recorder (``HOROVOD_FLIGHTREC``), the wedge watchdog
    (``HOROVOD_WATCHDOG_SECS`` > 0), the signal/crash dump hooks, and —
    in a launched job — a dedicated KV client so watchdog/crash bundles
    ride the push path into the launcher's ``GET /debug``. The memory
    ledger (``HOROVOD_MEMLEDGER``) arms the same path for its OOM
    forensics. With all knobs off, nothing is created and no hook is
    installed."""
    from ..utils import diag as diag_mod
    from ..utils import flightrec as flightrec_mod

    from ..utils import memledger as memledger_mod

    recorder = flightrec_mod.init_recorder(rank=_ctx.global_set.cross_rank)
    flightrec_mod.note("init_phase", phase="config")
    wd = diag_mod.init_watchdog(_ctx.config.watchdog_secs)
    # the memory ledger is a third reason to arm the dump path: its OOM
    # forensics contract is "an allocation failure yields a pushed oom
    # bundle the launcher's GET /debug can attribute", with no flight
    # recorder or watchdog required
    if recorder is None and wd is None and not memledger_mod.enabled():
        return
    addr = os.environ.get(env_schema.HOROVOD_GLOO_RENDEZVOUS_ADDR)
    port = os.environ.get(env_schema.HOROVOD_GLOO_RENDEZVOUS_PORT)
    if addr and port:
        from ..runner.http_server import KVStoreClient

        # NOT the MetricsDumper's client: dumps fire from the watchdog /
        # signal context concurrently with the dumper cadence, and the
        # keep-alive socket is per-thread state
        diag_mod.set_kv_client(KVStoreClient(addr, int(port)))
    # after _install_fatal_exit_hook (in _maybe_init_distributed), so the
    # excepthook chain runs dump-first, then print-and-os._exit
    diag_mod.install_crash_hooks()


def _start_metrics_dumper():
    """Start the metrics publisher when there is somewhere to publish:
    a ``HOROVOD_METRICS_FILE`` path and/or (in a launched job) the
    launcher's KV store, where pushed snapshots feed its ``GET /metrics``.
    With neither, no thread is created at all — standalone single-process
    use pays nothing for the subsystem."""
    from ..utils import metrics as metrics_mod

    crank = _ctx.global_set.cross_rank
    path = _ctx.config.metrics_file
    if path and crank != 0:
        # every rank's dump is a distinct post-mortem artifact; same-host
        # ranks share the env value, so suffix to avoid clobbering
        path = f"{path}.rank{crank}"
    kv = None
    addr = os.environ.get(env_schema.HOROVOD_GLOO_RENDEZVOUS_ADDR)
    port = os.environ.get(env_schema.HOROVOD_GLOO_RENDEZVOUS_PORT)
    if _ctx.config.metrics_push and addr and port:
        from ..runner.http_server import KVStoreClient

        kv = KVStoreClient(addr, int(port))
    if not path and kv is None:
        return
    _ctx.metrics_dumper = metrics_mod.MetricsDumper(
        metrics_mod.get_registry(), file_path=path,
        interval_s=_ctx.config.metrics_dump_interval_s,
        kv_client=kv, rank=crank)
    _ctx.metrics_dumper.start()


def shutdown(drain: bool = True):
    """Tear down (reference: horovod_shutdown, operations.cc:728).

    Pending async operations fail with HorovodInternalError, mirroring
    FinalizeTensorQueue (tensor_queue.h:35). ``drain=False`` skips the
    cooperative shutdown barrier — for error-recovery teardown
    (elastic reinit), where waiting on a broken lockstep only delays
    the new generation.
    """
    with _ctx.lock:
        if not _ctx.initialized:
            return
        if _ctx.runtime is not None:
            _ctx.runtime.stop(drain=drain)
            _ctx.runtime = None
        if _ctx.timeline is not None:
            _ctx.timeline.close()
            _ctx.timeline = None
        if _ctx.metrics_dumper is not None:
            # stop() performs a final flush: the metrics file / KV push
            # reflects everything the drained runtime counted
            _ctx.metrics_dumper.stop()
            _ctx.metrics_dumper = None
        from ..utils import health as health_mod

        # after the dumper's final flush so the HOROVOD_HEALTH_FILE dump
        # carries the last sampled window (engine survives shutdown like
        # the ledgers: one continuous history per process)
        health_mod.dump_on_exit()
        from ..utils import diag as diag_mod

        # the flight recorder survives shutdown (one continuous ring per
        # process, like the metrics registry); the watchdog thread and
        # its KV client do not
        diag_mod.reset_watchdog()
        diag_mod.set_kv_client(None)
        _ctx.stall_inspector = None
        _ctx.autotuner = None
        _ctx.global_set = None
        _ctx.process_sets = {}
        _ctx.initialized = False


atexit.register(shutdown)


def _require_init() -> _Context:
    if not _ctx.initialized:
        raise ValueError(
            "horovod_tpu has not been initialized; call horovod_tpu.init() first."
        )
    return _ctx


def is_initialized() -> bool:
    return _ctx.initialized


def global_process_set() -> ProcessSet:
    return _require_init().global_set


def add_process_set(ranks: Sequence[int], name: Optional[str] = None) -> ProcessSet:
    """Create a sub-communicator over a subset of global chip indices."""
    ctx = _require_init()
    name = name or f"set_{','.join(map(str, ranks))}"
    with ctx.lock:
        if name in ctx.process_sets:
            return ctx.process_sets[name]
        devs = [ctx.global_set.devices[i] for i in ranks]
        ps = ProcessSet(name, devs)
        ctx.process_sets[name] = ps
        return ps


def remove_process_set(name: str):
    ctx = _require_init()
    with ctx.lock:
        if name == "global":
            raise ValueError("cannot remove the global process set")
        ctx.process_sets.pop(name, None)


# --- rank/size API (reference: operations.cc:766-910, basics.py) ------------

def size() -> int:
    return _require_init().global_set.size


def rank() -> int:
    return _require_init().global_set.rank


def local_size() -> int:
    """Under a launcher (multi-process-per-host), the number of worker
    processes on this host (launcher-injected env, reference
    gloo_context.cc:136-192 consumption); standalone, the chips this
    process drives — the TPU-sensible analogue."""
    ctx = _require_init()
    v = os.environ.get(env_schema.HOROVOD_LOCAL_SIZE)
    if v is not None:
        return int(v)
    return ctx.global_set.local_size


def local_rank() -> int:
    """This process's rank among processes on the same host.

    Standalone (no launcher env) this is 0: ONE process drives ALL local
    chips here, unlike the reference's process-per-GPU model. A ported
    script that maps ``local_rank()`` to a device index
    (``torch.cuda.set_device(hvd.local_rank())``-style) would silently
    address only device 0 — iterate ``jax.local_devices()`` or shard over
    the process set's mesh instead (see docs/running.md)."""
    ctx = _require_init()
    v = os.environ.get(env_schema.HOROVOD_LOCAL_RANK)
    if v is not None:
        return int(v)
    return 0 if ctx.global_set.local_size > 0 else -1


def cross_size() -> int:
    return _require_init().global_set.cross_size


def cross_rank() -> int:
    return _require_init().global_set.cross_rank


def is_homogeneous() -> bool:
    """True when every process drives the same number of chips
    (reference: horovod_is_homogeneous, operations.cc:840)."""
    return _require_init().global_set.is_homogeneous


def shard_id() -> int:
    """Input-pipeline shard index for this process (== cross_rank()).

    New helper: on TPU, datasets shard per *process*, not per chip.
    """
    return cross_rank()


def num_shards() -> int:
    return cross_size()


# --- capability probes (reference: operations.cc:846-910) --------------------

def mpi_threads_supported() -> bool:
    return False


def mpi_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def gloo_built() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def tpu_built() -> bool:
    """The one that matters here."""
    return True


def tpu_enabled() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def start_timeline(filename: str, mark_cycles: bool = False):
    """Runtime timeline control (reference operations.cc:738-764)."""
    ctx = _require_init()
    ctx.timeline.reopen(filename, mark_cycles=mark_cycles)


def stop_timeline():
    ctx = _require_init()
    ctx.timeline.reopen("", mark_cycles=False)
