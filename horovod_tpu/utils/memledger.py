"""Device-memory & compile ledger: HBM samples, plan-cost profiles,
OOM forensics.

The observability stack so far watches *time* (metrics → tracing →
flight recorder → perf ledger) but is blind to the two resources the
recent tentpoles actually trade in: device memory and XLA compile cost.
The ZeRO-1 sharded update (opt/sharded.py) claims a ~1/N optimizer-state
footprint and the quantized wire (ops/compression.py) claims smaller
buffers, yet neither claim was measured at runtime — exactly the gap
arXiv:2004.13336 motivates sharding with (per-replica memory is the
scaling wall). And for XLA the compile is the build step
(utils/compile_cache.py), so compile latency and persistent-cache
efficacy are production signals, not curiosities.

This module is both ledgers:

- **Memory side**: per-device stats via jax ``memory_stats()`` with a
  graceful fallback to live-array byte sums on platforms without an
  allocator stats API (CPU), sampled on the MetricsDumper cadence plus
  event-driven samples at plan build, elastic resize, and sharded-layout
  (re)build. Each sample carries a per-component attribution (plan
  cache / staging ring / EF residuals / sharded optimizer state) so the
  1/N sharding claim is a measured number. Exposure: ``hvd_mem_*``
  series, a ``mem/rank{k}`` KV push merged by the launcher's
  ``GET /memory``, and ``hvd.memory_report()``.
- **Compile side**: every fused/sharded/quantized plan built by
  ops/collectives.py is wrapped (:func:`instrument_plan`) so its
  first-call XLA compile is timed ahead-of-time and its serialized
  program size recorded, keyed by plan kind (``hvd_compile_seconds``
  histogram, ``hvd_compile_program_bytes_total{kind}``).
  Persistent-cache hit/miss is inferred from the cache-dir entry delta
  across the compile (utils/compile_cache.py records the active dir).
  Compile stalls are fed into the perf ledger's host-overhead
  attribution (``PerfLedger.note_compile``) so a recompile storm shows
  in ``hvd.perf_report()`` and can be bounded by an ``HOROVOD_SLO_SPEC``
  budget (``compile_seconds_p95<=…``).
- **Forensics**: :func:`forensics` assembles the memory section of the
  diagnostics bundle (utils/diag.py) — last N ledger samples, top live
  buffers by size, component attribution, and the suspect (dominant)
  component — so a ``RESOURCE_EXHAUSTED`` crash yields a named suspect
  instead of a dead rank.

Zero-cost contract (same as utils/tracing.py / utils/perfledger.py,
enforced by hvdlint's zero-cost-hooks rule and
benchmarks/memledger_overhead.py): with ``HOROVOD_MEMLEDGER`` unset no
ledger exists, hook sites pay one ``is None`` check, and no
``hvd_mem_*``/``hvd_compile_*`` series is registered. Metric handles are
resolved in ``MemLedger.__init__`` — lazily at enable — so the off state
adds zero series. Plan instrumentation additionally arms when
``HOROVOD_PLAN_CACHE_MAX_BYTES`` caps the plan cache (the cap needs the
per-plan program sizes even without the ledger).
"""

from __future__ import annotations

import collections
import logging
import time
from typing import Callable, List, Optional

from ..common import env as env_schema
from . import flightrec as flightrec_mod
from . import lockcheck

LOG = logging.getLogger("horovod_tpu")

#: KV scope the MetricsDumper pushes per-rank ledger snapshots under
#: (``mem/rank{k}``); the launcher's ``GET /memory`` merges the scope.
KV_SCOPE = "mem"

DEFAULT_CAPACITY = 512

#: How many compile records the compile ring keeps (compiles are rare —
#: a full ring means a recompile storm, which is exactly when the tail
#: matters).
COMPILE_RING = 256

#: The attributed memory components every sample carries. ``plan_cache``
#: / ``staging_ring`` / ``ef_residuals`` are pulled from their owners at
#: sample time; ``sharded_state`` is pushed by opt/sharded.py when the
#: sharded optimizer state is (re)built.
COMPONENTS = ("plan_cache", "staging_ring", "ef_residuals",
              "sharded_state")


def _device_memory() -> List[dict]:
    """Per-device allocator stats where the backend exposes them (TPU,
    GPU). Devices without ``memory_stats()`` (CPU) are simply absent —
    the caller falls back to live-array sums."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return []
    out = []
    for dev in devices:
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        out.append({
            "device": f"{dev.platform}:{dev.id}",
            "bytes_in_use": int(stats.get("bytes_in_use", 0) or 0),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0) or 0),
            "bytes_limit": int(stats.get("bytes_limit", 0) or 0),
        })
    return out


def _live_array_bytes() -> int:
    """CPU fallback: total bytes held by live jax arrays in this
    process. Coarser than allocator stats (no limit, no allocator
    overhead) but honest about what the process retains."""
    try:
        import jax

        arrs = jax.live_arrays()
    except Exception:
        return 0
    total = 0
    for a in arrs:
        try:
            total += int(a.nbytes)
        except Exception:
            continue
    return total


def top_live_buffers(n: int = 10) -> List[dict]:
    """The ``n`` largest live jax arrays — the "what is actually holding
    memory" table of the OOM forensics section."""
    try:
        import jax

        arrs = jax.live_arrays()
    except Exception:
        return []
    infos = []
    for a in arrs:
        try:
            infos.append({"shape": list(a.shape), "dtype": str(a.dtype),
                          "nbytes": int(a.nbytes)})
        except Exception:
            continue
    infos.sort(key=lambda i: -i["nbytes"])
    return infos[:max(int(n), 0)]


def _program_bytes(compiled) -> int:
    """Serialized-program size of an AOT-compiled executable, best
    effort: the compiler's own generated-code figure, else the HLO text
    length as a proxy, else 0 (never raises)."""
    try:
        ma = compiled.memory_analysis()
        size = getattr(ma, "generated_code_size_in_bytes", None)
        if size:
            return int(size)
    except Exception:
        pass
    try:
        return len(compiled.as_text())
    except Exception:
        return 0


class MemLedger:
    """Bounded ring of memory samples + compile-cost accounting.

    ``sample()`` runs on the MetricsDumper cadence plus rare events
    (plan build, reshard, elastic resize) — never per cycle — so it may
    walk live arrays and pull component owners. ``record_compile()``
    fires once per XLA compile. Both are safe from any thread.
    """

    def __init__(self, rank: int = 0, capacity: int = DEFAULT_CAPACITY):
        self.rank = rank
        self.capacity = max(int(capacity), 16)
        self._lock = lockcheck.make_lock("memledger.ring")
        self._ring = collections.deque(maxlen=self.capacity)  # guarded-by: _lock
        self._components: dict = {}  # guarded-by: _lock
        self._peak_live = 0  # guarded-by: _lock
        self._samples_total = 0  # guarded-by: _lock
        self._compiles = collections.deque(maxlen=COMPILE_RING)  # guarded-by: _lock
        self._compile_total_s = 0.0  # guarded-by: _lock
        self._compile_count = 0  # guarded-by: _lock
        self._compile_bytes = 0  # guarded-by: _lock
        from . import metrics as metrics_mod

        self._reg = metrics_mod.get_registry()
        self._m_live = self._reg.gauge(
            "hvd_mem_live_bytes",
            "live device/host-backed array bytes at the last sample")
        self._m_peak = self._reg.gauge(
            "hvd_mem_peak_bytes",
            "high-watermark of live bytes (allocator peak where the "
            "backend reports one, else max sampled live bytes)")
        self._m_comp = {
            comp: self._reg.gauge(
                "hvd_mem_component_bytes",
                "attributed bytes held by one runtime component",
                component=comp)
            for comp in COMPONENTS}
        # per-event sample counters and per-kind compile series are
        # label-lazy (events/kinds arrive at runtime); the base names are
        # fixed here so the docs/series contract stays literal
        self._m_samples: dict = {}
        self._m_compile_s: dict = {}
        self._m_compile_bytes: dict = {}
        self._m_persistent: dict = {}

    # -- memory side -------------------------------------------------------

    def _pull_components(self) -> dict:
        """Current attribution from the component owners; every pull is
        best-effort (a half-built runtime must not break a sample)."""
        comps = {}
        try:
            from ..ops import collectives as collectives_mod

            comps["plan_cache"] = int(collectives_mod.plan_cache_bytes())
        except Exception:
            pass
        try:
            from ..common import context as context_mod

            runtime = getattr(context_mod._ctx, "runtime", None)
        except Exception:
            runtime = None
        if runtime is not None:
            try:
                fb = getattr(runtime, "fusion_buffer", None)
                if fb is not None:
                    comps["staging_ring"] = int(fb.allocated_bytes())
            except Exception:
                pass
            try:
                store = getattr(runtime, "_quant_residuals", None)
                if store is not None:
                    comps["ef_residuals"] = int(store.nbytes())
            except Exception:
                pass
        return comps

    def sample(self, event: str = "interval") -> dict:
        """Take one memory sample and publish the ``hvd_mem_*`` series.

        ``event`` labels why the sample fired (``interval`` for the
        dumper cadence; ``plan_build`` / ``reshard`` /
        ``sharded_state_build`` / ``elastic_resize`` for the
        event-driven sites).
        """
        devices = _device_memory()
        live = sum(d["bytes_in_use"] for d in devices)
        dev_peak = sum(d["peak_bytes_in_use"] for d in devices)
        source = "memory_stats"
        if not devices:
            live = _live_array_bytes()
            source = "live_arrays"
        pulled = self._pull_components()
        with self._lock:
            self._components.update(pulled)
            comps = dict(self._components)
            self._peak_live = max(self._peak_live, live, dev_peak)
            peak = self._peak_live
            self._samples_total += 1
            snap = {"ts": time.time(), "ts_mono": time.monotonic(),
                    "event": event, "source": source,
                    "live_bytes": int(live), "peak_bytes": int(peak),
                    "devices": devices, "components": comps}
            self._ring.append(snap)
        self._m_live.set(int(live))
        self._m_peak.set(int(peak))
        for comp, nbytes in comps.items():
            gauge = self._m_comp.get(comp)
            if gauge is None:
                gauge = self._reg.gauge(
                    "hvd_mem_component_bytes",
                    "attributed bytes held by one runtime component",
                    component=comp)
                self._m_comp[comp] = gauge
            gauge.set(int(nbytes))
        counter = self._m_samples.get(event)
        if counter is None:
            counter = self._reg.counter(
                "hvd_mem_samples_total", "memory-ledger samples taken",
                event=event)
            self._m_samples[event] = counter
        counter.inc()
        return snap

    def set_component(self, component: str, nbytes: int) -> None:
        """Push-style attribution for owners that know their footprint
        at (re)build time rather than exposing an accessor
        (opt/sharded.py's sharded optimizer state)."""
        nbytes = int(nbytes)
        with self._lock:
            self._components[component] = nbytes
        gauge = self._m_comp.get(component)
        if gauge is None:
            gauge = self._reg.gauge(
                "hvd_mem_component_bytes",
                "attributed bytes held by one runtime component",
                component=component)
            self._m_comp[component] = gauge
        gauge.set(nbytes)

    def components(self) -> dict:
        with self._lock:
            return dict(self._components)

    def samples(self, last: Optional[int] = None) -> List[dict]:
        """The sample ring, oldest first (``last`` keeps the newest N)."""
        with self._lock:
            out = list(self._ring)
        if last is not None:
            out = out[-int(last):]
        return out

    # -- compile side ------------------------------------------------------

    def record_compile(self, kind: str, seconds: float,
                       program_bytes: int = 0,
                       persistent: Optional[str] = None) -> None:
        """Account one XLA compile: per-kind histogram + program-size
        counter, the compile ring, a ``compile`` flight-recorder event,
        the perf ledger's host-overhead attribution, and an event-driven
        memory sample (a compile IS a plan build)."""
        seconds = max(float(seconds), 0.0)
        program_bytes = max(int(program_bytes), 0)
        entry = {"ts": time.time(), "kind": kind,
                 "seconds": round(seconds, 6),
                 "program_bytes": program_bytes,
                 "persistent_cache": persistent}
        with self._lock:
            self._compiles.append(entry)
            self._compile_total_s += seconds
            self._compile_count += 1
            self._compile_bytes += program_bytes
        hist = self._m_compile_s.get(kind)
        if hist is None:
            from . import metrics as metrics_mod

            hist = self._reg.histogram(
                "hvd_compile_seconds", "XLA compile wall time per plan",
                buckets=metrics_mod.LATENCY_BUCKETS_S, kind=kind)
            self._m_compile_s[kind] = hist
        hist.observe(seconds)
        ctr = self._m_compile_bytes.get(kind)
        if ctr is None:
            ctr = self._reg.counter(
                "hvd_compile_program_bytes_total",
                "serialized XLA program bytes compiled, by plan kind",
                kind=kind)
            self._m_compile_bytes[kind] = ctr
        ctr.inc(program_bytes)
        if persistent is not None:
            pctr = self._m_persistent.get(persistent)
            if pctr is None:
                pctr = self._reg.counter(
                    "hvd_compile_persistent_cache_total",
                    "persistent compile-cache verdicts inferred from the "
                    "cache-dir entry delta across a compile",
                    verdict=persistent)
                self._m_persistent[persistent] = pctr
            pctr.inc()
        flightrec_mod.note("compile", kind=kind,
                           seconds=round(seconds, 4),
                           program_bytes=program_bytes,
                           persistent_cache=persistent, rank=self.rank)
        from . import perfledger as perfledger_mod

        pledger = perfledger_mod.get_ledger()
        if pledger is not None:
            pledger.note_compile(seconds)
        from . import anatomy as anatomy_mod

        profiler = anatomy_mod.get_profiler()
        if profiler is not None:
            profiler.note_compile(seconds)
        self.sample(event="plan_build")

    def compile_stats(self) -> dict:
        """Derived compile-cost view (also the source of the
        ``compile_seconds_*`` extras bench.py reports)."""
        with self._lock:
            entries = list(self._compiles)
            total_s = self._compile_total_s
            count = self._compile_count
            total_bytes = self._compile_bytes
        secs = sorted(e["seconds"] for e in entries)
        by_kind: dict = {}
        persistent = {"hit": 0, "miss": 0, "unknown": 0}
        for e in entries:
            k = by_kind.setdefault(e["kind"],
                                   {"compiles": 0, "seconds": 0.0,
                                    "program_bytes": 0})
            k["compiles"] += 1
            k["seconds"] = round(k["seconds"] + e["seconds"], 6)
            k["program_bytes"] += e["program_bytes"]
            verdict = e["persistent_cache"] or "unknown"
            persistent[verdict] = persistent.get(verdict, 0) + 1
        from .perfledger import _percentile

        return {"compiles": count,
                "compile_seconds_total": round(total_s, 6),
                "compile_seconds_p95": round(_percentile(secs, 0.95), 6),
                "compile_program_bytes_total": int(total_bytes),
                "persistent_cache": persistent,
                "by_kind": by_kind}

    # -- views -------------------------------------------------------------

    def suspect_component(self) -> Optional[str]:
        """The dominant attributed component — the OOM forensics
        verdict. None when nothing has been attributed yet."""
        with self._lock:
            comps = dict(self._components)
        comps = {k: v for k, v in comps.items() if v > 0}
        if not comps:
            return None
        return max(comps.items(), key=lambda kv: kv[1])[0]

    def forensics(self, last_samples: int = 20, buffers: int = 10) -> dict:
        """The memory section of a diagnostics bundle: recent samples,
        attribution, top live buffers, compile summary, and the suspect
        component."""
        with self._lock:
            peak = self._peak_live
        return {"enabled": True,
                "peak_bytes": int(peak),
                "components": self.components(),
                "suspect": self.suspect_component(),
                "recent_samples": self.samples(last=last_samples),
                "top_live_buffers": top_live_buffers(buffers),
                "compile": self.compile_stats()}

    def snapshot(self) -> dict:
        """Push payload for ``mem/rank{k}`` (compact: attribution +
        newest few samples + compile stats, not the whole ring)."""
        with self._lock:
            total = self._samples_total
            peak = self._peak_live
        recent = self.samples()
        live = recent[-1]["live_bytes"] if recent else 0
        return {"rank": self.rank, "ts": time.time(),
                "samples": total,
                "live_bytes": int(live), "peak_bytes": int(peak),
                "components": self.components(),
                "recent": recent[-5:],
                "compile": self.compile_stats()}

    def report(self) -> dict:
        """``hvd.memory_report()`` body for this rank."""
        out = self.snapshot()
        out["enabled"] = True
        out["capacity"] = self.capacity
        out["suspect"] = self.suspect_component()
        return out


# --------------------------------------------------------------------------
# Plan-build compile instrumentation (used by ops/collectives.py)
# --------------------------------------------------------------------------


class _CompileTimingWrapper:
    """First-call AOT compile probe around one jit-compiled callable.

    The first call lowers and compiles ahead-of-time inside a timed
    window (plan cache keys carry exact shapes/dtypes, so the compiled
    executable serves every later call), records the compile to the
    ledger, and reports the serialized program size to ``size_cb`` (the
    plan-cache byte accounting). Steady state is one attribute load plus
    the compiled executable — cheaper than jit's own dispatch, so the
    A/A overhead gate holds. Anything AOT cannot handle falls back to
    the original jit callable permanently.
    """

    __slots__ = ("_fn", "_kind", "_size_cb", "_target")

    def __init__(self, fn, kind: str,
                 size_cb: Optional[Callable[[int], None]] = None):
        self._fn = fn
        self._kind = kind
        self._size_cb = size_cb
        self._target = None

    def __call__(self, *args, **kw):
        if kw:
            # AOT specialization only covers positional calls; keyword
            # callers keep the original jit dispatch untouched
            return self._fn(*args, **kw)
        target = self._target
        if target is None:
            return self._first_call(args)
        try:
            return target(*args)
        except (TypeError, ValueError):
            # AOT signature drift (weak type / sharding changed between
            # calls): the retraceable jit fn takes over for good
            self._target = self._fn
            return self._fn(*args)

    def _first_call(self, args):
        fn = self._fn
        from . import compile_cache as compile_cache_mod

        before = compile_cache_mod.cache_entries()
        t0 = time.perf_counter()
        try:
            compiled = fn.lower(*args).compile()
        except Exception:
            self._target = fn
            return fn(*args)
        seconds = time.perf_counter() - t0
        persistent = None
        if before >= 0:
            after = compile_cache_mod.cache_entries()
            if after >= 0:
                persistent = "hit" if after <= before else "miss"
        nbytes = _program_bytes(compiled)
        self._target = compiled
        # byte accounting BEFORE the ledger record: record_compile takes
        # the plan_build memory sample, and that sample's plan_cache
        # component pull must already see this program's bytes
        if self._size_cb is not None and nbytes:
            try:
                self._size_cb(nbytes)
            except Exception:
                LOG.debug("plan size callback failed", exc_info=True)
        ledger = _LEDGER
        if ledger is not None:
            ledger.record_compile(self._kind, seconds, nbytes,
                                  persistent=persistent)
        return compiled(*args)


def accounting_armed() -> bool:
    """Whether plan builds should be instrumented: the ledger is on, or
    the plan-cache byte cap needs program sizes even without it. Called
    once per cache miss (cold)."""
    return (_LEDGER is not None
            or env_schema.get_int(env_schema.HOROVOD_PLAN_CACHE_MAX_BYTES,
                                  0) > 0)


def instrument_plan(plan, kind: str,
                    size_cb: Optional[Callable[[int], None]] = None):
    """Wrap the jit callables behind a freshly built plan with
    first-call compile accounting. Bare jitted functions are wrapped and
    returned; plan objects get their callable slots (``pack`` /
    ``quantize`` / ``run``) wrapped in place."""
    if plan is None:
        return plan
    if hasattr(plan, "lower") and callable(plan):
        return _CompileTimingWrapper(plan, kind, size_cb)
    for slot in ("pack", "quantize", "run"):
        fn = getattr(plan, slot, None)
        if fn is not None and hasattr(fn, "lower"):
            try:
                setattr(plan, slot, _CompileTimingWrapper(fn, kind, size_cb))
            except AttributeError:
                pass
    return plan


# --------------------------------------------------------------------------
# Process-global ledger (the utils/tracing.py module-trio pattern):
# get_ledger() returns None when HOROVOD_MEMLEDGER is off, and every hook
# site costs exactly one is-None check in that state.
# --------------------------------------------------------------------------

_LEDGER: Optional[MemLedger] = None


def enabled() -> bool:
    return env_schema.get_bool(env_schema.HOROVOD_MEMLEDGER)


def get_ledger() -> Optional[MemLedger]:
    return _LEDGER


def init_ledger(rank: int = 0) -> Optional[MemLedger]:
    """Create the process ledger when ``HOROVOD_MEMLEDGER`` is set
    (idempotent, like flightrec's init_recorder); no-op returning None
    when off."""
    global _LEDGER
    if not enabled():
        return _LEDGER
    if _LEDGER is None:
        capacity = env_schema.get_int(env_schema.HOROVOD_MEMLEDGER_BUFFER,
                                      DEFAULT_CAPACITY)
        _LEDGER = MemLedger(rank=rank, capacity=capacity)
    return _LEDGER


def reset_ledger() -> None:
    """Drop the process ledger (test/bench helper)."""
    global _LEDGER
    _LEDGER = None


def sample_event(event: str) -> None:
    """Cold-path convenience: take an event-driven sample iff the ledger
    is on (plan builds, elastic resizes, sharded-layout rebuilds)."""
    ledger = _LEDGER
    if ledger is None:
        return
    ledger.sample(event=event)


def note_sharded_state(state) -> None:
    """Measure the ZeRO-1 claim: attribute the (re)built sharded
    optimizer state's actual byte footprint and take a sample."""
    ledger = _LEDGER
    if ledger is None:
        return
    total = 0
    try:
        import jax

        for leaf in jax.tree_util.tree_leaves(state):
            total += int(getattr(leaf, "nbytes", 0) or 0)
    except Exception:
        return
    ledger.set_component("sharded_state", total)
    ledger.sample(event="sharded_state_build")


def forensics() -> dict:
    """Memory section for the diagnostics bundle: ``{"enabled": False}``
    plus a live-buffer table when the ledger is off (an OOM postmortem
    deserves the table even unattributed), the full forensics view when
    on."""
    ledger = _LEDGER
    if ledger is None:
        return {"enabled": False, "top_live_buffers": top_live_buffers(10)}
    return ledger.forensics()


def report() -> dict:
    """``hvd.memory_report()`` body: ``{"enabled": False}`` when the
    ledger is off, else this rank's samples/attribution/compile stats."""
    ledger = _LEDGER
    if ledger is None:
        return {"enabled": False}
    return ledger.report()
