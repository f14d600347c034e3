"""Flight recorder: a bounded, lock-cheap ring of control-plane events.

The metrics registry answers "how much/how fast" and the tracer answers
"when did each collective run", but neither answers the postmortem
question "what was the control plane *doing* right before it stopped?"
— every wedged bench round so far (BENCH_r01–r05) died with zero record
of the last init phase reached, negotiation round opened, retry fired,
or fault injected. This module is that record: an always-cheap
append-only ring of structured events (monotonic + wall timestamps,
category, rank, free-form kv fields) that the diagnostics bundle
(utils/diag.py) snapshots at the moment of a hang, crash, or signal.

Categories are a closed registry (:data:`CATEGORIES`): hvdlint's
event-names rule checks every ``note("<category>", ...)`` call site
against it and requires each category to be snake_case, unique, and
documented in docs/observability.md — the same contract metric names
live under.

Zero-cost contract (same as utils/tracing.py, enforced by hvdlint's
zero-cost-hooks rule and benchmarks/flightrec_overhead.py): with
``HOROVOD_FLIGHTREC`` unset no recorder exists, hot paths pay one
``is None`` check per hook, and no ``hvd_flightrec_*`` series is
registered. Metric handles are resolved in ``FlightRecorder.__init__``
— lazily at enable — so the off state adds zero series.
"""

from __future__ import annotations

import collections
import time
from typing import List, Optional

from ..common import env as env_schema
from . import lockcheck

#: The closed event-category registry: (name, meaning). hvdlint parses
#: this tuple (tools/hvdlint/core.py) the way it parses faults.py SITES;
#: add a row here (and a docs/observability.md mention) before noting a
#: new category anywhere.
CATEGORIES = (
    ("init_phase", "hvd.init() milestone reached"),
    ("negotiation_round", "controller negotiation round begin/end"),
    ("elastic_generation", "elastic discovery epoch/generation change"),
    ("retry_attempt", "control-plane retry about to back off"),
    ("fault_injected", "chaos fault fired at an instrumented site"),
    ("plan_cache_invalidated", "compiled fused-chunk plans dropped"),
    ("reshard", "sharded-update layout (re)built"),
    ("watchdog", "wedge watchdog fired"),
    ("diag_dump", "diagnostic bundle written"),
    ("quant_fallback", "tensor kept off the quantized wire"),
    ("slo_breach", "declared SLO budget crossed its bound"),
    ("compile", "XLA program compiled for a cached plan"),
    ("leader_round", "node-leader negotiation round merged or fell back"),
    ("autotune_step", "autotuner proposed/applied/reverted a config"),
    ("checkpoint", "async checkpoint snapshot/flush/restore lifecycle"),
    ("megaplan", "whole-step schedule captured/replayed/invalidated"),
    ("health", "fleet-health anomaly latched or cleared on a drifted series"),
)

CATEGORY_NAMES = frozenset(name for name, _ in CATEGORIES)

DEFAULT_CAPACITY = 2048


class FlightRecorder:
    """Bounded structured event ring, safe to write from any thread.

    ``note()`` is the only hot method: one tuple build plus a deque
    append under a short lock. Readers (:meth:`events`) copy the ring
    under the same lock, so a watchdog dump mid-flight sees a clean cut.
    """

    def __init__(self, rank: int = 0, capacity: int = DEFAULT_CAPACITY):
        self.rank = rank
        self.capacity = max(int(capacity), 16)
        self._lock = lockcheck.make_lock("flightrec.ring")
        self._ring = collections.deque(maxlen=self.capacity)  # guarded-by: _lock
        from . import metrics as metrics_mod

        reg = metrics_mod.get_registry()
        self._m_events = reg.counter(
            "hvd_flightrec_events_total", "flight-recorder events noted")
        self._m_dropped = reg.counter(
            "hvd_flightrec_dropped_total",
            "flight-recorder events evicted by ring wraparound")

    def note(self, category: str, **kv) -> None:
        """Append one event. ``kv`` must be JSON-able scalars (the bundle
        serializes the ring); callers keep payloads tiny — this is a
        breadcrumb trail, not a log."""
        ev = (time.monotonic(), time.time(), category, kv)
        with self._lock:
            dropped = len(self._ring) == self.capacity
            self._ring.append(ev)
        self._m_events.inc()
        if dropped:
            self._m_dropped.inc()

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def events(self, last: Optional[int] = None) -> List[dict]:
        """The ring's contents, oldest first, as JSON-able dicts
        (``last`` keeps only the newest N)."""
        with self._lock:
            evs = list(self._ring)
        if last is not None:
            evs = evs[-int(last):]
        return [{"ts_mono": mono, "ts": wall, "cat": cat,
                 "rank": self.rank, "kv": kv}
                for mono, wall, cat, kv in evs]

    def snapshot(self, last: int = 200) -> dict:
        """Push/bundle payload: rank + the newest ``last`` events."""
        return {"rank": self.rank, "events": self.events(last=last)}


# --------------------------------------------------------------------------
# Process-global recorder (the utils/tracing.py module-trio pattern):
# get_recorder() returns None when HOROVOD_FLIGHTREC is off, and every
# hook site costs exactly one is-None check in that state.
# --------------------------------------------------------------------------

_RECORDER: Optional[FlightRecorder] = None


def enabled() -> bool:
    return env_schema.get_bool(env_schema.HOROVOD_FLIGHTREC)


def get_recorder() -> Optional[FlightRecorder]:
    return _RECORDER


def init_recorder(rank: int = 0) -> Optional[FlightRecorder]:
    """Create the process recorder when ``HOROVOD_FLIGHTREC`` is set
    (idempotent: reuses a live recorder so init/shutdown cycles keep one
    continuous ring); no-op returning None when off."""
    global _RECORDER
    if not enabled():
        return _RECORDER
    if _RECORDER is None:
        capacity = env_schema.get_int(env_schema.HOROVOD_FLIGHTREC_BUFFER,
                                      DEFAULT_CAPACITY)
        _RECORDER = FlightRecorder(rank=rank, capacity=capacity)
    return _RECORDER


def reset_recorder() -> None:
    """Drop the process recorder (test/bench helper)."""
    global _RECORDER
    _RECORDER = None


def note(category: str, **kv) -> None:
    """Cold-path convenience: record an event iff the recorder is on.

    Hot paths (ops/queue.py) resolve the handle once at construction
    instead; this wrapper is for the sites that fire rarely (retries,
    faults, elastic transitions, probe verdicts)."""
    recorder = _RECORDER
    if recorder is None:
        return
    recorder.note(category, **kv)
