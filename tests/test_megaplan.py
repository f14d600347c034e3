"""Whole-step megaplan capture & replay (horovod_tpu/ops/megaplan.py):
the Python-free steady state — capture after a stable window, replay
through one chained dispatch, and atomic invalidation back to the
negotiated path on any epoch / signature / membership / lease change.

The manager is OFF for the session-scoped hvd.init() (conftest); tests
that need one arm a private manager via the ``manager`` fixture and
drive a private, non-started BackgroundRuntime inline (the
tests/test_fusion_plan.py pattern), so the zero-cost default holds for
every other test file.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.common import context as ctx_mod
from horovod_tpu.common.env import RuntimeConfig
from horovod_tpu.ops import collectives as C
from horovod_tpu.ops import megaplan
from horovod_tpu.ops.controller import KVController
from horovod_tpu.ops.queue import BackgroundRuntime, TensorEntry
from horovod_tpu.runner.http_server import KVStoreClient, RendezvousServer
from horovod_tpu.utils import anatomy, faults, metrics, tracing

REG = metrics.get_registry()
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIG_ROW = ["allreduce", "float32", [4], 0, 0, 1.0, 1.0, "global", "host"]


@pytest.fixture
def manager(monkeypatch):
    """Create (and on exit drop) a process manager, HOROVOD_MEGAPLAN on."""

    def _make(rank=0, stable_rounds=3):
        monkeypatch.setenv("HOROVOD_MEGAPLAN", "1")
        monkeypatch.setenv("HOROVOD_MEGAPLAN_STABLE_ROUNDS",
                           str(stable_rounds))
        megaplan.reset_manager()
        return megaplan.init_manager(rank=rank)

    yield _make
    megaplan.reset_manager()


@pytest.fixture
def kv_server():
    srv = RendezvousServer()
    port = srv.start()
    yield "127.0.0.1", port
    srv.stop()


def _runtime():
    """Private, non-started BackgroundRuntime driven via run_cycle().
    Built AFTER the manager is armed — the runtime resolves the
    manager handle once at construction."""
    cfg = RuntimeConfig()
    cfg.stall_check_disable = True
    return BackgroundRuntime(ctx_mod.global_process_set(), cfg)


def _arrays(n=4, elems=64, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(elems).astype(np.float32)
            for _ in range(n)]


def _cycle(rt, arrays, prefix="mp"):
    """Enqueue the fixed-name batch, run one cycle inline, return outputs."""
    handles = [rt.enqueue(TensorEntry(name=f"{prefix}.{i}", op="allreduce",
                                      tensor=a))
               for i, a in enumerate(arrays)]
    rt.run_cycle()
    return [np.asarray(rt.handles.wait(h)) for h in handles]


def _inval_count(reason):
    return sum(c["value"] for c in REG.snapshot()["counters"]
               if c["name"] == "hvd_megaplan_invalidations_total"
               and c["labels"].get("reason") == reason)


# --- zero-cost contract ------------------------------------------------------

def test_megaplan_disabled_by_default(monkeypatch):
    monkeypatch.delenv("HOROVOD_MEGAPLAN", raising=False)
    megaplan.reset_manager()
    assert not megaplan.enabled()
    assert megaplan.init_manager(rank=0) is None
    assert megaplan.get_manager() is None
    assert megaplan.report() == {"enabled": False}
    assert hvd.megaplan_report() == {"enabled": False}
    # an un-armed runtime resolves no handle: one is-None field, and the
    # flag-off cycle loop is behavior-identical to the pre-megaplan path
    rt = _runtime()
    assert rt._mp is None
    outs = _cycle(rt, _arrays(), prefix="mp.off")
    for a, o in zip(_arrays(), outs):
        np.testing.assert_array_equal(a, o)


def test_megaplan_off_registers_zero_series():
    """Acceptance: with HOROVOD_MEGAPLAN unset, no hvd_megaplan_* series
    of ANY kind exists. Checked in a pristine subprocess — the
    in-process registry accumulates series from tests that DO arm the
    manager."""
    script = textwrap.dedent("""
        import os
        assert "HOROVOD_MEGAPLAN" not in os.environ
        from horovod_tpu.ops import megaplan
        from horovod_tpu.utils import metrics
        assert not megaplan.enabled()
        assert megaplan.init_manager(rank=0) is None
        snap = metrics.get_registry().snapshot()
        names = {m["name"]
                 for kind in ("counters", "gauges", "histograms")
                 for m in snap[kind]}
        bad = {n for n in names if n.startswith("hvd_megaplan")}
        assert not bad, bad
        print("zero-series OK")
    """)
    env = dict(os.environ)
    env.pop("HOROVOD_MEGAPLAN", None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "zero-series OK" in proc.stdout


# --- capture → replay steady state -------------------------------------------

def test_capture_then_replay_steady_state(manager):
    mgr = manager(stable_rounds=3)
    caps0 = REG.counter_value("hvd_megaplan_captures_total")
    reps0 = REG.counter_value("hvd_megaplan_replays_total")
    rt = _runtime()
    assert rt._mp is mgr
    arrays = _arrays()
    for i in range(10):
        outs = _cycle(rt, arrays)
        for a, o in zip(arrays, outs):
            np.testing.assert_allclose(a, o)
    rep = hvd.megaplan_report()
    # cycle 3 hits the stability threshold and captures; 4..10 replay
    assert rep["captures"] == 1 and rep["capture_rounds"] == 3
    assert rep["replays"] == 7 and rep["misses"] == 0
    assert rep["replay_hit_rate"] == 1.0
    assert rep["active"] and rep["plan"]["tensors"] == 4
    # 4 small same-dtype tensors fuse into ONE chunk: one chained step
    assert rep["plan"]["chunks"] == 1
    assert REG.counter_value("hvd_megaplan_captures_total") == caps0 + 1
    assert REG.counter_value("hvd_megaplan_replays_total") == reps0 + 7
    gauges = {g["name"]: g["value"] for g in REG.snapshot()["gauges"]}
    assert gauges["hvd_megaplan_active"] == 1
    assert gauges["hvd_megaplan_capture_rounds"] == 3


def test_replay_bitwise_equal_to_reference(manager):
    """Acceptance: a replayed steady state converges bitwise-equal to a
    never-replayed reference run — the captured schedule executes the
    same compiled chunk programs the negotiated path would."""
    mgr = manager(stable_rounds=3)
    rt = _runtime()
    arrays = _arrays(elems=128, seed=11)
    replayed = [_cycle(rt, arrays, prefix="mp.bw") for _ in range(8)]
    assert mgr.replays >= 4  # the tail cycles really replayed
    megaplan.reset_manager()
    ref_rt = _runtime()
    assert ref_rt._mp is None
    for outs in replayed:
        ref = _cycle(ref_rt, arrays, prefix="mp.bw")
        for o, r in zip(outs, ref):
            np.testing.assert_array_equal(o, r)


def test_signature_change_invalidates_then_recaptures(manager):
    mgr = manager(stable_rounds=3)
    rt = _runtime()
    arrays = _arrays()
    for _ in range(5):
        _cycle(rt, arrays)
    assert mgr.plan is not None and mgr.replays == 2
    # same names, one new shape: the signature misses — the cycle runs
    # negotiated (correct results), the plan drops with reason recorded
    inval0 = _inval_count("signature")
    changed = list(arrays)
    changed[2] = np.ones(96, np.float32)
    outs = _cycle(rt, changed)
    for a, o in zip(changed, outs):
        np.testing.assert_allclose(a, o)
    assert mgr.plan is None
    assert _inval_count("signature") == inval0 + 1
    # the new stable shape re-captures after a fresh window
    for _ in range(4):
        _cycle(rt, changed)
    assert mgr.captures == 2 and mgr.plan is not None
    assert mgr.plan.sig == megaplan.batch_signature(
        [TensorEntry(name=f"mp.{i}", op="allreduce", tensor=a)
         for i, a in enumerate(changed)])


# --- the autotuner handshake -------------------------------------------------

def test_knob_change_during_replay_never_executes_stale_schedule(manager):
    """Regression (the autotuner handshake): a tuned-params push landing
    mid-replay invalidates within one round — the next cycle negotiates
    under the new knobs and the re-captured schedule carries the NEW
    chunk boundaries, never the stale ones."""
    mgr = manager(stable_rounds=3)
    rt = _runtime()
    arrays = _arrays()
    for _ in range(6):
        _cycle(rt, arrays)
    assert mgr.plan is not None and len(mgr.plan.chunks) == 1
    replays_before = mgr.replays
    inval0 = _inval_count("tuned_params")
    epoch0 = megaplan.epoch()
    # the coordinator-synchronized apply path every knob setter routes
    # through: chunk cap 1 moves every chunk boundary
    rt._apply_tuned_params({"chunk": 1})
    assert megaplan.epoch() > epoch0
    assert mgr.plan is None  # dropped immediately, not at next miss
    assert _inval_count("tuned_params") == inval0 + 1
    # next cycle: negotiated under the new knob, correct results
    outs = _cycle(rt, arrays)
    for a, o in zip(arrays, outs):
        np.testing.assert_allclose(a, o)
    assert mgr.replays == replays_before  # no replay of a stale plan
    for _ in range(3):
        _cycle(rt, arrays)
    # re-captured under the NEW boundaries: one chunk per tensor
    assert mgr.captures == 2 and mgr.plan is not None
    assert len(mgr.plan.chunks) == 4


def test_setter_funnel_invalidates(manager):
    """Every boundary-moving setter routes through the single
    invalidate_megaplan() funnel with its own reason."""
    mgr = manager(stable_rounds=2)
    rt = _runtime()
    arrays = _arrays(n=2)
    for _ in range(3):
        _cycle(rt, arrays)
    assert mgr.plan is not None
    ring0 = _inval_count("ring_slots")
    rt.set_staging_slots(rt.staging_ring_slots + 1)
    assert mgr.plan is None
    assert _inval_count("ring_slots") == ring0 + 1
    for _ in range(3):
        _cycle(rt, arrays)
    assert mgr.plan is not None
    plan0 = _inval_count("plan_cache")
    C.invalidate_fused_plans()
    assert mgr.plan is None
    assert _inval_count("plan_cache") == plan0 + 1


def test_elastic_generation_bump_invalidates(manager, monkeypatch):
    """An elastic resize bumps the plan epoch (HOROVOD_ELASTIC_GEN): the
    captured schedule misses within one round and the run converges
    equal to a never-replayed reference."""
    mgr = manager(stable_rounds=3)
    rt = _runtime()
    arrays = _arrays()
    for _ in range(5):
        _cycle(rt, arrays, prefix="mp.el")
    assert mgr.plan is not None
    inval0 = _inval_count("epoch")
    monkeypatch.setenv("HOROVOD_ELASTIC_GEN",
                       str(C._plan_epoch() + 1))
    outs = _cycle(rt, arrays, prefix="mp.el")
    assert mgr.plan is None
    assert _inval_count("epoch") == inval0 + 1
    megaplan.reset_manager()
    ref = _cycle(_runtime(), arrays, prefix="mp.el")
    for o, r in zip(outs, ref):
        np.testing.assert_array_equal(o, r)


# --- chaos: injected capture / replay faults ---------------------------------

@pytest.mark.chaos
def test_capture_fault_aborts_and_recaptures(manager, monkeypatch):
    mgr = manager(stable_rounds=3)
    rt = _runtime()
    arrays = _arrays()
    monkeypatch.setenv("HOROVOD_FAULT_SPEC", "megaplan.capture:error#1")
    faults.reset()
    try:
        for _ in range(4):
            outs = _cycle(rt, arrays, prefix="mp.cf")
            for a, o in zip(arrays, outs):
                np.testing.assert_allclose(a, o)
        # the first capture attempt (cycle 3) died: no plan, no capture,
        # every cycle still produced correct negotiated results
        assert mgr.captures == 0 and mgr.plan is None
    finally:
        monkeypatch.delenv("HOROVOD_FAULT_SPEC", raising=False)
        faults.reset()
    # re-stabilize: a fresh stable window re-captures and replays
    for _ in range(4):
        _cycle(rt, arrays, prefix="mp.cf")
    assert mgr.captures == 1 and mgr.plan is not None
    assert mgr.replays >= 1


@pytest.mark.chaos
def test_replay_fault_degrades_with_zero_leaked_spans(manager, monkeypatch):
    """Acceptance: an injected mid-replay invalidation degrades to
    negotiated mode with zero leaked spans and no torn ring state, and
    re-captures once the set re-stabilizes."""
    monkeypatch.setenv("HOROVOD_TRACE", "1")
    tracer = tracing.init_tracer(rank=0)
    mgr = manager(stable_rounds=3)
    rt = _runtime()
    assert rt.tracer is tracer
    arrays = _arrays()
    try:
        for _ in range(5):
            _cycle(rt, arrays, prefix="mp.rf")
        assert mgr.plan is not None and mgr.replays == 2
        monkeypatch.setenv("HOROVOD_FAULT_SPEC", "megaplan.replay:error#1")
        faults.reset()
        try:
            # the fault fires BEFORE any ring work: this cycle degrades
            # to the negotiated path and still completes correctly
            outs = _cycle(rt, arrays, prefix="mp.rf")
            for a, o in zip(arrays, outs):
                np.testing.assert_allclose(a, o)
        finally:
            monkeypatch.delenv("HOROVOD_FAULT_SPEC", raising=False)
            faults.reset()
        assert mgr.plan is None and _inval_count("fault") >= 1
        assert mgr.misses == 1 and mgr.replay_hit_rate() < 1.0
        # no torn ring state: the same runtime re-stabilizes, re-captures
        # and replays again through the same staging ring
        for _ in range(5):
            _cycle(rt, arrays, prefix="mp.rf")
        assert mgr.captures == 2 and mgr.replays >= 4
        assert tracer.open_spans() == 0
    finally:
        tracing.reset_tracer()


# --- anatomy integration -----------------------------------------------------

def test_replay_headroom_drops_and_megaplan_lane_appears(manager,
                                                         monkeypatch):
    """Acceptance: once replay engages, the profiler's replay headroom
    collapses toward ~0 and the timeline grows a ``megaplan`` lane."""
    monkeypatch.setenv("HOROVOD_ANATOMY", "1")
    anatomy.reset_profiler()
    prof = anatomy.init_profiler(rank=0)
    mgr = manager(stable_rounds=3)
    rt = _runtime()
    assert rt.profiler is prof
    arrays = _arrays()
    try:
        for _ in range(8):
            _cycle(rt, arrays, prefix="mp.an")
        assert mgr.replays >= 4
        recs = prof.records()
        replay_recs = [r for r in recs
                       if any(e["kind"] == "megaplan"
                              for e in r["entities"])]
        assert len(replay_recs) == mgr.replays
        rec = replay_recs[-1]
        ent = next(e for e in rec["entities"] if e["kind"] == "megaplan")
        assert ent["name"].startswith("megaplan:")
        assert ent["tensors"] == 4
        # negotiate + host-gap residue in a replayed cycle is the ~single
        # is-valid check: well under 10 ms even on a loaded CI host
        assert rec["replay_headroom_s"] < 0.010
        # the merged timeline shows the megaplan lane
        snap = prof.snapshot()
        lane = next(l for l in snap["lanes"]
                    if l["kind"] == "megaplan")
        buffers = [{"rank": 0, "clock_offset_s": 0.0, "spans": []}]
        merged = tracing.merge_chrome_trace(buffers, anatomy=[snap])
        lanes = [e for e in merged["traceEvents"]
                 if e.get("cat") == "anatomy"
                 and e.get("name") == lane["name"]]
        assert lanes, merged["traceEvents"]
    finally:
        anatomy.reset_profiler()


# --- the coordinator lease ---------------------------------------------------

def _both(ctl0, ctl1, fn0, fn1):
    """Run one lockstep round: both ranks' calls concurrently."""
    out = {}

    def side():
        out["r1"] = fn1(ctl1)

    t = threading.Thread(target=side)
    t.start()
    out["r0"] = fn0(ctl0)
    t.join(timeout=60)
    assert not t.is_alive()
    return out["r0"], out["r1"]


def test_coordinator_grants_and_drops_lease(kv_server, monkeypatch):
    """The lease protocol: granted after STABLE_ROUNDS consecutive
    all-marker rounds, renewed by marker-only lease rounds, and dropped
    for EVERY rank in the same round one rank breaks stability."""
    addr, port = kv_server
    monkeypatch.setenv("HOROVOD_MEGAPLAN", "1")
    monkeypatch.setenv("HOROVOD_MEGAPLAN_STABLE_ROUNDS", "2")
    sig = {"c0": list(SIG_ROW)}
    sig2 = {"c0": list(SIG_ROW), "c1": list(SIG_ROW)}
    ctl0 = KVController(KVStoreClient(addr, port), rank=0, size=2,
                        poll_timeout=60.0)
    ctl1 = KVController(KVStoreClient(addr, port), rank=1, size=2,
                        poll_timeout=60.0)
    neg = lambda s: (lambda c: c.negotiate(dict(s)))
    lease = lambda c: c.lease_round()
    try:
        # round 1: full payloads — no streak yet
        r0, r1 = _both(ctl0, ctl1, neg(sig), neg(sig))
        assert r0["ready"] == ["c0"] and r1["ready"] == ["c0"]
        assert not ctl0.megaplan_lease and not ctl1.megaplan_lease
        # rounds 2-3: identical sets ride the 1-byte marker; the streak
        # reaches the threshold and the grant lands on BOTH ranks
        _both(ctl0, ctl1, neg(sig), neg(sig))
        assert not ctl0.megaplan_lease  # streak 1 < 2: not yet
        _both(ctl0, ctl1, neg(sig), neg(sig))
        assert ctl0.megaplan_lease and ctl1.megaplan_lease
        # replay-mode lease rounds renew the grant (and stay correct)
        r0, r1 = _both(ctl0, ctl1, lease, lease)
        assert r0["ready"] == ["c0"] and r1["ready"] == ["c0"]
        assert ctl0.megaplan_lease and ctl1.megaplan_lease
        # rank 1 breaks stability (a new tensor: full payload) while
        # rank 0 is mid-replay: the lease drops for both in that round
        r0, r1 = _both(ctl0, ctl1, lease, neg(sig2))
        assert not ctl0.megaplan_lease and not ctl1.megaplan_lease
        # the consumed round still negotiated correctly: the common
        # subset is released to both ranks
        assert r0["ready"] == ["c0"] and r1["ready"] == ["c0"]
        # re-stabilize on the new common set: the lease comes back
        _both(ctl0, ctl1, neg(sig2), neg(sig2))
        for _ in range(2):
            _both(ctl0, ctl1, neg(sig2), neg(sig2))
        assert ctl0.megaplan_lease and ctl1.megaplan_lease
    finally:
        ctl0.stop()
        ctl1.stop()


# --- benchmark harness + benchguard gates ------------------------------------

def _load_bench(name):
    import importlib.util as ilu

    spec = ilu.spec_from_file_location(
        f"_megaplan_bench_{name}",
        os.path.join(REPO, "benchmarks", f"{name}.py"))
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod



@pytest.mark.slow
def test_megaplan_gate_benchguard():
    """The checked-in acceptance gate: steady-state ``negotiate`` +
    ``host_overhead`` phase shares ≈0 across all three workloads with
    replay hit rate 1.0, AND the megaplan-off A/A within 2% of the
    featureless baseline — judged by tools/benchguard against
    benchmarks/megaplan_budgets.json."""
    sys.path.insert(0, REPO)
    from tools import benchguard

    co = _load_bench("cycle_overhead")
    ov = _load_bench("megaplan_overhead")
    rows = {wl: co.measure_replay(wl, cycles=30) for wl in co.WORKLOADS}
    ov.measure_megaplan(False, cycles=10, warmup=2)  # discarded warm-up
    runs = {"baseline": [], "off": []}
    for _ in range(3):
        runs["baseline"].append(ov.measure_megaplan(False, cycles=30))
        runs["off"].append(ov.measure_megaplan(False, cycles=30))
    base, off = (min(runs[k], key=lambda r: r["dispatch_ms_median"])
                 for k in ("baseline", "off"))
    extras = {}
    for wl, r in rows.items():
        extras[f"{wl}_negotiate_share"] = r["negotiate_share"]
        extras[f"{wl}_host_overhead_share"] = r["host_overhead_share"]
    extras["worst_host_overhead_p95_ms"] = max(
        r["host_overhead_p95_ms"] for r in rows.values())
    extras["worst_replay_hit_rate"] = min(
        r["replay_hit_rate"] or 0.0 for r in rows.values())
    extras["aa_off_over_baseline"] = (
        off["dispatch_ms_median"] / base["dispatch_ms_median"])
    result = {"bench": "cycle_overhead_megaplan",
              "metric": "megaplan_worst_steady_state_share",
              "value": max(r["negotiate_share"] + r["host_overhead_share"]
                           for r in rows.values()),
              "extras": extras}
    budgets = benchguard.load_budgets(
        os.path.join(REPO, "benchmarks", "megaplan_budgets.json"))
    verdict = benchguard.compare(result, history=[], budgets=budgets)
    assert verdict["status"] == "ok", (verdict, result)
