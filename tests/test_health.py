"""Fleet health engine (horovod_tpu/utils/health.py): bounded history
rings, the online drift/anomaly detector (latch-once, re-arm), the
escalation paths (metrics, flightrec, StallInspector, autotune re-tune),
the auth-exempt ``GET /history``/``GET /health`` merges with the shared
push-staleness helper, the benchtrend ``--from-history`` bridge, and the
2-process acceptance run where a fault-injected negotiate delay on rank
1 latches an anomaly, degrades the fleet verdict with rank 1 as top
suspect, and clears after the fault window ends.

The engine is OFF for the session-scoped hvd.init() (conftest); tests
that need one arm a private engine via the ``engine`` fixture and drop
it on exit — the tests/test_anatomy.py ``profiler`` pattern — so the
zero-cost default holds for every other test file.
"""

import json
import os
import subprocess
import sys
import textwrap
import time
import urllib.request

import pytest

import horovod_tpu as hvd
from horovod_tpu.runner.http_server import KVStoreClient, RendezvousServer
from horovod_tpu.runner.launch import run_commandline
from horovod_tpu.utils import faults, health, metrics, perfledger

REG = metrics.get_registry()
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def engine(monkeypatch):
    """Create (and on exit drop) a process engine, HOROVOD_HEALTH on."""

    def _make(rank=0, capacity=None, warmup=None, **kw):
        monkeypatch.setenv("HOROVOD_HEALTH", "1")
        if capacity is not None:
            monkeypatch.setenv("HOROVOD_HEALTH_BUFFER", str(capacity))
        if warmup is not None:
            monkeypatch.setenv("HOROVOD_HEALTH_WARMUP", str(warmup))
        health.reset_engine()
        return health.init_engine(rank=rank, **kw)

    yield _make
    health.reset_engine()


@pytest.fixture
def ledger(monkeypatch):
    """A private perf ledger feeding the engine's windowed collector."""
    monkeypatch.setenv("HOROVOD_PERFLEDGER", "1")
    perfledger.reset_ledger()
    led = perfledger.init_ledger(rank=0)
    yield led
    perfledger.reset_ledger()


@pytest.fixture
def kv_server():
    srv = RendezvousServer(secret_key="health-secret")
    port = srv.start()
    yield "127.0.0.1", port
    srv.stop()


def _steps(led, n, wall=0.010, neg=0.002):
    for _ in range(n):
        led.record_step(wall, negotiate_s=neg, dispatch_s=wall * 0.8,
                        exec_s=wall * 0.6)


def _windows(eng, led, n, wall=0.010, neg=0.002, steps=3):
    """Drive n dump windows: record steps, then one sampling pass each."""
    events = []
    for _ in range(n):
        _steps(led, steps, wall=wall, neg=neg)
        events.extend(eng.sample_and_detect())
    return events


# --- zero-cost contract ------------------------------------------------------

def test_health_disabled_by_default(monkeypatch):
    monkeypatch.delenv("HOROVOD_HEALTH", raising=False)
    health.reset_engine()
    assert not health.enabled()
    assert health.init_engine(rank=0) is None
    assert health.get_engine() is None
    assert health.report() == {"enabled": False}
    assert hvd.health_report() == {"enabled": False}
    health.dump_on_exit()  # no engine: a silent no-op, never an error


def test_health_off_registers_zero_series():
    """Acceptance: with HOROVOD_HEALTH unset, no hvd_health_* series of
    ANY kind exists, and the dumper's flush hook pays its one is-None
    check without sampling. Checked in a pristine subprocess — the
    in-process registry accumulates series from tests that DO arm the
    engine."""
    script = textwrap.dedent("""
        import os
        assert "HOROVOD_HEALTH" not in os.environ
        from horovod_tpu.utils import health, metrics
        assert not health.enabled()
        assert health.init_engine(rank=0) is None
        # the only hook: a full dumper flush with the engine off
        reg = metrics.get_registry()
        metrics.MetricsDumper(reg, interval_s=60.0).flush()
        snap = reg.snapshot()
        names = {m["name"]
                 for kind in ("counters", "gauges", "histograms")
                 for m in snap[kind]}
        bad = {n for n in names if n.startswith("hvd_health")}
        assert not bad, bad
        print("zero-series OK")
    """)
    env = dict(os.environ)
    env.pop("HOROVOD_HEALTH", None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "zero-series OK" in proc.stdout


def _load_health_overhead():
    import importlib.util as ilu

    spec = ilu.spec_from_file_location(
        "_health_overhead_test",
        os.path.join(REPO, "benchmarks", "health_overhead.py"))
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod



@pytest.mark.slow
def test_health_aa_gate_benchguard():
    """The checked-in A/A acceptance gate: health-off within 2% of the
    featureless baseline (best-of-3 interleaved reps), judged by
    tools/benchguard against benchmarks/health_budgets.json.

    The off and baseline arms run IDENTICAL code (measure_health(False)
    twice), so an out-of-budget A/A ratio can only mean the host's noise
    floor exceeded 2% during this sample — never a code regression. The
    whole measurement is therefore retried on a noisy verdict; a real
    engine-cost regression trips the on_over_baseline budget on every
    attempt."""
    sys.path.insert(0, REPO)
    from tools import benchguard

    mod = _load_health_overhead()
    budgets = benchguard.load_budgets(
        os.path.join(REPO, "benchmarks", "health_budgets.json"))
    for attempt in range(3):
        mod.measure_health(False, cycles=10, warmup=2)  # discarded warm-up
        runs = {"baseline": [], "off": [], "on": []}
        for _ in range(3):
            runs["baseline"].append(mod.measure_health(False, cycles=30))
            runs["off"].append(mod.measure_health(False, cycles=30))
            runs["on"].append(mod.measure_health(True, cycles=30))
        base, off, on = (
            min(runs[k], key=lambda r: r["dispatch_ms_median"])
            for k in ("baseline", "off", "on"))
        result = {"bench": "health_overhead",
                  "metric": "health_off_over_baseline_ratio",
                  "value": (off["dispatch_ms_median"]
                            / base["dispatch_ms_median"]),
                  "extras": {"on_over_baseline":
                             on["dispatch_ms_median"]
                             / base["dispatch_ms_median"]}}
        verdict = benchguard.compare(result, history=[], budgets=budgets)
        if verdict["status"] == "ok":
            break
    assert verdict["status"] == "ok", (verdict, result)


# --- the history rings -------------------------------------------------------

def test_series_ring_bounds_and_downsamples():
    ring = health.SeriesRing(capacity=16)
    for i in range(40):
        ring.append(float(i), float(i))
    assert ring.total == 40
    assert len(ring.raw) == 16  # oldest evicted
    assert ring.raw[0] == (24.0, 24.0)
    # every DOWNSAMPLE_EVERY raw points collapse to one mean point
    # stamped with the group's first ts
    assert len(ring.tier) == 40 // health.DOWNSAMPLE_EVERY
    ts0, mean0 = ring.tier[0]
    assert ts0 == 0.0
    assert mean0 == pytest.approx(
        sum(range(health.DOWNSAMPLE_EVERY)) / health.DOWNSAMPLE_EVERY)


def test_engine_samples_windowed_ledger_series(engine, ledger):
    eng = engine(rank=0, warmup=4)
    _windows(eng, ledger, 2, wall=0.010, neg=0.002)
    rep = eng.report()
    assert rep["enabled"] and rep["verdict"] == "healthy"
    assert rep["series"]["step_time_ms"]["n"] == 2
    assert rep["series"]["step_time_ms"]["last"] == pytest.approx(10.0)
    assert rep["series"]["negotiate_ms"]["last"] == pytest.approx(2.0)
    assert rep["series"]["exposed_comm_frac"]["last"] == pytest.approx(0.2)
    # a window with no recorded steps contributes no step samples
    eng.sample_and_detect()
    assert eng.report()["series"]["step_time_ms"]["n"] == 2
    snap = eng.snapshot()
    json.dumps(snap)  # the KV push payload must be JSON-able
    assert snap["series"]["step_time_ms"]["samples"][-1][1] == \
        pytest.approx(10.0)


def test_gauge_value_is_non_creating():
    assert REG.gauge_value("hvd_health_probe_never_exists") is None
    snap = REG.snapshot()
    assert all(g["name"] != "hvd_health_probe_never_exists"
               for g in snap["gauges"])
    g = REG.gauge("hvd_health_probe_gauge", "test gauge")
    g.set(7.5)
    assert REG.gauge_value("hvd_health_probe_gauge") == 7.5


# --- the online detector -----------------------------------------------------

def test_detector_drift_latches_once_and_rearms():
    det = health._Detector("step_time_ms", "high", warmup=4)
    for i in range(4):
        assert det.observe(float(i), 10.0 + 0.1 * i) is None
    assert det.median is not None  # baseline frozen after warmup
    # baseline: median 10.1, scale 0.505 (the 5% floor), so 15.0 reads
    # z ~ 9.7 — drift territory, below the spike threshold
    assert det.observe(5.0, 15.0) is None  # debounced: no latch yet
    ev = det.observe(6.0, 15.0)
    assert ev and ev["event"] == "latch" and ev["kind"] == "drift"
    assert health.Z_DRIFT <= ev["z"] < health.Z_SPIKE
    assert ev["baseline"] == pytest.approx(det.median)
    # latched once: the episode stays silent however long it persists
    for i in range(5):
        assert det.observe(7.0 + i, 15.0) is None
    # re-arm after CLEAR_SAMPLES in-bound samples, then a fresh episode
    assert det.observe(20.0, 10.0) is None
    ev = det.observe(21.0, 10.0)
    assert ev and ev["event"] == "clear"
    assert det.observe(22.0, 15.0) is None
    ev = det.observe(23.0, 15.0)
    assert ev and ev["event"] == "latch"  # second episode latches again


def test_detector_spike_latches_immediately_and_low_direction():
    det = health._Detector("step_time_ms", "high", warmup=4)
    for i in range(4):
        det.observe(float(i), 10.0)
    ev = det.observe(5.0, 500.0)  # far beyond Z_SPIKE: no debounce
    assert ev and ev["kind"] == "spike"
    # direction-aware: plan_hit_rate drifting DOWN is the regression,
    # and an upward move never latches
    low = health._Detector("plan_hit_rate", "low", warmup=4)
    for i in range(4):
        low.observe(float(i), 0.95)
    assert low.observe(5.0, 1.0) is None
    assert low.observe(6.0, 1.0) is None
    # 0.5 against median 0.95 / scale 0.0475 reads z ~ 9.5 downward
    assert low.observe(7.0, 0.5) is None  # debounce
    ev = low.observe(8.0, 0.5)
    assert ev and ev["event"] == "latch" and ev["series"] == "plan_hit_rate"
    assert ev["kind"] == "drift"


def test_engine_latch_fires_metrics_flightrec_and_inspector(engine, ledger):
    class _Inspector:
        def __init__(self):
            self.noted = []

        def note_health_anomaly(self, series, detail):
            self.noted.append((series, detail))

        def straggler_rank(self):
            return 3

    insp = _Inspector()
    eng = engine(rank=0, warmup=4, stall_inspector=insp)
    a0 = REG.counter_value("hvd_health_anomaly_total")
    _windows(eng, ledger, 5, wall=0.010, neg=0.002)
    assert eng.report()["suspect_rank"] is None  # healthy: no suspect
    _windows(eng, ledger, 2, wall=0.200, neg=0.002)
    rep = eng.report()
    assert rep["verdict"] in ("degraded", "critical")
    latched = {a["series"] for a in rep["active"]}
    assert "step_time_ms" in latched
    assert REG.counter_value("hvd_health_anomaly_total") > a0
    assert REG.gauge_value("hvd_health_active_anomalies") == len(
        rep["active"])
    assert REG.gauge_value("hvd_health_verdict") >= 1.0
    # escalation named the series and observed-vs-baseline
    series_noted = {s for s, _ in insp.noted}
    assert "step_time_ms" in series_noted
    detail = dict(insp.noted)["step_time_ms"]
    assert "baseline" in detail and "z=" in detail
    # with anomalies active the report carries the inspector's suspect
    assert rep["suspect_rank"] == 3
    assert rep["anomalies_total"] == len(rep["active"])


# --- the autotune re-tune hook -----------------------------------------------

def test_drift_provokes_exactly_one_retune(engine, ledger):
    class _Tuner:
        def __init__(self):
            self.drifts = []

        def note_health_drift(self, series):
            self.drifts.append(series)

    tuner = _Tuner()
    eng = engine(rank=0, warmup=4, autotuner=tuner)
    _windows(eng, ledger, 5, wall=0.010, neg=0.002)
    # a sustained ~2.5x drift (below the spike threshold is not needed:
    # the hook fires on kind == "drift" only, so step through debounce
    # with a magnitude that stays under Z_SPIKE on the learned scale)
    base = eng.report()["baselines"]["step_time_ms"]
    drift_wall = (base["median"] + (health.Z_DRIFT + 2) * base["scale"]) / 1e3
    _windows(eng, ledger, 6, wall=drift_wall, neg=0.002 * drift_wall / 0.010)
    assert tuner.drifts.count("step_time_ms") == 1, tuner.drifts
    # the same latched episode never re-fires, however long it persists
    _windows(eng, ledger, 4, wall=drift_wall, neg=0.002 * drift_wall / 0.010)
    assert tuner.drifts.count("step_time_ms") == 1, tuner.drifts


def test_retune_restarts_real_autotuner_without_revert_loop():
    """note_health_drift on the real Autotuner restarts the search and
    voids the best-config memory, so the revert guardrail cannot loop
    the search back onto the pre-drift config."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_autotune import _JointRuntime

    from horovod_tpu.utils.autotune import Autotuner

    rt = _JointRuntime()
    at = Autotuner(rt, warmup_samples=0, max_samples=2,
                   revert_pct=20.0, revert_windows=2)
    at._score = lambda: 100.0
    at.sample()
    at.sample()
    assert at.done and at._best_score is not None
    s0 = REG.counter_value("hvd_autotune_workload_shifts_total")
    at.note_health_drift("step_time_ms")
    assert REG.counter_value(
        "hvd_autotune_workload_shifts_total") == s0 + 1
    assert not at.done and at._samples == 0
    assert at._best_score is None and at._best_params is None
    assert at._strikes == 0
    # post-drift scores are worse; with the memory voided the guardrail
    # must NOT fire a revert back onto the stale config
    r0 = REG.counter_value("hvd_autotune_reverts_total")
    at._score = lambda: 50.0
    at.sample()
    at.sample()
    assert at.done  # re-converged on the new regime
    assert REG.counter_value("hvd_autotune_reverts_total") == r0


# --- chaos: the health.sample fault site -------------------------------------

@pytest.fixture
def arm(monkeypatch):
    def _arm(spec):
        monkeypatch.setenv("HOROVOD_FAULT_SPEC", spec)
        faults.reset()

    yield _arm
    faults.reset()


class _FakeKV:
    def __init__(self):
        self.puts = []

    def put(self, scope, key, value):
        self.puts.append((scope, key, bytes(value)))


@pytest.mark.chaos
def test_dropped_sample_never_corrupts_ring_or_latches(engine, ledger, arm,
                                                       monkeypatch):
    monkeypatch.delenv("HOROVOD_FAULT_SPEC", raising=False)
    faults.reset()
    eng = engine(rank=0, warmup=4)
    kv = _FakeKV()
    dumper = metrics.MetricsDumper(REG, interval_s=5.0, kv_client=kv, rank=0)
    for _ in range(6):
        _steps(ledger, 3)
        dumper.flush()
    n0 = eng.report()["series"]["step_time_ms"]["n"]
    assert n0 == 6
    # two dropped passes: the fault point precedes the sample, so the
    # whole pass is skipped — no half-written ring, no sample at all
    arm("health.sample:drop#2")
    _steps(ledger, 3)
    dumper.flush()
    _steps(ledger, 3)
    dumper.flush()
    rep = eng.report()
    assert rep["series"]["step_time_ms"]["n"] == n0
    assert rep["active"] == [] and rep["verdict"] == "healthy"
    faults.reset()
    monkeypatch.delenv("HOROVOD_FAULT_SPEC", raising=False)
    # the recovery pass consumes the whole ledger backlog (the dropped
    # windows' records were never read) as ONE window — a mean over
    # healthy steps, so nothing latches and the rings grow by one point
    _steps(ledger, 3)
    dumper.flush()
    rep = eng.report()
    assert rep["series"]["step_time_ms"]["n"] == n0 + 1
    assert rep["series"]["step_time_ms"]["last"] == pytest.approx(10.0)
    assert rep["active"] == [] and rep["verdict"] == "healthy"


@pytest.mark.chaos
def test_torn_push_skipped_by_merge_not_fatal(engine, ledger, arm,
                                              kv_server, monkeypatch):
    monkeypatch.delenv("HOROVOD_FAULT_SPEC", raising=False)
    faults.reset()
    addr, port = kv_server
    eng = engine(rank=0, warmup=4)
    kv = KVStoreClient(addr, port, secret_key="health-secret")
    dumper = metrics.MetricsDumper(REG, interval_s=5.0, kv_client=kv, rank=0)
    arm("health.sample:torn#1")
    _steps(ledger, 3)
    dumper.flush()  # the pushed payload is truncated mid-JSON
    faults.reset()
    monkeypatch.delenv("HOROVOD_FAULT_SPEC", raising=False)
    merged = json.loads(urllib.request.urlopen(
        f"http://{addr}:{port}/history", timeout=10).read())
    # local ring intact (torn only corrupts the wire copy), local merge
    # serves it; the torn KV entry was skipped, not fatal
    assert merged["ranks"]["0"]["series"]["step_time_ms"]["n"] == 1
    assert eng.report()["verdict"] == "healthy"
    # a later healthy push replaces the torn entry
    _steps(ledger, 3)
    dumper.flush()
    merged = json.loads(urllib.request.urlopen(
        f"http://{addr}:{port}/history", timeout=10).read())
    assert merged["ranks"]["0"]["series"]["step_time_ms"]["n"] == 2


# --- pushes, GET /history, GET /health ---------------------------------------

def test_metrics_dumper_pushes_stamped_health(engine, ledger):
    eng = engine(rank=2, warmup=4)
    _steps(ledger, 3)
    kv = _FakeKV()
    dumper = metrics.MetricsDumper(REG, interval_s=5.0, kv_client=kv, rank=2)
    dumper.flush()
    pushed = [(k, json.loads(v)) for scope, k, v in kv.puts
              if scope == health.KV_SCOPE]
    assert len(pushed) == 1
    key, snap = pushed[0]
    assert key == "rank2" and snap["rank"] == 2
    assert snap["verdict"] == "healthy"
    assert snap["series"]["step_time_ms"]["n"] == 1
    assert snap["push_seq"] == 1 and snap["push_interval_s"] == 5.0
    assert isinstance(snap["push_ts"], float)
    assert eng.report()["series"]["step_time_ms"]["n"] == 1


STALE_ENDPOINTS = [
    ("perf", "perf"),
    ("memory", "mem"),
    ("anatomy", "anatomy"),
    ("checkpoint", "ckpt"),
    ("history", "health"),
]


@pytest.mark.parametrize("endpoint,scope", STALE_ENDPOINTS,
                         ids=[e for e, _ in STALE_ENDPOINTS])
def test_all_merge_endpoints_share_stale_semantics(kv_server, endpoint,
                                                   scope):
    """Regression for the shared-staleness satellite: after unifying the
    merge into _merged_snapshots, every endpoint keeps the identical
    stamp semantics — fresh False, lagging True (annotated, not
    dropped), torn skipped, unstamped never marked."""
    addr, port = kv_server
    kv = KVStoreClient(addr, port, secret_key="health-secret")
    now = time.time()
    fresh = {"rank": 0, "push_ts": now, "push_interval_s": 2.0,
             "push_seq": 9}
    lagging = {"rank": 1, "push_ts": now - 600, "push_interval_s": 2.0,
               "push_seq": 3}
    unstamped = {"rank": 7}
    kv.put(scope, "rank0", json.dumps(fresh).encode())
    kv.put(scope, "rank1", json.dumps(lagging).encode())
    kv.put(scope, "rank7", json.dumps(unstamped).encode())
    kv.put(scope, "rank-torn", b"{half a json")  # skipped, not fatal
    merged = json.loads(urllib.request.urlopen(
        f"http://{addr}:{port}/{endpoint}", timeout=10).read())
    ranks = merged["ranks"]
    assert set(ranks) >= {"0", "1", "7"}
    assert ranks["0"]["stale"] is False
    assert ranks["1"]["stale"] is True
    assert ranks["7"]["stale"] is False  # unjudgeable: never marked
    assert "-torn" not in ranks


def test_health_endpoint_carries_stale_annotation(kv_server):
    addr, port = kv_server
    kv = KVStoreClient(addr, port, secret_key="health-secret")
    now = time.time()
    kv.put("health", "rank0", json.dumps(
        {"rank": 0, "verdict": "healthy", "active": [],
         "push_ts": now, "push_interval_s": 2.0}).encode())
    kv.put("health", "rank1", json.dumps(
        {"rank": 1, "verdict": "healthy", "active": [],
         "push_ts": now - 600, "push_interval_s": 2.0}).encode())
    fleet = json.loads(urllib.request.urlopen(
        f"http://{addr}:{port}/health", timeout=10).read())
    assert fleet["ranks"]["0"]["stale"] is False
    assert fleet["ranks"]["1"]["stale"] is True


def test_history_endpoint_windows_series_and_since(kv_server, engine,
                                                   ledger):
    addr, port = kv_server
    eng = engine(rank=0, warmup=4)
    kv = KVStoreClient(addr, port, secret_key="health-secret")
    dumper = metrics.MetricsDumper(REG, interval_s=5.0, kv_client=kv, rank=0)
    _steps(ledger, 3)
    dumper.flush()
    cut = time.time()
    time.sleep(0.02)
    _steps(ledger, 3)
    dumper.flush()
    url = f"http://{addr}:{port}/history"
    full = json.loads(urllib.request.urlopen(url, timeout=10).read())
    series = full["ranks"]["0"]["series"]
    assert "step_time_ms" in series and "negotiate_ms" in series
    assert len(series["step_time_ms"]["samples"]) == 2
    filt = json.loads(urllib.request.urlopen(
        f"{url}?series=step_time_ms&since={cut}", timeout=10).read())
    series = filt["ranks"]["0"]["series"]
    assert set(series) == {"step_time_ms"}
    assert len(series["step_time_ms"]["samples"]) == 1  # pre-cut dropped
    assert eng.report()["series"]["step_time_ms"]["n"] == 2


# --- fleet verdict + suspect ranking -----------------------------------------

def _rank_snap(rank, step_ms, active=(), suspect=None):
    return {"rank": rank,
            "verdict": health._local_verdict(len(active)),
            "active": list(active),
            "anomalies_total": len(active),
            "baselines": {},
            "suspect_rank": suspect,
            "series": {"step_time_ms":
                       {"n": 10, "samples": [[100.0, step_ms]],
                        "downsampled": []}}}


def test_fleet_view_ranks_outlier_as_top_suspect():
    anom = {"event": "latch", "series": "step_time_ms", "kind": "drift",
            "observed": 30.0, "baseline": 10.0, "z": 20.0, "ts": 100.0}
    view = health.fleet_view({
        "0": _rank_snap(0, 10.0),
        "1": _rank_snap(1, 30.0, active=[anom]),
        "2": _rank_snap(2, 10.1),
    })
    assert view["verdict"] == "degraded"
    assert view["suspects"][0]["rank"] == "1"
    assert view["suspects"][0]["series"]["active_anomalies"] == 1
    assert "step_time_ms" in view["suspects"][0]["series"]
    assert view["anomalies"] == [dict(anom, rank="1")]
    assert view["ranks"]["1"]["verdict"] == "degraded"
    # the 2-rank case anchors on the healthy (lower-median) rank: the
    # slow rank reads positive badness, the fast one reads none
    two = health.fleet_view({"0": _rank_snap(0, 10.0),
                             "1": _rank_snap(1, 30.0)})
    assert [s["rank"] for s in two["suspects"]] == ["1"]


def test_fleet_view_straggler_attribution_outweighs_victim_anomalies():
    """A lockstep delay latches anomalies on the WAITING rank too; the
    coordinator's straggler verdict (pushed as suspect_rank) must still
    name the culprit as top suspect."""
    victim_anoms = [
        {"series": "stall_share", "kind": "drift", "observed": 0.5,
         "baseline": 0.01, "z": 30.0, "ts": 1.0, "event": "latch"},
        {"series": "step_time_ms", "kind": "drift", "observed": 30.0,
         "baseline": 10.0, "z": 20.0, "ts": 1.0, "event": "latch"}]
    culprit_anom = [
        {"series": "negotiate_ms", "kind": "drift", "observed": 25.0,
         "baseline": 2.0, "z": 40.0, "ts": 1.0, "event": "latch"}]
    view = health.fleet_view({
        "0": _rank_snap(0, 30.0, active=victim_anoms, suspect=1),
        "1": _rank_snap(1, 30.5, active=culprit_anom, suspect=1),
    })
    assert view["suspects"][0]["rank"] == "1", view["suspects"]
    assert view["suspects"][0]["series"]["named_straggler"] > 0
    assert view["verdict"] == "critical"  # >= 3 anomalies fleet-wide


def test_fleet_view_worst_verdict_and_empty():
    assert health.fleet_view({})["verdict"] == "healthy"
    a = {"series": "s", "kind": "drift", "event": "latch"}
    view = health.fleet_view({
        "0": _rank_snap(0, 10.0),
        "1": _rank_snap(1, 10.0, active=[a, a, a]),
    })
    assert view["verdict"] == "critical"  # worst-of-ranks wins


# --- the on-exit dump + benchtrend bridge ------------------------------------

def test_dump_on_exit_renders_through_benchtrend(engine, ledger, tmp_path,
                                                 monkeypatch):
    sys.path.insert(0, REPO)
    from tools.benchtrend import __main__ as trend_cli
    from tools.benchtrend import load_history_dump

    eng = engine(rank=0, warmup=4)
    _windows(eng, ledger, 6, wall=0.010, neg=0.002)
    path = tmp_path / "health.json"
    monkeypatch.setenv("HOROVOD_HEALTH_FILE", str(path))
    health.dump_on_exit()
    assert path.exists()
    doc = json.loads(path.read_text())
    assert doc["rank"] == 0 and "step_time_ms" in doc["series"]
    # single-rank dump: bare series names, so resolve_direction still
    # reads the _ms suffix
    rounds = load_history_dump(str(path))
    assert rounds and rounds[0]["parsed"]["metric"] in doc["series"]
    assert trend_cli.main(["--from-history", str(path)]) == 0
    # a GET /history shaped dump (multi-rank): rank-prefixed metrics
    fleet = tmp_path / "fleet.json"
    fleet.write_text(json.dumps(
        {"ranks": {"0": doc, "1": dict(doc, rank=1)}}))
    rounds = load_history_dump(str(fleet))
    assert any(r["parsed"]["metric"].startswith("rank0/") for r in rounds)
    assert any(r["parsed"]["metric"].startswith("rank1/") for r in rounds)
    assert trend_cli.main(["--from-history", str(fleet), "--json"]) == 0
    # exit-code contract: unreadable / shapeless dumps exit 2
    assert trend_cli.main(["--from-history", str(tmp_path / "nope.json")]) \
        == 2
    bad = tmp_path / "bad.json"
    bad.write_text("[1, 2, 3]")
    assert trend_cli.main(["--from-history", str(bad)]) == 2


def test_bench_extras_none_when_off(monkeypatch):
    monkeypatch.delenv("HOROVOD_HEALTH", raising=False)
    health.reset_engine()
    rep = hvd.health_report()
    assert rep == {"enabled": False}
    # the bench.py block reads these three keys off the report
    assert rep.get("verdict") is None
    assert rep.get("anomalies_total") is None
    assert rep.get("suspect_rank") is None


# ---------------------------------------------------------------------------
# two-process acceptance: a fault-injected negotiate delay on rank 1
# after warmup latches an anomaly, GET /health degrades and names rank 1
# top suspect, and the verdict clears once the fault budget exhausts —
# zero leaked spans, lockcheck armed (conftest) throughout
# ---------------------------------------------------------------------------

HEALTH_WORKER = textwrap.dedent("""
    import json, os, sys, time, urllib.request
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common.exceptions import HorovodInternalError
    from horovod_tpu.utils import faults, health, tracing

    out_dir = sys.argv[1]
    hvd.init()
    r = hvd.cross_rank()
    eng = health.get_engine()
    assert eng is not None, "HOROVOD_HEALTH should arm the engine"

    def step():
        try:
            h = hvd.allreduce_async(np.ones(64, np.float32), op=hvd.Sum,
                                    name="e2e_health")
            hvd.synchronize(h)
        except HorovodInternalError as e:
            if "Multiprocess computations" not in str(e):
                raise
            # this jax build cannot EXECUTE multi-process CPU
            # collectives; the negotiation (the signal under test)
            # already completed

    def run_until(pred, deadline_s, what):
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            step()
            if pred():
                return
            time.sleep(0.05)
        raise AssertionError("timed out waiting for " + what)

    # phase 1: healthy lockstep until the negotiate baseline freezes on
    # this rank (warmup samples collected on the 0.3 s dump cadence)
    run_until(lambda: "negotiate_ms" in eng.report()["baselines"],
              90, "baseline freeze")

    # phase 2: rank 1 drags its polls — every round slows fleet-wide,
    # and the coordinator's straggler verdict names rank 1 (it is last
    # to submit every subsequent round). The budget far exceeds the
    # window: the handshake below, not exhaustion, ends the fault.
    if r == 1:
        os.environ["HOROVOD_FAULT_SPEC"] = "controller.poll:delay=400ms#500"
        faults.reset()
    run_until(lambda: eng.report()["active"], 120, "anomaly latch")
    rep = eng.report()
    open(os.path.join(out_dir, f"latched{r}.json"), "w").write(
        json.dumps(rep))

    url = None
    degraded_path = os.path.join(out_dir, "degraded.json")
    if r == 0:
        addr = os.environ["HOROVOD_GLOO_RENDEZVOUS_ADDR"]
        port = os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"]
        url = f"http://{addr}:{port}/health"

        def degraded_names_rank1():
            fleet = json.loads(
                urllib.request.urlopen(url, timeout=10).read())
            ok = (fleet["verdict"] in ("degraded", "critical")
                  and fleet["suspects"]
                  and fleet["suspects"][0]["rank"] == "1")
            if ok:
                tmp = degraded_path + ".tmp"
                open(tmp, "w").write(json.dumps(fleet))
                os.replace(tmp, degraded_path)
            return ok

        run_until(degraded_names_rank1, 120, "degraded fleet verdict")

    # phase 3: rank 1 holds the fault until rank 0 banked the degraded
    # verdict (anomalies clear within two dump windows of the fault
    # ending, so an early unarm could close the observation window),
    # then disarms; rounds return to baseline, the episodes clear and
    # the verdicts re-arm fleet-wide
    if r == 1:
        run_until(lambda: os.path.exists(degraded_path), 150,
                  "degraded handshake")
        os.environ.pop("HOROVOD_FAULT_SPEC", None)
        faults.reset()
    run_until(lambda: not eng.report()["active"], 120, "anomaly clear")
    assert eng.report()["verdict"] == "healthy"
    if r == 0:
        def fleet_recovers():
            fleet = json.loads(
                urllib.request.urlopen(url, timeout=10).read())
            if fleet["verdict"] == "healthy":
                open(os.path.join(out_dir, "recovered.json"), "w").write(
                    json.dumps(fleet))
                return True
            return False

        run_until(fleet_recovers, 120, "fleet recovery")

    # out of collective work: contribute zeros until the peer finishes
    # its own phases (reference join semantics), so the rank that clears
    # first cannot strand the other's tail steps mid-negotiation
    hvd.join()

    tracer = tracing.get_tracer()
    assert tracer is not None
    open_spans = tracer.open_spans()
    open(os.path.join(out_dir, f"worker{r}.json"), "w").write(json.dumps(
        {"rank": r, "report": hvd.health_report(),
         "open_spans": open_spans}))
    assert open_spans == 0, open_spans
    print("health worker OK", r)
""")


@pytest.mark.chaos
@pytest.mark.slow
def test_two_process_drift_degrades_and_recovers(tmp_path, monkeypatch):
    """Acceptance: rank 1's fault-injected 400 ms poll delay (armed
    after the baseline froze) latches an anomaly, GET /health reports
    degraded with rank 1 as top suspect, and once the fault budget
    exhausts every rank's verdict clears back to healthy — with zero
    leaked spans and the lock auditor armed the whole run."""
    script = tmp_path / "worker.py"
    script.write_text(HEALTH_WORKER)
    monkeypatch.setenv("HOROVOD_HEALTH", "1")
    # wide enough for the frozen MAD to capture this host's scheduling
    # jitter (a 4-sample warmup can freeze a near-zero scale and then
    # latch on every jitter spike, never stabilizing back to healthy)
    monkeypatch.setenv("HOROVOD_HEALTH_WARMUP", "12")
    monkeypatch.setenv("HOROVOD_PERFLEDGER", "1")
    monkeypatch.setenv("HOROVOD_TRACE", "1")  # straggler attribution
    # wide enough windows that one scheduling hiccup (a lone 50 ms wait
    # in an otherwise healthy window) averages out instead of reading as
    # a spike on the near-zero-baseline series (stall_share,
    # straggler_wait_ms) — the production cadence is 30 s with hundreds
    # of steps per window
    monkeypatch.setenv("HOROVOD_METRICS_DUMP_INTERVAL", "2.0")
    faults.reset()
    try:
        rc = run_commandline(["-np", "2", sys.executable, str(script),
                              str(tmp_path)])
    finally:
        faults.reset()
    assert rc == 0

    for r in (0, 1):
        path = tmp_path / f"worker{r}.json"
        assert path.exists(), list(tmp_path.iterdir())
        w = json.loads(path.read_text())
        assert w["open_spans"] == 0, (r, w)
        rep = w["report"]
        assert rep["enabled"] and rep["verdict"] == "healthy", (r, rep)
        assert rep["anomalies_total"] >= 1, (r, rep)
        latched = json.loads((tmp_path / f"latched{r}.json").read_text())
        assert latched["active"], (r, latched)

    degraded = json.loads((tmp_path / "degraded.json").read_text())
    assert degraded["verdict"] in ("degraded", "critical")
    assert degraded["suspects"][0]["rank"] == "1", degraded["suspects"]
    assert degraded["anomalies"], degraded
    recovered = json.loads((tmp_path / "recovered.json").read_text())
    assert recovered["verdict"] == "healthy", recovered
