"""Per-step performance ledger + SLO budget engine
(horovod_tpu/utils/perfledger.py), the freshness-stamped metrics/perf
merges (``GET /metrics`` stale annotation, the new auth-exempt
``GET /perf``), the pod-scale controller budget gate, and the 2-process
acceptance run where a delayed rank's negotiate phase dominates in
``GET /perf`` and the negotiate-p95 SLO budget fires.

The ledger is OFF for the session-scoped hvd.init() (conftest); tests
that need one arm a private ledger via the ``ledger`` fixture and drop
it on exit — the tests/test_flightrec.py ``recorder`` pattern — so the
zero-cost default holds for every other test file.
"""

import json
import logging
import os
import subprocess
import sys
import textwrap
import time
import urllib.request

import pytest

import horovod_tpu as hvd
from horovod_tpu.common import context as ctx_mod
from horovod_tpu.common.env import RuntimeConfig
from horovod_tpu.ops.queue import BackgroundRuntime
from horovod_tpu.runner.http_server import (KVStoreClient, RendezvousServer,
                                            _stale_ranks)
from horovod_tpu.runner.launch import run_commandline
from horovod_tpu.utils import faults, flightrec, metrics, perfledger
from horovod_tpu.utils.stall import StallInspector

REG = metrics.get_registry()
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def ledger(monkeypatch):
    """Create (and on exit drop) a process ledger, HOROVOD_PERFLEDGER on;
    optionally arm the SLO engine via ``slo=``."""

    def _make(rank=0, capacity=None, slo=None):
        monkeypatch.setenv("HOROVOD_PERFLEDGER", "1")
        if capacity is not None:
            monkeypatch.setenv("HOROVOD_PERFLEDGER_BUFFER", str(capacity))
        if slo is not None:
            monkeypatch.setenv("HOROVOD_SLO_SPEC", slo)
        perfledger.reset_ledger()
        return perfledger.init_ledger(rank=rank)

    yield _make
    perfledger.reset_ledger()


@pytest.fixture
def kv_server():
    srv = RendezvousServer(secret_key="perf-secret")
    port = srv.start()
    yield "127.0.0.1", port
    srv.stop()


# --- zero-cost contract ------------------------------------------------------

def test_perfledger_disabled_by_default(monkeypatch):
    monkeypatch.delenv("HOROVOD_PERFLEDGER", raising=False)
    perfledger.reset_ledger()
    assert not perfledger.enabled()
    assert perfledger.init_ledger(rank=0) is None
    assert perfledger.get_ledger() is None
    assert perfledger.get_engine() is None
    assert perfledger.evaluate_slos() == []  # engine-less no-op
    assert perfledger.report() == {"enabled": False}
    assert hvd.perf_report() == {"enabled": False}
    # an un-armed runtime resolves no handle: one is-None field
    cfg = RuntimeConfig()
    cfg.stall_check_disable = True
    rt = BackgroundRuntime(ctx_mod.global_process_set(), cfg)
    assert rt.ledger is None


def test_perfledger_off_registers_zero_series():
    """Acceptance: with HOROVOD_PERFLEDGER unset, no hvd_perf_* /
    hvd_slo_* series of ANY kind exists. Checked in a pristine
    subprocess — the in-process registry accumulates series from tests
    that DO arm the ledger."""
    script = textwrap.dedent("""
        import os
        assert "HOROVOD_PERFLEDGER" not in os.environ
        assert "HOROVOD_SLO_SPEC" not in os.environ
        from horovod_tpu.utils import metrics, perfledger
        assert not perfledger.enabled()
        assert perfledger.init_ledger(rank=0) is None
        snap = metrics.get_registry().snapshot()
        names = {m["name"]
                 for kind in ("counters", "gauges", "histograms")
                 for m in snap[kind]}
        bad = {n for n in names if n.startswith(("hvd_perf", "hvd_slo"))}
        assert not bad, bad
        print("zero-series OK")
    """)
    env = dict(os.environ)
    env.pop("HOROVOD_PERFLEDGER", None)
    env.pop("HOROVOD_SLO_SPEC", None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "zero-series OK" in proc.stdout



# --- the ring + phase decomposition ------------------------------------------

def test_record_step_phase_decomposition(ledger):
    led = ledger(rank=0)
    rec = led.record_step(0.10, negotiate_s=0.04, dispatch_s=0.05,
                          exec_s=0.03, tensors=20, straggler=(2, 0.01))
    # another rank straggled: its wait is OUR exposed stall slice
    assert rec["stall_s"] == pytest.approx(0.01)
    assert rec["negotiate_s"] == pytest.approx(0.03)
    assert rec["fuse_dispatch_s"] == pytest.approx(0.02)
    assert rec["device_exec_s"] == pytest.approx(0.03)
    assert rec["host_overhead_s"] == pytest.approx(0.01)
    assert sum(rec[p + "_s"] for p in perfledger.PHASES) \
        == pytest.approx(rec["wall_s"])
    assert rec["straggler_rank"] == 2 and rec["tensors"] == 20
    # this rank itself straggling is its own negotiate time, not a stall
    rec2 = led.record_step(0.10, negotiate_s=0.04, dispatch_s=0.05,
                           exec_s=0.03, straggler=(0, 0.02))
    assert rec2["stall_s"] == 0.0
    assert rec2["negotiate_s"] == pytest.approx(0.04)


def test_ring_capacity_and_records_since(ledger):
    led = ledger(rank=3, capacity=16)
    for i in range(20):
        led.record_step(0.001 * (i + 1))
    assert len(led) == 16  # oldest 4 evicted
    cursor, recs = led.records_since(0)
    assert cursor == 20 and len(recs) == 16
    led.record_step(0.5)
    cursor, recs = led.records_since(cursor)
    assert cursor == 21 and len(recs) == 1
    assert recs[0]["wall_s"] == pytest.approx(0.5)
    assert led.records_since(cursor) == (21, [])


def test_stats_snapshot_and_metrics(ledger):
    steps0 = REG.counter_value("hvd_perf_steps_total")
    led = ledger(rank=1)
    for _ in range(10):
        led.record_step(0.010, negotiate_s=0.004, dispatch_s=0.005,
                        exec_s=0.003, straggler=(4, 0.002))
    st = led.stats()
    assert st["steps"] == 10
    assert st["step_p50_ms"] == pytest.approx(10.0, rel=1e-3)
    # negotiate stats cover the full round INCLUDING the stall slice
    assert st["negotiate_p95_ms"] == pytest.approx(4.0, rel=1e-3)
    assert st["stall_p95_ms"] == pytest.approx(2.0, rel=1e-3)
    assert st["exposed_comm_frac"] == pytest.approx(0.4, rel=1e-3)
    assert st["plan_hit_rate"] == 1.0  # idle window: nothing missed
    snap = led.snapshot()
    assert snap["rank"] == 1 and snap["steps"] == 10
    assert len(snap["recent"]) == 5
    shares = {p: snap["phases"][p]["share"] for p in perfledger.PHASES}
    assert sum(shares.values()) == pytest.approx(1.0, abs=1e-4)
    rep = led.report()
    assert rep["enabled"] and rep["capacity"] == led.capacity
    assert REG.counter_value("hvd_perf_steps_total") == steps0 + 10


def test_counter_deltas_ride_records(ledger):
    led = ledger(rank=0)
    led.record_step(0.01)  # baseline capture: first-step deltas are 0
    REG.counter("hvd_allreduce_bytes_total",
                dtype="float32_testdelta").inc(4096)
    rec = led.record_step(0.01, dispatch_s=0.004, exec_s=0.004)
    assert rec["wire_bytes"] == pytest.approx(4096)
    assert led.stats()["step_wire_bytes"] == pytest.approx(2048)  # 2 steps
    # goodput gauge follows: 4096 B over the exec seconds seen so far
    gbps = next(g["value"] for g in REG.snapshot()["gauges"]
                if g["name"] == "hvd_perf_allreduce_gbps")
    assert gbps > 0


def test_perf_report_marks_unattributed_stall(ledger, caplog, monkeypatch):
    """Bugfix: without HOROVOD_TRACE the stall phase reads 0 because no
    coordinator verdicts arrive — perf_report() used to present that as
    a clean decomposition. It now marks the field unattributed and warns
    exactly once per ledger lifetime."""
    from horovod_tpu.utils import tracing

    ledger(rank=0).record_step(0.01, negotiate_s=0.004)
    assert tracing.get_tracer() is None
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        rep = hvd.perf_report()
        rep2 = hvd.perf_report()
    assert rep["enabled"] and rep["stall_attributed"] is False
    assert rep2["stall_attributed"] is False
    warned = [r for r in caplog.records if "HOROVOD_TRACE" in r.getMessage()]
    assert len(warned) == 1  # once, not per call
    # with tracing armed the verdicts flow: attributed, no warning
    monkeypatch.setenv("HOROVOD_TRACE", "1")
    tracing.reset_tracer()
    tracing.init_tracer(rank=0)
    try:
        caplog.clear()
        with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
            rep3 = hvd.perf_report()
        assert rep3["stall_attributed"] is True
        assert not [r for r in caplog.records
                    if "HOROVOD_TRACE" in r.getMessage()]
    finally:
        tracing.reset_tracer()


# --- SLO budget engine -------------------------------------------------------

def test_parse_slo_spec_forms(tmp_path):
    assert perfledger.parse_slo_spec("") == []
    assert perfledger.parse_slo_spec(
        "negotiate_p95_ms<=5, plan_hit_rate>=0.95") == [
        ("negotiate_p95_ms", "<=", 5.0), ("plan_hit_rate", ">=", 0.95)]
    assert perfledger.parse_slo_spec(
        '{"exposed_comm_frac": "<=0.3"}') == [
        ("exposed_comm_frac", "<=", 0.3)]
    spec_file = tmp_path / "slo.json"
    spec_file.write_text('{"step_p95_ms": "<=100"}')
    assert perfledger.parse_slo_spec(str(spec_file)) == [
        ("step_p95_ms", "<=", 100.0)]
    for bad in ("negotiate_p95_ms", "x<=notanum", "{not json",
                '["list"]', "<=5"):
        with pytest.raises(ValueError):
            perfledger.parse_slo_spec(bad)
    # a malformed env spec is skipped at init, never fatal
    os.environ["HOROVOD_PERFLEDGER"] = "1"
    os.environ["HOROVOD_SLO_SPEC"] = "garbage"
    try:
        perfledger.reset_ledger()
        assert perfledger.init_ledger(rank=0) is not None
        assert perfledger.get_engine() is None
    finally:
        os.environ.pop("HOROVOD_PERFLEDGER", None)
        os.environ.pop("HOROVOD_SLO_SPEC", None)
        perfledger.reset_ledger()


def test_slo_breach_latches_rearms_and_escalates(ledger, caplog):
    """A sustained breach fires ONCE (latched); the budget re-arms on a
    healthy window and fires again on the next breach — and each fire
    goes through the stall-warning path naming the budget."""
    breach0 = REG.counter_value("hvd_slo_breach_total")
    led = ledger(rank=0, slo="negotiate_p95_ms<=5,plan_hit_rate>=0.5")
    engine = perfledger.get_engine()
    assert engine is not None
    inspector = StallInspector(disabled=True)
    engine.attach_stall_inspector(inspector)
    warnings0 = REG.counter_value("hvd_stall_warnings_total")

    assert engine.evaluate() == []  # no records yet: no evaluation
    led.record_step(0.02, negotiate_s=0.02)  # 20 ms round: breach
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        fired = engine.evaluate()
    assert [f["budget"] for f in fired] == ["negotiate_p95_ms"]
    assert "negotiate_p95_ms" in caplog.text  # warning names the budget
    assert REG.counter_value("hvd_slo_breach_total") == breach0 + 1
    assert REG.counter_value("hvd_stall_warnings_total") == warnings0 + 1

    led.record_step(0.02, negotiate_s=0.02)  # still breaching: latched
    assert engine.evaluate() == []
    assert REG.counter_value("hvd_slo_breach_total") == breach0 + 1
    assert engine.state()["budgets"][0]["breaching"]

    led.record_step(0.002, negotiate_s=0.001)  # healthy window: re-arms
    assert engine.evaluate() == []
    assert not engine.state()["budgets"][0]["breaching"]

    led.record_step(0.02, negotiate_s=0.02)  # second breach window
    assert [f["budget"] for f in engine.evaluate()] == ["negotiate_p95_ms"]
    assert REG.counter_value("hvd_slo_breach_total") == breach0 + 2


def test_slo_breach_notes_flightrec_event(ledger, monkeypatch):
    monkeypatch.setenv("HOROVOD_FLIGHTREC", "1")
    flightrec.reset_recorder()
    rec = flightrec.init_recorder(rank=0)
    try:
        led = ledger(rank=0, slo="step_p95_ms<=1")
        led.record_step(0.05)
        assert perfledger.evaluate_slos()
    finally:
        flightrec.reset_recorder()
    evs = [e for e in rec.events() if e["cat"] == "slo_breach"]
    assert len(evs) == 1
    assert evs[0]["kv"]["budget"] == "step_p95_ms"
    assert evs[0]["kv"]["bound"] == "<=1"


@pytest.mark.chaos
def test_slo_breach_once_per_window_under_poll_delay(ledger, monkeypatch):
    """Chaos acceptance: negotiation rounds slowed by an injected
    ``controller.poll`` delay breach the budget exactly once per breach
    window across repeated dumper-cadence evaluations."""
    breach0 = REG.counter_value("hvd_slo_breach_total")
    led = ledger(rank=0, slo="negotiate_p95_ms<=10")
    monkeypatch.setenv("HOROVOD_FAULT_SPEC", "controller.poll:delay=30ms#4")
    faults.reset()
    try:
        # breach window 1: two slowed rounds, two evaluations -> one fire
        for _ in range(2):
            t0 = time.perf_counter()
            faults.fault_point("controller.poll")  # the poll-path delay
            dt = time.perf_counter() - t0
            assert dt >= 0.025
            led.record_step(dt + 0.001, negotiate_s=dt)
            perfledger.evaluate_slos()
        assert REG.counter_value("hvd_slo_breach_total") == breach0 + 1
        # healthy window: the fault budget (#4) still has charges, but
        # these rounds don't hit the poll site -> budget re-arms
        led.record_step(0.002, negotiate_s=0.001)
        perfledger.evaluate_slos()
        # breach window 2: slowed rounds again -> exactly one more fire
        for _ in range(2):
            t0 = time.perf_counter()
            faults.fault_point("controller.poll")
            dt = time.perf_counter() - t0
            led.record_step(dt + 0.001, negotiate_s=dt)
            perfledger.evaluate_slos()
        assert REG.counter_value("hvd_slo_breach_total") == breach0 + 2
    finally:
        monkeypatch.delenv("HOROVOD_FAULT_SPEC", raising=False)
        faults.reset()


# --- freshness stamps + stale annotation -------------------------------------

def test_stale_ranks_judgement():
    now = time.time()
    fresh = {"push_ts": now, "push_interval_s": 5.0}
    lagging = {"push_ts": now - 100.0, "push_interval_s": 5.0}
    assert _stale_ranks([("0", fresh), ("1", lagging)]) == {"1"}
    # threshold is max(3 intervals, 15 s floor): a 4 s lag at 5 s
    # interval absorbs dumper jitter
    near = {"push_ts": now - 4.0, "push_interval_s": 5.0}
    assert _stale_ranks([("0", fresh), ("1", near)]) == set()
    # unstamped snapshots (pre-stamp pushers) are never judged
    assert _stale_ranks([("0", fresh), ("1", {})]) == set()
    # a single stamped snapshot has no peer to lag behind
    assert _stale_ranks([("1", lagging)]) == set()


def test_metrics_dumper_stamps_pushes():
    class _FakeKV:
        def __init__(self):
            self.puts = []

        def put(self, scope, key, value):
            self.puts.append((scope, key, bytes(value)))

    kv = _FakeKV()
    dumper = metrics.MetricsDumper(REG, interval_s=5.0, kv_client=kv,
                                   rank=2)
    dumper.flush()
    dumper.flush()
    pushed = [json.loads(v) for scope, _, v in kv.puts
              if scope == metrics.KV_SCOPE]
    assert [p["push_seq"] for p in pushed] == [1, 2]  # monotonic stamp
    assert all(p["push_interval_s"] == 5.0 for p in pushed)
    assert all(isinstance(p["push_ts"], float) for p in pushed)


def test_metrics_merge_annotates_stale_rank(kv_server):
    """Regression: GET /metrics used to serve a wedged rank's frozen
    snapshot indistinguishably from a live one. The merge now annotates
    (never drops) ranks whose push stamp lags the newest push."""
    addr, port = kv_server
    kv = KVStoreClient(addr, port, secret_key="perf-secret")
    now = time.time()

    def snap(counter, ts):
        return {"ts": ts, "push_ts": ts, "push_interval_s": 5.0,
                "counters": [{"name": counter, "labels": {}, "value": 7}],
                "gauges": [], "histograms": []}

    kv.put("metrics", "rank0",
           json.dumps(snap("hvd_e2e_fresh_total", now)).encode())
    kv.put("metrics", "rank1",
           json.dumps(snap("hvd_e2e_lagging_total", now - 900)).encode())
    body = urllib.request.urlopen(
        f"http://{addr}:{port}/metrics", timeout=10).read().decode()
    lag_lines = [ln for ln in body.splitlines()
                 if ln.startswith("hvd_e2e_lagging_total{")]
    fresh_lines = [ln for ln in body.splitlines()
                   if ln.startswith("hvd_e2e_fresh_total{")]
    assert lag_lines and fresh_lines  # annotated, NOT dropped
    assert all('stale="1"' in ln and 'rank="1"' in ln for ln in lag_lines)
    assert all("stale" not in ln for ln in fresh_lines)


def test_perf_endpoint_merges_and_flags_stale(kv_server, ledger):
    addr, port = kv_server
    kv = KVStoreClient(addr, port, secret_key="perf-secret")
    now = time.time()
    led = ledger(rank=0)
    led.record_step(0.01, negotiate_s=0.004)
    fresh = led.snapshot()
    fresh.update(push_ts=now, push_interval_s=2.0)
    lagging = {"rank": 1, "steps": 3, "stats": {"steps": 3},
               "phases": {}, "recent": [],
               "push_ts": now - 600, "push_interval_s": 2.0}
    kv.put("perf", "rank0", json.dumps(fresh).encode())
    kv.put("perf", "rank1", json.dumps(lagging).encode())
    kv.put("perf", "rank-torn", b"{half a json")  # skipped, not fatal
    merged = json.loads(urllib.request.urlopen(
        f"http://{addr}:{port}/perf", timeout=10).read())
    assert set(merged["ranks"]) == {"0", "1"}
    assert merged["ranks"]["0"]["stale"] is False
    assert merged["ranks"]["1"]["stale"] is True  # annotated, not dropped
    assert merged["ranks"]["1"]["steps"] == 3
    assert merged["ranks"]["0"]["stats"]["steps"] == 1


# --- benchguard + controller-scaling gates -----------------------------------

def test_benchguard_cli_on_banked_trajectory(tmp_path):
    """Tier-1 smoke: the CLI judges the newest banked round against the
    full trajectory and exits 0 — artifacts in the driver's wrapper
    shape (an outlier first round, two null-parse rounds) stay
    guardable."""
    for n, value in ((1, 2241.08), (2, None), (3, None), (4, 0.65),
                     (5, 0.62), (6, 0.64)):
        parsed = None if value is None else {
            "metric": "resnet50_images_per_sec_per_chip", "value": value,
            "unit": "images/sec/chip", "extras": {}}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(
            {"n": n, "cmd": "python bench.py", "rc": 0, "tail": "",
             "parsed": parsed}))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.benchguard", "BENCH_r06.json",
         "--history", "BENCH_r*.json", "--json"],
        cwd=tmp_path, env={**os.environ, "PYTHONPATH": REPO},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    verdict = json.loads(proc.stdout)
    assert verdict["status"] == "ok"
    assert verdict["history_comparable"] >= 3  # r02/r03 banked no parse


def _load_controller_scaling():
    import importlib.util as ilu

    spec = ilu.spec_from_file_location(
        "_controller_scaling_test",
        os.path.join(REPO, "benchmarks", "controller_scaling.py"))
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.slow
def test_controller_scaling_budget_64_simulated_ranks(capsys):
    """ROADMAP item-3 gate: negotiation p95 over a 64-rank simulated pod
    (threads against one real HTTP store) stays within the static
    budget, asserted through tools.benchguard's compare engine."""
    mod = _load_controller_scaling()
    rc = mod.budget_main(["--ranks", "64", "--rounds", "15", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0, out
    assert out["result"]["extras"]["flat"]["ranks"] == 64
    assert out["verdict"]["status"] == "ok"
    assert out["result"]["value"] <= 500.0


@pytest.mark.slow
def test_controller_scaling_gate_256_simulated_ranks(capsys):
    """The scale-out acceptance gate (docs/scaling.md): at 256 simulated
    ranks the hierarchical+binary leg must halve negotiation p95
    (hier_speedup >= 2) and cut wire bytes/rank/round >= 3x, with the
    flat leg inside its absolute p95 budget — all three asserted by
    tools.benchguard against benchmarks/controller_budgets.json."""
    mod = _load_controller_scaling()
    rc = mod.budget_main(["--ranks", "256", "--rounds", "30",
                          "--repeat", "2", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0, out
    assert out["verdict"]["status"] == "ok"
    extras = out["result"]["extras"]
    assert extras["hier"]["format"] == "v2"
    assert extras["flat"]["format"] == "v1"
    assert extras["hier_speedup"] >= 2.0, extras
    assert extras["bytes_reduction"] >= 3.0, extras


# ---------------------------------------------------------------------------
# two-process acceptance: rank 1's delayed negotiation submit shows up as
# rank 1's dominant negotiate phase in GET /perf, breaches the
# negotiate-p95 SLO budget, and the escalation warning names the budget
# ---------------------------------------------------------------------------

PERF_WORKER = textwrap.dedent("""
    import json, logging, os, sys, time, urllib.request
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    if int(os.environ.get("HOROVOD_RANK", "0")) == 1:
        # slow THIS rank's negotiation submits by 1 s for a window of
        # rounds. The lockstep negotiates every cycle (idle rounds
        # included, and idle rounds don't reach the ledger), so a
        # single-charge delay would burn on an init-time idle round —
        # 20 charges pace EVERY early round at >= 1 s, including the
        # working round that carries the tensor: rank 1's round time is
        # its own negotiate phase; rank 0 waits out the coordinator's
        # straggler verdict naming rank 1
        os.environ["HOROVOD_FAULT_SPEC"] = "controller.submit:delay=1#20"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common.exceptions import HorovodInternalError

    out_dir = sys.argv[1]
    slo_warnings = []

    class _Capture(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if "SLO budget" in msg:
                slo_warnings.append(msg)

    logging.getLogger("horovod_tpu").addHandler(_Capture())

    hvd.init()
    r = hvd.cross_rank()
    dispatch_failed = False
    # several working rounds, not one: the coordinator's straggler
    # verdict is decided while a round is in flight, and the very first
    # round can record before the verdict reaches rank 0 — later rounds
    # (still paced >= 1 s by the remaining fault charges) carry it
    # deterministically
    for _step in range(6):
        try:
            h = hvd.allreduce_async(np.ones(64, np.float32), op=hvd.Sum,
                                    name="e2e_perf")
            hvd.synchronize(h)
        except HorovodInternalError as e:
            if "Multiprocess computations" not in str(e):
                raise
            # this jax build cannot EXECUTE multi-process CPU
            # collectives; the negotiation (the phase under test)
            # already completed
            dispatch_failed = True

    from horovod_tpu.utils import metrics, perfledger
    led = perfledger.get_ledger()
    assert led is not None, "HOROVOD_PERFLEDGER should arm the ledger"
    assert perfledger.get_engine() is not None, \\
        "HOROVOD_SLO_SPEC should arm the engine"
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and len(led) == 0:
        time.sleep(0.1)
    assert len(led) >= 1, "no step recorded"
    # the dumper cadence (0.5 s here) evaluates budgets and pushes
    # perf/rank{k}; the ~1 s negotiation round breaches <=500 ms
    reg = metrics.get_registry()
    while time.monotonic() < deadline and \\
            reg.counter_value("hvd_slo_breach_total") < 1:
        time.sleep(0.1)
    breaches = reg.counter_value("hvd_slo_breach_total")
    assert breaches >= 1, "SLO breach never fired"

    merged = {}
    if r == 0:
        addr = os.environ["HOROVOD_GLOO_RENDEZVOUS_ADDR"]
        port = os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"]
        url = f"http://{addr}:{port}/perf"
        while time.monotonic() < deadline:
            merged = json.loads(
                urllib.request.urlopen(url, timeout=10).read())
            got = merged.get("ranks", {})
            if len(got) >= 2 and all(
                    v.get("steps", 0) >= 1 for v in got.values()):
                # hold out for a push carrying rank 0's straggler
                # verdict; the last merged view stands at the deadline
                if any(rec.get("straggler_rank") == 1
                       for rec in got.get("0", {}).get("recent", [])):
                    break
            time.sleep(0.2)
        open(os.path.join(out_dir, "perf.json"), "w").write(
            json.dumps(merged))
    open(os.path.join(out_dir, f"worker{r}.json"), "w").write(json.dumps(
        {"rank": r, "breaches": breaches, "slo_warnings": slo_warnings,
         "stats": led.stats(), "phases": led.phase_summary(),
         "dispatch_failed": dispatch_failed}))
    print("perf worker OK", r)
""")


@pytest.mark.chaos
def test_two_process_perf_merge_names_slow_rank(tmp_path, monkeypatch):
    """Acceptance: with the ledger + tracing + a negotiate-p95 budget on
    and rank 1's submits delayed 1 s, GET /perf shows rank 1's negotiate
    phase dominating its step decomposition,
    hvd_slo_breach_total{budget="negotiate_p95_ms"} increments on both
    ranks, and the stall-path warning names the budget."""
    script = tmp_path / "worker.py"
    script.write_text(PERF_WORKER)
    monkeypatch.setenv("HOROVOD_PERFLEDGER", "1")
    monkeypatch.setenv("HOROVOD_TRACE", "1")  # straggler attribution
    monkeypatch.setenv("HOROVOD_SLO_SPEC", "negotiate_p95_ms<=500")
    monkeypatch.setenv("HOROVOD_METRICS_DUMP_INTERVAL", "0.5")
    faults.reset()
    try:
        rc = run_commandline(["-np", "2", sys.executable, str(script),
                              str(tmp_path)])
    finally:
        faults.reset()
    assert rc == 0

    workers = {}
    for r in (0, 1):
        path = tmp_path / f"worker{r}.json"
        assert path.exists(), list(tmp_path.iterdir())
        workers[r] = json.loads(path.read_text())
    for r, w in workers.items():
        assert w["breaches"] >= 1, w
        assert any("negotiate_p95_ms" in msg for msg in w["slo_warnings"]), \
            (r, w["slo_warnings"])
        # a >= 1 s round against a 500 ms budget: p95 beyond bound
        assert w["stats"]["negotiate_p95_ms"] > 500.0, w["stats"]
    # the delayed rank's own lateness is its own negotiate phase
    shares1 = {p: w["share"]
               for p, w in workers[1]["phases"].items()}
    assert shares1["negotiate"] == max(shares1.values()), shares1
    assert shares1["negotiate"] > 0.5, shares1

    # GET /perf (scraped by rank 0 while the job ran) merged both ranks
    merged = json.loads((tmp_path / "perf.json").read_text())
    assert set(merged["ranks"]) == {"0", "1"}, merged
    r1 = merged["ranks"]["1"]
    assert r1["phases"]["negotiate"]["share"] > 0.5, r1["phases"]
    assert not r1["stale"]
    # rank 0's view of the same rounds: the coordinator attributed the
    # straggle to rank 1, so rank 0 records stall (or at minimum carries
    # the straggler verdict in its records)
    r0_recent = merged["ranks"]["0"].get("recent", [])
    assert any(rec.get("straggler_rank") == 1 for rec in r0_recent), \
        r0_recent
