"""Postmortem diagnostics: the control-plane flight recorder
(horovod_tpu/utils/flightrec.py), the wedge watchdog + diagnostic
bundles + crash hooks (horovod_tpu/utils/diag.py), the rendezvous
server's auth-exempt ``GET /debug`` merge, and the 2-process acceptance
run where a fault-wedged negotiation fires the watchdog on BOTH ranks
and ``GET /debug`` names the injected rank.

The flight recorder is OFF for the session-scoped hvd.init() (conftest);
tests that need one arm a private recorder via the ``recorder`` fixture
and drop it on exit — the tests/test_tracing.py ``traced`` pattern — so
the zero-cost default holds for every other test file.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request

import pytest

import horovod_tpu as hvd
from horovod_tpu.common import context as ctx_mod
from horovod_tpu.common.env import RuntimeConfig
from horovod_tpu.ops.queue import BackgroundRuntime
from horovod_tpu.runner.http_server import KVStoreClient, RendezvousServer
from horovod_tpu.runner.launch import run_commandline
from horovod_tpu.utils import diag, faults, flightrec, metrics
from horovod_tpu.utils.retry import Retrier, RetryPolicy

REG = metrics.get_registry()


@pytest.fixture
def recorder(monkeypatch):
    """Create (and on exit drop) a process recorder, HOROVOD_FLIGHTREC on."""

    def _make(rank=0, capacity=None):
        monkeypatch.setenv("HOROVOD_FLIGHTREC", "1")
        if capacity is not None:
            monkeypatch.setenv("HOROVOD_FLIGHTREC_BUFFER", str(capacity))
        flightrec.reset_recorder()
        return flightrec.init_recorder(rank=rank)

    yield _make
    flightrec.reset_recorder()


@pytest.fixture
def kv_server():
    srv = RendezvousServer(secret_key="diag-secret")
    port = srv.start()
    yield "127.0.0.1", port
    srv.stop()


def _wait_until(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return pred()


# --- zero-cost contract ------------------------------------------------------

def test_flightrec_disabled_by_default(monkeypatch):
    monkeypatch.delenv("HOROVOD_FLIGHTREC", raising=False)
    flightrec.reset_recorder()
    assert not flightrec.enabled()
    assert flightrec.init_recorder(rank=0) is None
    assert flightrec.get_recorder() is None
    flightrec.note("init_phase", phase="never_recorded")  # must be a no-op
    # an un-armed runtime resolves no handles: one is-None field each
    cfg = RuntimeConfig()
    cfg.stall_check_disable = True
    rt = BackgroundRuntime(ctx_mod.global_process_set(), cfg)
    assert rt.recorder is None and rt.watchdog is None


def test_flightrec_off_registers_zero_series():
    """Acceptance: with HOROVOD_FLIGHTREC unset, no hvd_flightrec_* /
    hvd_watchdog_* series exists. Checked in a pristine subprocess — the
    in-process registry accumulates series from tests that DO arm the
    recorder."""
    script = textwrap.dedent("""
        import os
        assert "HOROVOD_FLIGHTREC" not in os.environ
        from horovod_tpu.utils import flightrec, metrics
        assert not flightrec.enabled()
        assert flightrec.init_recorder(rank=0) is None
        names = {c["name"]
                 for c in metrics.get_registry().snapshot()["counters"]}
        bad = {n for n in names
               if n.startswith(("hvd_flightrec", "hvd_watchdog"))}
        assert not bad, bad
        print("zero-series OK")
    """)
    env = dict(os.environ)
    env.pop("HOROVOD_FLIGHTREC", None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "zero-series OK" in proc.stdout



# --- the ring ----------------------------------------------------------------

def test_ring_capacity_and_drop_accounting():
    events0 = REG.counter_value("hvd_flightrec_events_total")
    dropped0 = REG.counter_value("hvd_flightrec_dropped_total")
    rec = flightrec.FlightRecorder(rank=5, capacity=16)
    for i in range(20):
        rec.note("init_phase", seq=i)
    assert len(rec) == 16
    evs = rec.events()
    # oldest evicted: the ring holds seq 4..19, oldest first
    assert [e["kv"]["seq"] for e in evs] == list(range(4, 20))
    for e in evs:
        assert e["cat"] == "init_phase" and e["rank"] == 5
        assert e["ts_mono"] > 0 and e["ts"] > 0
    assert [e["kv"]["seq"] for e in rec.events(last=3)] == [17, 18, 19]
    snap = rec.snapshot(last=2)
    assert snap["rank"] == 5 and len(snap["events"]) == 2
    assert REG.counter_value("hvd_flightrec_events_total") == events0 + 20
    assert REG.counter_value("hvd_flightrec_dropped_total") == dropped0 + 4


def test_init_recorder_idempotent_and_module_note(recorder):
    rec = recorder(rank=2, capacity=64)
    assert rec is not None and rec.capacity == 64 and rec.rank == 2
    assert flightrec.init_recorder(rank=9) is rec  # reused, rank kept
    flightrec.note("watchdog", ok=True)
    evs = rec.events()
    assert evs and evs[-1]["cat"] == "watchdog"
    assert evs[-1]["rank"] == 2 and evs[-1]["kv"] == {"ok": True}


def test_retry_backoff_records_event(recorder):
    rec = recorder()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("first attempt torn")
        return 42

    r = Retrier("kv.get", RetryPolicy(max_attempts=2, base_delay_s=0.0,
                                      max_delay_s=0.0),
                sleep=lambda s: None)
    assert r.call(flaky) == 42
    evs = [e for e in rec.events() if e["cat"] == "retry_attempt"]
    assert len(evs) == 1
    assert evs[0]["kv"]["site"] == "kv.get" and evs[0]["kv"]["attempt"] == 1


@pytest.mark.chaos
def test_fault_injection_records_event(recorder, monkeypatch):
    rec = recorder()
    monkeypatch.setenv("HOROVOD_FAULT_SPEC", "kv.get:delay=1ms#1")
    faults.reset()
    try:
        faults.fault_point("kv.get")
    finally:
        monkeypatch.delenv("HOROVOD_FAULT_SPEC", raising=False)
        faults.reset()
    evs = [e for e in rec.events() if e["cat"] == "fault_injected"]
    assert evs and evs[0]["kv"] == {"site": "kv.get", "mode": "delay"}


# --- diagnostic bundles ------------------------------------------------------

def test_build_bundle_contents(recorder):
    rec = recorder(rank=0)
    rec.note("init_phase", phase="config")
    diag.register_probe("test.good", lambda: {"answer": 42})
    diag.register_probe("test.broken",
                        lambda: (_ for _ in ()).throw(ValueError("nope")))
    try:
        bundle = diag.build_bundle("diagnose")
    finally:
        diag.unregister_probe("test.good")
        diag.unregister_probe("test.broken")
    assert bundle["reason"] == "diagnose" and bundle["pid"] == os.getpid()
    # this very function appears in some thread's stack
    assert any("test_build_bundle_contents" in t["stack"]
               for t in bundle["threads"])
    assert bundle["lockcheck"]["enabled"]
    assert any(c["name"].startswith("hvd_")
               for c in bundle["metrics"]["counters"])
    assert any(e["cat"] == "init_phase" for e in bundle["flight_events"])
    assert bundle["probes"]["test.good"] == {"answer": 42}
    assert "ValueError" in bundle["probes"]["test.broken"]["error"]
    # the session runtime registered its cycle-state probe at start()
    assert "runtime" in bundle["probes"]
    # bundles must be JSON round-trippable as written
    assert json.loads(json.dumps(bundle, default=repr))["reason"] \
        == "diagnose"


def test_hvd_diagnose_smoke():
    bundle = hvd.diagnose()
    assert bundle["reason"] == "diagnose"
    assert bundle["threads"] and "metrics" in bundle and "probes" in bundle


class _FakeKV:
    def __init__(self, fail=False):
        self.calls = []
        self.fail = fail

    def put(self, scope, key, value):
        if self.fail:
            raise ConnectionError("injected push failure")
        self.calls.append((scope, key, bytes(value)))


def test_dump_bundle_writes_file_and_pushes(tmp_path, monkeypatch, recorder):
    recorder(rank=0)
    monkeypatch.setenv("HOROVOD_DIAG_DIR", str(tmp_path))
    monkeypatch.setenv("HOROVOD_RANK", "4")
    kv = _FakeKV()
    diag.set_kv_client(kv)
    try:
        path = diag.dump_bundle("diagnose")
    finally:
        diag.set_kv_client(None)
    assert path == str(tmp_path / "hvd_diag.rank4.diagnose.json")
    bundle = json.loads(open(path).read())
    assert bundle["reason"] == "diagnose" and bundle["rank"] == 4
    assert kv.calls and kv.calls[0][:2] == ("diag", "rank4")
    assert json.loads(kv.calls[0][2]) == bundle


def test_dump_bundle_never_raises(tmp_path, monkeypatch):
    """Diagnostics taking down the job they diagnose is the unforgivable
    failure mode: a failing KV push and push=False must both still leave
    the file."""
    monkeypatch.setenv("HOROVOD_DIAG_DIR", str(tmp_path))
    diag.set_kv_client(_FakeKV(fail=True))
    try:
        path = diag.dump_bundle("crash")
    finally:
        diag.set_kv_client(None)
    assert os.path.exists(path)
    quiet = _FakeKV()
    diag.set_kv_client(quiet)
    try:
        diag.dump_bundle("exit", push=False)
    finally:
        diag.set_kv_client(None)
    assert quiet.calls == []


# --- wedge watchdog ----------------------------------------------------------

def test_watchdog_fires_once_per_wedge_and_rearms():
    fired0 = REG.counter_value("hvd_watchdog_fired_total")
    dumps = []
    wd = diag.Watchdog(0.12, dump=lambda reason, stall=None:
                       dumps.append((reason, stall)) or "")
    wd.start()
    try:
        assert _wait_until(lambda: wd.fired_count == 1)
        time.sleep(0.4)  # still wedged: the latch holds, no second dump
        assert wd.fired_count == 1 and len(dumps) == 1
        reason, stall = dumps[0]
        assert reason == "watchdog"
        assert stall["phase"] == "" and stall["age_s"] >= 0.12

        wd.beat()  # progress resumed: the next wedge fires again
        assert _wait_until(lambda: wd.fired_count == 2)

        wd.enter("negotiate")  # a phased wedge is attributed to its phase
        assert _wait_until(lambda: wd.fired_count == 3)
        assert dumps[-1][1]["phase"] == "negotiate"
        wd.exit_phase("negotiate")
        st = wd.state()
        assert st["phase"] == "" and st["fired_count"] == 3
        assert st["threshold_s"] == pytest.approx(0.12)
    finally:
        wd.stop()
    assert REG.counter_value("hvd_watchdog_fired_total") == fired0 + 3


def test_init_watchdog_gated_by_threshold():
    assert diag.get_watchdog() is None  # session runs with the knob off
    assert diag.init_watchdog(0.0) is None
    try:
        wd = diag.init_watchdog(30.0)
        assert wd is not None and wd.is_alive()
        assert diag.init_watchdog(30.0) is wd  # idempotent
        # threshold <= 0 leaves an armed watchdog untouched (shutdown
        # passes the config value straight through)
        assert diag.init_watchdog(0.0) is wd
    finally:
        diag.reset_watchdog()
    assert diag.get_watchdog() is None


# --- cross-rank merge + GET /debug -------------------------------------------

def _bundle(rank, reason="watchdog", stall=None, coord=None):
    b = {"reason": reason, "rank": rank, "hostname": f"h{rank}",
         "time_unix": 1.0, "threads": [{"name": "MainThread", "stack": ""}],
         "flight_events": [], "probes": {}}
    if stall is not None:
        b["stall"] = stall
    if coord is not None:
        b["probes"]["coordinator"] = coord
    return b


def test_merge_bundles_coordinator_gather_wins():
    """missing_ranks from a coordinator probe out-rank stall ages: the
    ranks the coordinator was still waiting on ARE the wedge."""
    merged = diag.merge_bundles({
        0: _bundle(0, stall={"phase": "negotiate", "age_s": 3.0},
                   coord={"round": 7, "missing_ranks": [1],
                          "elapsed_s": 2.5}),
        1: _bundle(1, stall={"phase": "negotiate", "age_s": 99.0}),
    })
    assert merged["suspects"] == [1]
    assert "coordinator gather" in merged["attribution"]
    assert merged["ranks"]["0"]["coordinator"]["round"] == 7


def test_merge_bundles_stall_age_fallback_and_empty():
    merged = diag.merge_bundles({
        0: _bundle(0, stall={"phase": "", "age_s": 1.0}),
        1: _bundle(1, stall={"phase": "negotiate", "age_s": 7.5}),
        2: "not a bundle",  # torn push: skipped, not fatal
    })
    assert merged["suspects"] == [1]
    assert merged["attribution"] == "largest watchdog stall age"
    assert set(merged["ranks"]) == {"0", "1"}
    healthy = diag.merge_bundles({0: _bundle(0, reason="diagnose")})
    assert healthy["suspects"] == [] and healthy["attribution"] == "none"


def test_debug_endpoint_merges_pushed_bundles(kv_server):
    """GET /debug is auth-exempt (a wedged job can't sign anything) and
    merges the diag/ KV scope into the attribution view."""
    addr, port = kv_server
    kv = KVStoreClient(addr, port, secret_key="diag-secret")
    kv.put("diag", "rank0", json.dumps(
        _bundle(0, coord={"round": 3, "missing_ranks": [1],
                          "elapsed_s": 4.0})).encode())
    kv.put("diag", "rank1", json.dumps(
        _bundle(1, stall={"phase": "negotiate", "age_s": 12.0})).encode())
    kv.put("diag", "rank-torn", b"{half a json")  # skipped, not fatal
    merged = json.loads(urllib.request.urlopen(
        f"http://{addr}:{port}/debug", timeout=10).read())
    assert merged["suspects"] == [1]
    assert "coordinator gather" in merged["attribution"]
    assert set(merged["ranks"]) == {"0", "1"}
    assert merged["ranks"]["1"]["stall"]["age_s"] == 12.0


# --- signal / crash hooks (subprocess: hooks are process-global) -------------

def test_sigusr1_dumps_and_continues(tmp_path):
    script = textwrap.dedent("""
        import os, signal, time
        from horovod_tpu.utils import diag, flightrec
        flightrec.init_recorder(rank=7)
        flightrec.note("init_phase", phase="config")
        diag.install_crash_hooks()
        os.kill(os.getpid(), signal.SIGUSR1)
        time.sleep(0.2)
        print("alive after sigusr1")
    """)
    env = dict(os.environ)
    env.update({"HOROVOD_DIAG_DIR": str(tmp_path), "HOROVOD_RANK": "7",
                "HOROVOD_FLIGHTREC": "1", "JAX_PLATFORMS": "cpu"})
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "alive after sigusr1" in proc.stdout  # observed, not killed
    bundle = json.loads(
        (tmp_path / "hvd_diag.rank7.sigusr1.json").read_text())
    assert bundle["reason"] == "sigusr1" and bundle["rank"] == 7
    assert bundle["threads"]
    assert any(e["cat"] == "init_phase" for e in bundle["flight_events"])


def test_uncaught_exception_dumps_crash_bundle(tmp_path):
    script = textwrap.dedent("""
        from horovod_tpu.utils import diag
        diag.install_crash_hooks()
        raise RuntimeError("boom for the excepthook")
    """)
    env = dict(os.environ)
    env.update({"HOROVOD_DIAG_DIR": str(tmp_path), "JAX_PLATFORMS": "cpu"})
    env.pop("HOROVOD_RANK", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "boom for the excepthook" in proc.stderr  # prev hook chained
    bundle = json.loads(
        (tmp_path / "hvd_diag.rank0.crash.json").read_text())
    assert bundle["reason"] == "crash" and bundle["threads"]


# ---------------------------------------------------------------------------
# two-process acceptance: a fault-wedged negotiation fires the watchdog
# on BOTH ranks and GET /debug names the injected rank
# ---------------------------------------------------------------------------

WEDGE_WORKER = textwrap.dedent("""
    import json, os, sys, time, urllib.request
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    if int(os.environ.get("HOROVOD_RANK", "0")) == 1:
        # wedge THIS rank's first negotiation submit for 6 s: rank 1
        # sleeps inside the fault, rank 0's coordinator gathers with
        # missing={1} — both sides stop beating past the 2 s threshold
        os.environ["HOROVOD_FAULT_SPEC"] = "controller.submit:delay=6#1"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common.exceptions import HorovodInternalError

    out_dir = sys.argv[1]
    hvd.init()
    r = hvd.cross_rank()
    dispatch_failed = False
    try:
        h = hvd.allreduce_async(np.ones(64, np.float32), op=hvd.Sum,
                                name="e2e_wedge")
        hvd.synchronize(h)
    except HorovodInternalError as e:
        if "Multiprocess computations" not in str(e):
            raise
        # this jax build cannot EXECUTE multi-process CPU collectives;
        # the negotiation (and therefore the wedge + watchdog fire)
        # already completed, which is all this test needs
        dispatch_failed = True

    from horovod_tpu.utils import diag, flightrec
    wd = diag.get_watchdog()
    assert wd is not None, "HOROVOD_WATCHDOG_SECS should arm the watchdog"
    deadline = time.monotonic() + 15
    while wd.fired_count == 0 and time.monotonic() < deadline:
        time.sleep(0.1)
    assert wd.fired_count >= 1, wd.state()
    rec = flightrec.get_recorder()
    assert rec is not None, "HOROVOD_FLIGHTREC should arm the recorder"
    cats = {e["cat"] for e in rec.events()}
    assert "init_phase" in cats and "negotiation_round" in cats, cats
    if r == 1:
        assert "fault_injected" in cats, cats

    if r == 0:
        addr = os.environ["HOROVOD_GLOO_RENDEZVOUS_ADDR"]
        port = os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"]
        url = f"http://{addr}:{port}/debug"
        deadline = time.monotonic() + 30
        merged = {}
        while time.monotonic() < deadline:
            merged = json.loads(
                urllib.request.urlopen(url, timeout=10).read())
            if len(merged.get("ranks", {})) >= 2 and merged.get("suspects"):
                break
            time.sleep(0.2)
        open(os.path.join(out_dir, "debug.json"), "w").write(
            json.dumps(merged))
    print("wedge worker OK", r, "dispatch_failed", dispatch_failed)
""")


@pytest.mark.chaos
def test_two_process_wedge_watchdog_names_suspect_rank(tmp_path,
                                                       monkeypatch):
    """Acceptance: rank 1's negotiation submit is delayed past the
    watchdog threshold; both ranks dump watchdog bundles (thread stacks
    showing the stuck negotiate frame) and the launcher's GET /debug
    attributes the wedge to rank 1."""
    script = tmp_path / "worker.py"
    script.write_text(WEDGE_WORKER)
    monkeypatch.setenv("HOROVOD_FLIGHTREC", "1")
    monkeypatch.setenv("HOROVOD_WATCHDOG_SECS", "2")
    monkeypatch.setenv("HOROVOD_DIAG_DIR", str(tmp_path))
    faults.reset()
    try:
        rc = run_commandline(["-np", "2", sys.executable, str(script),
                              str(tmp_path)])
    finally:
        faults.reset()
    assert rc == 0

    # BOTH ranks left watchdog bundles as files
    bundles = {}
    for r in (0, 1):
        path = tmp_path / f"hvd_diag.rank{r}.watchdog.json"
        assert path.exists(), list(tmp_path.iterdir())
        bundles[r] = json.loads(path.read_text())
    for r, b in bundles.items():
        assert b["reason"] == "watchdog" and b["rank"] == r
        assert b["stall"]["phase"] == "negotiate"
        assert b["stall"]["age_s"] >= 2.0
        cats = {e["cat"] for e in b["flight_events"]}
        assert "negotiation_round" in cats and "watchdog" in cats
    # the wedged rank's stacks show the stuck negotiate frame
    assert any("_negotiate" in t["stack"] for t in bundles[1]["threads"]), \
        [t["name"] for t in bundles[1]["threads"]]
    # rank 0's coordinator probe recorded who it was waiting on
    coord = bundles[0]["probes"].get("coordinator") or {}
    assert coord.get("missing_ranks") == [1], bundles[0]["probes"]

    # GET /debug (scraped by rank 0 while the job ran) named rank 1
    merged = json.loads((tmp_path / "debug.json").read_text())
    assert merged["suspects"] == [1], merged
    assert "coordinator gather" in merged["attribution"]
    assert set(merged["ranks"]) == {"0", "1"}
