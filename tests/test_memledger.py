"""Device-memory & compile ledger (horovod_tpu/utils/memledger.py,
ISSUE 12): HBM/live-bytes sampling with per-component attribution,
plan-compile accounting (time + serialized program size + persistent
cache verdicts) feeding the perf ledger's host-overhead phase and the
SLO engine, memory-pressure eviction of the compiled-plan cache, OOM
forensics in the diag bundle (classifier, suspect naming, merge
attribution), the auth-exempt ``GET /memory`` merge, and the 2-process
acceptance run where a simulated allocation failure yields a merged
``GET /debug`` attribution naming the dominant component.

The ledger is OFF for the session-scoped hvd.init() (conftest); tests
that need one arm a private ledger via the ``ledger`` fixture and drop
it on exit, so the zero-cost default holds for every other test file.
"""

import json
import os
import subprocess
import sys
import textwrap
import time
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu.common import env as env_schema
from horovod_tpu.ops import collectives as C
from horovod_tpu.runner.http_server import KVStoreClient, RendezvousServer
from horovod_tpu.runner.launch import run_commandline
from horovod_tpu.utils import diag, flightrec, memledger, metrics, perfledger

REG = metrics.get_registry()
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def ledger(monkeypatch):
    """Create (and on exit drop) a process memory ledger,
    HOROVOD_MEMLEDGER on."""

    def _make(rank=0, capacity=None):
        monkeypatch.setenv(env_schema.HOROVOD_MEMLEDGER, "1")
        if capacity is not None:
            monkeypatch.setenv(env_schema.HOROVOD_MEMLEDGER_BUFFER,
                               str(capacity))
        memledger.reset_ledger()
        return memledger.init_ledger(rank=rank)

    yield _make
    memledger.reset_ledger()


@pytest.fixture
def kv_server():
    srv = RendezvousServer(secret_key="mem-secret")
    port = srv.start()
    yield "127.0.0.1", port
    srv.stop()


# --- zero-cost contract ------------------------------------------------------

def test_memledger_disabled_by_default(monkeypatch):
    monkeypatch.delenv(env_schema.HOROVOD_MEMLEDGER, raising=False)
    monkeypatch.delenv(env_schema.HOROVOD_PLAN_CACHE_MAX_BYTES,
                       raising=False)
    memledger.reset_ledger()
    assert not memledger.enabled()
    assert memledger.init_ledger(rank=0) is None
    assert memledger.get_ledger() is None
    assert not memledger.accounting_armed()
    assert memledger.report() == {"enabled": False}
    assert hvd.memory_report() == {"enabled": False}
    # the cold hooks are is-None no-ops
    memledger.sample_event("interval")
    memledger.note_sharded_state({"x": np.zeros(4)})
    # off-state forensics still serve the top-buffers view (the OOM
    # excepthook must say *something* even on an unarmed process)
    assert memledger.forensics()["enabled"] is False


def test_memledger_off_registers_zero_series():
    """Acceptance: with HOROVOD_MEMLEDGER unset (and no plan-cache byte
    cap), no hvd_mem_* / hvd_compile_* series of ANY kind exists and
    plan builds skip the compile-timing wrapper. Checked in a pristine
    subprocess — the in-process registry accumulates series from tests
    that DO arm the ledger."""
    script = textwrap.dedent("""
        import os
        assert "HOROVOD_MEMLEDGER" not in os.environ
        assert "HOROVOD_PLAN_CACHE_MAX_BYTES" not in os.environ
        import jax.numpy as jnp
        from horovod_tpu.ops import collectives as C
        from horovod_tpu.utils import memledger, metrics
        assert not memledger.enabled()
        assert memledger.init_ledger(rank=0) is None
        assert not memledger.accounting_armed()
        # build + run an eager cached plan: must stay unwrapped
        x = jnp.arange(64, dtype=jnp.float32)
        C._cached_slice(x, 0, 32)
        snap = metrics.get_registry().snapshot()
        names = {m["name"]
                 for kind in ("counters", "gauges", "histograms")
                 for m in snap[kind]}
        bad = {n for n in names
               if n.startswith(("hvd_mem_", "hvd_compile_"))}
        assert not bad, bad
        assert C.plan_cache_bytes() == 0  # nothing accounted when off
        print("zero-series OK")
    """)
    env = dict(os.environ)
    env.pop("HOROVOD_MEMLEDGER", None)
    env.pop("HOROVOD_PLAN_CACHE_MAX_BYTES", None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "zero-series OK" in proc.stdout



# --- sampling + component attribution ----------------------------------------

def test_sample_ring_components_and_peak(ledger, monkeypatch):
    # hermetic: a live session runtime from an earlier test must not
    # overwrite the pushed components with its own staging-ring bytes
    monkeypatch.setattr(memledger.MemLedger, "_pull_components",
                        lambda self: {})
    led = ledger(rank=2, capacity=32)
    snap0 = led.sample(event="interval")
    assert snap0["event"] == "interval"
    assert snap0["source"] in ("memory_stats", "live_arrays")
    assert snap0["live_bytes"] >= 0
    led.set_component("ef_residuals", 4096)
    led.set_component("staging_ring", 128)
    snap1 = led.sample(event="plan_build")
    assert snap1["components"]["ef_residuals"] == 4096
    assert led.suspect_component() == "ef_residuals"
    assert led.snapshot()["peak_bytes"] >= snap1["live_bytes"]
    assert [s["event"] for s in led.samples()] == ["interval", "plan_build"]
    rep = led.report()
    assert rep["enabled"] and rep["samples"] == 2
    assert rep["suspect"] == "ef_residuals"
    # the component gauge follows the push
    g = next(g["value"] for g in REG.snapshot()["gauges"]
             if g["name"] == "hvd_mem_component_bytes"
             and g["labels"].get("component") == "ef_residuals")
    assert g == 4096


def test_sample_ring_is_bounded(ledger):
    led = ledger(rank=0, capacity=16)
    for _ in range(40):
        led.sample(event="interval")
    assert len(led.samples()) == 16


def test_note_sharded_state_attributes_bytes(ledger):
    led = ledger(rank=0)
    state = {"m": np.zeros(1024, np.float32), "v": np.zeros(1024,
                                                            np.float32)}
    memledger.note_sharded_state(state)
    assert led.components()["sharded_state"] == 8192
    assert led.samples()[-1]["event"] == "sharded_state_build"


# --- compile accounting ------------------------------------------------------

def test_compile_accounting_on_eager_plan(ledger, monkeypatch):
    """A plan-cache miss with the ledger armed AOT-compiles the program
    under a timer: compile time + serialized program bytes land in the
    ledger keyed by plan kind, the flight recorder gets a ``compile``
    event, and the dispatch result stays correct."""
    monkeypatch.setenv("HOROVOD_FLIGHTREC", "1")
    flightrec.reset_recorder()
    rec = flightrec.init_recorder(rank=0)
    led = ledger(rank=0)
    try:
        x = jnp.arange(977, dtype=jnp.float32)
        out = C._cached_slice(x, 3, 977)  # odd bounds: a fresh cache key
        np.testing.assert_array_equal(np.asarray(out),
                                      np.arange(3, 977, dtype=np.float32))
        cs = led.compile_stats()
        assert cs["compiles"] >= 1
        assert cs["compile_seconds_total"] > 0
        assert cs["by_kind"]["eager"]["program_bytes"] > 0
        assert C.plan_cache_bytes() > 0
        rows = C.plan_cache_table()
        assert any(r["kind"] == "eager" and r["program_bytes"] > 0
                   for r in rows)
        # replay: the wrapper dispatches straight to the compiled target
        out2 = C._cached_slice(x, 3, 977)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
        assert led.compile_stats()["compiles"] == cs["compiles"]
        # a compile-event breadcrumb for the postmortem trail
        evs = [e for e in rec.events() if e["cat"] == "compile"]
        assert evs and evs[-1]["kv"]["kind"] == "eager"
        # the plan-build event sampled memory (components pulled)
        assert any(s["event"] == "plan_build" for s in led.samples())
        assert led.components()["plan_cache"] > 0
    finally:
        flightrec.reset_recorder()


def test_compile_seconds_feed_perfledger_and_slo(ledger, monkeypatch):
    """Compile stalls surface as host overhead in the step decomposition
    and bind to HOROVOD_SLO_SPEC budgets: a recompile storm is a perf
    regression, not a mystery."""
    monkeypatch.setenv("HOROVOD_PERFLEDGER", "1")
    monkeypatch.setenv("HOROVOD_SLO_SPEC", "compile_seconds_p95<=0.1")
    perfledger.reset_ledger()
    pled = perfledger.init_ledger(rank=0)
    led = ledger(rank=0)
    try:
        led.record_compile("fused", 0.5, program_bytes=2048,
                           persistent="miss")
        rec = pled.record_step(1.0, dispatch_s=0.1, exec_s=0.9)
        # the 0.5 s compile is charged to host overhead, not device exec
        assert rec["compile_s"] == pytest.approx(0.5)
        assert rec["host_overhead_s"] >= 0.5
        st = pled.stats()
        assert st["compile_seconds_total"] == pytest.approx(0.5)
        assert st["compile_seconds_p95"] == pytest.approx(0.5)
        fired = perfledger.evaluate_slos()
        assert [f["budget"] for f in fired] == ["compile_seconds_p95"]
        # ledger-side rollup agrees
        cs = led.compile_stats()
        assert cs["persistent_cache"]["miss"] == 1
        assert cs["by_kind"]["fused"]["seconds"] == pytest.approx(0.5)
    finally:
        perfledger.reset_ledger()


# --- plan-cache memory-pressure eviction -------------------------------------

def test_plan_cache_memory_eviction(monkeypatch):
    """HOROVOD_PLAN_CACHE_MAX_BYTES bounds the compiled-plan cache by
    accounted program bytes: oldest plans evict with reason="memory"
    (never the newest — the plan just built must survive its own
    insertion), and the byte gauge tracks the survivors. Works without
    the memory ledger: the cap alone arms program-size accounting."""
    monkeypatch.delenv(env_schema.HOROVOD_MEMLEDGER, raising=False)
    memledger.reset_ledger()
    monkeypatch.setenv(env_schema.HOROVOD_PLAN_CACHE_MAX_BYTES, "800")
    C.clear_eager_cache()
    assert memledger.accounting_armed()
    evict0 = REG.counter_value("hvd_fused_plan_evictions_total")
    try:
        for i, n in enumerate((64, 128, 256, 512)):
            plan = C.sharded_pack_plan(None, 2, (n,), ((n,),), "float32",
                                       n // 2, f"mem_evict_{i}")
            plan(jnp.arange(n, dtype=jnp.float32))
        assert C.plan_cache_bytes() <= 800
        assert REG.counter_value("hvd_fused_plan_evictions_total") > evict0
        mem_evictions = next(
            c["value"] for c in REG.snapshot()["counters"]
            if c["name"] == "hvd_fused_plan_evictions_total"
            and c["labels"].get("reason") == "memory")
        assert mem_evictions >= 1
        gauge = next(g["value"] for g in REG.snapshot()["gauges"]
                     if g["name"] == "hvd_fused_plan_program_bytes")
        assert gauge == C.plan_cache_bytes()
        # the newest plan always survives its own insertion
        assert any(r["program_bytes"] > 0 for r in C.plan_cache_table())
    finally:
        C.clear_eager_cache()


def test_plan_cache_invalidation_forgets_bytes(ledger, monkeypatch):
    """Elastic invalidation must release the accounted bytes too — a
    leak here would trigger phantom memory evictions forever after."""
    ledger(rank=0)
    C.clear_eager_cache()
    try:
        x = jnp.arange(555, dtype=jnp.float32)
        C._cached_slice(x, 5, 555)
        assert C.plan_cache_bytes() > 0
        C.clear_eager_cache()
        assert C.plan_cache_bytes() == 0
        assert C.plan_cache_table() == []
    finally:
        C.clear_eager_cache()


# --- OOM forensics -----------------------------------------------------------

def test_alloc_failure_classifier():
    assert diag.is_alloc_failure(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating "
                     "2147483648 bytes"))
    assert diag.is_alloc_failure(Exception("XLA:TPU failed to allocate "
                                           "14.5G"))
    assert diag.is_alloc_failure(MemoryError())
    assert not diag.is_alloc_failure(ValueError("shape mismatch"))
    assert not diag.is_alloc_failure(RuntimeError("deadline exceeded"))


def test_bundle_carries_memory_and_plan_cache(ledger, monkeypatch,
                                              tmp_path):
    monkeypatch.setenv(env_schema.HOROVOD_DIAG_DIR, str(tmp_path))
    monkeypatch.setattr(memledger.MemLedger, "_pull_components",
                        lambda self: {})
    led = ledger(rank=0)
    led.set_component("ef_residuals", 1 << 20)
    led.sample(event="interval")
    bundle = diag.build_bundle("diagnose")
    mem = bundle["memory"]
    assert mem["enabled"] and mem["suspect"] == "ef_residuals"
    assert mem["recent_samples"]
    assert isinstance(bundle["plan_cache"], list)
    # allocation-shaped exception -> an "oom" bundle on disk
    path = diag.maybe_dump_alloc_failure(
        RuntimeError("RESOURCE_EXHAUSTED: out of HBM"))
    assert path and os.path.exists(path)
    assert json.load(open(path))["reason"] == "oom"
    # a non-alloc exception dumps nothing
    assert diag.maybe_dump_alloc_failure(ValueError("boom")) == ""


def test_merge_bundles_names_oom_suspect():
    oom = {"reason": "oom", "hostname": "a",
           "memory": {"suspect": "plan_cache", "peak_bytes": 999},
           "stall": {}}
    healthy = {"reason": "watchdog", "hostname": "b",
               "stall": {"age_s": 3.0}}
    merged = diag.merge_bundles({0: oom, 1: healthy})
    assert merged["suspects"] == [0]
    assert "allocation failure" in merged["attribution"]
    assert "plan_cache" in merged["attribution"]
    assert merged["ranks"]["0"]["memory_suspect"] == "plan_cache"
    assert merged["ranks"]["0"]["peak_bytes"] == 999
    # no oom bundle: the pre-existing stall-age attribution still wins
    merged2 = diag.merge_bundles({1: healthy})
    assert "allocation failure" not in merged2["attribution"]


# --- GET /memory merge + dumper cadence --------------------------------------

def test_metrics_dumper_samples_and_pushes_memory(ledger):
    class _FakeKV:
        def __init__(self):
            self.puts = []

        def put(self, scope, key, value):
            self.puts.append((scope, key, bytes(value)))

    led = ledger(rank=3)
    kv = _FakeKV()
    dumper = metrics.MetricsDumper(REG, interval_s=5.0, kv_client=kv,
                                   rank=3)
    dumper.flush()
    dumper.flush()
    # each flush takes one interval sample...
    assert [s["event"] for s in led.samples()] == ["interval", "interval"]
    # ...and pushes a clock-stamped snapshot under the mem/ scope
    pushed = [json.loads(v) for scope, _, v in kv.puts
              if scope == memledger.KV_SCOPE]
    assert [p["push_seq"] for p in pushed] == [1, 2]
    assert all(isinstance(p["push_ts"], float) for p in pushed)
    assert all(p["rank"] == 3 and p["samples"] >= 1 for p in pushed)


def test_memory_endpoint_merges_and_flags_stale(kv_server, ledger):
    addr, port = kv_server
    kv = KVStoreClient(addr, port, secret_key="mem-secret")
    now = time.time()
    led = ledger(rank=0)
    # sharded_state is push-only attribution: a live pull can't zero it
    # between the set and the snapshot (staging_ring/plan_cache would be
    # re-pulled from the session runtime by the sample below)
    led.set_component("sharded_state", 2048)
    led.sample(event="interval")
    fresh = led.snapshot()
    fresh.update(push_ts=now, push_interval_s=2.0)
    lagging = {"rank": 1, "samples": 4, "live_bytes": 11, "peak_bytes": 22,
               "components": {}, "recent": [], "compile": {},
               "push_ts": now - 600, "push_interval_s": 2.0}
    kv.put("mem", "rank0", json.dumps(fresh).encode())
    kv.put("mem", "rank1", json.dumps(lagging).encode())
    kv.put("mem", "rank-torn", b"{half a json")  # skipped, not fatal
    merged = json.loads(urllib.request.urlopen(
        f"http://{addr}:{port}/memory", timeout=10).read())
    assert set(merged["ranks"]) == {"0", "1"}
    assert merged["ranks"]["0"]["stale"] is False
    assert merged["ranks"]["1"]["stale"] is True  # annotated, not dropped
    assert merged["ranks"]["0"]["components"]["sharded_state"] == 2048
    assert merged["ranks"]["1"]["peak_bytes"] == 22
    assert all(isinstance(v["push_ts"], float)
               for v in merged["ranks"].values())


# ---------------------------------------------------------------------------
# two-process acceptance: both ranks' ledgers push clock-stamped snapshots
# that GET /memory merges; a simulated allocation failure on rank 1 lands
# an "oom" bundle whose GET /debug merge names the dominant component
# ---------------------------------------------------------------------------

MEM_WORKER = textwrap.dedent("""
    import json, os, sys, time, urllib.request
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common.exceptions import HorovodInternalError
    from horovod_tpu.ops import collectives as C
    from horovod_tpu.utils import diag, memledger

    out_dir = sys.argv[1]
    hvd.init()
    r = hvd.cross_rank()
    led = memledger.get_ledger()
    assert led is not None, "HOROVOD_MEMLEDGER should arm the ledger"

    # real compile activity: the eager cached slice is single-device, so
    # it works under multiprocess CPU where collectives cannot execute —
    # its program bytes give the plan_cache component a nonzero value
    x = jnp.arange(500 + r, dtype=jnp.float32)
    C._cached_slice(x, 1, 400 + r)
    assert C.plan_cache_bytes() > 0
    assert led.compile_stats()["compiles"] >= 1

    oom_path = ""
    if r == 1:
        try:
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory while trying to "
                "allocate 2147483648 bytes")
        except RuntimeError as e:
            oom_path = diag.maybe_dump_alloc_failure(e)
        assert oom_path, "alloc failure must dump an oom bundle"

    deadline = time.monotonic() + 30
    if r == 0:
        addr = os.environ["HOROVOD_GLOO_RENDEZVOUS_ADDR"]
        port = os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"]
        mem = {}
        while time.monotonic() < deadline:
            mem = json.loads(urllib.request.urlopen(
                f"http://{addr}:{port}/memory", timeout=10).read())
            got = mem.get("ranks", {})
            if len(got) >= 2 and all(
                    v.get("samples", 0) >= 1 and "push_ts" in v
                    for v in got.values()):
                break
            time.sleep(0.2)
        open(os.path.join(out_dir, "memory.json"), "w").write(
            json.dumps(mem))
        debug = {}
        while time.monotonic() < deadline:
            debug = json.loads(urllib.request.urlopen(
                f"http://{addr}:{port}/debug", timeout=10).read())
            if "allocation failure" in debug.get("attribution", ""):
                break
            time.sleep(0.2)
        open(os.path.join(out_dir, "debug.json"), "w").write(
            json.dumps(debug))
    open(os.path.join(out_dir, f"worker{r}.json"), "w").write(json.dumps(
        {"rank": r, "oom_path": oom_path, "report": led.report()}))
    print("mem worker OK", r)
""")


def test_two_process_memory_merge_and_oom_forensics(tmp_path, monkeypatch):
    """Acceptance: with the ledger on and the dumper on a 0.5 s cadence,
    GET /memory serves clock-stamped snapshots from both ranks, and a
    simulated RESOURCE_EXHAUSTED on rank 1 produces a diag bundle whose
    merged GET /debug attribution names the dominant component."""
    script = tmp_path / "worker.py"
    script.write_text(MEM_WORKER)
    monkeypatch.setenv(env_schema.HOROVOD_MEMLEDGER, "1")
    monkeypatch.setenv("HOROVOD_METRICS_DUMP_INTERVAL", "0.5")
    monkeypatch.setenv(env_schema.HOROVOD_DIAG_DIR, str(tmp_path))
    rc = run_commandline(["-np", "2", sys.executable, str(script),
                          str(tmp_path)])
    assert rc == 0

    workers = {}
    for r in (0, 1):
        path = tmp_path / f"worker{r}.json"
        assert path.exists(), list(tmp_path.iterdir())
        workers[r] = json.loads(path.read_text())
    for r, w in workers.items():
        rep = w["report"]
        assert rep["enabled"] and rep["samples"] >= 1, rep
        assert rep["compile"]["compiles"] >= 1, rep
        assert rep["components"]["plan_cache"] > 0, rep
    assert workers[1]["oom_path"]
    oom_bundle = json.loads(
        open(workers[1]["oom_path"]).read())
    assert oom_bundle["reason"] == "oom"
    assert oom_bundle["memory"]["suspect"] is not None

    # GET /memory merged clock-stamped snapshots from both ranks
    merged = json.loads((tmp_path / "memory.json").read_text())
    assert set(merged["ranks"]) == {"0", "1"}, merged
    for snap in merged["ranks"].values():
        assert snap["samples"] >= 1
        assert isinstance(snap["push_ts"], float)
        assert not snap["stale"]

    # GET /debug named the failing rank and its dominant component
    debug = json.loads((tmp_path / "debug.json").read_text())
    assert "allocation failure" in debug.get("attribution", ""), debug
    assert "dominant component" in debug["attribution"], debug
    assert debug["suspects"] == [1], debug
    assert debug["ranks"]["1"]["memory_suspect"] is not None, debug
