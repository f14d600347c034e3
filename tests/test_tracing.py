"""Cross-rank distributed tracing (horovod_tpu/utils/tracing.py):
collective lifecycle spans through the eager runtime, the negotiation
wire's zero-cost contract, clock-offset estimation against GET /clock,
the merged Chrome-trace GET /timeline, coordinator-side straggler
attribution, and the stall inspector's suspect-rank warnings.

Tracing is OFF for the session-scoped hvd.init() (conftest); every test
that needs a tracer creates a private one via the ``traced`` fixture and
drives a private, non-started BackgroundRuntime inline — the
benchmarks/cycle_overhead.py pattern — so the global runtime stays
untraced for every other test file.
"""

import json
import sys
import textwrap
import threading
import time
import urllib.request

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.common import context as ctx_mod
from horovod_tpu.common.env import RuntimeConfig
from horovod_tpu.common.exceptions import DuplicateNameError
from horovod_tpu.ops.controller import KVController
from horovod_tpu.ops.queue import BackgroundRuntime, TensorEntry
from horovod_tpu.runner.http_server import KVStoreClient, RendezvousServer
from horovod_tpu.runner.launch import run_commandline
from horovod_tpu.utils import faults, metrics, tracing
from horovod_tpu.utils.stall import StallInspector

REG = metrics.get_registry()


@pytest.fixture
def traced(monkeypatch):
    """Create (and on exit drop) a process tracer with HOROVOD_TRACE on."""

    def _make(rank=0, offset=None, addr=None, port=None):
        monkeypatch.setenv("HOROVOD_TRACE", "1")
        if offset is not None:
            monkeypatch.setenv("HOROVOD_TRACE_CLOCK_OFFSET", str(offset))
        return tracing.init_tracer(rank=rank, addr=addr, port=port)

    yield _make
    tracing.reset_tracer()


@pytest.fixture
def kv_server():
    srv = RendezvousServer()
    port = srv.start()
    yield "127.0.0.1", port
    srv.stop()


def _runtime():
    """Private, non-started BackgroundRuntime driven via run_cycle()."""
    cfg = RuntimeConfig()
    cfg.stall_check_disable = True
    return BackgroundRuntime(ctx_mod.global_process_set(), cfg)


def _entry(name, n=64):
    return TensorEntry(name=name, op="allreduce",
                       tensor=np.ones(n, np.float32))


# --- zero-cost contract ------------------------------------------------------

def test_tracing_disabled_by_default(monkeypatch):
    monkeypatch.delenv("HOROVOD_TRACE", raising=False)
    assert not tracing.enabled()
    assert tracing.init_tracer(rank=0) is tracing.get_tracer()
    assert hvd.trace_report() == {"enabled": False}
    # the untraced runtime allocates no Span: entries stay span-less
    rt = _runtime()
    assert rt.tracer is None
    h = rt.enqueue(_entry("trace.off.0"))
    rt.run_cycle()
    rt.handles.wait(h)


def test_negotiation_wire_identical_when_off_and_stamped_when_on(
        kv_server, traced, monkeypatch):
    """The SAME_AS_LAST 1-byte fast path survives tracing: untraced
    rounds are byte-identical to the pre-tracing wire; traced rounds
    append a timestamp the coordinator strips before caching."""
    addr, port = kv_server
    sig = {"w0": ["allreduce", "float32", [4], 0, 0, 1.0, 1.0,
                  "global", "host"]}

    def submissions(ctl_client, rounds):
        sent = []
        orig_put = ctl_client.put

        def put(scope, key, value):
            if key.startswith("ready/"):
                sent.append(bytes(value))
            return orig_put(scope, key, value)

        ctl_client.put = put
        ctl = KVController(ctl_client, rank=0, size=1, poll_timeout=30.0)
        try:
            for _ in range(rounds):
                assert ctl.negotiate(dict(sig))["ready"] == ["w0"]
        finally:
            ctl.stop()
        return sent

    monkeypatch.setenv("HOROVOD_ELASTIC_GEN", "951")
    off = submissions(KVStoreClient(addr, port), 3)
    assert off[0] != KVController.SAME_AS_LAST  # first round: full payload
    assert b'"t"' not in off[0]
    assert off[1] == off[2] == KVController.SAME_AS_LAST  # 1 byte exactly

    monkeypatch.setenv("HOROVOD_ELASTIC_GEN", "952")
    traced(rank=0)
    on = submissions(KVStoreClient(addr, port), 3)
    assert json.loads(on[0])["t"] > 0  # full payload carries the stamp
    for wire in on[1:]:
        assert wire[:1] == KVController.SAME_AS_LAST and len(wire) > 1
        assert json.loads(wire[1:])["t"] > 0



# --- span lifecycle ----------------------------------------------------------

def test_single_process_span_lifecycle(traced):
    tracer = traced(rank=0)
    rt = _runtime()
    assert rt.tracer is tracer
    handles = [rt.enqueue(_entry(f"trace.life.{i}")) for i in range(3)]
    rt.run_cycle()
    for h in handles:
        rt.handles.wait(h)
    assert tracer.open_spans() == 0
    recs = tracer.records()
    assert len(recs) == 3
    T = tracing
    for r in recs:
        assert r["n"].startswith("trace.life.")
        assert r["o"] == "allreduce" and not r["e"]
        t = r["t"]
        # single process: no negotiation phase, everything else stamped
        assert t[T.T_NEG_START] is None and t[T.T_NEG_END] is None
        assert r["r"] == -1
        assert (t[T.T_SUBMIT] <= t[T.T_DRAIN]
                <= t[T.T_DISPATCH_START] <= t[T.T_DISPATCH_END]
                <= t[T.T_DONE])
        # the three tensors fused into one chunk
        assert r["ct"] == 3 and r["cb"] == 3 * 64 * 4
    rep = hvd.trace_report()
    assert rep["enabled"] and rep["spans"] == 3 and rep["open_spans"] == 0
    for lane in ("queue", "dispatch", "total"):
        assert rep["phases"][lane]["count"] == 3
        assert rep["phases"][lane]["p95_ms"] >= rep["phases"][lane]["p50_ms"] >= 0


def test_enqueue_rejection_and_shutdown_finalize_spans(traced):
    """The no-leak invariant on the paths that never reach _finish:
    duplicate-name rejection and runtime teardown with queued work."""
    tracer = traced(rank=0)
    rt = _runtime()
    h = rt.enqueue(_entry("trace.dup"))
    with pytest.raises(DuplicateNameError):
        rt.enqueue(_entry("trace.dup"))
    assert tracer.open_spans() == 1  # the rejected span closed, first open
    rt.run_cycle()
    rt.handles.wait(h)
    assert tracer.open_spans() == 0
    recs = tracer.records()
    errs = [r for r in recs if r["n"] == "trace.dup" and r["e"]]
    assert len(errs) == 1  # the rejection, finalized with error=True

    rt2 = _runtime()
    rt2.enqueue(_entry("trace.stopped"))
    rt2.stop()  # never cycled: stop() must close the span
    assert tracer.open_spans() == 0
    assert any(r["n"] == "trace.stopped" and r["e"] for r in tracer.records())


# --- clock alignment ---------------------------------------------------------

def test_clock_offset_estimation_and_override(kv_server, traced,
                                              monkeypatch):
    addr, port = kv_server
    offset, uncertainty = tracing.estimate_clock_offset(addr, port)
    # same host, same clock: offset within the round trip, tight bound
    assert abs(offset) < 0.5 and 0.0 <= uncertainty < 0.5

    tracer = traced(rank=1, offset=3.25)
    assert tracer.clock_offset_s == 3.25 and tracer.clock_uncertainty_s == 0.0
    assert tracer.aligned_now() == pytest.approx(time.time() + 3.25, abs=0.2)

    monkeypatch.delenv("HOROVOD_TRACE_CLOCK_OFFSET", raising=False)
    tracer = traced(rank=1, addr=addr, port=port)  # estimated path
    assert abs(tracer.clock_offset_s) < 0.5
    assert tracer.clock_uncertainty_s is not None


def test_merge_chrome_trace_applies_offsets():
    span = {"n": "grad/w", "o": "allreduce", "r": 3,
            "t": [10.0, 10.1, 10.2, 10.3, 10.4, 10.5, 10.6],
            "cb": 128, "ct": 2, "sr": 1, "sw": 0.25, "e": 0}
    merged = tracing.merge_chrome_trace([
        {"rank": 0, "clock_offset_s": 0.0, "clock_uncertainty_s": 0.001,
         "spans": [span]},
        {"rank": 1, "clock_offset_s": 2.5, "clock_uncertainty_s": 0.002,
         "spans": [dict(span)]},
        {"bogus": True},  # half-written push: skipped, not fatal
    ])
    ev = merged["traceEvents"]
    ops = {e["pid"]: e for e in ev
           if e.get("ph") == "X" and e["tid"] == tracing.OP_LANE_TID}
    assert set(ops) == {0, 1}
    assert ops[0]["name"] == ops[1]["name"] == "grad/w"
    assert ops[0]["ts"] == pytest.approx(10.0 * 1e6)
    assert ops[1]["ts"] == pytest.approx((10.0 + 2.5) * 1e6)  # aligned
    assert ops[1]["dur"] == pytest.approx(0.6 * 1e6)  # offset cancels
    assert ops[1]["args"]["straggler_rank"] == 1
    lanes = {e["args"]["name"] for e in ev if e.get("ph") == "M"
             and e["name"] == "thread_name" and e["pid"] == 0}
    assert lanes == {"op", "queue", "negotiate", "fuse", "dispatch"}
    hv = merged["horovod"]
    assert hv["ranks"]["1"]["clock_offset_s"] == 2.5
    assert hv["stragglers"]["last_rank_counts"] == {"1": 2}
    assert hv["stragglers"]["total_wait_s"] == pytest.approx(0.5)


def test_timeline_endpoint_merges_pushed_and_local(kv_server, traced):
    addr, port = kv_server
    tracer = traced(rank=0)
    s = tracer.begin("t.local", "allreduce")
    tracer.finish(s)
    c = KVStoreClient(addr, port)
    c.put("trace", "rank1", json.dumps(
        {"rank": 1, "clock_offset_s": 0.5, "spans": [
            {"n": "t.pushed", "o": "allreduce", "r": 0,
             "t": [1.0, None, None, None, None, None, 1.1],
             "cb": 0, "ct": 0, "sr": -1, "sw": 0.0, "e": 0}]}).encode())
    # a stale push for the server's OWN rank is superseded by its tracer
    c.put("trace", "rank0", json.dumps(
        {"rank": 0, "clock_offset_s": 0.0, "spans": []}).encode())
    c.put("trace", "rank-torn", b"{half a json")  # skipped, not fatal
    merged = json.loads(urllib.request.urlopen(
        f"http://{addr}:{port}/timeline", timeout=10).read())
    assert set(merged["horovod"]["ranks"]) == {"0", "1"}
    assert merged["horovod"]["ranks"]["0"]["spans"] == 1  # local, not stale
    names = {e["name"] for e in merged["traceEvents"] if e.get("ph") == "X"}
    assert {"t.local", "t.pushed"} <= names


# --- straggler attribution ---------------------------------------------------

def test_stall_warning_names_straggler(caplog):
    insp = StallInspector(warning_time_s=0.01)
    insp.note_straggler("grad/s", 3, 1.234)
    insp.record_pending("grad/s")
    time.sleep(0.05)
    with caplog.at_level("WARNING", logger="horovod_tpu"):
        insp.check()
    msgs = [r.getMessage() for r in caplog.records]
    assert any("Straggler attribution: rank 3" in m and "1.234" in m
               for m in msgs), msgs
    # stale attribution is history, not a lead: kept out of the warning
    insp2 = StallInspector(warning_time_s=0.01)
    insp2._last_straggler = (1, "grad/s", 0.5,
                             time.monotonic() - 10_000)
    assert insp2._suspect() == ""


@pytest.mark.chaos
def test_chaos_negotiation_attributes_delayed_rank(kv_server, traced,
                                                   monkeypatch):
    """Chaos at KV/controller sites must not break attribution: two
    in-process controllers negotiate through injected drop+delay faults;
    the artificially delayed rank 1 is named, with the right metrics."""
    addr, port = kv_server
    monkeypatch.setenv("HOROVOD_ELASTIC_GEN", "953")
    monkeypatch.setenv("HOROVOD_FAULT_SPEC",
                       "kv.wait:drop#1,controller.poll:delay=50ms#1")
    faults.reset()
    traced(rank=0)
    sig = {"c0": ["allreduce", "float32", [4], 0, 0, 1.0, 1.0,
                  "global", "host"]}
    ctl0 = KVController(KVStoreClient(addr, port), rank=0, size=2,
                        poll_timeout=60.0)
    ctl1 = KVController(KVStoreClient(addr, port), rank=1, size=2,
                        poll_timeout=60.0)
    out = {}

    def late_rank():
        time.sleep(0.4)  # the straggler under test
        out["r1"] = ctl1.negotiate(dict(sig))

    t = threading.Thread(target=late_rank)
    t.start()
    try:
        resp = ctl0.negotiate(dict(sig))
        t.join(timeout=60)
        assert not t.is_alive()
        assert resp["ready"] == ["c0"]
        assert out["r1"]["ready"] == ["c0"]
        # both ranks receive the same attribution in the round response
        for r in (resp, out["r1"]):
            last, wait = r["strag"]["c0"]
            assert last == 1
            assert 0.2 < wait < 30.0
        strag_counter = next(
            c for c in REG.snapshot()["counters"]
            if c["name"] == "hvd_straggler_last_rank_total"
            and c["labels"].get("rank") == "1")
        assert strag_counter["value"] >= 1
        hist = next(h for h in REG.snapshot()["histograms"]
                    if h["name"] == "hvd_straggler_wait_seconds")
        assert hist["count"] >= 1
    finally:
        monkeypatch.delenv("HOROVOD_FAULT_SPEC", raising=False)
        faults.reset()
        ctl0.stop()
        ctl1.stop()


# ---------------------------------------------------------------------------
# two-process end-to-end: spans on both ranks -> merged /timeline scrape
# ---------------------------------------------------------------------------

TRACE_WORKER = textwrap.dedent("""
    import json, os, sys, time, urllib.request
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    if int(os.environ.get("HOROVOD_RANK", "0")) == 1:
        # a large fake offset: the merge must shift this rank's events by
        # exactly this much (asserted against the raw span dump below)
        os.environ["HOROVOD_TRACE_CLOCK_OFFSET"] = "2.5"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import context as ctx_mod
    from horovod_tpu.common.exceptions import HorovodInternalError

    out_dir = sys.argv[1]
    hvd.init()
    r = hvd.cross_rank()
    if r == 1:
        time.sleep(0.8)  # the straggler under test
    dispatch_failed = False
    try:
        h = hvd.allreduce_async(np.ones(256, np.float32), op=hvd.Sum,
                                name="e2e_trace")
        assert np.allclose(np.asarray(hvd.synchronize(h)), 2.0)
    except HorovodInternalError as e:
        if "Multiprocess computations" not in str(e):
            raise
        # this jax build cannot EXECUTE multi-process CPU collectives;
        # negotiation + the span lifecycle still completed (the span is
        # finalized with error=True), so the trace assertions stand
        dispatch_failed = True

    from horovod_tpu.utils import tracing
    tracer = tracing.get_tracer()
    assert tracer is not None, "HOROVOD_TRACE should have armed the tracer"
    rep = hvd.trace_report()
    assert rep["enabled"] and rep["spans"] >= 1, rep
    assert rep["open_spans"] == 0, rep  # no span leaks, even on error
    open(os.path.join(out_dir, f"spans.rank{r}.json"), "w").write(
        json.dumps({"clock_offset_s": tracer.clock_offset_s,
                    "dispatch_failed": dispatch_failed,
                    "spans": tracer.records()}))

    ctx_mod.context().metrics_dumper.flush()  # pushes trace/rank{r}

    if r == 0:
        # the coordinator (this process) attributed the delayed rank
        last = [c for c in hvd.metrics_snapshot()["counters"]
                if c["name"] == "hvd_straggler_last_rank_total"]
        assert any(c["labels"].get("rank") == "1" and c["value"] >= 1
                   for c in last), last
        addr = os.environ["HOROVOD_GLOO_RENDEZVOUS_ADDR"]
        port = os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"]
        url = f"http://{addr}:{port}/timeline"
        deadline = time.monotonic() + 30
        merged = {}
        while time.monotonic() < deadline:
            merged = json.loads(
                urllib.request.urlopen(url, timeout=10).read())
            if len(merged.get("horovod", {}).get("ranks", {})) >= 2:
                break
            time.sleep(0.2)
        open(os.path.join(out_dir, "merged.json"), "w").write(
            json.dumps(merged))
    print("trace worker OK", r, "dispatch_failed", dispatch_failed)
""")


def _run_trace_e2e(tmp_path, monkeypatch):
    script = tmp_path / "worker.py"
    script.write_text(TRACE_WORKER)
    monkeypatch.setenv("HOROVOD_TRACE", "1")
    monkeypatch.setenv("HOROVOD_METRICS_DUMP_INTERVAL", "1")
    rc = run_commandline(["-np", "2", sys.executable, str(script),
                          str(tmp_path)])
    assert rc == 0
    merged = json.loads((tmp_path / "merged.json").read_text())
    raw1 = json.loads((tmp_path / "spans.rank1.json").read_text())
    return merged, raw1


def test_two_process_timeline_scrape_clock_aligned(tmp_path, monkeypatch):
    """Acceptance: a 2-process run produces a valid merged Chrome trace
    with the same named collective from both ranks, rank 1's events
    shifted by its clock offset, and the delayed rank attributed."""
    merged, raw1 = _run_trace_e2e(tmp_path, monkeypatch)

    assert isinstance(merged["traceEvents"], list)
    ops = {e["pid"]: e for e in merged["traceEvents"]
           if e.get("ph") == "X" and e["tid"] == tracing.OP_LANE_TID
           and e["name"] == "e2e_trace"}
    assert set(ops) == {0, 1}  # the SAME collective, from BOTH ranks
    for e in ops.values():
        assert e["cat"] == "collective" and e["dur"] >= 0

    # clock alignment: rank 1's merged ts == (raw local ts + 2.5) us
    assert raw1["clock_offset_s"] == 2.5
    assert merged["horovod"]["ranks"]["1"]["clock_offset_s"] == 2.5
    raw_span = next(s for s in raw1["spans"] if s["n"] == "e2e_trace")
    assert ops[1]["ts"] == pytest.approx(
        (raw_span["t"][tracing.T_SUBMIT] + 2.5) * 1e6, abs=1.0)

    # straggler attribution rode the merged trace: rank 1 named
    assert merged["horovod"]["stragglers"]["last_rank_counts"].get(
        "1", 0) >= 1
    assert raw_span["sr"] == 1 and raw_span["sw"] > 0.3


@pytest.mark.chaos
def test_chaos_two_process_spans_never_leak(tmp_path, monkeypatch):
    """Chaos e2e: with drop/delay faults armed at the KV sites in every
    process (launcher included), every started span still finalizes on
    both ranks and the delayed rank is still attributed."""
    monkeypatch.setenv("HOROVOD_FAULT_SPEC",
                       "kv.wait:drop#1,controller.poll:delay=50ms#1")
    faults.reset()
    try:
        merged, raw1 = _run_trace_e2e(tmp_path, monkeypatch)
    finally:
        monkeypatch.delenv("HOROVOD_FAULT_SPEC", raising=False)
        faults.reset()
    # the worker already asserted open_spans == 0 (rc would be non-zero);
    # cross-check from the artifacts: every rank-1 span carries T_DONE
    for s in raw1["spans"]:
        assert s["t"][tracing.T_DONE] is not None
    assert merged["horovod"]["stragglers"]["last_rank_counts"].get(
        "1", 0) >= 1
