"""Unified metrics registry + /metrics endpoint (utils/metrics.py).

Covers the registry primitives, the Prometheus text exposition (format
0.0.4 validity + exact values vs ``hvd.metrics_snapshot()``), the
rendezvous server's auth-exempt ``GET /metrics`` scrape, the worker→
launcher snapshot push/merge, the ``HOROVOD_METRICS_FILE`` JSON dump, and
the stall inspector's warning→shutdown escalation counters.
"""

import json
import re
import sys
import textwrap
import threading
import time
import urllib.request

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.runner.http_server import KVStoreClient, RendezvousServer
from horovod_tpu.runner.launch import run_commandline
from horovod_tpu.utils import metrics as mm


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = mm.MetricsRegistry()
    c = reg.counter("c_total", "help")
    c.inc()
    c.inc(5)
    assert c.value == 6
    g = reg.gauge("g", "help")
    g.set(3)
    g.inc(2)
    g.dec()
    assert g.value == 4
    # get-or-create returns the same instance per (name, labels)
    assert reg.counter("c_total") is c
    assert reg.counter("c_total", dtype="f32") is not c


def test_metric_kind_conflict_raises():
    reg = mm.MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(TypeError):
        reg.gauge("x_total")


def test_histogram_buckets_cumulative():
    reg = mm.MetricsRegistry()
    h = reg.histogram("h", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    cum = dict(h.cumulative())
    assert cum[1.0] == 2
    assert cum[10.0] == 3
    assert cum[100.0] == 4
    assert cum["+Inf"] == 5
    assert h.count == 5
    assert h.sum == pytest.approx(556.0)
    # an observation exactly on a bound lands in that bound's bucket
    h.observe(10.0)
    assert dict(h.cumulative())[10.0] == 4


def test_counter_value_sums_family():
    reg = mm.MetricsRegistry()
    reg.counter("b_total", dtype="f32").inc(10)
    reg.counter("b_total", dtype="bf16").inc(5)
    assert reg.counter_value("b_total") == 15
    assert reg.counter_value("missing") == 0


def test_reset_zeros_in_place():
    reg = mm.MetricsRegistry()
    c = reg.counter("c_total")
    h = reg.histogram("h", buckets=(1.0,))
    c.inc(9)
    h.observe(0.5)
    reg.reset()
    assert c.value == 0 and h.count == 0 and h.sum == 0.0
    c.inc()  # cached instances stay live after reset
    assert reg.counter_value("c_total") == 1


def test_concurrent_increments_are_lossless():
    reg = mm.MetricsRegistry()
    c = reg.counter("c_total")
    h = reg.histogram("h", buckets=(0.5,))

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.1)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000


# ---------------------------------------------------------------------------
# exposition format
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z0-9_]+="[^"]*"'
    r'(,[a-zA-Z0-9_]+="[^"]*")*\})? -?[0-9eE.+\-]+(e[+-]?\d+)?$')
_TYPE_RE = re.compile(
    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)$")


def _check_exposition(text: str):
    """Every line is a valid TYPE header or sample; each family has
    exactly one TYPE header and it precedes the family's samples."""
    seen_types = {}
    for ln in text.splitlines():
        if not ln:
            continue
        if ln.startswith("#"):
            assert _TYPE_RE.match(ln), ln
            fam = ln.split()[2]
            assert fam not in seen_types, f"duplicate TYPE for {fam}"
            seen_types[fam] = ln.split()[3]
        else:
            assert _SAMPLE_RE.match(ln), ln
            name = re.split(r"[{ ]", ln, 1)[0]
            base = re.sub(r"_(bucket|sum|count)$", "", name)
            assert name in seen_types or base in seen_types, ln
    return seen_types


def _parse_samples(text: str):
    """{(name, frozen-label-str): float} for every sample line."""
    out = {}
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        head, val = ln.rsplit(" ", 1)
        out[head] = float(val)
    return out


def test_render_prometheus_valid_and_exact():
    reg = mm.MetricsRegistry()
    reg.counter("ops_total", "ops", op="allreduce").inc(7)
    reg.gauge("depth").set(3)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = reg.render_prometheus()
    kinds = _check_exposition(text)
    assert kinds == {"ops_total": "counter", "depth": "gauge",
                     "lat_seconds": "histogram"}
    s = _parse_samples(text)
    assert s['ops_total{op="allreduce"}'] == 7
    assert s["depth"] == 3
    assert s['lat_seconds_bucket{le="0.1"}'] == 1
    assert s['lat_seconds_bucket{le="1"}'] == 1
    assert s['lat_seconds_bucket{le="+Inf"}'] == 2
    assert s["lat_seconds_count"] == 2
    assert s["lat_seconds_sum"] == pytest.approx(5.05)


def test_render_snapshots_merges_ranks_under_one_header():
    reg_a, reg_b = mm.MetricsRegistry(), mm.MetricsRegistry()
    reg_a.counter("w_total").inc(2)
    reg_b.counter("w_total").inc(3)
    text = mm.render_snapshots([({"rank": "0"}, reg_a.snapshot()),
                                ({"rank": "1"}, reg_b.snapshot())])
    _check_exposition(text)  # asserts ONE "# TYPE w_total" header
    s = _parse_samples(text)
    assert s['w_total{rank="0"}'] == 2
    assert s['w_total{rank="1"}'] == 3


def test_snapshot_json_roundtrip_and_dump(tmp_path):
    reg = mm.MetricsRegistry()
    reg.counter("c_total", dtype="float32").inc(4)
    reg.histogram("h", buckets=(1.0,)).observe(0.5)
    path = tmp_path / "metrics.json"
    mm.MetricsDumper(reg, file_path=str(path)).flush()
    loaded = json.loads(path.read_text())
    assert loaded["counters"] == [
        {"name": "c_total", "labels": {"dtype": "float32"}, "value": 4}]
    (hist,) = loaded["histograms"]
    assert hist["count"] == 1 and hist["buckets"][-1] == ["+Inf", 1]
    # the dump is also a render_snapshots input (launcher merge path)
    assert 'c_total{dtype="float32",rank="9"} 4' in mm.render_snapshots(
        [({"rank": "9"}, loaded)])


# ---------------------------------------------------------------------------
# live runtime -> /metrics scrape (single process, session runtime)
# ---------------------------------------------------------------------------

def _scrape(port: int) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=10) as r:
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        return r.read().decode()


def test_runtime_metrics_scrape_matches_snapshot():
    """Allreduces through the live runtime, then GET /metrics: valid
    exposition whose counter values equal hvd.metrics_snapshot()."""
    reg = mm.get_registry()
    bytes_before = reg.counter_value("hvd_allreduce_bytes_total")
    handles = [hvd.allreduce_async(np.ones(1024, np.float32),
                                   name=f"metrics.t{i}", op=hvd.Sum)
               for i in range(4)]
    for h in handles:
        hvd.synchronize(h)
    delta = reg.counter_value("hvd_allreduce_bytes_total") - bytes_before
    assert delta == 4 * 1024 * 4  # four 1024-float32 payloads

    srv = RendezvousServer(secret_key="test-secret")
    port = srv.start()
    try:
        text = _scrape(port)
        # the scrape endpoint must NOT relax auth on the KV namespace
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/somescope/k", timeout=10)
        assert ei.value.code == 403
    finally:
        srv.stop()

    _check_exposition(text)
    s = _parse_samples(text)
    snap = hvd.metrics_snapshot()
    # exact agreement between the two exposures, family by family
    for fam in ("hvd_allreduce_bytes_total", "hvd_allreduce_ops_total",
                "hvd_ops_enqueued_total"):
        scraped = sum(v for k, v in s.items() if k.startswith(fam))
        snapped = sum(c["value"] for c in snap["counters"]
                      if c["name"] == fam)
        assert scraped == snapped > 0, fam
    fusion = next(h for h in snap["histograms"]
                  if h["name"] == "hvd_fusion_batch_size")
    assert s["hvd_fusion_batch_size_count"] == fusion["count"] > 0
    assert s['hvd_fusion_batch_size_bucket{le="+Inf"}'] == fusion["count"]
    cycles = next(h for h in snap["histograms"]
                  if h["name"] == "hvd_cycle_seconds")
    assert cycles["count"] > 0
    assert any(k.startswith("hvd_cycle_seconds_bucket") for k in s)


def test_metrics_endpoint_merges_pushed_worker_snapshots():
    """A worker-side MetricsDumper pushes its snapshot into the store;
    the next scrape shows the series with that worker's rank label."""
    srv = RendezvousServer(secret_key="push-secret")
    port = srv.start()
    try:
        worker_reg = mm.MetricsRegistry()
        worker_reg.counter("hvd_push_probe_total").inc(11)
        kv = KVStoreClient("127.0.0.1", port, secret_key="push-secret")
        mm.MetricsDumper(worker_reg, kv_client=kv, rank=3).flush()
        text = _scrape(port)
    finally:
        srv.stop()
    _check_exposition(text)
    assert _parse_samples(text)['hvd_push_probe_total{rank="3"}'] == 11


def test_metrics_merge_drops_stale_generation_snapshots(monkeypatch):
    """Metrics continuity across elastic restarts: every push is tagged
    with (elastic_epoch, elastic_gen); the scrape keeps only the newest
    generation, so a removed rank's ghost series stops haunting the
    endpoint after a reset (regression for exactly that)."""
    monkeypatch.delenv("HOROVOD_ELASTIC_EPOCH", raising=False)
    monkeypatch.delenv("HOROVOD_ELASTIC_GEN", raising=False)
    srv = RendezvousServer(secret_key="gen-secret")
    port = srv.start()
    try:
        kv = KVStoreClient("127.0.0.1", port, secret_key="gen-secret")
        reg0 = mm.MetricsRegistry()
        reg0.counter("hvd_push_probe_total").inc(5)
        mm.MetricsDumper(reg0, kv_client=kv, rank=0).flush()
        reg1 = mm.MetricsRegistry()
        reg1.counter("hvd_push_probe_total").inc(7)
        mm.MetricsDumper(reg1, kv_client=kv, rank=1).flush()
        both = _parse_samples(_scrape(port))
        assert both['hvd_push_probe_total{rank="0"}'] == 5
        assert both['hvd_push_probe_total{rank="1"}'] == 7

        # the runtime bumps the generation on an in-process reinit; the
        # surviving rank 0 re-pushes, the removed rank 1 never does
        monkeypatch.setenv("HOROVOD_ELASTIC_GEN", "2")
        reg2 = mm.MetricsRegistry()
        reg2.counter("hvd_push_probe_total").inc(9)
        mm.MetricsDumper(reg2, kv_client=kv, rank=0).flush()
        text = _scrape(port)
    finally:
        srv.stop()
    _check_exposition(text)
    s = _parse_samples(text)
    assert s['hvd_push_probe_total{rank="0"}'] == 9
    # rank 1's generation-(0,0) snapshot is stale: dropped, not merged
    assert 'hvd_push_probe_total{rank="1"}' not in s


# ---------------------------------------------------------------------------
# stall inspector: gauges, warning message, warning -> shutdown escalation
# ---------------------------------------------------------------------------

def test_stall_warning_then_shutdown_escalation(caplog):
    from horovod_tpu.common.exceptions import StalledTensorError
    from horovod_tpu.utils.stall import StallInspector

    reg = mm.get_registry()
    warn0 = reg.counter_value("hvd_stall_warnings_total")
    stalled0 = reg.counter_value("hvd_stall_stalled_tensors_total")
    shut0 = reg.counter_value("hvd_stall_shutdowns_total")

    insp = StallInspector(warning_time_s=0.05, shutdown_time_s=0.25)
    insp.record_pending("grad/a")
    insp.record_pending("grad/b")
    insp.check()  # below the warning threshold: nothing fires
    assert reg.counter_value("hvd_stall_warnings_total") == warn0
    oldest = next(g for g in hvd.metrics_snapshot()["gauges"]
                  if g["name"] == "hvd_stall_oldest_pending_age_seconds")
    assert oldest["value"] >= 0

    time.sleep(0.1)
    with caplog.at_level("WARNING", logger="horovod_tpu"):
        insp.check()
    # both tensors warned once, with the queue-age distribution attached
    assert reg.counter_value("hvd_stall_warnings_total") == warn0 + 2
    assert reg.counter_value("hvd_stall_stalled_tensors_total") == stalled0 + 2
    msgs = [r.getMessage() for r in caplog.records
            if "pending" in r.getMessage()]
    assert any("2 pending (age min/median/max" in m for m in msgs), msgs
    insp.check()  # already-warned tensors do not re-warn
    assert reg.counter_value("hvd_stall_warnings_total") == warn0 + 2

    time.sleep(0.25)
    with pytest.raises(StalledTensorError) as ei:
        insp.check()
    assert ei.value.names == ["grad/a", "grad/b"]
    assert reg.counter_value("hvd_stall_shutdowns_total") == shut0 + 1

    # completion clears the pending table and the gauges go back to zero
    insp.record_done("grad/a")
    insp.record_done("grad/b")
    insp.check()
    gauges = {g["name"]: g["value"] for g in hvd.metrics_snapshot()["gauges"]}
    assert gauges["hvd_stall_pending_tensors"] == 0
    assert gauges["hvd_stall_oldest_pending_age_seconds"] == 0


# ---------------------------------------------------------------------------
# two-process end-to-end: fused allreduces -> launcher scrape + file dump
# ---------------------------------------------------------------------------

METRICS_WORKER = textwrap.dedent("""
    import json, os, sys, time, urllib.request
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import context as ctx_mod
    from horovod_tpu.common.exceptions import HorovodInternalError

    out_dir = sys.argv[1]
    hvd.init()
    r = hvd.cross_rank()
    try:
        handles = [hvd.allreduce_async(np.ones(512, np.float32),
                                       op=hvd.Sum, name=f"m{i}")
                   for i in range(4)]
        for h in handles:
            assert np.allclose(np.asarray(hvd.synchronize(h)), 2.0)
    except HorovodInternalError as e:
        if "Multiprocess computations" in str(e):
            # this jax build cannot run multi-process CPU collectives;
            # signal the test to skip rather than fail
            open(os.path.join(out_dir, "SKIP"), "w").write(str(e))
            os._exit(0)
        raise

    dumper = ctx_mod.context().metrics_dumper
    assert dumper is not None, "rendezvous env should enable the KV push"
    dumper.flush()

    if r == 0:
        addr = os.environ["HOROVOD_GLOO_RENDEZVOUS_ADDR"]
        port = os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"]
        url = f"http://{addr}:{port}/metrics"
        deadline = time.monotonic() + 30
        text = ""
        while time.monotonic() < deadline:
            text = urllib.request.urlopen(url, timeout=10).read().decode()
            if 'rank="0"' in text and 'rank="1"' in text:
                break
            time.sleep(0.2)
        for fam in ("hvd_allreduce_bytes_total", "hvd_cycle_seconds_bucket",
                    "hvd_fusion_batch_size"):
            assert fam in text, (fam, text[:2000])
        for rk in ('rank="0"', 'rank="1"'):
            assert f'hvd_allreduce_bytes_total{{dtype="float32",{rk}}}' \\
                in text, text[:2000]
        open(os.path.join(out_dir, "SCRAPE_OK"), "w").write(text)

    hvd.shutdown()  # final MetricsDumper flush writes HOROVOD_METRICS_FILE
    path = os.environ["HOROVOD_METRICS_FILE"]
    if r != 0:
        path += f".rank{r}"
    dump = json.loads(open(path).read())
    by_name = {}
    for c in dump["counters"]:
        by_name[c["name"]] = by_name.get(c["name"], 0) + c["value"]
    assert by_name["hvd_allreduce_bytes_total"] == 4 * 512 * 4, by_name
    # the four tensors may fuse into one chunk: an op is a dispatched
    # collective, so only what was enqueued is a fixed count
    assert by_name["hvd_ops_enqueued_total"] == 4, by_name
    assert by_name["hvd_allreduce_ops_total"] >= 1, by_name
    print("metrics worker OK", r)
""")


def test_two_process_scrape_and_metrics_file(tmp_path, monkeypatch):
    """Acceptance path: a 2-process job runs fused allreduces; the
    launcher's /metrics exposes both ranks' counters; each rank's
    HOROVOD_METRICS_FILE holds the same counters after shutdown()."""
    script = tmp_path / "worker.py"
    script.write_text(METRICS_WORKER)
    monkeypatch.setenv("HOROVOD_METRICS_FILE", str(tmp_path / "m.json"))
    monkeypatch.setenv("HOROVOD_METRICS_DUMP_INTERVAL", "1")
    rc = run_commandline(["-np", "2", sys.executable, str(script),
                          str(tmp_path)])
    if (tmp_path / "SKIP").exists():
        pytest.skip("jax build lacks multi-process CPU collectives: "
                    + (tmp_path / "SKIP").read_text()[:120])
    assert rc == 0
    scraped = (tmp_path / "SCRAPE_OK").read_text()
    _check_exposition(scraped)
