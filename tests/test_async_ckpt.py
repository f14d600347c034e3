"""Preemption-tolerant async sharded checkpointing (utils/async_ckpt.py,
ISSUE 17): snapshot/flush/manifest roundtrip, the depth-1 newest-wins
queue, manifest completeness across world sizes, checksum verification,
torn-write atomicity (the ``ckpt.write:torn`` chaos contract), the
SIGTERM preempt-flush chain, the elastic driver's preemption grace
window, the auth-exempt ``GET /checkpoint`` merge, the MetricsDumper
``ckpt/rank{k}`` push, the zero-cost-off subprocess assertion, the A/A
overhead gate, the 2-process SIGTERM→flush→restart acceptance run, and
the chaos soak gate (benchmarks/chaos_soak.py).

The checkpointer is OFF for the session-scoped hvd.init() (conftest);
tests build private ``AsyncCheckpointer`` instances against tmp dirs and
stop them on exit, so the zero-cost default holds for every other file.
"""

import json
import logging
import os
import re
import signal
import subprocess
import sys
import textwrap
import time
import urllib.request

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.common import env as env_schema
from horovod_tpu.common.exceptions import FaultInjectedError
from horovod_tpu.elastic.driver import ElasticDriver
from horovod_tpu.runner.http_server import KVStoreClient, RendezvousServer
from horovod_tpu.utils import async_ckpt, checkpoint, faults, metrics

REG = metrics.get_registry()
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def arm(monkeypatch):
    """Arm a fault spec for this test only (tests/test_faults.py shape)."""

    def _arm(spec):
        monkeypatch.setenv("HOROVOD_FAULT_SPEC", spec)
        faults.reset()

    yield _arm
    monkeypatch.delenv("HOROVOD_FAULT_SPEC", raising=False)
    faults.reset()
    # drop the injection series this test created: the registry is
    # process-global and tests/test_faults.py asserts an unconfigured run
    # has NO hvd_fault_* series
    with REG._lock:
        for key in [k for k in REG._metrics
                    if k[0].startswith("hvd_fault_")]:
            del REG._metrics[key]


@pytest.fixture
def kv_server():
    srv = RendezvousServer(secret_key="ckpt-secret")
    port = srv.start()
    yield "127.0.0.1", port
    srv.stop()


def _shard(rank, scale=1.0):
    return {"m": np.arange(64, dtype=np.float32) * (rank + 1) * scale,
            "v": np.full(16, float(rank), np.float32)}


def _mk(tmp_path, rank, world):
    return async_ckpt.AsyncCheckpointer(rank=rank, world=world,
                                        directory=str(tmp_path))


def _kill_writer(ckpt):
    """Stop the background writer so commits happen only through
    flush() — makes fault-injection on the commit path deterministic."""
    ckpt._stop.set()
    ckpt._wakeup.set()
    ckpt._thread.join(timeout=5.0)


def _counters():
    return {k: REG.counter_value(f"hvd_ckpt_{k}_total")
            for k in ("snapshots", "dropped", "commits", "failures")}


# ---------------------------------------------------------------------------
# snapshot → commit → manifest → restore roundtrip
# ---------------------------------------------------------------------------

def test_snapshot_flush_manifest_roundtrip(tmp_path):
    c0 = _counters()
    ckpts = [_mk(tmp_path, r, 2) for r in range(2)]
    try:
        rep = {"params": np.linspace(0, 1, 32, dtype=np.float32)}
        assert ckpts[0].snapshot(3, _shard(0), replicated=rep,
                                 generation=4)
        assert ckpts[1].snapshot(3, _shard(1), generation=4)
        for c in ckpts:
            assert c.flush(deadline_s=10.0)
        m = async_ckpt.read_manifest(str(tmp_path))
        assert m is not None
        assert (m["step"], m["generation"], m["world"]) == (3, 4, 2)
        assert set(m["ranks"]) == {0, 1}
        # every shard carries its own checksum and step
        manifest, payloads = async_ckpt.load_shards(str(tmp_path))
        assert manifest["step"] == 3
        for r in range(2):
            got = payloads[r]["shard_state"]
            want = _shard(r)
            assert all(np.array_equal(got[k], want[k]) for k in want)
        # replicated leaves live on rank 0 only
        assert np.array_equal(payloads[0]["replicated"]["params"],
                              rep["params"])
        assert payloads[1]["replicated"] is None
        # same-world fast path: this rank's payload verbatim
        own = async_ckpt.load_own_shard(str(tmp_path), 1)
        assert own is not None and own["step"] == 3
        assert np.array_equal(own["shard_state"]["m"], _shard(1)["m"])
        # status surfaces the committed step for pushes / GET /checkpoint
        st = ckpts[0].snapshot_status()
        assert st["last_step"] == 3 and st["last_shard_bytes"] > 0
        assert st["rank"] == 0 and not st["queued"] and not st["inflight"]
        assert ckpts[0].report()["enabled"] is True
    finally:
        for c in ckpts:
            c.stop()
    c1 = _counters()
    assert c1["snapshots"] - c0["snapshots"] == 2
    assert c1["commits"] - c0["commits"] == 2
    assert c1["failures"] == c0["failures"]
    assert REG.counter_value("hvd_ckpt_bytes_total") > 0


def test_snapshot_queue_is_depth1_newest_wins(tmp_path):
    """The snapshot-copy budget: a slow disk drops superseded snapshots
    instead of ever blocking the step."""
    c0 = _counters()
    ckpt = _mk(tmp_path, 0, 1)
    try:
        _kill_writer(ckpt)  # a "disk" that never catches up
        assert ckpt.snapshot(1, _shard(0)) is True
        assert ckpt.snapshot(2, _shard(0, 2.0)) is False  # displaced step 1
        assert ckpt.flush(deadline_s=10.0)
        m = async_ckpt.read_manifest(str(tmp_path))
        assert m["step"] == 2  # only the newest snapshot ever hit disk
        own = async_ckpt.load_own_shard(str(tmp_path), 0)
        assert np.array_equal(own["shard_state"]["m"], _shard(0, 2.0)["m"])
    finally:
        ckpt.stop()
    c1 = _counters()
    assert c1["snapshots"] - c0["snapshots"] == 2
    assert c1["dropped"] - c0["dropped"] == 1
    assert c1["commits"] - c0["commits"] == 1
    # accounting closes: every snapshot commits, is displaced, or fails
    assert (c1["snapshots"] - c0["snapshots"]
            == (c1["commits"] - c0["commits"])
            + (c1["dropped"] - c0["dropped"])
            + (c1["failures"] - c0["failures"]))


def test_manifest_requires_complete_world_and_excludes_stale_ranks(tmp_path):
    """A group wins only with every rank of its world present: after a
    3→2 shrink the old rank-2 shard can never join the new snapshot."""
    old = [_mk(tmp_path, r, 3) for r in range(3)]
    try:
        for r, c in enumerate(old):
            assert c.snapshot(5, _shard(r))
            assert c.flush(deadline_s=10.0)
    finally:
        for c in old:
            c.stop()
    assert async_ckpt.read_manifest(str(tmp_path))["world"] == 3
    new = [_mk(tmp_path, r, 2) for r in range(2)]
    try:
        for r, c in enumerate(new):
            assert c.snapshot(9, _shard(r, 3.0))
            assert c.flush(deadline_s=10.0)
    finally:
        for c in new:
            c.stop()
    m = async_ckpt.read_manifest(str(tmp_path))
    # rank 2's leftover step-5 manifest is incomplete (ranks 0/1 moved
    # on) and its world-3 shard cannot complete the world-2 group
    assert (m["step"], m["world"]) == (9, 2)
    assert set(m["ranks"]) == {0, 1}
    assert async_ckpt.load_own_shard(str(tmp_path), 2) is None
    # one straggler manifest alone is no snapshot at all
    os.remove(tmp_path / "manifest_rank1.json")
    m2 = async_ckpt.read_manifest(str(tmp_path))
    assert m2 is None


def test_checksum_mismatch_refuses_restore(tmp_path):
    ckpt = _mk(tmp_path, 0, 1)
    try:
        assert ckpt.snapshot(1, _shard(0))
        assert ckpt.flush(deadline_s=10.0)
    finally:
        ckpt.stop()
    shard_path = tmp_path / "shard_rank0.ckpt"
    with open(shard_path, "ab") as f:
        f.write(b"bitrot")
    with pytest.raises(async_ckpt.CheckpointError, match="checksum"):
        async_ckpt.load_shards(str(tmp_path))
    # the escape hatch is explicit, never the default
    _, payloads = async_ckpt.load_shards(str(tmp_path), verify=False)
    assert np.array_equal(payloads[0]["shard_state"]["m"], _shard(0)["m"])


# ---------------------------------------------------------------------------
# chaos: write faults, flush retries, torn-write atomicity
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_flush_retries_through_transient_write_fault(tmp_path, arm):
    """One injected commit error is absorbed by the flush retry budget:
    the snapshot still lands, the job never sees the fault."""
    ckpt = _mk(tmp_path, 0, 1)
    try:
        _kill_writer(ckpt)
        arm("ckpt.write:fail#1")
        assert ckpt.snapshot(4, _shard(0))
        assert ckpt.flush(deadline_s=10.0) is True
    finally:
        ckpt.stop()
    assert async_ckpt.read_manifest(str(tmp_path))["step"] == 4
    inj = sum(c["value"] for c in REG.snapshot()["counters"]
              if c["name"] == "hvd_fault_injected_total"
              and c["labels"].get("site") == "ckpt.write")
    assert inj >= 1


@pytest.mark.chaos
def test_torn_write_never_leaves_half_readable_checkpoint(tmp_path, arm):
    """Acceptance (satellite 2): ``ckpt.write:torn`` tears the payload
    mid-write; the same-directory tmp + fsync + rename sequence means the
    committed path transitions valid → valid only — the previous
    checkpoint stays bitwise readable, never a half-written one."""
    # -- direct save_pytree contract ------------------------------------
    path = str(tmp_path / "direct.ckpt")
    first = {"w": np.arange(32, dtype=np.float32)}
    checkpoint.save_pytree(path, first)
    arm("ckpt.write:torn#1")
    with pytest.raises(FaultInjectedError, match="torn"):
        checkpoint.save_pytree(path, {"w": np.zeros(32, np.float32)})
    # the torn attempt left no tmp litter and the old payload intact
    assert [n for n in os.listdir(tmp_path) if "direct" in n] == [
        "direct.ckpt"]
    assert np.array_equal(checkpoint.load_pytree(path)["w"], first["w"])
    checkpoint.save_pytree(path, {"w": np.ones(32, np.float32)})  # healed
    assert checkpoint.load_pytree(path)["w"][0] == 1.0

    # -- through the async writer: every retry torn, commit fails loudly,
    #    the previous snapshot survives verification ----------------------
    c0 = _counters()
    ckpt = _mk(tmp_path, 0, 1)
    try:
        assert ckpt.snapshot(1, _shard(0))
        assert ckpt.flush(deadline_s=10.0)
        _kill_writer(ckpt)
        arm("ckpt.write:torn")  # unlimited: no retry can succeed
        assert ckpt.snapshot(2, _shard(0, 9.0))
        assert ckpt.flush(deadline_s=10.0) is False
    finally:
        ckpt.stop()
    m, payloads = async_ckpt.load_shards(str(tmp_path))  # verify=True
    assert m["step"] == 1
    assert np.array_equal(payloads[0]["shard_state"]["m"], _shard(0)["m"])
    c1 = _counters()
    assert c1["failures"] > c0["failures"]


# ---------------------------------------------------------------------------
# zero-cost-off contract
# ---------------------------------------------------------------------------

def test_disabled_by_default(monkeypatch):
    monkeypatch.delenv(env_schema.HOROVOD_ASYNC_CKPT, raising=False)
    assert not async_ckpt.enabled()
    assert async_ckpt.init_checkpointer(rank=0, world=1) is None
    assert async_ckpt.get_checkpointer() is None
    assert async_ckpt.report() == {"enabled": False}
    assert hvd.checkpoint_report() == {"enabled": False}


def test_off_registers_zero_series_subprocess():
    """Acceptance: with HOROVOD_ASYNC_CKPT unset, no hvd_ckpt_* series of
    ANY kind exists. Checked in a pristine subprocess — this file's own
    tests register the series by building checkpointers."""
    script = textwrap.dedent("""
        import os
        assert "HOROVOD_ASYNC_CKPT" not in os.environ
        from horovod_tpu.utils import async_ckpt, metrics
        assert not async_ckpt.enabled()
        assert async_ckpt.init_checkpointer(rank=0, world=1) is None
        assert async_ckpt.report() == {"enabled": False}
        snap = metrics.get_registry().snapshot()
        names = {m["name"]
                 for kind in ("counters", "gauges", "histograms")
                 for m in snap[kind]}
        bad = {n for n in names if n.startswith("hvd_ckpt")}
        assert not bad, bad
        print("zero-series OK")
    """)
    env = dict(os.environ)
    env.pop("HOROVOD_ASYNC_CKPT", None)
    env.pop("HOROVOD_ASYNC_CKPT_DIR", None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "zero-series OK" in proc.stdout


# ---------------------------------------------------------------------------
# SIGTERM: preempt-flush chain + the driver's grace window
# ---------------------------------------------------------------------------

PREEMPT_SCRIPT = textwrap.dedent("""
    import os, signal, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["HOROVOD_ASYNC_CKPT"] = "1"
    os.environ["HOROVOD_ASYNC_CKPT_DIR"] = sys.argv[1]
    os.environ["HOROVOD_PREEMPT_GRACE_S"] = "10"
    # slow commits: the step-7 flush below can only be the handler's work
    os.environ["HOROVOD_FAULT_SPEC"] = "ckpt.write:delay=300ms"
    import numpy as np
    from horovod_tpu.utils import async_ckpt, faults
    faults.reset()
    ckpt = async_ckpt.init_checkpointer(rank=0, world=1)
    assert ckpt is not None
    ckpt.snapshot(0, {"m": np.arange(8, dtype=np.float32)})
    assert ckpt.flush(deadline_s=10.0)
    # dead writer: the pending step-7 snapshot is durable only if the
    # SIGTERM handler's deadline-bounded flush commits it
    ckpt._stop.set(); ckpt._wakeup.set(); ckpt._thread.join()
    ckpt.snapshot(7, {"m": np.arange(8, dtype=np.float32) * 2})
    print("PRE-SIGTERM", flush=True)
    os.kill(os.getpid(), signal.SIGTERM)
    time.sleep(30)
    print("SURVIVED-SIGTERM", flush=True)
""")


def test_sigterm_flushes_pending_snapshot_then_dies(tmp_path):
    """Acceptance: SIGTERM → deadline-bounded flush of the pending
    snapshot → chain to the previous disposition (the process still dies
    of SIGTERM)."""
    script = tmp_path / "preempt.py"
    script.write_text(PREEMPT_SCRIPT)
    ckpt_dir = tmp_path / "ckpt"
    env = dict(os.environ)
    env.pop("HOROVOD_FAULT_SPEC", None)
    # the script lives in tmp_path: the child finds the checkout by path
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in (env.get("PYTHONPATH"),) if p])
    proc = subprocess.run([sys.executable, str(script), str(ckpt_dir)],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert "PRE-SIGTERM" in proc.stdout, proc.stdout + proc.stderr
    assert "SURVIVED-SIGTERM" not in proc.stdout
    assert proc.returncode == -signal.SIGTERM, (proc.returncode,
                                                proc.stderr[-2000:])
    m = async_ckpt.read_manifest(str(ckpt_dir))
    assert m is not None and m["step"] == 7, m
    own = async_ckpt.load_own_shard(str(ckpt_dir), 0)
    assert np.array_equal(own["shard_state"]["m"],
                          np.arange(8, dtype=np.float32) * 2)


class _FakeSlot:
    def __init__(self, rank):
        self.rank = rank


class _FakeHandle:
    """A worker that exits ``exit_after`` seconds after terminate() —
    or never, when None (the straggler the driver must SIGKILL)."""

    def __init__(self, exit_after):
        self.exit_after = exit_after
        self.terminated_at = None
        self.killed = False

    def terminate(self):
        self.terminated_at = time.monotonic()

    def poll(self):
        if self.killed:
            return -9
        if (self.terminated_at is not None and self.exit_after is not None
                and time.monotonic() - self.terminated_at
                >= self.exit_after):
            return 0
        return None

    def kill(self):
        self.killed = True


def test_driver_terminate_waits_grace_window_then_escalates(monkeypatch,
                                                            caplog):
    """Satellite 3: _terminate forwards SIGTERM, waits out
    HOROVOD_PREEMPT_GRACE_S so checkpoint flushes can complete, and only
    then escalates stragglers to SIGKILL — logging rank + elapsed."""
    monkeypatch.setenv(env_schema.HOROVOD_PREEMPT_GRACE_S, "0.4")
    prompt = _FakeHandle(exit_after=0.1)
    straggler = _FakeHandle(exit_after=None)
    alive = {"a:0": (_FakeSlot(0), prompt), "a:1": (_FakeSlot(1), straggler)}
    t0 = time.monotonic()
    with caplog.at_level(logging.INFO, logger="horovod_tpu"):
        ElasticDriver._terminate(None, alive)
    elapsed = time.monotonic() - t0
    assert alive == {}
    assert not prompt.killed and straggler.killed
    # the straggler consumed the grace window before the escalation
    assert 0.4 <= elapsed < 5.0
    msgs = [r.getMessage() for r in caplog.records]
    assert any("rank 0 exited" in m and "grace window 0.4s" in m
               for m in msgs), msgs
    assert any("rank 1" in m and "escalating to SIGKILL" in m
               for m in msgs), msgs


# ---------------------------------------------------------------------------
# observability: GET /checkpoint merge + the MetricsDumper push
# ---------------------------------------------------------------------------

def test_checkpoint_endpoint_merges_pushes_and_manifest(kv_server, tmp_path,
                                                        monkeypatch):
    """hvdlint rule #8 surface: the launcher's auth-exempt
    ``GET /checkpoint`` merges the per-rank ``ckpt/rank{k}`` pushes
    (stale-annotated, torn pushes skipped) and reports the newest
    consistent on-disk manifest."""
    ckpt = _mk(tmp_path, 0, 1)
    try:
        assert ckpt.snapshot(2, _shard(0), generation=1)
        assert ckpt.flush(deadline_s=10.0)
    finally:
        ckpt.stop()
    monkeypatch.setenv(env_schema.HOROVOD_ASYNC_CKPT_DIR, str(tmp_path))
    addr, port = kv_server
    kv = KVStoreClient(addr, port, secret_key="ckpt-secret")
    now = time.time()
    fresh = {"rank": 0, "world": 2, "last_step": 2, "queued": False,
             "inflight": False, "push_ts": now, "push_interval_s": 2.0}
    lagging = {"rank": 1, "world": 2, "last_step": 0, "queued": True,
               "inflight": False, "push_ts": now - 600,
               "push_interval_s": 2.0}
    kv.put("ckpt", "rank0", json.dumps(fresh).encode())
    kv.put("ckpt", "rank1", json.dumps(lagging).encode())
    kv.put("ckpt", "rank-torn", b"{half a json")  # skipped, not fatal
    # unauthenticated on purpose: the endpoint is auth-exempt telemetry
    merged = json.loads(urllib.request.urlopen(
        f"http://{addr}:{port}/checkpoint", timeout=10).read())
    assert set(merged["ranks"]) == {"0", "1"}
    assert merged["ranks"]["0"]["stale"] is False
    assert merged["ranks"]["1"]["stale"] is True  # annotated, not dropped
    assert merged["ranks"]["1"]["last_step"] == 0
    man = merged["manifest"]
    assert man is not None
    assert (man["step"], man["generation"], man["world"]) == (2, 1, 1)
    assert "ranks" not in man  # the per-rank entries stay server-side


def test_metrics_dumper_pushes_stamped_ckpt_status(tmp_path, monkeypatch):
    class _FakeKV:
        def __init__(self):
            self.puts = []

        def put(self, scope, key, value):
            self.puts.append((scope, key, bytes(value)))

    ckpt = _mk(tmp_path, 2, 3)
    try:
        assert ckpt.snapshot(6, _shard(2))
        assert ckpt.flush(deadline_s=10.0)
        monkeypatch.setattr(async_ckpt, "_CHECKPOINTER", ckpt)
        kv = _FakeKV()
        dumper = metrics.MetricsDumper(REG, interval_s=5.0, kv_client=kv,
                                       rank=2)
        dumper.flush()
    finally:
        ckpt.stop()
    pushed = [(k, json.loads(v)) for scope, k, v in kv.puts
              if scope == async_ckpt.KV_SCOPE]
    assert len(pushed) == 1
    key, snap = pushed[0]
    assert key == "rank2" and snap["rank"] == 2 and snap["world"] == 3
    assert snap["last_step"] == 6 and snap["last_shard_bytes"] > 0
    assert snap["push_seq"] == 1 and snap["push_interval_s"] == 5.0
    assert isinstance(snap["push_ts"], float)


# ---------------------------------------------------------------------------
# the A/A overhead gate (benchmarks/async_ckpt_overhead.py)
# ---------------------------------------------------------------------------

def _load_overhead_bench():
    import importlib.util as ilu

    spec = ilu.spec_from_file_location(
        "_async_ckpt_overhead_test",
        os.path.join(REPO, "benchmarks", "async_ckpt_overhead.py"))
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod



@pytest.mark.slow
def test_async_ckpt_aa_gate_benchguard():
    """The checked-in A/A acceptance gate: checkpointer-off within 2% of
    the featureless baseline (best-of-3 interleaved reps), judged by
    tools/benchguard against benchmarks/async_ckpt_budgets.json.

    The off and baseline arms run IDENTICAL code (measure_async_ckpt(False)
    twice), so an out-of-budget A/A ratio can only mean the host's noise
    floor exceeded 2% during this sample — never a code regression. The
    whole measurement is therefore retried on a noisy verdict; a real
    checkpointer-cost regression trips the on_over_baseline budget on
    every attempt."""
    sys.path.insert(0, REPO)
    from tools import benchguard

    mod = _load_overhead_bench()
    budgets = benchguard.load_budgets(
        os.path.join(REPO, "benchmarks", "async_ckpt_budgets.json"))
    for attempt in range(3):
        mod.measure_async_ckpt(False, cycles=10, warmup=2)  # discarded
        runs = {"baseline": [], "off": [], "on": []}
        for _ in range(3):
            runs["baseline"].append(mod.measure_async_ckpt(False, cycles=30))
            runs["off"].append(mod.measure_async_ckpt(False, cycles=30))
            runs["on"].append(mod.measure_async_ckpt(True, cycles=30))
        base, off, on = (
            min(runs[k], key=lambda r: r["dispatch_ms_median"])
            for k in ("baseline", "off", "on"))
        result = {"bench": "async_ckpt_overhead",
                  "metric": "async_ckpt_off_over_baseline_ratio",
                  "value": (off["dispatch_ms_median"]
                            / base["dispatch_ms_median"]),
                  "extras": {"on_over_baseline":
                             on["dispatch_ms_median"]
                             / base["dispatch_ms_median"]}}
        verdict = benchguard.compare(result, history=[], budgets=budgets)
        if verdict["status"] == "ok":
            break
    assert verdict["status"] == "ok", (verdict, result)


# ---------------------------------------------------------------------------
# the chaos soak gate (benchmarks/chaos_soak.py)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_soak_200_steps_gate():
    """Tentpole acceptance: ≥200 steps of the mixed workload (dense
    allreduce cycles + sharded update + quantized wire + hierarchical
    negotiation + live autotuner) under the rotating fault spec with
    elastic resizes and a mid-soak preemption drill — zero leaked spans,
    zero lock inversions, no SLO false latches, checkpoint accounting
    closed, and end-state convergence bitwise-equal to the unfaulted
    reference. Runs as a subprocess so the soak's chaos env and registry
    churn can never leak into this session."""
    env = dict(os.environ)
    env.pop("HOROVOD_FAULT_SPEC", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "chaos_soak.py"),
         "--steps", "200"],
        env=env, capture_output=True, text=True, timeout=580)
    assert proc.returncode == 0, (proc.stdout[-4000:], proc.stderr[-4000:])
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["bench"] == "chaos_soak"
    assert verdict["steps"] >= 200
    assert verdict["ok"] is True, verdict["checks"]
    assert all(verdict["checks"].values()), verdict["checks"]
    assert verdict["chaos"]["faults_injected"] > 0


# ---------------------------------------------------------------------------
# 2-process acceptance: SIGTERM'd job restores from its shards and the
# loss trajectory matches the uninterrupted run bitwise
# ---------------------------------------------------------------------------

CKPT_E2E_WORKER = textwrap.dedent("""
    import os, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.utils import async_ckpt

    hvd.init()
    r = hvd.cross_rank()
    inc = int(os.environ["HOROVOD_ELASTIC_EPOCH"])
    ckpt = async_ckpt.get_checkpointer()
    assert ckpt is not None and ckpt.world == 2, ckpt
    ckpt_dir = ckpt.directory

    # deterministic fp32 "training": no cross-process collectives (this
    # jax build cannot execute multi-process CPU collectives; the
    # contract under test is the checkpoint lifecycle)
    w = np.zeros(64, np.float32)
    step0 = 0
    own = async_ckpt.load_own_shard(ckpt_dir, r)
    if own is not None:
        w = own["shard_state"]["w"]
        step0 = own["step"] + 1
    print(f"CKPT-E2E-RESUME rank={r} inc={inc} step0={step0}", flush=True)
    for step in range(step0, 10):
        g = np.random.RandomState(1000 + step).standard_normal(
            64).astype(np.float32)
        w = w - np.float32(0.1) * g
        loss = float(np.square(w).sum(dtype=np.float32))
        print(f"CKPT-E2E-LOSS rank={r} inc={inc} step={step} "
              f"{loss.hex()}", flush=True)
        time.sleep(0.25)
        if step == 4:
            # both ranks flush the SAME step: manifest completeness
            # requires every rank of the world present at one step
            assert ckpt.snapshot(4, {"w": w})
            assert ckpt.flush(deadline_s=20.0)
        if inc == 0 and r == 1 and step == 6:
            os._exit(9)  # preempted AFTER the durable step-4 snapshot
    print(f"CKPT-E2E-DONE rank={r} inc={inc} final={w.sum():.6f}",
          flush=True)
""")


@pytest.mark.slow
@pytest.mark.chaos
def test_e2e_sigterm_restart_restores_bitwise_trajectory(tmp_path):
    """Acceptance: a 2-process elastic job whose rank 1 dies after the
    step-4 flush restarts, both ranks restore their own shards, and the
    post-restore loss trajectory is bitwise-equal (fp32 hex) to the
    uninterrupted schedule — with no SIGKILL escalation (the surviving
    rank's SIGTERM handler flushed and exited inside the grace window)."""
    worker = tmp_path / "worker.py"
    worker.write_text(CKPT_E2E_WORKER)
    disc = tmp_path / "discover.sh"
    disc.write_text("#!/bin/sh\necho localhost:2\n")
    disc.chmod(0o755)
    ckpt_dir = tmp_path / "ckpt"
    logs_dir = tmp_path / "logs"

    env = dict(os.environ)
    env.pop("HOROVOD_FAULT_SPEC", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["HOROVOD_ELASTIC_RESPAWN_ATTEMPTS"] = "1"
    env["HOROVOD_ELASTIC_RESPAWN_BACKOFF"] = "0.1"
    env["HOROVOD_ASYNC_CKPT"] = "1"
    env["HOROVOD_ASYNC_CKPT_DIR"] = str(ckpt_dir)
    env["HOROVOD_PREEMPT_GRACE_S"] = "20"
    p = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         "--min-np", "2", "--max-np", "2",
         "--host-discovery-script", str(disc),
         "--output-filename", str(logs_dir),
         sys.executable, str(worker)],
        env=env, capture_output=True, text=True, timeout=300)
    out = p.stdout + p.stderr
    assert p.returncode == 0, out[-4000:]
    # the CKPT-E2E markers are parsed from the per-rank tee files, not
    # the merged console stream: two ranks share one console pipe, and a
    # worker whose buffered flush exceeds PIPE_BUF can tear mid-line at
    # the 4K boundary, gluing another rank's line into the middle of a
    # record. The tee files are written one line at a time by a
    # dedicated thread per rank pipe, so they cannot interleave.
    marks = "".join(
        (logs_dir / f"rank.{r}.out").read_text() for r in (0, 1))

    # the replay the workers must reproduce bit-for-bit
    w = np.zeros(64, np.float32)
    expected = []
    for step in range(10):
        g = np.random.RandomState(1000 + step).standard_normal(
            64).astype(np.float32)
        w = w - np.float32(0.1) * g
        expected.append(float(np.square(w).sum(dtype=np.float32)).hex())

    resumes = re.findall(
        r"CKPT-E2E-RESUME rank=(\d) inc=(\d+) step0=(\d+)", marks)
    # incarnation 0 cold-starts; the respawned incarnation resumes at 5
    assert ("0", "0", "0") in resumes and ("1", "0", "0") in resumes, resumes
    restored = {(r, s) for r, i, s in resumes if i != "0"}
    assert restored == {("0", "5"), ("1", "5")}, (resumes, out[-2000:])
    losses = re.findall(
        r"CKPT-E2E-LOSS rank=(\d) inc=(\d+) step=(\d+) "
        r"(-?0x[01]\.[0-9a-f]+p[+-]\d+)", marks)
    for r, i, step, hexval in losses:
        if i != "0":
            assert hexval == expected[int(step)], (r, i, step)
    # post-restore coverage is complete on both ranks
    for r in ("0", "1"):
        got = sorted(int(s) for rr, i, s, _ in losses
                     if rr == r and i != "0")
        assert got == [5, 6, 7, 8, 9], (r, losses)
    done = re.findall(r"CKPT-E2E-DONE rank=(\d) inc=(\d+)", marks)
    assert {(r,) for r, i in done if i != "0"} == {("0",), ("1",)}, done
    # the terminated incarnation-0 survivor exited inside the grace
    # window: the driver never had to escalate
    assert "escalating to SIGKILL" not in out, out[-2000:]
    # the shard checkpoint that carried the restart is still consistent
    m = async_ckpt.read_manifest(str(ckpt_dir))
    assert m is not None and m["step"] == 4 and m["world"] == 2
