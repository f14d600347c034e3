"""Chaos suite: the fault-injection layer (utils/faults.py), the unified
retry policy (utils/retry.py), and the three adopted control-plane
surfaces — KV client, controller negotiation, elastic driver — each
driven through injected drop/delay/crash and asserted to recover (or
degrade gracefully) with the right metrics.

Every test that arms ``HOROVOD_FAULT_SPEC`` is marked ``chaos`` and uses
``monkeypatch.setenv`` (auto-cleaned); conftest fails loudly if the spec
leaks into a non-chaos test's environment. Injected delays are
sub-second by design — the whole suite must fit the tier-1 budget.
"""

import random
import time
import urllib.request

import pytest

from horovod_tpu.common.exceptions import (FaultInjectedError,
                                           RetriesExhaustedError)
from horovod_tpu.ops.controller import KVController
from horovod_tpu.runner.http_server import KVStoreClient, RendezvousServer
from horovod_tpu.utils import faults, metrics
from horovod_tpu.utils.retry import (Retrier, RetryPolicy,
                                     default_retryable)

REG = metrics.get_registry()


def _counter(name, **labels):
    return REG.counter(name, **labels)


@pytest.fixture
def kv_server():
    srv = RendezvousServer()
    port = srv.start()
    yield "127.0.0.1", port
    srv.stop()


@pytest.fixture
def arm(monkeypatch):
    """Arm a fault spec for this test only; re-parse so trigger budgets
    start fresh."""

    def _arm(spec, seed=None):
        monkeypatch.setenv("HOROVOD_FAULT_SPEC", spec)
        if seed is not None:
            monkeypatch.setenv("HOROVOD_FAULT_SEED", str(seed))
        faults.reset()

    yield _arm
    monkeypatch.delenv("HOROVOD_FAULT_SPEC", raising=False)
    faults.reset()


# --- inertness (must run before any chaos test in this module) --------------

def test_fault_points_inert_when_unconfigured():
    """Acceptance: with HOROVOD_FAULT_SPEC unset, fault points are no-ops
    and no hvd_fault_* series exists in the registry at all."""
    import os

    assert not os.environ.get("HOROVOD_FAULT_SPEC")
    for site in faults.SITES:
        faults.fault_point(site)  # returns, raises nothing, sleeps nothing
    assert faults.corrupt("kv.put", b"payload") == b"payload"
    assert not any(n == "hvd_fault_injected_total"
                   for (n, _) in REG._metrics), \
        "hvd_fault_* series registered without any injection configured"


def test_fault_point_is_cheap_when_unconfigured():
    t0 = time.perf_counter()
    for _ in range(10_000):
        faults.fault_point("kv.get")
    # one env-dict lookup per call; generous bound for slow CI
    assert time.perf_counter() - t0 < 0.5


# --- spec parsing / gating ---------------------------------------------------

@pytest.mark.chaos
def test_spec_count_budget(arm):
    arm("kv.get:drop#2")
    for _ in range(2):
        with pytest.raises(ConnectionError):
            faults.fault_point("kv.get")
    for _ in range(10):
        faults.fault_point("kv.get")  # budget spent: inert


@pytest.mark.chaos
def test_spec_every_nth_gate(arm):
    arm("s.x:fail@3")
    fired = []
    for i in range(9):
        try:
            faults.fault_point("s.x")  # hvdlint: disable=fault-sites
            fired.append(False)
        except FaultInjectedError:
            fired.append(True)
    assert fired == [False, False, True] * 3


@pytest.mark.chaos
def test_spec_probability_deterministic(arm):
    arm("s.p:fail@0.5", seed=42)

    def draw():
        out = []
        for _ in range(32):
            try:
                faults.fault_point("s.p")  # hvdlint: disable=fault-sites
                out.append(0)
            except FaultInjectedError:
                out.append(1)
        return out

    first = draw()
    assert 0 < sum(first) < 32  # actually probabilistic
    faults.reset()  # same spec + seed -> identical replay
    assert draw() == first


@pytest.mark.chaos
def test_spec_delay_duration_and_metric(arm):
    arm("s.d:delay=50ms#1")
    t0 = time.perf_counter()
    faults.fault_point("s.d")  # hvdlint: disable=fault-sites
    assert time.perf_counter() - t0 >= 0.045
    assert _counter("hvd_fault_injected_total",
                    site="s.d", mode="delay").value == 1
    faults.fault_point("s.d")  # budget spent  # hvdlint: disable=fault-sites


@pytest.mark.chaos
def test_malformed_spec_is_loud_but_harmless(arm, caplog):
    arm("kv.get-no-mode")
    with caplog.at_level("ERROR", logger="horovod_tpu"):
        faults.fault_point("kv.get")  # must not raise
    assert "malformed" in caplog.text


# --- Retrier ----------------------------------------------------------------

def test_retrier_backoff_shape_and_exhaustion():
    sleeps = []
    pol = RetryPolicy(max_attempts=4, base_delay_s=0.1, max_delay_s=0.3,
                      multiplier=2.0)
    r = Retrier("unit.a", pol, sleep=sleeps.append,
                rng=random.Random(7))
    calls = []
    ex_before = _counter("hvd_retry_exhausted_total", site="unit.a").value

    def fn():
        calls.append(1)
        raise ConnectionResetError("boom")

    with pytest.raises(ConnectionResetError):  # last exception re-raises
        r.call(fn)
    assert len(calls) == 4
    assert len(sleeps) == 3  # no sleep after the final attempt
    # full jitter: each delay in [0, min(cap, base * mult**k)]
    for s, cap in zip(sleeps, (0.1, 0.2, 0.3)):
        assert 0.0 <= s <= cap
    assert _counter("hvd_retry_exhausted_total",
                    site="unit.a").value == ex_before + 1


def test_retrier_success_after_transients():
    attempts = []
    r = Retrier("unit.b", RetryPolicy(max_attempts=5, base_delay_s=0.001),
                sleep=lambda s: None)

    def fn():
        attempts.append(1)
        if len(attempts) < 3:
            raise TimeoutError("flaky")
        return 42

    assert r.call(fn) == 42
    assert r.attempts == 3


def test_retrier_non_retryable_raises_immediately():
    r = Retrier("unit.c", RetryPolicy(max_attempts=5))
    calls = []

    def fn():
        calls.append(1)
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        r.call(fn)
    assert len(calls) == 1


def test_retrier_overall_deadline():
    r = Retrier("unit.d",
                RetryPolicy(max_attempts=None, deadline_s=0.2,
                            base_delay_s=0.01, max_delay_s=0.05))
    t0 = time.monotonic()
    # the last real exception re-raises, unless the deadline expires
    # during a backoff sleep (then RetriesExhaustedError carries the site)
    with pytest.raises((ConnectionError, RetriesExhaustedError)):
        r.call(lambda: (_ for _ in ()).throw(ConnectionError("x")))
    elapsed = time.monotonic() - t0
    assert 0.15 < elapsed < 2.0
    assert r.attempts >= 2  # genuinely re-tried within the window


def test_retrier_deadline_expired_before_first_attempt():
    slept = []
    pol = RetryPolicy(max_attempts=None, deadline_s=0.05,
                      base_delay_s=10.0, max_delay_s=10.0)
    r = Retrier("unit.e", pol, sleep=lambda s: (slept.append(s),
                                                time.sleep(s)))
    with pytest.raises((ConnectionError, RetriesExhaustedError)):
        r.call(lambda: (_ for _ in ()).throw(ConnectionError("x")))
    # backoff was clamped to the deadline, not the 10 s base
    assert all(s <= 0.06 for s in slept)


def test_retry_policy_env_overrides(monkeypatch):
    monkeypatch.setenv("HOROVOD_RETRY_MAX_ATTEMPTS", "7")
    monkeypatch.setenv("HOROVOD_RETRY_DEADLINE", "9.5")
    pol = RetryPolicy.from_env(max_attempts=2, base_delay_s=0.5)
    assert pol.max_attempts == 7
    assert pol.deadline_s == 9.5
    assert pol.base_delay_s == 0.5  # untouched default passes through


def test_default_classifier():
    import http.client

    assert default_retryable(ConnectionResetError("x"))
    assert default_retryable(TimeoutError("x"))
    assert default_retryable(http.client.BadStatusLine("x"))
    assert not default_retryable(ValueError("x"))
    assert not default_retryable(KeyError("x"))


# --- KV client surface ------------------------------------------------------

@pytest.mark.chaos
def test_kv_get_survives_one_drop(kv_server, arm):
    addr, port = kv_server
    c = KVStoreClient(addr, port)
    c.put("s", "k", b"v")
    arm("kv.get:drop#1")
    att = _counter("hvd_retry_attempts_total", site="kv.get")
    before = att.value
    assert c.get("s", "k") == b"v"
    assert att.value - before == 2  # the drop + exactly one retry
    assert _counter("hvd_fault_injected_total",
                    site="kv.get", mode="drop").value >= 1


@pytest.mark.chaos
def test_kv_stale_keepalive_reconnect_exactly_one_retry(kv_server, arm):
    """The round-1 special case, now policy-driven: a stale keep-alive
    socket (simulated by a drop fault inside the request attempt) gets
    exactly ONE transparent reconnect by default — and only for
    idempotent verbs."""
    addr, port = kv_server
    c = KVStoreClient(addr, port)
    c.put("s", "stale", b"v1")
    assert c.get("s", "stale") == b"v1"  # keep-alive conn established
    assert getattr(c._local, "conn", None) is not None

    # one drop: absorbed
    arm("kv.get:drop#1")
    assert c.get("s", "stale") == b"v1"

    # persistent drops: exactly two attempts (1 + 1 retry), then raise
    arm("kv.get:drop")
    att = _counter("hvd_retry_attempts_total", site="kv.get")
    before = att.value
    with pytest.raises(ConnectionError):
        c.get("s", "stale")
    assert att.value - before == 2

    # non-idempotent verb: no transparent retry, first fault surfaces
    arm("kv.post:drop")
    att_post = _counter("hvd_retry_attempts_total", site="kv.post")
    before_post = att_post.value
    with pytest.raises(ConnectionError):
        c._request("POST", "s/stale", b"x", {}, 5.0)
    assert att_post.value - before_post == 1


@pytest.mark.chaos
def test_kv_blocking_get_404_semantics_preserved(kv_server, arm):
    """A blocking-GET timeout is a 404 HTTPError, not a retried fault —
    the negotiation protocol distinguishes 'key not there yet' from
    'store unreachable' by exception type."""
    from urllib.error import HTTPError

    addr, port = kv_server
    c = KVStoreClient(addr, port)
    arm("kv.put:drop#1")  # unrelated site armed: must not affect GET
    t0 = time.monotonic()
    with pytest.raises(HTTPError) as ei:
        c.get("s", "never-put", timeout=0.3)
    assert ei.value.code == 404
    assert time.monotonic() - t0 < 5.0


@pytest.mark.chaos
def test_kv_put_drop_survives_and_delete_retries(kv_server, arm):
    addr, port = kv_server
    c = KVStoreClient(addr, port)
    arm("kv.put:drop#1")
    c.put("s", "k2", b"v2")  # transparent retry
    assert c.get("s", "k2") == b"v2"
    arm("kv.delete:drop#1")
    c.delete_scope("s")
    from urllib.error import HTTPError

    with pytest.raises(HTTPError):
        c.get("s", "k2", timeout=0.2)


@pytest.mark.chaos
def test_torn_metrics_push_skipped_by_scrape(kv_server, arm):
    """Torn-write chaos on the metrics push: the half-written snapshot is
    stored, and the launcher's /metrics merge skips it instead of
    failing the scrape; the next (healed) push lands."""
    addr, port = kv_server
    c = KVStoreClient(addr, port)
    dumper = metrics.MetricsDumper(REG, kv_client=c, rank=3)
    arm("metrics.push:torn#1")
    dumper.flush()  # stored torn: half a JSON document
    stored = c.get("metrics", "rank3")
    with pytest.raises(ValueError):
        import json

        json.loads(stored)
    body = urllib.request.urlopen(
        f"http://{addr}:{port}/metrics", timeout=10).read().decode()
    assert 'rank="3"' not in body  # torn push skipped, scrape healthy
    assert "hvd_fault_injected_total" in body  # launcher's own registry
    dumper.flush()  # budget spent: this push is whole
    body = urllib.request.urlopen(
        f"http://{addr}:{port}/metrics", timeout=10).read().decode()
    assert 'rank="3"' in body


@pytest.mark.chaos
def test_metrics_push_drop_is_absorbed(kv_server, arm):
    addr, port = kv_server
    c = KVStoreClient(addr, port)
    dumper = metrics.MetricsDumper(REG, kv_client=c, rank=4)
    arm("metrics.push:fail")
    dumper.flush()  # telemetry is best-effort: no raise


# --- controller surface -----------------------------------------------------

@pytest.mark.chaos
def test_controller_poll_survives_drop(kv_server, arm, monkeypatch):
    monkeypatch.setenv("HOROVOD_ELASTIC_GEN", "901")  # private KV scope
    addr, port = kv_server
    c = KVStoreClient(addr, port)
    arm("controller.poll:drop#1")
    ctl = KVController(c, rank=0, size=1, poll_timeout=30.0)
    try:
        resp = ctl.negotiate(
            {"t0": ["allreduce", "float32", [4], 0, 0, 1.0, 1.0,
                    "global", "host"]})
        assert resp["ready"] == ["t0"]
        assert not ctl.broken
    finally:
        ctl.stop()


@pytest.mark.chaos
def test_controller_poll_bounded_repoll_until_deadline(kv_server, arm,
                                                       monkeypatch):
    """The raw flat 300 s poll is gone: a worker whose coordinator never
    answers re-polls with backoff and declares the peer dead at its own
    deadline — several attempts, not one flat block."""
    monkeypatch.setenv("HOROVOD_ELASTIC_GEN", "902")
    addr, port = kv_server
    c = KVStoreClient(addr, port)
    w = KVController(c, rank=1, size=2, poll_timeout=1.2)
    att = _counter("hvd_retry_attempts_total", site="controller.poll")
    before = att.value
    t0 = time.monotonic()
    with pytest.raises(Exception):
        w.negotiate({})
    elapsed = time.monotonic() - t0
    assert 0.9 < elapsed < 6.0  # bounded by poll_timeout, not 300 s
    assert att.value - before >= 2  # genuinely re-polled
    assert w.broken


@pytest.mark.chaos
def test_controller_submit_fault_breaks_cleanly(kv_server, arm,
                                                monkeypatch):
    """A fault at the submission step that transport retries cannot see
    (post-retry budget) surfaces as a broken controller — the elastic
    reinit path, not a hang or a desync."""
    monkeypatch.setenv("HOROVOD_ELASTIC_GEN", "903")
    addr, port = kv_server
    c = KVStoreClient(addr, port)
    arm("controller.submit:fail#1")
    ctl = KVController(c, rank=0, size=1, poll_timeout=5.0)
    try:
        with pytest.raises(FaultInjectedError):
            ctl.negotiate({})
        assert ctl.broken
        with pytest.raises(RuntimeError):
            ctl.negotiate({})  # broken stays broken until reinit
    finally:
        ctl.stop()


@pytest.mark.chaos
def test_controller_round_survives_kv_wait_drop(kv_server, arm,
                                                monkeypatch):
    """Coordinator-side chaos: the bulk prefix-read hits a dropped
    socket; the transport retry (and the per-rank GET fallback) keep the
    round converging."""
    monkeypatch.setenv("HOROVOD_ELASTIC_GEN", "904")
    addr, port = kv_server
    c = KVStoreClient(addr, port)
    arm("kv.wait:drop#1")
    ctl = KVController(c, rank=0, size=1, poll_timeout=30.0)
    try:
        resp = ctl.negotiate(
            {"w0": ["allreduce", "float32", [2], 0, 0, 1.0, 1.0,
                    "global", "host"]})
        assert resp["ready"] == ["w0"]
    finally:
        ctl.stop()


# --- elastic surface --------------------------------------------------------

@pytest.mark.chaos
def test_elastic_spawn_fault_respawns_not_blacklists(arm):
    from test_elastic import Scenario, run_driver_async, wait_for

    from horovod_tpu.elastic import ElasticDriver, FixedHosts

    arm("elastic.spawn:fail#1")
    disc = FixedHosts({"a": 1})
    driver = ElasticDriver(disc, min_np=1, respawn_retries=1,
                           respawn_backoff_s=0.01)
    sc = Scenario()
    t, result = run_driver_async(driver, sc)
    # first spawn faults (transient SSH blip); the host is struck but
    # retried, and the second round's spawn succeeds
    assert wait_for(lambda: len(sc.workers) == 1)
    assert not driver.host_manager.is_blacklisted("a")
    assert driver._host_strikes.get("a") == 1
    sc.workers[0][1].finish(0)
    t.join(timeout=10)
    assert result["rc"] == 0
    # clean exit healed the strike count
    assert "a" not in driver._host_strikes
    assert _counter("hvd_fault_injected_total",
                    site="elastic.spawn", mode="error").value >= 1
    driver.stop()


@pytest.mark.chaos
def test_elastic_heartbeat_faults_degrade_gracefully(arm):
    from test_elastic import Scenario, run_driver_async, wait_for

    from horovod_tpu.elastic import ElasticDriver, FixedHosts

    # every heartbeat faults: membership changes go unseen, but worker
    # monitoring and round completion must be unaffected
    arm("elastic.heartbeat:fail")
    disc = FixedHosts({"a": 2})
    driver = ElasticDriver(disc, min_np=1)
    sc = Scenario()
    t, result = run_driver_async(driver, sc)
    assert wait_for(lambda: len(sc.workers) == 2)
    for _, w in sc.workers:
        w.finish(0)
    t.join(timeout=10)
    assert result["rc"] == 0
    driver.stop()


# --- end-to-end: killed worker host is retried, not blacklisted -------------

CHAOS_E2E_WORKER = """
import os
import time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import horovod_tpu as hvd
from horovod_tpu.elastic import ObjectState

hvd.init()
r = hvd.cross_rank()
incarnation = int(os.environ["HOROVOD_ELASTIC_EPOCH"])
state = ObjectState(step=0)  # resumes from HOROVOD_ELASTIC_STORE
# no cross-process collectives here: this test is about the DRIVER's
# kill -> respawn -> (not) blacklist lifecycle, and the timed steps keep
# rank 0 alive long past the driver's failure detection of rank 1
while state.step < 6:
    time.sleep(0.25)
    state.step += 1
    state.commit()
    if incarnation == 0 and r == 1 and state.step == 2:
        os._exit(9)  # killed worker (preemption), AFTER the commit
print(f"CHAOS-E2E-DONE rank={r} step={state.step} inc={incarnation}",
      flush=True)
"""


@pytest.mark.chaos
def test_e2e_killed_worker_host_respawned_not_blacklisted(tmp_path):
    """Acceptance: a 2-process elastic job whose worker is killed once
    recovers by RESPAWNING the host (transient preemption) — the host is
    not blacklisted, and training completes on the retried host."""
    import os
    import re
    import subprocess
    import sys as _sys

    worker = tmp_path / "worker.py"
    worker.write_text(CHAOS_E2E_WORKER)
    disc = tmp_path / "discover.sh"
    disc.write_text("#!/bin/sh\necho localhost:2\n")
    disc.chmod(0o755)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["HOROVOD_ELASTIC_RESPAWN_ATTEMPTS"] = "1"
    env["HOROVOD_ELASTIC_RESPAWN_BACKOFF"] = "0.1"
    p = subprocess.run(
        [_sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         "--min-np", "2", "--max-np", "2",
         "--host-discovery-script", str(disc),
         _sys.executable, str(worker)],
        env=env, capture_output=True, text=True, timeout=300)
    out = p.stdout + p.stderr
    assert p.returncode == 0, out[-3000:]
    done = re.findall(r"CHAOS-E2E-DONE rank=(\d) step=(\d+) inc=(\d+)", out)
    # recovery happened and the respawned incarnation finished on BOTH
    # ranks (rank 0 of incarnation 0 may or may not have finished before
    # the driver's failure detection terminated its round — either
    # ordering is sound, and either way the host's strike budget covers
    # the crash)
    finished = {(r, s) for r, s, i in done if i != "0"}
    assert finished == {("0", "6"), ("1", "6")}, (done, out[-2000:])
    # the ONLY host was retried, not blacklisted — with a single host a
    # first-strike blacklist would have failed the job below min_np
    assert "respawning before blacklist" in out, out[-2000:]
    assert "blacklisting" not in out, out[-2000:]
