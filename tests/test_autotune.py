"""Joint online autotuner (utils/autotune.py, docs/autotune.md): the
mixed continuous/categorical search space, GP + EI numerics and the
small-sample bandit, synchronized multi-rank proposals, the workload
shift / revert / tuned-file guardrails, and the zero-cost-off contract.

Multi-rank worlds are in-process (N KVControllers on N threads against
one real RendezvousServer — the tests/test_hier_negotiation.py harness
shape): real cross-process XLA collectives don't exist on the CPU
backend, but parameter synchronization is pure control plane and runs
the full wire protocol here."""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from horovod_tpu.common.exceptions import FaultInjectedError
from horovod_tpu.utils import autotune, faults, metrics
from horovod_tpu.utils.autotune import (Autotuner, BayesianOptimizer,
                                        BoolKnob, ChoiceKnob, LogKnob,
                                        SearchSpace, _argmax_tiebreak,
                                        _from_params, _GP, _to_params,
                                        load_tuned_config,
                                        save_tuned_config)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REG = metrics.get_registry()


def _load_bench(name):
    import importlib.util as ilu

    spec = ilu.spec_from_file_location(
        f"_autotune_bench_{name.split('.')[0]}",
        os.path.join(REPO, "benchmarks", name))
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _JointRuntime:
    """Duck-typed runtime carrying the full joint knob surface, with the
    real runtime's ``_apply_tuned_params`` hook recording every applied
    proposal (the torn-config assertions read ``applied``)."""

    def __init__(self):
        self.fusion_threshold = 64 << 20
        self.cycle_time_ms = 1.0
        self.bytes_processed = 0
        self.controller = None
        self.staging_ring_slots = 4
        self.plan_chunk_tensors = 0
        self.applied = []

    def set_fusion_threshold(self, v):
        self.fusion_threshold = int(v)

    def set_staging_slots(self, n):
        self.staging_ring_slots = int(n)

    def set_plan_chunk_tensors(self, n):
        self.plan_chunk_tensors = int(n)

    def _apply_tuned_params(self, p):
        self.applied.append(dict(p))
        if "fusion" in p:
            self.set_fusion_threshold(p["fusion"])
        if "cycle" in p:
            self.cycle_time_ms = float(p["cycle"])
        if "ring_slots" in p:
            self.set_staging_slots(p["ring_slots"])
        if "chunk" in p:
            self.set_plan_chunk_tensors(p["chunk"])


def _space():
    return SearchSpace([
        LogKnob("fusion", 1 << 20, 256 << 20, integer=True),
        LogKnob("cycle", 0.5, 25.0),
        BoolKnob("hier_ar"),
        ChoiceKnob("ring_slots", (1, 2, 4, 8)),
        ChoiceKnob("chunk", (0, 2, 4, 8, 16)),
    ])


# --- surrogate + acquisition internals --------------------------------------

def test_gp_interpolates_and_widens_away_from_data():
    gp = _GP()
    X = np.array([[0.0], [0.1]])
    gp.fit(X, np.array([0.0, 1.0]))
    mu, sigma = gp.predict(X)
    assert np.allclose(mu, [0.0, 1.0], atol=0.15)
    assert (sigma < 0.3).all()
    _, far_sigma = gp.predict(np.array([[1.0]]))
    assert far_sigma[0] > 0.5  # posterior widens far from the data


def test_gp_survives_duplicate_observations():
    # penalize() re-observes a reverted candidate at its own x; the
    # kernel matrix gains identical rows and fit must not blow up
    X = np.stack([[0.5, 0.5]] * 6 + [[0.2, 0.8]])
    y = np.array([1.0] * 6 + [2.0])
    gp = _GP()
    gp.fit(X, y)
    mu, sigma = gp.predict(np.array([[0.2, 0.8]]))
    assert abs(mu[0] - 2.0) < 0.5 and np.isfinite(sigma[0])


def test_ei_argmax_tiebreak_is_deterministic():
    assert _argmax_tiebreak([0.1, 0.9, 0.2], [0.0, 0.0, 0.0]) == 1
    # EI ties break on the posterior mean
    assert _argmax_tiebreak([1.0, 1.0, 0.5], [0.1, 0.9, 2.0]) == 1
    # full tie: lowest index
    assert _argmax_tiebreak([1.0, 1.0, 1.0], [0.3, 0.3, 0.3]) == 0
    # sub-epsilon EI differences count as ties (surrogate noise)
    assert _argmax_tiebreak([1.0, 1.0 + 1e-14], [5.0, 0.0]) == 0


def test_params_roundtrip_across_joint_space():
    space = _space()
    for ring in (1, 2, 4, 8):
        for chunk in (0, 2, 4, 8, 16):
            for hier in (False, True):
                params = {"fusion": 8 << 20, "cycle": 2.0,
                          "hier_ar": hier, "ring_slots": ring,
                          "chunk": chunk}
                out = space.to_params(space.from_params(params))
                assert out["fusion"] == params["fusion"]
                assert out["cycle"] == pytest.approx(params["cycle"])
                assert out["hier_ar"] is hier
                assert out["ring_slots"] == ring
                assert out["chunk"] == chunk


def test_legacy_module_level_roundtrip():
    # the legacy 4-dim layout behind _to_params/_from_params still
    # round-trips for any normalized vector
    x = np.array([0.25, 0.5, 0.75, 0.25])
    params = _to_params(x)
    again = _to_params(_from_params(params))
    assert again == params


def test_choice_knob_snaps_out_of_menu_values():
    k = ChoiceKnob("ring_slots", (1, 2, 4, 8))
    # a hand-set env value off the menu snaps to the nearest choice
    # instead of failing the sample loop
    assert k.decode(k.encode(3)) == 2  # equidistant: lower choice wins
    assert k.decode(k.encode(6)) == 4
    assert k.decode(k.encode(100)) == 8
    with pytest.raises(ValueError):
        k.encode("bogus")


def test_suggest_deterministic_under_seed():
    def run():
        space = _space()
        opt = BayesianOptimizer(dims=space.dims, n_random=4, seed=7,
                                space=space)
        seq = []
        for _ in range(8):
            x = opt.suggest()
            seq.append(np.array(x))
            opt.observe(x, -float(((x - 0.6) ** 2).sum()))
        return seq

    a, b = run(), run()
    for xa, xb in zip(a, b):
        np.testing.assert_allclose(xa, xb)


def test_bandit_phase_visits_every_arm_with_feasible_encodings():
    space = _space()
    arms = space.arms()
    opt = BayesianOptimizer(dims=space.dims, n_random=10 ** 9, seed=0,
                            space=space)
    for _ in range(len(arms)):
        x = opt.suggest()
        # every categorical block is a pure one-hot (feasible manifold)
        for k in space.knobs:
            if isinstance(k, ChoiceKnob):
                off = space.offsets[k.name]
                block = x[off:off + k.dims]
                assert sorted(block)[-1] == 1.0 and block.sum() == 1.0
        opt.observe(x, 0.0)
    assert set(opt._arm_n) == set(arms)  # unseen arms explored first


def test_penalize_buries_candidate_below_worst():
    space = _space()
    opt = BayesianOptimizer(dims=space.dims, n_random=0, seed=0,
                            space=space)
    x_good = space.snap(np.full(space.dims, 0.9))
    x_bad = space.snap(np.full(space.dims, 0.1))
    opt.observe(x_good, 5.0)
    opt.observe(space.snap(np.full(space.dims, 0.5)), 3.0)
    opt.penalize(x_bad)
    assert opt.y[-1] < 3.0  # strictly below the worst real observation
    np.testing.assert_allclose(opt.best(), x_good)


# --- tuned-file persistence --------------------------------------------------

def test_tuned_file_roundtrip(tmp_path):
    path = str(tmp_path / "tuned.json")
    params = {"fusion": 32 << 20, "cycle": 2.5, "hier_ar": False,
              "ring_slots": 2, "chunk": 4, "compression": "bf16",
              "hier_group": 8}
    save_tuned_config(path, params, 1234.5)
    assert load_tuned_config(path) == params


@pytest.mark.parametrize("doc", [
    "not json {",
    json.dumps({"version": 99, "params": {"fusion": 1}}),
    json.dumps({"version": 1, "params": {}}),
    json.dumps({"version": 1, "params": {"fusion": 1, "bogus": 2}}),
    json.dumps({"version": 1, "params": {"fusion": -5}}),
    json.dumps({"version": 1, "params": {"compression": "zstd"}}),
    json.dumps({"version": 1, "params": {"cycle": "fast"}}),
    json.dumps([1, 2, 3]),
])
def test_tuned_file_reload_is_all_or_nothing(tmp_path, doc):
    path = tmp_path / "tuned.json"
    path.write_text(doc)
    assert load_tuned_config(str(path)) is None


def test_tuned_file_missing_is_none(tmp_path):
    assert load_tuned_config(str(tmp_path / "absent.json")) is None


def test_warm_start_proposes_persisted_config_filtered_to_space(tmp_path):
    path = str(tmp_path / "tuned.json")
    save_tuned_config(path, {"fusion": 32 << 20, "cycle": 2.0,
                             "ring_slots": 2, "chunk": 4,
                             "hier_group": 4}, 99.0)
    rt = _JointRuntime()
    at = Autotuner(rt, warmup_samples=0, max_samples=5,
                   tuned_file=path)
    at.sample()  # first sample proposes the warm config, before scoring
    assert rt.applied, "warm start never proposed"
    warm = rt.applied[0]
    assert warm["fusion"] == 32 << 20 and warm["ring_slots"] == 2
    # this runtime has no hierarchical controller: the hier_group knob
    # is not in its space and must be dropped, not half-applied
    assert "hier_group" not in warm


# --- guardrails --------------------------------------------------------------

def test_revert_guardrail_restores_best_config():
    rt = _JointRuntime()
    at = Autotuner(rt, warmup_samples=0, max_samples=100,
                   revert_pct=20.0, revert_windows=2)
    scores = iter([100.0, 50.0, 50.0])
    at._score = lambda: next(scores)
    r0 = REG.counter_value("hvd_autotune_reverts_total")

    at.sample()  # score 100 on the defaults: becomes the best config
    best = dict(at._best_params)
    assert best["fusion"] == 64 << 20
    at.sample()  # regressed >=20%: strike 1, keeps searching
    assert rt.applied[-1].get("final") is False
    at.sample()  # strike 2: revert fires
    assert REG.counter_value("hvd_autotune_reverts_total") == r0 + 1
    # the live runtime is back on the best known config, whole
    assert rt.fusion_threshold == best["fusion"]
    assert rt.cycle_time_ms == pytest.approx(best["cycle"])
    assert rt.staging_ring_slots == best["ring_slots"]
    assert rt.plan_chunk_tensors == best["chunk"]
    assert at._strikes == 0  # re-armed for the next candidate


def test_workload_shift_is_debounced_then_retunes():
    batch_a = [SimpleNamespace(name="grad/a", tensor=np.zeros((8, 8)))]
    batch_b = [SimpleNamespace(name="grad/b", tensor=np.zeros((16,)))]

    def drive(at, windows, batch):
        for _ in range(windows):
            for _ in range(3):
                at.note_cycle(batch)
            at.sample()

    rt = _JointRuntime()
    at = Autotuner(rt, warmup_samples=0, max_samples=2)
    at._score = lambda: 100.0
    s0 = REG.counter_value("hvd_autotune_workload_shifts_total")
    drive(at, 3, batch_a)
    assert at.done
    # a one-window blip must NOT thrash the converged search
    drive(at, 1, batch_b)
    drive(at, 1, batch_a)
    assert at.done
    assert REG.counter_value("hvd_autotune_workload_shifts_total") == s0
    # a sustained new signature restarts it after SHIFT_WINDOWS windows
    drive(at, autotune.SHIFT_WINDOWS, batch_b)
    # the shift-window's own sample still scores after the reset
    assert not at.done and at._samples == 1
    assert REG.counter_value("hvd_autotune_workload_shifts_total") == s0 + 1
    # and the search re-converges on the new workload
    drive(at, 3, batch_b)
    assert at.done


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
    # has NO hvd_fault_* series (reset() rebuilt the rules, so no live
    # object caches the deleted counter instance)
    reg = metrics.get_registry()
    with reg._lock:
        for key in [k for k in reg._metrics
                    if k[0].startswith("hvd_fault_")]:
            del reg._metrics[key]


@pytest.mark.chaos
def test_chaos_faulted_proposal_skips_round_whole(arm):
    arm("autotune.propose:fail#1")
    rt = _JointRuntime()
    at = Autotuner(rt, warmup_samples=0, max_samples=10)
    at._score = lambda: 100.0
    with pytest.raises(FaultInjectedError):
        at.sample()
    # the fault fired before anything was handed over: no torn config
    assert rt.applied == []
    assert rt.fusion_threshold == 64 << 20
    at._score = lambda: 110.0
    at.sample()  # trigger budget spent: tuning resumes
    assert len(rt.applied) == 1
    assert {"fusion", "cycle", "final"} <= set(rt.applied[0])


# --- multi-rank consistency (in-process control-plane world) ----------------

SIG = ["allreduce", "float32", [1024], 0, -1, 1.0, 1.0, "global", "host"]

P1 = {"fusion": 32 << 20, "cycle": 2.0, "ring_slots": 2, "chunk": 4,
      "final": False}
P2 = {"fusion": 128 << 20, "cycle": 1.0, "ring_slots": 8, "chunk": 0,
      "final": True}


def test_multirank_params_apply_same_round_despite_straggler():
    """Every rank applies the SAME proposal at the SAME round boundary
    (reference Controller::SynchronizeParameters, controller.cc:39-53),
    whole, even with one rank dragging its feet mid-round."""
    from horovod_tpu.ops.controller import KVController
    from horovod_tpu.runner.http_server import (KVStoreClient,
                                                RendezvousServer)

    nranks = 4
    schedule = [{"warm": SIG}, {"t0": SIG}, {"t1": SIG}, {"t2": SIG}]
    submits = {1: P1, 2: P2}  # rank 0 proposes before rounds 1 and 2
    delays = {(1, 2): 0.3}    # rank 2 straggles in the P1 round
    srv = RendezvousServer()
    port = srv.start()
    applied = [[] for _ in range(nranks)]
    errs = []

    def run(rank):
        ctl = None
        try:
            cli = KVStoreClient("127.0.0.1", port)
            ctl = KVController(cli, rank, nranks, poll_timeout=60.0,
                               hier=False)
            ctl.on_params = lambda p: applied[rank].append(dict(p))
            for i, pending in enumerate(schedule):
                if (i, rank) in delays:
                    time.sleep(delays[(i, rank)])
                if rank == 0 and i in submits:
                    ctl.submit_params(dict(submits[i]))
                ctl.negotiate(dict(pending))
        except Exception as e:  # pragma: no cover - surfaced via errs
            errs.append((rank, repr(e)))
        finally:
            if ctl is not None:
                try:
                    ctl.stop()
                except Exception:
                    pass

    threads = [threading.Thread(target=run, args=(r,), daemon=True)
               for r in range(nranks)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    hung = [i for i, t in enumerate(threads) if t.is_alive()]
    srv.stop()
    assert not hung, f"ranks wedged: {hung}"
    assert not errs, f"ranks failed: {errs}"
    # every rank — rank 0 included — applied both proposals, in proposal
    # order, each dict whole (no torn config), none duplicated
    for rank in range(nranks):
        assert applied[rank] == [P1, P2], (rank, applied[rank])


# --- zero-cost-off contract --------------------------------------------------

def test_autotune_off_registers_zero_series():
    """Acceptance: with HOROVOD_AUTOTUNE unset, no Autotuner exists, the
    runtime hook is None, and no hvd_autotune_* series of ANY kind is
    registered. Checked in a pristine subprocess — the in-process
    registry accumulates series from tests that DO build tuners."""
    script = textwrap.dedent("""
        import os
        assert "HOROVOD_AUTOTUNE" not in os.environ
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import horovod_tpu as hvd
        hvd.init()
        from horovod_tpu.common import context as ctx_mod
        ctx = ctx_mod.context()
        assert ctx.autotuner is None
        assert ctx.runtime.autotuner is None
        from horovod_tpu.utils import metrics
        snap = metrics.get_registry().snapshot()
        names = {m["name"]
                 for kind in ("counters", "gauges", "histograms")
                 for m in snap[kind]}
        bad = {n for n in names if n.startswith("hvd_autotune")}
        assert not bad, bad
        print("zero-series OK")
    """)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("HOROVOD_AUTOTUNE")}
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "zero-series OK" in proc.stdout



# --- end-to-end on the real runtime ------------------------------------------

def test_plan_hit_rate_returns_to_one_after_tuning():
    """Acceptance: after the tuner converges (each proposal having
    invalidated the fused-plan cache), the steady-state window replays
    compiled plans at a 1.0 hit rate."""
    co = _load_bench("cycle_overhead.py")
    out = co.measure_workload("dense_many_small", cycles=6, warmup=2,
                              autotune=True, autotune_cap=400)
    assert out["autotuned"]["converged"], out["autotuned"]
    assert out["plan_hit_rate"] == 1.0, out


@pytest.mark.slow
def test_autotuned_matches_best_hand_config_benchguard():
    """The headline acceptance gate: on every CPU workload the autotuned
    config's dispatch median must land within the budgeted ratio of the
    best hand-tuned grid row, judged by tools/benchguard against
    benchmarks/autotune_budgets.json."""
    sys.path.insert(0, REPO)
    from tools import benchguard

    co = _load_bench("cycle_overhead.py")
    budgets = benchguard.load_budgets(
        os.path.join(REPO, "benchmarks", "autotune_budgets.json"))
    extras = {}
    for wl in co.WORKLOADS:
        cmp = co.compare_workload(wl, cycles=30, warmup=5)
        extras[f"{wl}_autotuned_over_best"] = cmp["autotuned_over_best"]
    result = {"bench": "cycle_overhead_autotune",
              "metric": "autotuned_over_best_hand_ratio",
              "value": max(extras.values()), "extras": extras}
    verdict = benchguard.compare(result, history=[], budgets=budgets)
    assert verdict["status"] == "ok", verdict
