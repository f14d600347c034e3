"""ZeRO-1 sharded weight update (opt/sharded.py, ISSUE 7).

A/B contract: a simulated N-rank world driven through the compiled
pack → reduce-scatter → sharded step → allgather plan chain must land
on bitwise-identical fp32 parameters (tolerance for bf16) versus the
replicated path that allreduces every gradient and repeats the full
optimizer step — while holding ~1/N of the optimizer state per rank.
Plus: the shared leaf-sharding heuristic pin (parallel/sharding_policy
vs parallel/fsdp), layout determinism/digest sensitivity, plan-cache
hit-rate and elastic-generation keying, elastic 2→3 resize continuity,
the zero-cost-when-off subprocess assertion, the framework-shim
surfacing, and the CPU microbench smoke.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.common import env as env_schema
from horovod_tpu.ops import collectives as C
from horovod_tpu.opt import sharded as sharded_mod
from horovod_tpu.parallel import fsdp
from horovod_tpu.parallel.sharding_policy import (
    DEFAULT_MIN_SHARD_ELEMS,
    assign_owners,
    shard_dim,
    should_shard,
)
from horovod_tpu.utils import metrics as metrics_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _params(dtype=jnp.float32):
    """Mixed pytree: two shardable mats + one shardable vector, with
    sub-threshold bias/small-mat/scalar leaves on the classic path."""
    r = np.random.RandomState(0)
    return {
        "w1": jnp.asarray(r.randn(256, 256), dtype),
        "b1": jnp.asarray(r.randn(256), dtype),
        "w2": jnp.asarray(r.randn(64, 64), dtype),
        "big": jnp.asarray(r.randn(16384), dtype),
        "scale": jnp.asarray(1.5, dtype),
    }


def _grads(params, world, step):
    return [jax.tree.map(
        lambda p, r=r: jnp.asarray(
            np.random.RandomState(97 * step + r).standard_normal(p.shape),
            p.dtype), params) for r in range(world)]


def _rep_step_fn(opt):
    """Replicated baseline: per-leaf stacked mean of the per-rank grads
    (the same reduce body the RS plans lower to — `(a+b)+c / 3` is NOT
    bitwise-equal to it) + the full inner update on every rank.
    Deliberately NOT jitted as one program: a fused XLA step may
    contract the adam arithmetic differently in the last bit, and the
    contract under test is bitwise equality of the *math*, not of two
    unrelated compilation strategies."""
    def f(p, gs, s):
        g = jax.tree.map(lambda *x: jnp.mean(jnp.stack(x), axis=0), *gs)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s

    return f


def _tree_bytes(tree):
    return sum(np.asarray(x).nbytes for x in jax.tree.leaves(tree))


def _sharded_counts():
    reg = metrics_mod.get_registry()
    return (reg.counter_value("hvd_sharded_plan_hits_total"),
            reg.counter_value("hvd_sharded_plan_misses_total"))


# ---------------------------------------------------------------------------
# satellite 1: the shared leaf-sharding heuristic, pinned
# ---------------------------------------------------------------------------

SHAPE_GRID = [
    (), (1,), (37,), (2048,), (16384,), (128, 128), (128, 129),
    (256, 256), (3, 3, 64, 64), (7, 11), (8, 2048), (5, 3, 2),
]


@pytest.mark.parametrize("axis_size", [None, 2, 8])
def test_shard_dim_pins_fsdp_leaf_spec(axis_size):
    """fsdp annotations and the ZeRO-1 planner share one dim-choice rule:
    _leaf_spec must be exactly shard_dim rendered as a PartitionSpec."""
    for shape in SHAPE_GRID:
        leaf = jnp.zeros(shape, jnp.float32)
        spec = fsdp._leaf_spec(leaf, "dp", DEFAULT_MIN_SHARD_ELEMS,
                               axis_size)
        dim = shard_dim(shape, axis_size=axis_size)
        if dim is None:
            assert spec == P(), shape
        else:
            want = P(*("dp" if j == dim else None
                       for j in range(len(shape))))
            assert spec == want, shape


def test_shard_dim_pinned_values():
    # scalars and sub-threshold leaves replicate
    assert shard_dim(()) is None
    assert shard_dim((2048,)) is None
    # at threshold: largest dim wins; divisibility filters
    assert shard_dim((16384,)) == 0
    assert shard_dim((128, 128)) == 0
    assert shard_dim((8, 2048)) == 1
    # 129 not divisible by 8 → the divisible runner-up dim wins
    assert shard_dim((128, 129), axis_size=8) == 0
    assert shard_dim((127, 129), axis_size=8) is None
    # threshold is a parameter, not a constant
    assert shard_dim((100,), min_shard_elems=50) == 0


def test_should_shard_threshold():
    assert not should_shard(())
    assert not should_shard((DEFAULT_MIN_SHARD_ELEMS - 1,))
    assert should_shard((DEFAULT_MIN_SHARD_ELEMS,))


def test_assign_owners_deterministic_and_balanced():
    sizes = [100_000, 90_000, 80_000, 70_000, 10, 5]
    a = assign_owners(sizes, 2)
    assert a == assign_owners(sizes, 2)          # deterministic
    assert a[4] is None and a[5] is None         # sub-threshold replicate
    load = [0, 0]
    for s, o in zip(sizes, a):
        if o is not None:
            load[o] += s
    assert abs(load[0] - load[1]) <= max(sizes)  # greedy balance
    assert assign_owners(sizes, 1)[:4] == [0, 0, 0, 0]


# ---------------------------------------------------------------------------
# layout planner: determinism + digest sensitivity
# ---------------------------------------------------------------------------

def test_layout_deterministic_and_digest_sensitivity():
    params = _params()
    lay = sharded_mod.plan_shard_layout(params, 2, generation=0)
    assert lay.digest == sharded_mod.plan_shard_layout(
        params, 2, generation=0).digest
    # classification: w1 (65536), big (16384) shard; b1/w2/scale replicate
    leaves = jax.tree.leaves(params)
    sharded_idx = [i for g in lay.groups for i in g.indices]
    for i in lay.replicated:
        assert leaves[i].size < DEFAULT_MIN_SHARD_ELEMS
    for i in sharded_idx:
        assert leaves[i].size >= DEFAULT_MIN_SHARD_ELEMS
    assert sorted(sharded_idx + list(lay.replicated)) == list(
        range(lay.num_leaves))
    # padded per-rank cut is world-divisible and covers the group
    for g in lay.groups:
        assert g.shard_elems * lay.world_size >= g.total
    # every layout knob is digest-visible
    assert lay.digest != sharded_mod.plan_shard_layout(
        params, 4, generation=0).digest
    assert lay.digest != sharded_mod.plan_shard_layout(
        params, 2, generation=1).digest
    assert lay.digest != sharded_mod.plan_shard_layout(
        params, 2, min_shard_elems=2 ** 10, generation=0).digest


# ---------------------------------------------------------------------------
# tentpole A/B: simulated 2-rank world vs replicated, bitwise (fp32)
# ---------------------------------------------------------------------------

def test_simulated_ab_fp32_bitwise():
    opt = optax.adam(1e-3)
    params = _params()
    engines = sharded_mod.make_simulated_engines(opt, 2)
    states = [e.init(params) for e in engines]
    rep_step = _rep_step_fn(opt)
    rp, rs = params, opt.init(params)
    sp = params
    for step in range(5):
        gs = _grads(params, 2, step)
        sp, states = sharded_mod.simulated_step(engines, sp, gs, states)
        rp, rs = rep_step(rp, gs, rs)
    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_flatten_with_path(sp)[0],
            jax.tree_util.tree_flatten_with_path(rp)[0]):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"{jax.tree_util.keystr(ka)}: sharded != replicated (bitwise)")


def test_simulated_ab_bf16_tolerance():
    opt = optax.sgd(1e-2, momentum=0.9)
    params = _params(jnp.bfloat16)
    engines = sharded_mod.make_simulated_engines(opt, 2)
    states = [e.init(params) for e in engines]
    rep_step = _rep_step_fn(opt)
    rp, rs = params, opt.init(params)
    sp = params
    for step in range(3):
        gs = _grads(params, 2, step)
        sp, states = sharded_mod.simulated_step(engines, sp, gs, states)
        rp, rs = rep_step(rp, gs, rs)
    for a, b in zip(jax.tree.leaves(sp), jax.tree.leaves(rp)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0.05, atol=0.05)


def test_state_footprint_is_sharded():
    """The ZeRO-1 ledger: per-rank inner state ≈ replicated/world plus
    the replicated-leaf remainder."""
    opt = optax.adam(1e-3)
    params = _params()
    engines = sharded_mod.make_simulated_engines(opt, 2)
    states = [e.init(params) for e in engines]
    rep_bytes = _tree_bytes(opt.init(params))
    shard_bytes = _tree_bytes(states[0])
    lay = engines[0].layout
    assert lay.shard_fraction > 0.9   # this pytree is mostly shardable
    assert shard_bytes < 0.62 * rep_bytes   # ~0.5 + padding + replicated


def test_memledger_measures_sharded_state_attribution(monkeypatch):
    """The memory ledger turns the ZeRO-1 claim into a measured number:
    with HOROVOD_MEMLEDGER on, ``engine.init`` pushes the built state's
    bytes into the ``sharded_state`` component, and that measured value
    must land at ~1/N of the replicated optimizer state."""
    from horovod_tpu.utils import memledger as memledger_mod

    monkeypatch.setenv(env_schema.HOROVOD_MEMLEDGER, "1")
    # hermetic: a live session runtime from an earlier test must not pull
    # its staging-ring bytes over the suspect this test asserts on
    monkeypatch.setattr(memledger_mod.MemLedger, "_pull_components",
                        lambda self: {})
    memledger_mod.reset_ledger()
    ledger = memledger_mod.init_ledger(rank=0)
    try:
        opt = optax.adam(1e-3)
        params = _params()
        engines = sharded_mod.make_simulated_engines(opt, 2)
        [e.init(params) for e in engines]
        rep_bytes = _tree_bytes(opt.init(params))
        measured = ledger.components()["sharded_state"]
        # note_sharded_state records the LAST engine built (one engine
        # per process in a real world); each simulated rank holds the
        # same ~1/2 + replicated remainder
        assert 0.3 * rep_bytes < measured < 0.62 * rep_bytes, (
            f"measured sharded_state={measured} vs replicated={rep_bytes}")
        assert ledger.report()["suspect"] == "sharded_state"
    finally:
        memledger_mod.reset_ledger()


def test_plan_hit_rate_steady_state():
    opt = optax.adam(1e-3)
    params = _params()
    engines = sharded_mod.make_simulated_engines(opt, 2)
    states = [e.init(params) for e in engines]
    sp = params
    for step in range(2):   # warmup: compiles
        sp, states = sharded_mod.simulated_step(
            engines, sp, _grads(params, 2, step), states)
    h0, m0 = _sharded_counts()
    for step in range(2, 5):
        sp, states = sharded_mod.simulated_step(
            engines, sp, _grads(params, 2, step), states)
    h1, m1 = _sharded_counts()
    assert m1 == m0, "steady state must not compile new sharded plans"
    assert h1 > h0
    assert (h1 - h0) / ((h1 - h0) + (m1 - m0)) == 1.0


# ---------------------------------------------------------------------------
# elastic: resize 2 → 3 rebuilds the layout and converges identically
# ---------------------------------------------------------------------------

def test_elastic_resize_2_to_3_converges(monkeypatch):
    opt = optax.adam(1e-3)
    params = _params()
    monkeypatch.setenv(env_schema.HOROVOD_ELASTIC_GEN, "0")
    engines = sharded_mod.make_simulated_engines(opt, 2)
    states = [e.init(params) for e in engines]
    rep_step = _rep_step_fn(opt)
    rp, rs = params, opt.init(params)
    sp = params
    for step in range(3):
        gs = _grads(params, 2, step)
        sp, states = sharded_mod.simulated_step(engines, sp, gs, states)
        rp, rs = rep_step(rp, gs, rs)
    digest_before = engines[0].layout.digest
    # commit payload every rank can restore from under any future layout
    full = sharded_mod.simulated_full_state(engines, states)
    # --- resize: generation bump, new world, state re-materialized ------
    monkeypatch.setenv(env_schema.HOROVOD_ELASTIC_GEN, "1")
    sharded_mod.notify_reshard()
    engines3 = sharded_mod.make_simulated_engines(opt, 3)
    for e in engines3:
        e.ensure_layout(sp)
    assert engines3[0].layout.generation == 1
    assert engines3[0].layout.digest != digest_before
    states3 = [e.load_full_state(full, sp) for e in engines3]
    for step in range(3, 6):
        gs = _grads(params, 3, step)
        sp, states3 = sharded_mod.simulated_step(engines3, sp, gs, states3)
        rp, rs = rep_step(rp, gs, rs)
    for a, b in zip(jax.tree.leaves(sp), jax.tree.leaves(rp)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            "post-resize divergence from the replicated baseline")


# ---------------------------------------------------------------------------
# elastic resize through the shard checkpoint (utils/async_ckpt.py):
# a preempted world's shards restore into a different world bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w_from,w_to", [(2, 3), (3, 2)])
def test_restore_after_resize_from_shard_checkpoint(tmp_path, monkeypatch,
                                                    w_from, w_to):
    """N→M restore: each rank of the old world flushes its shard, the new
    world reassembles the full state by re-planning the SAVED layout and
    re-slicing through load_full_state — and the continued trajectory
    stays bitwise-equal to the replicated baseline (grow and shrink)."""
    from horovod_tpu.utils import async_ckpt

    opt = optax.adam(1e-3)
    params = _params()
    monkeypatch.setenv(env_schema.HOROVOD_ELASTIC_GEN, "0")
    engines = sharded_mod.make_simulated_engines(opt, w_from)
    states = [e.init(params) for e in engines]
    rep_step = _rep_step_fn(opt)
    rp, rs = params, opt.init(params)
    sp = params
    for step in range(3):
        gs = _grads(params, w_from, step)
        sp, states = sharded_mod.simulated_step(engines, sp, gs, states)
        rp, rs = rep_step(rp, gs, rs)
    # the durable artifact a preemption leaves behind: every rank's own
    # shard + the replicated leaves (params) on rank 0
    ckpts = [async_ckpt.AsyncCheckpointer(rank=r, world=w_from,
                                          directory=str(tmp_path))
             for r in range(w_from)]
    try:
        for r, c in enumerate(ckpts):
            assert c.snapshot(
                2, states[r],
                replicated={"params": sp} if r == 0 else None,
                layout=engines[r].layout)
            assert c.flush(deadline_s=10.0)
    finally:
        for c in ckpts:
            c.stop()
    # --- resize: generation bump, new world restores from disk ----------
    monkeypatch.setenv(env_schema.HOROVOD_ELASTIC_GEN, "1")
    sharded_mod.notify_reshard()
    engines2 = sharded_mod.make_simulated_engines(opt, w_to)
    states2, restored_params = [], None
    for e in engines2:
        e.ensure_layout(sp)
        manifest, state, replicated = async_ckpt.restore_sharded(
            str(tmp_path), sp, e)
        assert manifest["step"] == 2 and manifest["world"] == w_from
        states2.append(state)
        if replicated is not None:
            restored_params = replicated["params"]
    assert engines2[0].layout.generation == 1
    # params travelled in rank 0's replicated leaves, bitwise
    for a, b in zip(jax.tree.leaves(restored_params), jax.tree.leaves(sp)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    sp = restored_params
    for step in range(3, 6):
        gs = _grads(params, w_to, step)
        sp, states2 = sharded_mod.simulated_step(engines2, sp, gs, states2)
        rp, rs = rep_step(rp, gs, rs)
    for a, b in zip(jax.tree.leaves(sp), jax.tree.leaves(rp)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"post-restore ({w_from}->{w_to}) divergence from the "
            "replicated baseline")


def test_restore_refuses_changed_layout_threshold(tmp_path, monkeypatch):
    """The layout digest is load-bearing: a min_shard_elems change since
    the save must refuse the restore, never silently mis-slice."""
    from horovod_tpu.utils import async_ckpt

    opt = optax.adam(1e-3)
    params = _params()
    monkeypatch.setenv(env_schema.HOROVOD_ELASTIC_GEN, "0")
    engines = sharded_mod.make_simulated_engines(opt, 2)
    states = [e.init(params) for e in engines]
    ckpts = [async_ckpt.AsyncCheckpointer(rank=r, world=2,
                                          directory=str(tmp_path))
             for r in range(2)]
    try:
        for r, c in enumerate(ckpts):
            assert c.snapshot(0, states[r], layout=engines[r].layout)
            assert c.flush(deadline_s=10.0)
    finally:
        for c in ckpts:
            c.stop()
    manifest, payloads = async_ckpt.load_shards(str(tmp_path))
    with pytest.raises(async_ckpt.CheckpointError, match="digest"):
        async_ckpt.assemble_full_state(manifest, payloads, params,
                                       min_shard_elems=2 ** 10)


# ---------------------------------------------------------------------------
# satellite 6: plan signatures carry the elastic generation
# ---------------------------------------------------------------------------

def test_sharded_plan_key_includes_generation(monkeypatch):
    """A stale plan must be unreachable after a resize even if the cache
    were never cleared: the generation is part of every key."""
    monkeypatch.setenv(env_schema.HOROVOD_ELASTIC_GEN, "0")
    args = (None, 2, (16384,), ((16384,),), "float32", 8192, "deadbeef")
    C.sharded_pack_plan(*args)
    h0, m0 = _sharded_counts()
    C.sharded_pack_plan(*args)
    h1, m1 = _sharded_counts()
    assert (h1 - h0, m1 - m0) == (1, 0)
    monkeypatch.setenv(env_schema.HOROVOD_ELASTIC_GEN, "7")
    C.sharded_pack_plan(*args)
    h2, m2 = _sharded_counts()
    assert (h2 - h1, m2 - m1) == (0, 1), (
        "generation bump must miss onto a fresh plan, not replay")


def test_fused_chunk_plan_key_includes_generation(monkeypatch):
    from horovod_tpu.common import context as ctx_mod

    monkeypatch.setenv(env_schema.HOROVOD_ELASTIC_GEN, "0")
    ps = ctx_mod.global_process_set()
    reg = metrics_mod.get_registry()

    def counts():
        return (reg.counter_value("hvd_fused_plan_hits_total"),
                reg.counter_value("hvd_fused_plan_misses_total"))

    args = (ps, C.ReduceOp.SUM, 1.0, 1.0, ("t0", "t1"), (8, 8),
            ((8,), (8,)), np.float32, False)
    C.fused_chunk_plan(*args)
    h0, m0 = counts()
    C.fused_chunk_plan(*args)
    h1, m1 = counts()
    assert (h1 - h0, m1 - m0) == (1, 0)
    monkeypatch.setenv(env_schema.HOROVOD_ELASTIC_GEN, "9")
    C.fused_chunk_plan(*args)
    h2, m2 = counts()
    assert (h2 - h1, m2 - m1) == (0, 1)


def test_reshard_invalidation_counts_with_reason(monkeypatch):
    """The elastic reinit path drops plans through the accounting path:
    the eviction counter must attribute the drop to `invalidation`."""
    monkeypatch.setenv(env_schema.HOROVOD_ELASTIC_GEN, "0")
    C.sharded_pack_plan(None, 2, (16384,), ((16384,),), "float32",
                        8192, "cafebabe")

    def inval_count():
        return sum(
            c["value"] for c in metrics_mod.get_registry().snapshot()["counters"]
            if c["name"] == "hvd_fused_plan_evictions_total"
            and c["labels"].get("reason") == "invalidation")

    i0 = inval_count()
    dropped = C.invalidate_fused_plans()
    assert dropped >= 1
    assert inval_count() - i0 == dropped


# ---------------------------------------------------------------------------
# satellite 5: zero-cost when off — no sharded series may exist
# ---------------------------------------------------------------------------

def test_zero_cost_when_off_subprocess():
    """The metrics registry is process-global, so the only honest probe
    is a fresh interpreter: mode off → zero hvd_sharded_* series even
    after building a distributed optimizer and touching the planner
    module."""
    prog = (
        "import horovod_tpu as hvd, optax\n"
        "import horovod_tpu.opt.sharded  # import alone must not register\n"
        "opt = hvd.DistributedGradientTransformation(optax.adam(1e-3))\n"
        "names = {c['name'] for c in hvd.metrics_snapshot()['counters']}\n"
        "names |= {g['name'] for g in hvd.metrics_snapshot()['gauges']}\n"
        "bad = sorted(n for n in names if n.startswith('hvd_sharded'))\n"
        "assert not bad, bad\n"
        "print('ZERO_COST_OK')\n")
    env = dict(os.environ)
    env.pop("HOROVOD_SHARDED_UPDATE", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", prog], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "ZERO_COST_OK" in out.stdout


# ---------------------------------------------------------------------------
# traced flavor: ShardedDistributedOptimizer under shard_map
# ---------------------------------------------------------------------------

def _get_shard_map():
    sm = getattr(jax, "shard_map", None)
    if sm is not None:
        return sm, {"check_vma": False}
    try:
        from jax.experimental.shard_map import shard_map
        return shard_map, {"check_rep": False}
    except ImportError:
        pytest.skip("no shard_map in this jax version")


def test_traced_matches_distributed_gt():
    shard_map, kw = _get_shard_map()
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = Mesh(np.array(devs[:8]), ("hvd",))
    params = _params()
    gs = _grads(params, 8, 0)
    stacked = jax.tree.map(lambda *g: jnp.stack(g), *gs)

    def run(opt):
        state = opt.init(params)

        def step(g, p, s):
            g = jax.tree.map(lambda x: x[0], g)   # (1,)+S per-chip block
            u, _ = opt.update(g, s, p)
            return optax.apply_updates(p, u)

        f = jax.jit(shard_map(step, mesh=mesh,
                              in_specs=(P("hvd"), P(), P()),
                              out_specs=P(), **kw))
        return f(stacked, params, state)

    sharded = run(sharded_mod.ShardedDistributedOptimizer(
        optax.adam(1e-3), num_shards=8))
    replicated = run(hvd.DistributedGradientTransformation(optax.adam(1e-3)))
    for a, b in zip(jax.tree.leaves(sharded), jax.tree.leaves(replicated)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-6)


def test_traced_init_outside_trace_needs_num_shards():
    opt = sharded_mod.ShardedDistributedOptimizer(optax.adam(1e-3))
    with pytest.raises(ValueError, match="num_shards"):
        opt.init(_params())
    # state 1/N: the fp32 shard leaf is ceil(sharded_total / 8)
    opt8 = sharded_mod.ShardedDistributedOptimizer(optax.adam(1e-3),
                                                   num_shards=8)
    state = opt8.init(_params())
    lay = sharded_mod.plan_shard_layout(_params(), 8, generation=0)
    mu = state[0].mu  # optax.adam ScaleByAdamState
    assert mu["shard"]["float32"].shape == (lay.groups[0].shard_elems,)


# ---------------------------------------------------------------------------
# satellite 3: framework shims
# ---------------------------------------------------------------------------

def test_gt_routing_rejects_incompatible_knobs():
    with pytest.raises(ValueError, match="backward_passes_per_step"):
        hvd.DistributedGradientTransformation(
            optax.adam(1e-3), sharded_update=True, backward_passes_per_step=2)
    with pytest.raises(ValueError, match="compression"):
        hvd.DistributedGradientTransformation(
            optax.adam(1e-3), sharded_update=True,
            compression=hvd.Compression.bf16)


@pytest.fixture
def one_torch_thread():
    """Bitwise comparisons of two torch models: hold torch to one
    intra-op thread, so that no kernel's reduction order can follow the
    machine's load (MKL and OpenMP pick their thread count dynamically)."""
    torch = pytest.importorskip("torch")
    before = torch.get_num_threads()
    torch.set_num_threads(1)
    yield
    torch.set_num_threads(before)


def test_torch_sharded_matches_plain_world1(one_torch_thread):
    torch = pytest.importorskip("torch")
    import horovod_tpu.torch as hvdt

    torch.manual_seed(0)
    m1 = torch.nn.Sequential(torch.nn.Linear(200, 100),
                             torch.nn.Linear(100, 1))
    torch.manual_seed(0)
    m2 = torch.nn.Sequential(torch.nn.Linear(200, 100),
                             torch.nn.Linear(100, 1))
    o1 = hvdt.DistributedOptimizer(
        torch.optim.Adam(m1.parameters(), lr=1e-2),
        named_parameters=m1.named_parameters())
    o2 = hvdt.DistributedOptimizer(
        torch.optim.Adam(m2.parameters(), lr=1e-2),
        named_parameters=m2.named_parameters(),
        sharded_update=True, min_shard_elems=2 ** 10)
    assert type(o2).__name__ == "ShardedDistributedAdam"
    # whole-leaf ownership: the big kernel is owned, small leaves replicate
    owners = list(o2._owners.values())
    assert 0 in owners and None in owners
    x = torch.randn(16, 200)
    for _ in range(3):
        for m, o in ((m1, o1), (m2, o2)):
            o.zero_grad()
            m(x).pow(2).mean().backward()
            o.step()
    for p1, p2 in zip(m1.parameters(), m2.parameters()):
        assert torch.equal(p1, p2)


def test_torch_sharded_rejects_adasum():
    torch = pytest.importorskip("torch")
    import horovod_tpu.torch as hvdt

    if hvdt.cross_size() <= 1:
        pytest.skip("Adasum wrapper requires a >1 world to engage")
    m = torch.nn.Linear(4, 4)
    with pytest.raises(ValueError, match="Adasum"):
        hvdt.DistributedOptimizer(torch.optim.SGD(m.parameters(), lr=0.1),
                                  op=hvdt.Adasum, sharded_update=True)


def test_tf_keras_shims_reject_sharded():
    tf = pytest.importorskip("tensorflow")
    import horovod_tpu.tensorflow as hvdtf

    with pytest.raises(ValueError, match="sharded_update"):
        hvdtf.DistributedOptimizer(tf.keras.optimizers.SGD(),
                                   sharded_update=True)
    import horovod_tpu.keras as hvdk

    with pytest.raises(ValueError, match="sharded_update"):
        hvdk.DistributedOptimizer(tf.keras.optimizers.SGD(),
                                  sharded_update=True)
    # env knob must NOT raise — warn once and run replicated
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv(env_schema.HOROVOD_SHARDED_UPDATE, "1")
        hvdtf.DistributedOptimizer(tf.keras.optimizers.SGD())


# ---------------------------------------------------------------------------
# satellite 2: the CPU microbench, smoke-tested
# ---------------------------------------------------------------------------

def test_microbench_smoke():
    spec = importlib.util.spec_from_file_location(
        "sharded_update_bench",
        os.path.join(REPO, "benchmarks", "sharded_update.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    res = mod.measure(world=2, steps=3, warmup=1)
    assert res["update_wire_reduction_x"] >= 1.5   # acceptance floor
    assert res["plan_hit_rate"] == 1.0             # steady-state replay
    assert res["param_allgather_wire_bytes"] > 0   # reported, separately
    assert res["state_bytes_sharded_per_rank"] < 0.62 * res[
        "state_bytes_replicated"]
    json.dumps(res)   # the printed artifact must be JSON-able
