"""Steady-state fast path: compiled fused-chunk plans, staging ring and
chunk-boundary fusion (ISSUE 3).

The plan tests drive a PRIVATE, non-started BackgroundRuntime and call
``run_cycle()`` inline — the background thread's drain timing would
otherwise split a multi-tensor enqueue across cycles and make chunk
signatures (and therefore hit/miss counts) nondeterministic.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu.common import context as ctx_mod
from horovod_tpu.common.env import RuntimeConfig
from horovod_tpu.ops import collectives as C
from horovod_tpu.ops.queue import BackgroundRuntime, TensorEntry
from horovod_tpu.utils import metrics as metrics_mod


def _private_runtime(threshold=None, plans=True, slots=None):
    cfg = RuntimeConfig()
    cfg.stall_check_disable = True
    cfg.fused_plan_disable = not plans
    if threshold is not None:
        cfg.fusion_threshold_bytes = threshold
    if slots is not None:
        cfg.staging_ring_slots = slots
    return BackgroundRuntime(ctx_mod.global_process_set(), cfg)


def _run_chunked(rt, arrays, names=None):
    """Enqueue arrays, run one cycle inline, wait and return results."""
    handles = []
    for i, a in enumerate(arrays):
        n = names[i] if names else f"fp.{i}"
        handles.append(rt.enqueue(TensorEntry(name=n, op="allreduce",
                                              tensor=a)))
    rt.run_cycle()
    return [rt.handles.wait(h) for h in handles]


def _counts():
    reg = metrics_mod.get_registry()
    return (reg.counter_value("hvd_fused_plan_hits_total"),
            reg.counter_value("hvd_fused_plan_misses_total"))


# ---------------------------------------------------------------------------
# acceptance: steady state replays ONE compiled plan per chunk per cycle
# ---------------------------------------------------------------------------

def test_plan_cache_hits_after_warmup():
    rt = _private_runtime()
    arrays = [np.arange(24, dtype=np.float32).reshape(4, 6),
              np.full((7,), 3.0, np.float32),
              np.ones((2, 2, 2), np.float32)]
    h0, m0 = _counts()
    for cycle in range(5):
        outs = _run_chunked(rt, arrays)
        for a, o in zip(arrays, outs):
            assert np.asarray(o).shape == a.shape
            np.testing.assert_allclose(np.asarray(o), a)
    hits, misses = _counts()
    # identical chunk signature every cycle: compiled exactly once, then
    # pure replay — one program dispatch per chunk per cycle
    assert misses - m0 == 1
    assert hits - h0 == 4


def test_plans_disabled_uses_legacy_path():
    rt = _private_runtime(plans=False)
    h0, m0 = _counts()
    arrays = [np.ones((5,), np.float32), np.zeros((3, 3), np.float32)]
    for _ in range(3):
        outs = _run_chunked(rt, arrays)
    hits, misses = _counts()
    assert (hits, misses) == (h0, m0)  # no plan lookups at all
    np.testing.assert_allclose(np.asarray(outs[0]), arrays[0])


# ---------------------------------------------------------------------------
# satellite: chunk-boundary fusion (f32 host path / bf16 device path)
# ---------------------------------------------------------------------------

def _make_arrays(shapes, dtype):
    """f32 rides the host (numpy) path, bf16 rides the device-resident
    path (numpy has no native bfloat16) — together the two parametrize
    axes cover both staging routes."""
    rng = np.random.default_rng(42)
    out = []
    for s in shapes:
        base = rng.standard_normal(s).astype(np.float32)
        if dtype == "bfloat16":
            out.append(jax.block_until_ready(jnp.asarray(base, jnp.bfloat16)))
        else:
            out.append(base)
    return out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_single_tensor_larger_than_threshold(dtype):
    """A tensor bigger than fusion_threshold_bytes must go through alone
    — not be dropped, split, or block the tensors behind it."""
    rt = _private_runtime(threshold=1024)
    big = _make_arrays([(2048,)], dtype)[0]  # 4-8x the threshold
    small = _make_arrays([(8,), (3, 3)], dtype)
    _, m0 = _counts()
    outs = _run_chunked(rt, [big] + small, names=["big", "s0", "s1"])
    _, m1 = _counts()
    assert m1 - m0 == 2  # chunk [big] + chunk [s0, s1]
    for a, o in zip([big] + small, outs):
        o = np.asarray(o)
        assert o.shape == tuple(a.shape)
        assert str(o.dtype) == dtype
        np.testing.assert_allclose(o, np.asarray(a))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("ntensors", [1, 2, 7])
def test_mixed_chunks_unpack_exact(dtype, ntensors):
    """Mixed-shape chunks (spanning a chunk boundary for the larger
    counts) must unpack to the exact original shapes/dtypes/values."""
    shapes = [(64,), (7, 11), (128,), (2, 3, 4), (330,), (1,),
              (96,)][:ntensors]
    rt = _private_runtime(threshold=1000)  # 250 f32 elems per chunk
    arrays = _make_arrays(shapes, dtype)
    for _ in range(3):  # includes warm plan replays
        outs = _run_chunked(rt, arrays)
    for a, o in zip(arrays, outs):
        o = np.asarray(o)
        assert o.shape == tuple(a.shape)
        assert str(o.dtype) == dtype
        np.testing.assert_allclose(o, np.asarray(a))


def test_zero_element_tensor_roundtrips():
    """Zero-element chunks route through the legacy path (no plan covers
    them) and must still resolve their handles."""
    rt = _private_runtime()
    out = _run_chunked(rt, [np.zeros((0, 4), np.float32)])[0]
    assert np.asarray(out).shape == (0, 4)


# ---------------------------------------------------------------------------
# tentpole: autotuner threshold changes invalidate affected plans
# ---------------------------------------------------------------------------

def test_threshold_change_invalidates_plans():
    reg = metrics_mod.get_registry()
    rt = _private_runtime(threshold=65536)
    arrays = [np.ones((32,), np.float32), np.ones((16,), np.float32)]
    _run_chunked(rt, arrays)
    assert C._plan_count > 0
    inv0 = reg.counter_value("hvd_fused_plan_evictions_total")
    rt.set_fusion_threshold(4096)
    assert C._plan_count == 0
    assert reg.counter_value("hvd_fused_plan_evictions_total") > inv0
    # and the next cycle compiles fresh plans against the new boundaries
    _, m0 = _counts()
    outs = _run_chunked(rt, arrays)
    _, m1 = _counts()
    assert m1 - m0 == 1
    np.testing.assert_allclose(np.asarray(outs[0]), arrays[0])
    # no-op change must NOT invalidate
    _run_chunked(rt, arrays)
    n_before = C._plan_count
    rt.set_fusion_threshold(4096)
    assert C._plan_count == n_before


def test_tuned_params_route_through_setter():
    rt = _private_runtime(threshold=65536)
    _run_chunked(rt, [np.ones((32,), np.float32)])
    assert C._plan_count > 0
    rt._apply_tuned_params({"fusion": 8192, "cycle": 2.0})
    assert rt.fusion_threshold == 8192
    assert rt.cycle_time_ms == 2.0
    assert C._plan_count == 0


# ---------------------------------------------------------------------------
# tentpole: persistent staging ring
# ---------------------------------------------------------------------------

def test_staging_ring_reuse_and_no_aliasing_corruption():
    reg = metrics_mod.get_registry()
    rt = _private_runtime(threshold=65536, slots=2)
    r0 = reg.counter_value("hvd_staging_reuse_total")
    kept = []  # earlier cycles' results, held across later ring reuse
    payloads = []
    for cycle in range(4):
        arrays = [np.full((100,), float(cycle), np.float32),
                  np.full((50,), float(cycle) + 0.5, np.float32)]
        payloads.append(arrays)
        kept.append(_run_chunked(rt, arrays))
    assert reg.counter_value("hvd_staging_reuse_total") > r0
    # a reused slot must never corrupt a prior cycle's results (the
    # in-flight token gates reuse until the consumer finished reading)
    for arrays, outs in zip(payloads, kept):
        for a, o in zip(arrays, outs):
            np.testing.assert_allclose(np.asarray(o), a)


def test_staging_ring_oversize_falls_back_to_alloc():
    from horovod_tpu._native import StagingRing

    ring = StagingRing(64, slots=2)
    buf, lease = ring.acquire(1024)  # oversize: bypass
    assert buf is None and lease is None
    b1, l1 = ring.acquire(32)
    b2, l2 = ring.acquire(32)
    assert b1 is not None and b2 is not None
    b3, l3 = ring.acquire(32)  # both slots leased
    assert b3 is None and l3 is None
    l1.retire(None)  # immediate free
    b4, l4 = ring.acquire(16)
    assert b4 is not None
    l2.retire(None)
    l4.retire(None)


def test_staging_ring_waits_for_inflight_token():
    from horovod_tpu._native import StagingRing

    class Token:
        def __init__(self):
            self.ready = False

        def is_ready(self):
            return self.ready

    ring = StagingRing(64, slots=1)
    b1, l1 = ring.acquire(16)
    tok = Token()
    l1.retire(tok)
    b2, l2 = ring.acquire(16)
    assert b2 is None  # consumer still reading the staged bytes
    tok.ready = True
    b3, l3 = ring.acquire(16)
    assert b3 is not None
    l3.retire(None)


def test_fusion_buffer_resize_adopts_capacity():
    from horovod_tpu._native import FusionBuffer

    fb = FusionBuffer(128, slots=2)
    flat, lease = fb.pack_leased([np.arange(8, dtype=np.float32)])
    np.testing.assert_allclose(flat, np.arange(8, dtype=np.float32))
    if lease is not None:
        lease.retire(None)
    fb.resize(4096)
    assert fb.ring.capacity == 4096
    flat2, lease2 = fb.pack_leased([np.ones((16,), np.float32)])
    assert lease2 is not None  # fits the grown ring
    np.testing.assert_allclose(flat2, np.ones((16,), np.float32))
    lease2.retire(None)


# ---------------------------------------------------------------------------
# satellite: fusable-group key is the stable process-set name, not id()
# ---------------------------------------------------------------------------

def test_group_key_merges_default_and_explicit_global_set():
    """An entry with process_set=None resolves to the runtime's global
    set at dispatch; keying on the stable set NAME fuses it with an
    entry naming the global set explicitly (id()-keying split them —
    and, worse, could alias two different sets after GC id reuse)."""
    rt = _private_runtime()
    gps = ctx_mod.global_process_set()
    a = np.ones((8,), np.float32)
    b = np.full((4,), 2.0, np.float32)
    _, m0 = _counts()
    h1 = rt.enqueue(TensorEntry(name="gk.none", op="allreduce", tensor=a,
                                process_set=None))
    h2 = rt.enqueue(TensorEntry(name="gk.global", op="allreduce", tensor=b,
                                process_set=gps))
    rt.run_cycle()
    o1, o2 = rt.handles.wait(h1), rt.handles.wait(h2)
    _, m1 = _counts()
    assert m1 - m0 == 1  # ONE fused chunk => one plan compile
    np.testing.assert_allclose(np.asarray(o1), a)
    np.testing.assert_allclose(np.asarray(o2), b)


# ---------------------------------------------------------------------------
# satellite: cycle_overhead microbench smoke (fast-path CI regression net)
# ---------------------------------------------------------------------------

def test_cycle_overhead_microbench_smoke():
    import importlib.util as ilu
    import os as _os

    spec = ilu.spec_from_file_location(
        "_cycle_overhead_test",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), "benchmarks", "cycle_overhead.py"))
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    stats = mod.measure(plans_enabled=True, cycles=5, warmup=2)
    assert stats["tensors_per_cycle"] == 20
    # steady state must be pure replay: every lookup after warmup a hit
    assert stats["plan_hit_rate"] == 1.0
