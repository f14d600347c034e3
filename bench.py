"""Headline benchmark: ResNet-50 synthetic training throughput + the
BASELINE.md tracked configs.

Mirrors the reference harness
(/root/reference/examples/tensorflow2/tensorflow2_synthetic_benchmark.py):
synthetic ImageNet-shaped data, full training step (forward + backward +
gradient allreduce + update), report images/sec — plus:

- ``mfu``: model FLOPs utilization against the detected chip's bf16 peak
  (ResNet-50 fwd = 2 × 4.09 GMACs = 8.18 GFLOP/img at 224², training ≈
  3× fwd — the standard 2-FLOPs-per-MAC convention, audited against
  XLA cost_analysis in benchmarks/conv_analysis_cpu.py).
- ``allreduce_gbps``: eager fused allreduce bandwidth (BASELINE's stated
  collective metric; config 3 adds bf16-compressed wire format).
- ``adasum_step_ms``: Adasum reduction step (config 4).
- ``moe_alltoall_ms``: expert-parallel all_to_all exchange (config 5).

Timing uses an end-of-run *value fetch* as the sync point: the scalar
cannot reach the host before the device work that produces it is done.

Runs in ONE process and only on a TPU: with no chip it exits non-zero
and prints no metric line, a failing sub-benchmark fails the run, and an
unknown ``device_kind`` is an error (no assumed peak).

Prints ONE JSON line:
  {"metric": "<model>_images_per_sec_per_chip", "value": N,
   "unit": "images/sec/chip", "vs_baseline": N, "mfu": F, "extras": {...}}
where <model> is resnet50 (default), resnet101, vgg16, or inception3
(``HVD_BENCH_MODEL=...``) — the reference's full published benchmark
suite (docs/benchmarks.rst:11-41); resnet101 is apples-to-apples with
its only absolute number.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from collections import namedtuple

import horovod_tpu as hvd
from horovod_tpu.models import InceptionV3, ResNet50, ResNet101, VGG16
from horovod_tpu.models.inception import INCEPTION3_FWD_FLOP_PER_IMG
from horovod_tpu.models.vgg import VGG16_FWD_FLOP_PER_IMG
from horovod_tpu.parallel import data_parallel_step, shard_batch
from horovod_tpu.utils.compile_cache import enable_compilation_cache

BASELINE_PER_DEVICE = 1656.82 / 16  # reference ResNet-101, img/s per GPU


def _git_sha() -> "str | None":
    """HEAD commit of the repo this bench ran from (None outside a git
    checkout / without git): banked baselines must be attributable to
    the code that produced them, not just a date."""
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
    except (OSError, subprocess.SubprocessError):
        return None
    sha = out.stdout.strip()
    return sha if out.returncode == 0 and sha else None


def _knob_snapshot() -> dict:
    """The ACTIVE RuntimeConfig as a flat JSON-able dict — post-env,
    post-autotune (the runtime's live config object, which the autotuner
    mutates in place), so a banked result records the knobs that
    actually ran, not the defaults."""
    import dataclasses

    from horovod_tpu.common import context as _context_mod
    from horovod_tpu.common.env import RuntimeConfig

    cfg = getattr(_context_mod.context(), "config", None)
    if not dataclasses.is_dataclass(cfg):
        cfg = RuntimeConfig.from_env()
    return {k: (v if isinstance(v, (int, float, bool, str, type(None)))
                else str(v))
            for k, v in dataclasses.asdict(cfg).items()}

# FLOPs (2 x MACs — the standard MFU convention, and what XLA's own
# cost_analysis counts). ResNet-50 fwd = 4.09 GMACs = 8.18 GFLOP/img at
# 224^2; ResNet-101 = 7.8 GMACs. Rounds 1-4 mistakenly used the MAC
# count as the FLOP count, UNDERSTATING MFU by ~2x (audited against
# jax cost_analysis: analytic/xla = 0.47 before the fix, ~0.95 after —
# benchmarks/conv_analysis_cpu.py, docs/benchmarks.md round-5 section).
RESNET50_FWD_FLOP_PER_IMG = 2 * 4.09e9
RESNET101_FWD_FLOP_PER_IMG = 2 * 7.8e9
TRAIN_FLOP_MULT = 3.0  # fwd + bwd ≈ 3x fwd

# HVD_BENCH_MODEL picks the benchmarked model — the reference's full
# published benchmark suite (docs/benchmarks.rst:11-41: ResNet-101,
# Inception V3, VGG-16) plus resnet50 (BASELINE.json's driver target,
# the default). resnet101 is the apples-to-apples row for the
# reference's ONLY absolute number. resnet_knobs marks models that
# accept the space_to_depth/conv_impl stem options (swept on resnet50).
# default_batch/scan are the no-tuned-file starting points: conservative
# for the models never batch-swept on chip (an OOM burns a window).
_BenchModel = namedtuple(
    "_BenchModel",
    "metric fwd_flop cls image_size resnet_knobs default_batch default_scan")
_BENCH_MODELS = {
    "resnet50": _BenchModel("resnet50_images_per_sec_per_chip",
                            RESNET50_FWD_FLOP_PER_IMG, ResNet50, 224,
                            True, 128, 32),
    "resnet101": _BenchModel("resnet101_images_per_sec_per_chip",
                             RESNET101_FWD_FLOP_PER_IMG, ResNet101, 224,
                             True, 128, 8),
    "vgg16": _BenchModel("vgg16_images_per_sec_per_chip",
                         VGG16_FWD_FLOP_PER_IMG, VGG16, 224,
                         False, 64, 8),
    "inception3": _BenchModel("inception3_images_per_sec_per_chip",
                              INCEPTION3_FWD_FLOP_PER_IMG, InceptionV3, 299,
                              False, 64, 8),
}

# bf16 peak FLOP/s by device kind (first matching substring wins;
# published per-chip peaks, Google Cloud TPU documentation)
PEAK_FLOPS = [
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
    ("v6", 918e12), ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
]


def chip_peak_flops() -> float:
    """bf16 peak of the attached chip. A device that is not in the table
    is an error, not a default: an assumed peak makes the mfu a guess."""
    kind = jax.devices()[0].device_kind
    for sub, peak in PEAK_FLOPS:
        if sub in kind.lower():
            return peak
    raise ValueError(
        f"no bf16 peak known for device_kind {kind!r}; add it to "
        "bench.PEAK_FLOPS with its source")


def _sync(x) -> float:
    """True synchronization: fetch a scalar value."""
    return float(jnp.asarray(x).reshape(-1)[0])


def _env_s2d() -> bool:
    """Single source of truth for the stem-config env parse: the model
    builder and the result-artifact metadata must agree byte-for-byte."""
    return os.environ.get("HVD_BENCH_S2D", "0") == "1"


def _env_conv_impl() -> str:
    return os.environ.get("HVD_BENCH_CONV_IMPL", "native")


def bench_resnet(per_chip_batch: int, warmup: int = 5, iters: int = 30,
                 scan_steps: int = 1, model_fn=None, image_size: int = 224,
                 num_classes: int = 1000):
    """Full training-step throughput.

    ``scan_steps > 1`` runs that many optimizer steps per dispatch under
    ``lax.scan`` (same data each sub-step — synthetic-benchmark
    convention), which separates device throughput from per-dispatch
    host latency.
    """
    n = hvd.size()
    s2d = _env_s2d()
    conv_impl = _env_conv_impl()

    def default_model():
        spec = _BENCH_MODELS[_bench_model_name()]
        if spec.resnet_knobs:
            return spec.cls(num_classes=num_classes, dtype=jnp.bfloat16,
                            space_to_depth=s2d, conv_impl=conv_impl)
        return spec.cls(num_classes=num_classes, dtype=jnp.bfloat16)

    model = (model_fn or default_model)()
    rng = jax.random.PRNGKey(0)
    batch = per_chip_batch * n
    # each process makes the shard of its own chips and shard_batch
    # spreads it over them: a bare jnp.asarray would park the whole batch
    # on the first device and leave the step to reshard it on every call
    local = per_chip_batch * hvd.global_process_set().local_size
    host_images = np.random.RandomState(0).randn(
        local, image_size, image_size, 3).astype(jnp.bfloat16)
    images, labels = shard_batch((
        host_images,
        np.random.RandomState(1).randint(0, num_classes, (local,))))

    # "dropout" rng: consumed by dropout-bearing models (VGG); flax
    # ignores unused rng streams for the others. BN-less models (VGG
    # again) have no batch_stats collection — carry an empty dict and
    # skip the mutable round trip.
    variables = model.init({"params": rng, "dropout": jax.random.PRNGKey(1)},
                           jnp.asarray(host_images[:2]), train=True)
    params = variables["params"]
    has_bn = "batch_stats" in variables
    batch_stats = variables["batch_stats"] if has_bn else {}
    opt = hvd.DistributedOptimizer(optax.sgd(0.05, momentum=0.9))
    opt_state = opt.init(params)
    params = hvd.broadcast_parameters(params, root_rank=0)

    def one_step(params, batch_stats, opt_state, step_rng, images, labels):
        # fresh dropout mask each sub-step, so scan cannot hoist the
        # mask generation out of the measured loop
        step_rng, drop = jax.random.split(step_rng)

        def loss_fn(p):
            vs = {"params": p}
            if has_bn:
                vs["batch_stats"] = batch_stats
            logits, upd = model.apply(
                vs, images, train=True,
                mutable=["batch_stats"] if has_bn else [],
                rngs={"dropout": drop})
            onehot = jax.nn.one_hot(labels, num_classes)
            loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))
            return loss, (upd["batch_stats"] if has_bn else batch_stats)

        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, step_rng, loss

    def step(train_state, opt_state, images, labels):
        params, batch_stats, step_rng = train_state
        if scan_steps <= 1:
            params, batch_stats, opt_state, step_rng, loss = one_step(
                params, batch_stats, opt_state, step_rng, images, labels)
        else:
            def body(carry, _):
                p, b, s, r = carry
                p, b, s, r, loss = one_step(p, b, s, r, images, labels)
                return (p, b, s, r), loss

            (params, batch_stats, opt_state, step_rng), losses = jax.lax.scan(
                body, (params, batch_stats, opt_state, step_rng), None,
                length=scan_steps)
            loss = losses[-1]
        return ((params, batch_stats, step_rng), opt_state,
                jax.lax.pmean(loss, "hvd"))

    compiled = data_parallel_step(step, batch_argnums=(2, 3))
    state = (params, batch_stats, jax.random.PRNGKey(2))
    for _ in range(warmup):
        state, opt_state, loss = compiled(state, opt_state, images, labels)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, opt_state, loss = compiled(state, opt_state, images, labels)
    _sync(loss)
    dt = time.perf_counter() - t0
    img_per_sec = batch * iters * max(scan_steps, 1) / dt
    return img_per_sec / n


def bench_eager_allreduce(nbytes: int = 64 << 20, iters: int = 10,
                          compressed: bool = False,
                          device_resident: bool = False):
    """Eager fused allreduce GB/s (BASELINE metric; config 3 = compressed
    wire). Single process: measures the host↔device staging + reduction
    path; multi-process adds the cross-process collective.
    ``device_resident``: feed a committed jax.Array (the fast path that
    skips host staging — VERDICT r2 #7)."""
    from horovod_tpu.ops.compression import Compression
    from horovod_tpu.utils import metrics as metrics_mod

    x = np.random.RandomState(2).randn(nbytes // 4).astype(np.float32)
    if device_resident:
        x = jnp.asarray(x)
        jax.block_until_ready(x)
    comp = Compression.bf16 if compressed else Compression.none
    tag = ("c" if compressed else "r") + ("d" if device_resident else "")

    def run_one(i):
        t, ctx = comp.compress(jnp.asarray(x)) if compressed else (x, None)
        h = hvd.allreduce_async(t if device_resident else np.asarray(t),
                                name=f"bench.ar.{tag}{i}", op=hvd.Sum)
        out = hvd.synchronize(h)
        return comp.decompress(out, ctx) if compressed else out

    run_one(0)
    # bytes come from the runtime's own wire counter, so the reported
    # GB/s is what actually moved: identical to nbytes for the raw
    # config, honest post-compression bytes for the compressed one
    reg = metrics_mod.get_registry()
    b0 = reg.counter_value("hvd_allreduce_bytes_total")
    t0 = time.perf_counter()
    out = None
    for i in range(1, iters + 1):
        out = run_one(i)
    _sync(out)
    dt = (time.perf_counter() - t0) / iters
    wire_bytes = (reg.counter_value("hvd_allreduce_bytes_total") - b0) / iters
    if wire_bytes <= 0:
        wire_bytes = nbytes  # counter unavailable: keep the old arithmetic
    return wire_bytes / dt / 1e9


def bench_adasum(nelem: int = 1 << 22, iters: int = 10):
    """Adasum reduction step over the chip mesh (config 4)."""
    from horovod_tpu.parallel import create_mesh
    from jax.sharding import PartitionSpec as P

    n = len(jax.devices())
    mesh = create_mesh({"hvd": n})
    x = jnp.asarray(np.random.RandomState(3).randn(n, nelem // n), jnp.float32)

    def per_chip(xl):
        return hvd.allreduce(xl[0], op=hvd.Adasum, axis_name="hvd")

    f = jax.jit(jax.shard_map(per_chip, mesh=mesh, in_specs=P("hvd"),
                              out_specs=P(), check_vma=False))
    _sync(f(x))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = f(x)
    _sync(out)
    return (time.perf_counter() - t0) / iters * 1e3


def bench_moe_alltoall(tokens_per_chip: int = 2048, d_model: int = 512,
                       iters: int = 20):
    """Expert-parallel all_to_all dispatch+combine exchange (config 5)."""
    from horovod_tpu.parallel import create_mesh
    from jax.sharding import PartitionSpec as P
    from jax import lax

    n = len(jax.devices())
    mesh = create_mesh({"ep": n})
    x = jnp.asarray(np.random.RandomState(4).randn(
        n * tokens_per_chip, d_model), jnp.bfloat16)

    def per_chip(xl):
        t = xl.reshape(n, tokens_per_chip // n, d_model)
        y = lax.all_to_all(t, "ep", split_axis=0, concat_axis=0, tiled=False)
        y = lax.all_to_all(y, "ep", split_axis=0, concat_axis=0, tiled=False)
        return y.reshape(xl.shape)

    f = jax.jit(jax.shard_map(per_chip, mesh=mesh, in_specs=P("ep"),
                              out_specs=P("ep"), check_vma=False))
    _sync(jnp.sum(f(x)))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = f(x)
    _sync(jnp.sum(out))
    return (time.perf_counter() - t0) / iters * 1e3


def _require_chip():
    """No chip, no benchmark: a CPU timing under a chip metric's name is
    worse than no number."""
    d = jax.devices()[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU and JAX found none (platform "
            f"{d.platform!r}, device_kind {d.device_kind!r})")


def main():
    _bench_model_name()  # a config typo fails before any device work
    enable_compilation_cache()
    hvd.init()
    _require_chip()
    peak = chip_peak_flops()
    quick = "--quick" in sys.argv  # smoke sizes, still on the chip
    # defaults come from the last MFU campaign on this machine when
    # available (benchmarks/mfu_campaign.py writes the winning config);
    # env vars always win
    tuned_batch, tuned_scan = _resolve_tuned_config(
        quick, single_process=hvd.cross_size() <= 1)
    per_chip = _sync_int_env("HVD_BENCH_BATCH", 32 if quick else tuned_batch)
    scan_steps = _sync_int_env("HVD_BENCH_SCAN_STEPS",
                               1 if quick else tuned_scan)
    spec = _BENCH_MODELS[_bench_model_name()]
    per_chip_ips = bench_resnet(per_chip, warmup=2 if quick else 5,
                                iters=3 if quick else 8,
                                scan_steps=scan_steps,
                                image_size=spec.image_size)
    metric_name, fwd_flop = spec.metric, spec.fwd_flop
    flops = per_chip_ips * fwd_flop * TRAIN_FLOP_MULT
    mfu = flops / peak
    ar_bytes = (1 << 20) if quick else (64 << 20)
    # a failing sub-benchmark fails the run: an "error: ..." string in a
    # result that exits 0 reads as a measurement
    extras = {
        "allreduce_gbps": round(bench_eager_allreduce(ar_bytes), 2),
        "allreduce_device_resident_gbps": round(
            bench_eager_allreduce(ar_bytes, device_resident=True), 2),
        "allreduce_bf16_compressed_gbps": round(
            bench_eager_allreduce(ar_bytes, compressed=True), 2),
        "adasum_step_ms": round(
            bench_adasum((1 << 16) if quick else (1 << 22)), 2),
        "moe_alltoall_ms": round(
            bench_moe_alltoall(256 if quick else 2048,
                               128 if quick else 512), 2),
        "per_chip_batch": per_chip,
        "scan_steps": scan_steps,
        # null for models whose builder ignores the resnet stem knobs —
        # the artifact must not claim a stem the model never used
        "s2d": _env_s2d() if spec.resnet_knobs else None,
        "conv_impl": _env_conv_impl() if spec.resnet_knobs else None,
        "device": jax.devices()[0].device_kind,
        # r5: constants corrected to 2 FLOPs/MAC (rounds 1-4 understated
        # mfu ~2x; round-1's 2241 img/s was ~0.28 mfu in this convention)
        "flop_convention": "2xMAC (audited vs XLA cost_analysis, "
                           "benchmarks/conv_analysis_cpu.py)",
    }
    # mfu is the headline quality number. vs_baseline (kept for the driver
    # contract) divides by the only absolute throughput the reference
    # publishes — ResNet-101 on 2017 Pascal GPUs (docs/benchmarks.rst:31-41)
    # — an era-mismatched denominator, labeled as such in extras.
    extras["vs_baseline_definition"] = (
        ("per-chip img/s vs the reference's ResNet-101 example on 16x 2017 "
         "Pascal GPUs (docs/benchmarks.rst:31-41) — same model "
         "(HVD_BENCH_MODEL=resnet101), era-mismatched hardware"
         if _bench_model_name() == "resnet101" else
         "per-chip img/s vs reference ResNet-101 example on 16x 2017 Pascal "
         "GPUs (docs/benchmarks.rst:31-41); era- AND model-mismatched — "
         "run HVD_BENCH_MODEL=resnet101 for apples-to-apples, read mfu "
         "for the honest utilization number"))
    # runtime-reported fusion behaviour over the eager sub-benchmarks
    # (hvd_fusion_batch_size histogram: count = fused dispatches, sum =
    # tensors they carried)
    fusion = next((h for h in hvd.metrics_snapshot()["histograms"]
                   if h["name"] == "hvd_fusion_batch_size"), None)
    extras["fused_batches"] = int(fusion["count"]) if fusion else 0
    extras["fused_tensors"] = int(fusion["sum"]) if fusion else 0
    # steady-state fast path telemetry (docs/performance.md): are cycles
    # actually replaying compiled fused-chunk plans, and is the staging
    # ring being reused instead of allocating per pack?
    from horovod_tpu.utils import metrics as _metrics_mod

    _reg = _metrics_mod.get_registry()
    plan_hits = _reg.counter_value("hvd_fused_plan_hits_total")
    plan_misses = _reg.counter_value("hvd_fused_plan_misses_total")
    plan_total = plan_hits + plan_misses
    extras["fused_plan_hit_rate"] = (
        round(plan_hits / plan_total, 4) if plan_total else None)
    extras["fused_plan_lookups"] = int(plan_total)
    extras["staging_ring_reuses"] = int(
        _reg.counter_value("hvd_staging_reuse_total"))
    extras["allreduce_gbps_semantics"] = (
        "wire bytes (hvd_allreduce_bytes_total delta / wall time); the "
        "compressed config therefore reports post-compression bytes")
    # ZeRO-1 sharded-update telemetry (docs/sharded_optimizer.md). The
    # zero-cost contract says these series do not exist while the mode is
    # off, so absent/zero reads report None rather than a misleading 0 —
    # benchmarks/sharded_update.py is the dedicated A/B microbench.
    _sh_wire = _reg.counter_value("hvd_sharded_update_wire_bytes_total")
    extras["sharded_update_wire_bytes"] = int(_sh_wire) if _sh_wire else None
    _sh_hits = _reg.counter_value("hvd_sharded_plan_hits_total")
    _sh_total = _sh_hits + _reg.counter_value("hvd_sharded_plan_misses_total")
    extras["sharded_plan_hit_rate"] = (
        round(_sh_hits / _sh_total, 4) if _sh_total else None)
    extras["sharded_shard_fraction"] = next(
        (round(g["value"], 4) for g in hvd.metrics_snapshot()["gauges"]
         if g["name"] == "hvd_sharded_update_shard_fraction"), None)
    # Quantized-wire telemetry (docs/performance.md). Same zero-cost
    # contract: with HOROVOD_COMPRESSION unset these series do not exist,
    # so absent/zero reads report None — benchmarks/quantized_allreduce.py
    # is the dedicated wire-format A/B microbench.
    _q_counters = [c for c in hvd.metrics_snapshot()["counters"]
                   if c["name"] == "hvd_quant_wire_bytes_total"]
    _q_wire = sum(c["value"] for c in _q_counters)
    extras["quant_wire_bytes"] = int(_q_wire) if _q_wire else None
    _q_fb = sum(c["value"] for c in hvd.metrics_snapshot()["counters"]
                if c["name"] == "hvd_quant_fallback_total")
    extras["quant_fallback_tensors"] = int(_q_fb) if _q_fb else None
    # per-span lifecycle summary when HOROVOD_TRACE is on (docs/timeline.md):
    # where did the eager sub-benchmarks' collectives spend their time, and
    # did the coordinator attribute any straggling?
    trep = hvd.trace_report()
    if trep.get("enabled"):
        ph = trep.get("phases", {})

        def _pct(phase, k):
            d = ph.get(phase) or {}
            return d.get(k)

        extras["trace_negotiate_p50_ms"] = _pct("negotiate", "p50_ms")
        extras["trace_negotiate_p95_ms"] = _pct("negotiate", "p95_ms")
        extras["trace_dispatch_p50_ms"] = _pct("dispatch", "p50_ms")
        extras["trace_dispatch_p95_ms"] = _pct("dispatch", "p95_ms")
        extras["trace_spans"] = trep.get("spans")
        strag = trep.get("straggler")
        if strag:
            extras["trace_straggler"] = strag
    # Per-step phase/goodput decomposition when HOROVOD_PERFLEDGER is on
    # (docs/observability.md "Performance ledger"). Same None-when-off
    # convention as the quant/sharded extras: absent ledger reads None,
    # so the driver's trend tooling can tell "off" from "zero".
    prep = hvd.perf_report()
    pstats = prep.get("stats", {}) if prep.get("enabled") else {}
    extras["perf_exposed_comm_frac"] = pstats.get("exposed_comm_frac")
    extras["perf_negotiate_p95_ms"] = pstats.get("negotiate_p95_ms")
    extras["perf_step_wire_bytes"] = pstats.get("step_wire_bytes")
    # residual per-step Python outside negotiate+dispatch — the share the
    # megaplan replay drives toward ≈0 (docs/performance.md "Whole-step
    # replay"); None while the ledger is off
    extras["perf_host_overhead_ms"] = pstats.get("host_overhead_p50_ms")
    # Control-plane scale-out telemetry (docs/scaling.md). Single-process
    # benches have no rendezvous controller at all — every field is None
    # then, and negotiation_format is None/"v1" whenever the hierarchy
    # flag is off (the zero-new-series contract's bench-side mirror).
    from horovod_tpu.common import context as _context_mod

    _ctl = getattr(getattr(_context_mod.context(), "runtime", None),
                   "controller", None)
    extras["negotiation_format"] = (
        _ctl.wire_format if _ctl is not None else None)
    _ctl_rounds = _reg.counter_value("hvd_negotiation_rounds_total")
    _ctl_wire = _reg.counter_value("hvd_controller_wire_bytes_total")
    extras["controller_wire_bytes_per_round"] = (
        round(_ctl_wire / _ctl_rounds, 1)
        if _ctl is not None and _ctl_rounds else None)
    extras["controller_round_p95_ms"] = pstats.get("negotiate_p95_ms") \
        if _ctl is not None else None
    # Joint autotuner state (docs/autotune.md). None-when-off convention:
    # with HOROVOD_AUTOTUNE off the autotuner object never exists, so all
    # three fields read None — the driver's trend tooling can tell
    # "tuning off" from "tuned zero rounds".
    _at = getattr(_context_mod.context(), "autotuner", None)
    extras["autotune_rounds"] = (
        int(_reg.counter_value("hvd_autotune_rounds_total"))
        if _at is not None else None)
    extras["autotune_best_score"] = (
        _at._best_score if _at is not None else None)
    extras["autotune_config"] = (
        _at.active_config() if _at is not None else None)
    # Device-memory & compile accounting when HOROVOD_MEMLEDGER is on
    # (docs/observability.md "Memory & compile ledger"). Same
    # None-when-off convention: the driver's trend tooling must tell
    # "ledger off" from "zero bytes compiled".
    mrep = hvd.memory_report()
    if mrep.get("enabled"):
        _mc = mrep.get("compile", {})
        extras["mem_peak_bytes"] = int(mrep.get("peak_bytes") or 0)
        extras["compile_seconds_total"] = _mc.get("compile_seconds_total")
        from horovod_tpu.ops import collectives as _C

        extras["plan_cache_program_bytes"] = int(_C.plan_cache_bytes())
    else:
        extras["mem_peak_bytes"] = None
        extras["compile_seconds_total"] = None
        extras["plan_cache_program_bytes"] = None
    # Step-anatomy critical path + headroom when HOROVOD_ANATOMY is on
    # (docs/observability.md "Step anatomy & headroom"). Same
    # None-when-off convention as the other observability extras.
    arep = hvd.anatomy_report()
    if arep.get("enabled"):
        _cp = arep.get("critical_path", {})
        _hr = arep.get("headroom", {})
        extras["anatomy_top_entity"] = _cp.get("top_entity")
        extras["anatomy_overlap_headroom_s"] = _hr.get("overlap_headroom_s")
        extras["anatomy_replay_headroom_s"] = _hr.get("replay_headroom_s")
    else:
        extras["anatomy_top_entity"] = None
        extras["anatomy_overlap_headroom_s"] = None
        extras["anatomy_replay_headroom_s"] = None
    # Whole-step megaplan capture/replay when HOROVOD_MEGAPLAN is on
    # (docs/performance.md "Whole-step replay"). Same None-when-off
    # convention: with the flag unset no manager exists, so both read
    # None — the driver's trend tooling can tell "replay off" from
    # "armed but never captured" (hit rate None) and "replaying" (1.0).
    mprep = hvd.megaplan_report()
    if mprep.get("enabled"):
        extras["megaplan_replay_hit_rate"] = mprep.get("replay_hit_rate")
        extras["megaplan_capture_rounds"] = mprep.get("capture_rounds")
    else:
        extras["megaplan_replay_hit_rate"] = None
        extras["megaplan_capture_rounds"] = None
    # Async-checkpoint write/restore costs when HOROVOD_ASYNC_CKPT is on
    # (docs/fault_tolerance.md "Surviving preemption"). Same
    # None-when-off convention as the other observability extras.
    crep = hvd.checkpoint_report()
    if crep.get("enabled"):
        extras["ckpt_write_s"] = crep.get("last_write_s")
        extras["ckpt_restore_s"] = crep.get("last_restore_s")
        extras["ckpt_shard_bytes"] = crep.get("last_shard_bytes")
    else:
        extras["ckpt_write_s"] = None
        extras["ckpt_restore_s"] = None
        extras["ckpt_shard_bytes"] = None
    # Fleet-health verdict when HOROVOD_HEALTH is on
    # (docs/observability.md "Fleet health & history"). Same
    # None-when-off convention: the driver's trend tooling can tell
    # "health off" from "healthy, zero anomalies" ("healthy"/0/None).
    hrep = hvd.health_report()
    if hrep.get("enabled"):
        extras["health_verdict"] = hrep.get("verdict")
        extras["health_anomalies_total"] = hrep.get("anomalies_total")
        extras["health_suspect_rank"] = hrep.get("suspect_rank")
    else:
        extras["health_verdict"] = None
        extras["health_anomalies_total"] = None
        extras["health_suspect_rank"] = None
    # Attribution stamp: which code and which knob snapshot produced
    # these numbers — benchguard baselines are meaningless without it.
    extras["git_sha"] = _git_sha()
    extras["knobs"] = _knob_snapshot()
    _emit_result({
        "metric": metric_name,
        "value": round(per_chip_ips, 2),
        "unit": "images/sec/chip",
        "mfu": round(mfu, 4),
        "vs_baseline": round(per_chip_ips / BASELINE_PER_DEVICE, 3),
        "extras": extras,
    })


_TUNED_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks", "bench_tuned.json")


def _resolve_tuned_config(quick: bool, single_process: bool,
                          tuned_path: str = _TUNED_PATH):
    """Resolve the batch/scan defaults and apply stem/lowering env
    defaults (``HVD_BENCH_S2D`` / ``HVD_BENCH_CONV_IMPL``).

    Precedence: env vars (launcher-propagated; always win — applied via
    ``setdefault`` here and ``_sync_int_env`` by the caller)
    > campaign-written ``bench_tuned.json`` (single-process resnet50
    only: per-machine files could hand multi-host ranks mismatched
    collective shapes) > in-code defaults equal to the round-5 on-chip
    winner (batch 128 / scan 32 / space-to-depth stem = 34.2% MFU,
    benchmarks/chip_evidence_r5/) so a fresh container with no tuned
    file still measures the winner.

    A tuned file WITHOUT an ``s2d`` key keeps the standard stem its own
    sweep used (pre-r5 files); an explicit opinion (True or False)
    always wins over the in-code default. quick/CI smoke never applies
    the stem/lowering defaults, and non-resnet50 models start from
    conservative defaults because the sweep ran on resnet50.

    Returns ``(batch, scan_steps)`` defaults.
    """
    model = _bench_model_name()
    # per-model starting points (_BENCH_MODELS): resnet50 = the swept
    # on-chip winner; resnet101 = its banked-artifact config (44.0% MFU,
    # chip_evidence_r5 — scan 32 measured within noise); vgg16 and
    # inception3 = conservative batches, never batch-swept on chip (an
    # OOM burns a window)
    spec = _BENCH_MODELS[model]
    tuned_batch, tuned_scan = spec.default_batch, spec.default_scan
    tuned_s2d = None       # None = no tuned-file opinion; resolved below
    tuned_file_read = False
    if single_process and model == "resnet50":
        try:
            with open(tuned_path) as f:
                tuned = json.load(f)
            # parse EVERY field before committing any of it: a torn or
            # hand-edited file must not half-apply (batch taken, scan
            # lost) while still claiming tuned_file_read below
            new_batch = int(tuned.get("batch", tuned_batch))
            new_scan = int(tuned.get("scan_steps", tuned_scan))
            new_s2d = bool(tuned["s2d"]) if "s2d" in tuned else tuned_s2d
            new_conv = (str(tuned["conv_impl"])
                        if tuned.get("conv_impl") else None)
            tuned_file_read = True
            tuned_batch, tuned_scan, tuned_s2d = new_batch, new_scan, new_s2d
            if new_conv and not quick:
                # campaign found a different conv lowering faster on
                # this platform (benchmarks/probe_conv.py)
                os.environ.setdefault("HVD_BENCH_CONV_IMPL", new_conv)
        except Exception:
            pass
    if model == "resnet50" and tuned_s2d is None and not tuned_file_read:
        # deterministic across ranks, so safe for multi-host runs too
        tuned_s2d = True
    if tuned_s2d and not quick:
        os.environ.setdefault("HVD_BENCH_S2D", "1")
    return tuned_batch, tuned_scan


def _bench_model_name() -> str:
    name = os.environ.get("HVD_BENCH_MODEL", "resnet50").lower()
    if name not in _BENCH_MODELS:
        raise SystemExit(f"HVD_BENCH_MODEL={name!r}: pick from "
                         f"{sorted(_BENCH_MODELS)}")
    return name


def _sync_int_env(name, default):
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


_RESULT_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "bench_result.json")


def _emit_result(doc: dict) -> None:
    """Print the result as the LAST stdout line and write the same line
    to bench_result.json, with two advisory verdicts banked in extras.

    - tools/benchguard judges the result against the banked
      BENCH_r*.json trajectory. Advisory here — the bench must emit its
      measurement even when it regressed (the benchguard CLI is the
      enforcing path), so a guard failure only logs.
    - the static-analysis verdict rides along the same way: advisory in
      the artifact, enforced by the tier-1 suite and the entry lint gate.
    """
    extras = doc.setdefault("extras", {})
    try:
        from tools.benchguard import compare, load_history
        hist = load_history(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_r*.json"))
        verdict = compare(doc, hist)
        extras["benchguard"] = {
            k: verdict.get(k)
            for k in ("status", "baseline", "ratio", "violations")}
    except Exception as e:
        sys.stderr.write(f"benchguard verdict skipped: {e}\n")
    extras["hvdlint"] = _lint_snapshot()
    json_line = json.dumps(doc)
    try:
        with open(_RESULT_FILE, "w") as f:
            f.write(json_line + "\n")
    except OSError as e:
        sys.stderr.write(f"bench_result.json not written: {e}\n")
    sys.stderr.flush()
    sys.stdout.write(json_line + "\n")
    sys.stdout.flush()


def _lint_snapshot(timeout_s: float = 180.0) -> dict:
    """Pre-test static-analysis verdict for the artifact: runs
    ``python -m tools.hvdlint --json`` (stdlib-ast, no JAX import) and
    returns a compact summary. Advisory, like the benchguard verdict —
    the bench must emit its measurement even on a dirty tree (the tier-1
    suite and ``__graft_entry__``'s lint gate are the enforcing paths) —
    but a banked number should record whether the code that produced it
    satisfied the project invariants."""
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    try:
        p = subprocess.run(
            [sys.executable, "-m", "tools.hvdlint", "--json"],
            cwd=here, capture_output=True, text=True, timeout=timeout_s)
        finds = json.loads(p.stdout or "[]")
        out = {"clean": p.returncode == 0, "findings": len(finds)}
        if finds:
            out["fingerprints"] = [
                f.get("fingerprint") for f in finds[:20]]
        return out
    except Exception as e:  # analyzer unavailable ≠ dirty: record which
        return {"clean": None, "error": repr(e)[:200]}


if __name__ == "__main__":
    sys.exit(main())
