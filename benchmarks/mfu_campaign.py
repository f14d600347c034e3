"""MFU campaign: run on the real chip when available.

Sweeps per-chip batch × scan-steps on the full training step, plus the
microbenchmark peaks (matmul / conv / no-BN forward) from ablate_mfu2.
Writes one JSON line per configuration to benchmarks/mfu_results.jsonl
(append), so partial progress survives interruptions.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from _common import (enable_compilation_cache, make_recorder,
                     require_tpu, start_stall_watchdog,
                     write_tuned_if_better)

record = make_recorder(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "mfu_results.jsonl"))


def main():
    import horovod_tpu as hvd
    from bench import (RESNET50_FWD_FLOP_PER_IMG as FWD,
                       TRAIN_FLOP_MULT, bench_resnet, chip_peak_flops)

    enable_compilation_cache()
    start_stall_watchdog(900)  # before require_tpu: backend init can hang
    require_tpu()
    hvd.init()
    PEAK = chip_peak_flops()
    record(event="start", device=jax.devices()[0].device_kind)

    # 1. pure matmul peak — what can this chip deliver at all?
    n = 4096
    a = jnp.asarray(np.random.randn(n, n), jnp.bfloat16)
    b = jnp.asarray(np.random.randn(n, n), jnp.bfloat16)
    f = jax.jit(lambda a, b: a @ b)
    for _ in range(3):
        out = f(a, b)
    float(jnp.asarray(out).ravel()[0])
    t0 = time.perf_counter()
    iters = 50
    for _ in range(iters):
        out = f(a, b)
    float(jnp.asarray(out).ravel()[0])
    dt = (time.perf_counter() - t0) / iters
    record(event="matmul4096", ms=dt * 1e3, tflops=2 * n ** 3 / dt / 1e12,
           mfu=2 * n ** 3 / dt / PEAK)

    # 1b. conv peaks — round-2 ablation said fwd-only is ~14% MFU, so the
    # deficit is the conv stack or dispatch latency; measure what the
    # chip's convs can deliver in isolation (stem 7x7/s2 + bottleneck 3x3)
    def conv_peak(tag, x_shape, k_shape, strides):
        x = jnp.asarray(np.random.randn(*x_shape), jnp.bfloat16)
        k = jnp.asarray(np.random.randn(*k_shape), jnp.bfloat16)
        g = jax.jit(lambda x, k: jax.lax.conv_general_dilated(
            x, k, strides, "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")))
        for _ in range(3):
            out = g(x, k)
        float(jnp.asarray(out).ravel()[0])
        t0 = time.perf_counter()
        for _ in range(20):
            out = g(x, k)
        float(jnp.asarray(out).ravel()[0])
        dt = (time.perf_counter() - t0) / 20
        oh, ow = out.shape[1], out.shape[2]
        flops = 2 * x_shape[0] * oh * ow * k_shape[0] * k_shape[1] \
            * k_shape[2] * k_shape[3]
        record(event=f"conv_{tag}", ms=round(dt * 1e3, 3),
               tflops=round(flops / dt / 1e12, 2),
               mfu=round(flops / dt / PEAK, 4))

    for tag, xs, ks, st in (
            ("stem7x7", (256, 224, 224, 3), (7, 7, 3, 64), (2, 2)),
            ("mid3x3", (256, 28, 28, 128), (3, 3, 128, 128), (1, 1))):
        try:  # independently: one conv failing must not drop the other
            conv_peak(tag, xs, ks, st)
        except Exception as e:
            record(event=f"conv_error_{tag}",
                   error=f"{type(e).__name__}: {e}"[:200])

    # 2. batch × scan sweep on the real training step. scan amortizes the
    # per-dispatch host latency — the scan→MFU curve separates device
    # throughput from dispatch latency.
    best = None
    from horovod_tpu.models import ResNet50

    def std_model():
        # explicit standard stem: the baseline must stay the baseline even
        # when HVD_BENCH_S2D=1 is exported in the environment
        return ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                        space_to_depth=False)

    for batch in (128, 256, 512):
        for scan in (1, 8, 32):
            try:
                ips = bench_resnet(batch, warmup=2, iters=4,
                                   scan_steps=scan, model_fn=std_model)
                record(event="resnet", batch=batch, scan=scan,
                       img_s=round(ips, 1),
                       mfu=round(ips * FWD * TRAIN_FLOP_MULT / PEAK, 4))
                if best is None or ips > best[0]:
                    best = (ips, batch, scan)
            except Exception as e:
                msg = f"{type(e).__name__}: {e}"
                record(event="resnet_error", batch=batch, scan=scan,
                       error=msg[:200])
                if "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower():
                    break  # OOM: larger scan won't help at this batch

    if best is None:
        sys.exit(3)  # no sweep data: the phase must NOT be marked done
    cfg = {"batch": best[1], "scan_steps": best[2],
           "img_s": round(best[0], 1)}
    record(event="tuned", **cfg)

    # 2b. space-to-depth stem at the winning config (MLPerf TPU stem:
    # the 7x7/s2 conv on 3 channels lights 3 of 128 MXU lanes; s2d
    # lights 12). If it wins, it becomes the tuned default.
    try:
        ips = bench_resnet(
            best[1], warmup=2, iters=4, scan_steps=best[2],
            model_fn=lambda: ResNet50(num_classes=1000,
                                      dtype=jnp.bfloat16,
                                      space_to_depth=True))
        record(event="resnet_s2d", batch=best[1], scan=best[2],
               img_s=round(ips, 1),
               mfu=round(ips * FWD * TRAIN_FLOP_MULT / PEAK, 4))
        if ips > best[0]:
            cfg.update(s2d=True, img_s=round(ips, 1))
            record(event="tuned_s2d", img_s=round(ips, 1))
    except Exception as e:
        record(event="resnet_s2d_error",
               error=f"{type(e).__name__}: {e}"[:200])

    # one write, after the s2d trial decided the final config;
    # bench.py picks this up (env vars win). NEVER clobber a faster
    # config someone else (resnet_phase.py's im2col trials) already
    # wrote — this sweep only covers native convs.
    written, prev = write_tuned_if_better(cfg)
    if not written:
        record(event="tuned_kept_existing", existing_img_s=prev)

    # 3. fwd-only at the winning batch: locates the residual deficit
    # (forward conv stack vs backward) for docs/benchmarks.md
    try:
        from horovod_tpu.models import ResNet50

        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
        x = jnp.asarray(np.random.randn(best[1], 224, 224, 3),
                        jnp.bfloat16)
        variables = model.init(jax.random.PRNGKey(0), x[:2], train=False)
        fwd = jax.jit(lambda v, x: model.apply(v, x, train=False))
        for _ in range(3):
            out = fwd(variables, x)
        float(jnp.asarray(out).ravel()[0])
        t0 = time.perf_counter()
        for _ in range(10):
            out = fwd(variables, x)
        float(jnp.asarray(out).ravel()[0])
        dt = (time.perf_counter() - t0) / 10
        ips = best[1] / dt
        record(event="fwd_only", batch=best[1], img_s=round(ips, 1),
               mfu=round(ips * FWD / PEAK, 4))
    except Exception as e:
        record(event="fwd_only_error",
               error=f"{type(e).__name__}: {e}"[:200])


if __name__ == "__main__":
    main()
