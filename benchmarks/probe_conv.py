"""Conv-deficit diagnosis on the chip.

The r3 MFU campaign measured matmul at ~31% MFU but convs at 0.4-1% —
a ~30-80x gap that caps ResNet MFU regardless of batching. This probe
isolates the cause:

- dispatch-latency calibration (tiny-op round trip, scan-amortized op)
- conv dtype (bf16 vs f32) and feature-depth sweep
- the same convolutions expressed as matmuls (1x1 conv == matmul;
  3x3 via conv_general_dilated_patches im2col) — if these run at
  matmul speed, XLA's native conv lowering is the problem and an
  im2col path in the model is the fix.

Appends JSON lines to benchmarks/probe_conv.jsonl.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from _common import (enable_compilation_cache, make_recorder, require_tpu,
                     start_stall_watchdog)

record = make_recorder(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "probe_conv.jsonl"))


def timeit(f, *args, warmup=3, iters=20):
    out = None
    for _ in range(warmup):
        out = f(*args)
    float(jnp.asarray(out).reshape(-1)[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    float(jnp.asarray(out).reshape(-1)[0])
    return (time.perf_counter() - t0) / iters


def main():
    enable_compilation_cache()
    start_stall_watchdog(420)  # before require_tpu: backend init can hang
    require_tpu()
    record(event="start", device=jax.devices()[0].device_kind)

    # 0. dispatch latency: how much does one dispatch cost?
    x1 = jnp.ones((8, 8), jnp.float32)
    tiny = jax.jit(lambda x: x + 1.0)
    dt = timeit(tiny, x1, warmup=5, iters=50)
    record(event="dispatch_tiny", ms=round(dt * 1e3, 3))

    # scan-amortized tiny op: per-step cost without dispatch
    def scanned(x):
        return lax.scan(lambda c, _: (c + 1.0, ()), x, None, length=100)[0]

    dt_scan = timeit(jax.jit(scanned), x1, warmup=3, iters=10)
    record(event="dispatch_scan100", ms_total=round(dt_scan * 1e3, 3),
           ms_per_step=round(dt_scan * 10, 4))

    # 1. THE DECISIVE COMPARISON FIRST (chip time is budgeted): native
    # 3x3 conv vs the same conv as im2col +
    # matmul vs a bare matmul of the same FLOPs.
    x = jnp.asarray(np.random.randn(256, 28, 28, 128), jnp.bfloat16)
    k3 = jnp.asarray(np.random.randn(3, 3, 128, 128), jnp.bfloat16)
    flops3 = 2 * 256 * 28 * 28 * 3 * 3 * 128 * 128

    def im2col_conv(x, k):
        n_, h, w, c = x.shape
        kh, kw, _, co = k.shape
        patches = lax.conv_general_dilated_patches(
            x, (kh, kw), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return (patches.reshape(-1, c * kh * kw)
                @ k.transpose(2, 0, 1, 3).reshape(c * kh * kw, co)
                ).reshape(n_, h, w, co)

    g = jax.jit(im2col_conv)
    dt = timeit(g, x, k3, warmup=2, iters=10)
    record(event="im2col_3x3_c128_bf16", ms=round(dt * 1e3, 3),
           tflops=round(flops3 / dt / 1e12, 2))

    # numerics check vs native conv (f32 reference)
    ref = lax.conv_general_dilated(
        x.astype(jnp.float32), k3.astype(jnp.float32), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    got = g(x, k3).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(ref - got)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    record(event="im2col_relerr", relerr=round(err, 5))

    # matmul reference point at conv-comparable FLOPs (~59 GFLOP)
    m, k, n = 3136, 4096, 2304
    a = jnp.asarray(np.random.randn(m, k), jnp.bfloat16)
    b = jnp.asarray(np.random.randn(k, n), jnp.bfloat16)
    f = jax.jit(lambda a, b: a @ b)
    dt = timeit(f, a, b)
    flops = 2 * m * k * n
    record(event="matmul_59gf", ms=round(dt * 1e3, 3),
           tflops=round(flops / dt / 1e12, 2))

    # 2. conv sweep: dtype x depth (stays at ~59 GFLOP each)
    def conv_bench(tag, xs, ks, strides, dtype, iters=10):
        x = jnp.asarray(np.random.randn(*xs), dtype)
        k = jnp.asarray(np.random.randn(*ks), dtype)
        g = jax.jit(lambda x, k: lax.conv_general_dilated(
            x, k, strides, "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")))
        try:
            dt = timeit(g, x, k, warmup=2, iters=iters)
        except Exception as e:
            record(event=f"conv_{tag}", error=f"{type(e).__name__}: {e}"[:160])
            return
        out_sp = (xs[1] // strides[0]) * (xs[2] // strides[1])
        flops = 2 * xs[0] * out_sp * ks[0] * ks[1] * ks[2] * ks[3]
        record(event=f"conv_{tag}", ms=round(dt * 1e3, 3),
               tflops=round(flops / dt / 1e12, 2))

    # 3x3 at increasing channel depth, constant FLOPs (batch shrinks)
    conv_bench("3x3_c128_bf16", (256, 28, 28, 128), (3, 3, 128, 128), (1, 1),
               jnp.bfloat16)
    conv_bench("3x3_c128_f32", (256, 28, 28, 128), (3, 3, 128, 128), (1, 1),
               jnp.float32)
    conv_bench("3x3_c256_bf16", (64, 28, 28, 256), (3, 3, 256, 256), (1, 1),
               jnp.bfloat16)
    conv_bench("3x3_c512_bf16", (16, 28, 28, 512), (3, 3, 512, 512), (1, 1),
               jnp.bfloat16)
    # 1x1 conv (a pure matmul in disguise): does the conv ROUTE matter,
    # or the shape?
    conv_bench("1x1_c512_bf16", (64, 28, 28, 512), (1, 1, 512, 1024), (1, 1),
               jnp.bfloat16)

    # 3. scan-amortized conv: is it dispatch latency after all?
    def conv_scan(x, kern):
        def body(c, _):
            return lax.conv_general_dilated(
                c, kern, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC")), ()
        return lax.scan(body, x, None, length=8)[0]

    g = jax.jit(conv_scan)
    dt = timeit(g, x, k3, warmup=2, iters=5)
    record(event="conv_scan8_3x3_c128", ms_per_conv=round(dt * 1e3 / 8, 3),
           tflops=round(8 * flops3 / dt / 1e12, 2))


if __name__ == "__main__":
    main()
