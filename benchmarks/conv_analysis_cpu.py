"""Pre-chip conv-MFU audit — everything that can be settled WITHOUT a
chip:

1. FLOP accounting: bench.py's analytic constants vs XLA's own
   cost_analysis() of the real train step (catches a mis-stated MFU
   denominator before any silicon number ships).
2. bf16 discipline: scan the lowered train-step StableHLO for any f32
   convolution/dot — a silent upcast halves the apparent MFU.
3. Per-shape lowering audit: the three ResNet conv classes (stem 7x7s2,
   mid 3x3, projection 1x1) under native vs im2col lowering — op mix and
   dtype in the optimized HLO, plus an arithmetic-intensity model giving
   each shape's roofline MFU ceiling on v5e (bf16 197 TFLOP/s, HBM
   819 GB/s).

Writes JSON lines to benchmarks/conv_analysis.jsonl and a markdown
summary to stdout. Runs on the CPU backend (HLO inspection is
backend-portable at the StableHLO level; the roofline model is the
TPU-side argument).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import make_recorder  # noqa: E402  (ts-stamped jsonl rows)

_raw_record = make_recorder(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "conv_analysis.jsonl"))


def record(**kw):
    _raw_record(**kw)
    return kw


# ---------------------------------------------------------------------------
# 1. FLOP accounting vs XLA cost analysis
# ---------------------------------------------------------------------------

def flop_audit(batch=8):
    from bench import (RESNET50_FWD_FLOP_PER_IMG, RESNET101_FWD_FLOP_PER_IMG,
                       TRAIN_FLOP_MULT)
    from horovod_tpu.models import ResNet50, ResNet101

    rows = []
    for name, cls, fwd_const in (
            ("resnet50", ResNet50, RESNET50_FWD_FLOP_PER_IMG),
            ("resnet101", ResNet101, RESNET101_FWD_FLOP_PER_IMG)):
        model = cls(num_classes=1000, dtype=jnp.bfloat16)
        rng = jax.random.PRNGKey(0)
        img = jnp.ones((batch, 224, 224, 3), jnp.bfloat16)
        variables = model.init(rng, img[:1], train=False)
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        labels = jnp.zeros((batch,), jnp.int32)
        opt = optax.sgd(0.1)
        opt_state = opt.init(params)

        def loss_fn(p, bs, x, y):
            out, upd = model.apply(
                {"params": p, "batch_stats": bs}, x, train=True,
                mutable=["batch_stats"])
            logp = jax.nn.log_softmax(out.astype(jnp.float32))
            return -jnp.mean(jnp.take_along_axis(
                logp, y[:, None], axis=1)), upd

        def train_step(p, bs, os_, x, y):
            (l, upd), g = jax.value_and_grad(loss_fn, has_aux=True)(
                p, bs, x, y)
            u, os2 = opt.update(g, os_)
            return optax.apply_updates(p, u), upd["batch_stats"], os2, l

        compiled = jax.jit(train_step).lower(
            params, batch_stats, opt_state, img, labels).compile()
        ca = compiled.cost_analysis()
        xla_flops = float(ca.get("flops", 0.0))
        analytic = fwd_const * TRAIN_FLOP_MULT * batch
        row = record(event="flop_audit", model=name, batch=batch,
                     xla_train_flops=xla_flops,
                     analytic_train_flops=analytic,
                     ratio_analytic_over_xla=round(analytic / xla_flops, 4)
                     if xla_flops else None)
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# 2. bf16 discipline: no f32 convolution/dot in the step HLO
# ---------------------------------------------------------------------------

def bf16_audit(batch=8):
    """Scan the FULL train step's StableHLO (fwd + bwd + SGD update) for
    f32 contractions: the backward pass is exactly where XLA or a model
    change would silently upcast, halving real MFU."""
    from horovod_tpu.models import ResNet50

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    img = jnp.ones((batch, 224, 224, 3), jnp.bfloat16)
    variables = model.init(rng, img[:1], train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    labels = jnp.zeros((batch,), jnp.int32)
    opt = optax.sgd(0.1)
    opt_state = opt.init(params)

    def loss_fn(p, bs, x, y):
        out, upd = model.apply({"params": p, "batch_stats": bs}, x,
                               train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(out.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1)), upd

    def train_step(p, bs, os_, x, y):
        (l, upd), g = jax.value_and_grad(loss_fn, has_aux=True)(p, bs, x, y)
        u, os2 = opt.update(g, os_)
        return optax.apply_updates(p, u), upd["batch_stats"], os2, l

    # StableHLO before backend optimization: backend-neutral dtype truth
    txt = jax.jit(train_step).lower(
        params, batch_stats, opt_state, img, labels).as_text()
    bad = []
    for line in txt.splitlines():
        if ("stablehlo.convolution" in line or "stablehlo.dot" in line):
            # operand dtypes appear as tensor<...xf32> / xbf16
            if "xf32" in line.split("->")[0]:
                bad.append(line.strip()[:160])
    return record(event="bf16_audit", model="resnet50", graph="train_step",
                  n_f32_contractions=len(bad), samples=bad[:6])


# ---------------------------------------------------------------------------
# 3. per-shape lowering audit + roofline
# ---------------------------------------------------------------------------

# v5e chip characteristics (public: 197 bf16 TFLOP/s, 819 GB/s HBM)
PEAK_F = 197e12
PEAK_B = 819e9

SHAPES = [
    # (name, N, H, W, Cin, Cout, k, stride)
    ("stem7x7s2", 256, 224, 224, 3, 64, 7, 2),
    ("mid3x3", 256, 14, 14, 256, 256, 3, 1),
    ("proj1x1", 256, 56, 56, 64, 256, 1, 1),
]


def conv_flops_bytes(N, H, W, Cin, Cout, k, s):
    Ho, Wo = H // s, W // s
    macs = N * Ho * Wo * Cout * Cin * k * k
    flops = 2 * macs
    bytes_ = 2 * (N * H * W * Cin + Cout * Cin * k * k + N * Ho * Wo * Cout)
    return flops, bytes_


def lowering_audit():
    from jax import lax

    rows = []
    for (name, N, H, W, Cin, Cout, k, s) in SHAPES:
        flops, bytes_ = conv_flops_bytes(N, H, W, Cin, Cout, k, s)
        ai = flops / bytes_
        # roofline ceiling: min(peak, AI * BW) / peak
        ceiling = min(1.0, ai * PEAK_B / PEAK_F)

        x = jnp.ones((N, H, W, Cin), jnp.bfloat16)
        w = jnp.ones((k, k, Cin, Cout), jnp.bfloat16)

        def native(x, w):
            return lax.conv_general_dilated(
                x, w, (s, s), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32)

        def im2col(x, w):
            # strided-slice tap gather, the same scheme as the model's
            # Im2ColConv (models/resnet.py)
            pad = (k - 1) // 2
            xp = jnp.pad(x, ((0, 0), (pad, k - 1 - pad),
                             (pad, k - 1 - pad), (0, 0)))
            ho = wo = H // s
            taps = [xp[:, di:di + (ho - 1) * s + 1:s,
                       dj:dj + (wo - 1) * s + 1:s, :]
                    for di in range(k) for dj in range(k)]
            patches = jnp.concatenate(taps, axis=-1)
            m = patches.reshape(-1, k * k * Cin)
            return (m @ w.reshape(k * k * Cin, Cout)).reshape(
                N, ho, wo, Cout)

        ops = {}
        for impl_name, fn in (("native", native), ("im2col", im2col)):
            txt = jax.jit(fn).lower(x, w).as_text()
            ops[impl_name] = {
                "convolution": txt.count("stablehlo.convolution"),
                "dot": txt.count("stablehlo.dot"),
                "f32_inputs": sum(
                    1 for ln in txt.splitlines()
                    if ("stablehlo.convolution" in ln
                        or "stablehlo.dot" in ln)
                    and "xf32" in ln.split("->")[0]),
            }
        # im2col pays patch materialization: write + read of the
        # [N, Ho, Wo, k*k*Cin] bf16 tensor (unless XLA fuses the gather
        # into the dot, which the round-3 chip numbers say it does not
        # fully do for big k)
        patch_bytes = 2 * 2 * N * (H // s) * (W // s) * k * k * Cin
        ai_im2col = flops / (bytes_ + patch_bytes)
        ceiling_im2col = min(1.0, ai_im2col * PEAK_B / PEAK_F)
        rows.append(record(
            event="lowering_audit", shape=name,
            flops=flops, bytes=bytes_, arith_intensity=round(ai, 1),
            roofline_mfu_ceiling=round(ceiling, 3),
            arith_intensity_im2col=round(ai_im2col, 1),
            roofline_mfu_ceiling_im2col=round(ceiling_im2col, 3),
            ops=ops))
    return rows


def main():
    print("# conv analysis (CPU-side; roofline = v5e)")
    for r in flop_audit():
        print(f"FLOPs {r['model']}: analytic/xla = "
              f"{r['ratio_analytic_over_xla']}")
    b = bf16_audit()
    print(f"bf16 audit: {b['n_f32_contractions']} f32 contractions "
          f"in fwd HLO")
    for r in lowering_audit():
        print(f"{r['shape']}: AI={r['arith_intensity']} "
              f"ceiling={r['roofline_mfu_ceiling']} ops={r['ops']}")


if __name__ == "__main__":
    main()
