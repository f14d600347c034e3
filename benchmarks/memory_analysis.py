"""Compiled-memory evidence for the memory features.

XLA's per-executable CompiledMemoryStats (temp = activations/scratch,
argument = resident inputs incl. params/optimizer state) turns the
framework's memory claims — remat, chunked cross-entropy — into
measured numbers.

Honest scope: on the CPU backend the stats are authoritative only for
STRUCTURAL changes (xent_chunk provably removes the [tokens, vocab]
logits buffers from the program — the reduction shows up everywhere).
Scheduling-dependent savings (remat) depend on the backend's buffer
liveness planning and on CPU can even report inverted; read the remat
rows only from a real-TPU run (--big), where temp == HBM.

Appends JSON lines to benchmarks/memory_analysis.jsonl and prints a
table.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _common import (make_recorder,  # noqa: E402
                     require_tpu, start_stall_watchdog)

record = make_recorder(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "memory_analysis.jsonl"))


def lm_step_stats(cfg, tokens, params, label: str):
    import jax
    import optax

    from horovod_tpu.models import transformer as T

    opt = optax.adam(1e-3)
    state = opt.init(params)

    def step(params, state, tokens):
        loss, g = jax.value_and_grad(
            lambda p: T.lm_loss(p, tokens, cfg, use_constraints=False))(params)
        u, state = opt.update(g, state, params)
        return optax.apply_updates(params, u), state, loss

    compiled = jax.jit(step).lower(params, state, tokens).compile()
    ma = compiled.memory_analysis()
    row = {"config": label,
           "backend": jax.default_backend(),
           "shape": f"b{tokens.shape[0]}xs{tokens.shape[1]}"
                    f"v{cfg.vocab_size}d{cfg.d_model}L{cfg.n_layers}",
           "temp_mb": round(ma.temp_size_in_bytes / 2**20, 2),
           "args_mb": round(ma.argument_size_in_bytes / 2**20, 2),
           "out_mb": round(ma.output_size_in_bytes / 2**20, 2)}
    record(event="lm_memory", **row)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--big", action="store_true",
                    help="HBM-sized shapes (real chip)")
    args = ap.parse_args()

    start_stall_watchdog(1200)  # must cover one --big remote compile
    if args.big:
        # --big is the campaign's HBM-evidence phase: a CPU-fallback run
        # would succeed (compile-only) and permanently mark the phase
        # done with meaningless remat rows
        require_tpu()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import transformer as T

    if args.big:
        dims = dict(vocab_size=32768, d_model=1024, n_heads=16, n_layers=8,
                    d_ff=4096, max_seq=4096)
        # batch 1: the DENSE baseline must itself fit in the v5e's
        # 15.75G HBM (measured 42.9G at batch 4 — watch.log 08:43) or
        # the comparison degenerates to an error row. At batch 1 dense
        # is ~10.7G temp, so dense vs remat vs xent_chunk are all real
        # CompiledMemoryStats numbers on the chip.
        batch, seq, chunk = 1, 4096, 4096
    else:
        dims = dict(vocab_size=8192, d_model=256, n_heads=8, n_layers=4,
                    d_ff=1024, max_seq=512)
        batch, seq, chunk = 2, 512, 512

    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, dims["vocab_size"], (batch, seq)))
    base = dict(dims, dtype=jnp.bfloat16, dp_axis=None, tp_axis=None,
                sp_axis=None)
    params = T.init(jax.random.PRNGKey(0), T.TransformerConfig(**base))

    rows = []
    for label, kw in (
            ("dense", {}),
            ("xent_chunk", {"xent_chunk": chunk}),
            ("remat", {"remat": True}),
            ("remat+xent_chunk", {"remat": True, "xent_chunk": chunk})):
        cfg = T.TransformerConfig(**base, **kw)
        try:
            rows.append(lm_step_stats(cfg, tokens, params, label))
        except Exception as e:
            # an HBM-overflow compile IS evidence (it bounds the dense
            # baseline); record it and keep measuring the other configs
            # instead of failing the phase — but a phase where NOTHING
            # compiled still fails (platform trouble, not memory truth)
            record(event="lm_memory_compile_error", config=label,
                   error=f"{type(e).__name__}: {e}"[:500])
    if not rows:
        sys.exit(1)

    width = max(len(r["config"]) for r in rows)
    if jax.default_backend() != "tpu":
        print("note: CPU backend — remat rows reflect CPU buffer "
              "planning, not HBM; xent_chunk rows are structural")
    print(f"{'config':<{width}}  temp_MB  args_MB")
    for r in rows:
        print(f"{r['config']:<{width}}  {r['temp_mb']:7.1f}  "
              f"{r['args_mb']:7.1f}")


if __name__ == "__main__":
    main()
