"""MoE expert-parallel alltoall exchange benchmark — BASELINE.md tracked
config 5 ("hvd.alltoall + hvd.allgather for MoE/expert-parallel gradient
exchange"; reference primitive: operations.cc:1131-1193 alltoall).

Measures (a) the full expert-parallel MoE layer step and (b) the raw
eager hvd.alltoall / hvd.allgather exchange bandwidth.

Run: python examples/moe_alltoall_benchmark.py        (all local chips)
     hvdrun -np 2 python examples/moe_alltoall_benchmark.py
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import horovod_tpu as hvd
from horovod_tpu.parallel import create_mesh
from horovod_tpu.parallel.moe import expert_layer, route
from jax.sharding import PartitionSpec as P


def bench_moe_layer(tokens_per_chip: int, d_model: int, n_experts: int,
                    iters: int = 20):
    n = len(jax.devices())
    mesh = create_mesh({"ep": n})
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(tokens_per_chip * n, d_model), jnp.bfloat16)
    gate_w = jnp.asarray(rng.randn(d_model, n_experts), jnp.float32)
    experts = {
        "gate": jnp.asarray(rng.randn(n_experts, d_model, 2 * d_model) * 0.02,
                            jnp.bfloat16),
        "up": jnp.asarray(rng.randn(n_experts, d_model, 2 * d_model) * 0.02,
                          jnp.bfloat16),
        "down": jnp.asarray(rng.randn(n_experts, 2 * d_model, d_model) * 0.02,
                            jnp.bfloat16),
    }

    def step(x, gate_w, experts):
        def per_chip(xl, gw, held):
            # dropless: two experts a token, wherever on the axis they live
            chosen, weights = route(xl.astype(jnp.float32) @ gw, 2)
            return expert_layer(xl, chosen, weights, held, axis_name="ep")

        return jax.shard_map(
            per_chip, mesh=mesh, in_specs=(P("ep"), P(), P("ep")),
            out_specs=P("ep"), check_vma=False)(x, gate_w, experts)

    compiled = jax.jit(step)
    y = compiled(x, gate_w, experts)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(iters):
        y = compiled(x, gate_w, experts)
    float(jnp.sum(y))  # value fetch = true sync
    dt = (time.perf_counter() - t0) / iters
    toks = tokens_per_chip * n
    print(f"expert_layer: {toks / dt:,.0f} tokens/s  ({dt * 1e3:.2f} ms/step, "
          f"{n} chips, {n_experts} experts)")
    return toks / dt


def bench_eager_exchange(nbytes: int, iters: int = 10):
    """Raw eager alltoall + allgather bandwidth (the BASELINE metric)."""
    n = hvd.size()
    elems = nbytes // 4
    x = np.random.RandomState(1).randn(elems).astype(np.float32)
    for name, fn in (
        ("alltoall", lambda i: hvd.alltoall(x, name=f"bench.a2a.{i}")),
        ("allgather", lambda i: hvd.allgather(x, name=f"bench.ag.{i}")),
    ):
        fn(0)  # warm the compiled program
        t0 = time.perf_counter()
        for i in range(1, iters + 1):
            out = fn(i)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        gbps = nbytes / dt / 1e9
        print(f"eager {name}: {gbps:.2f} GB/s ({nbytes / 1e6:.0f} MB, "
              f"{n} procs)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens-per-chip", type=int, default=4096)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--exchange-mb", type=int, default=64)
    args = ap.parse_args()

    hvd.init()
    n_experts = max(args.experts, len(jax.devices()))
    bench_moe_layer(args.tokens_per_chip, args.d_model, n_experts)
    bench_eager_exchange(args.exchange_mb << 20)


if __name__ == "__main__":
    main()
