"""Eager negotiated-allreduce bandwidth on a 2-process CPU mesh.

Measures BASELINE.md's "allreduce GB/s" metric on the *negotiated* eager
path (KV-store lockstep rounds + staging + XLA reduction) the way the
reference measures NCCL allreduce bandwidth — plus the negotiation
byte/fast-round counters, so the protocol overhead budget is explicit.

Run directly: ``python benchmarks/bench_eager_2proc.py``
(spawns itself under the hvdrun launcher, 2 CPU processes).
Results land in ``benchmarks/eager_allreduce_2proc.json`` and the table in
``docs/benchmarks.md``.
"""

import json
import os
import sys
import time

_CHILD = "_HVD_BENCH_EAGER_CHILD"


def main_parent():
    # workers inherit the parent env: force CPU before the launcher
    # fans out (this parent never initialises a backend itself)
    os.environ[_CHILD] = "1"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    from horovod_tpu.runner.launch import run_commandline

    np_ = os.environ.get("HVD_BENCH_NP", "2")
    return run_commandline(["-np", np_, sys.executable,
                            os.path.abspath(__file__)])


def main_worker():
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=2")
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.common import context as ctx_mod
    from horovod_tpu.ops.compression import Compression

    hvd.init()
    r = hvd.cross_rank()
    nproc = hvd.cross_size()
    rows = []

    def sweep(nbytes, mode, iters=8):
        comp = Compression.bf16 if mode == "bf16" else Compression.none
        x_np = np.random.RandomState(3).randn(nbytes // 4).astype(np.float32)
        x_dev = jnp.asarray(x_np)
        jax.block_until_ready(x_dev)

        def run_one(i):
            if mode == "bf16":
                t, ctx = comp.compress(x_dev)
                h = hvd.allreduce_async(np.asarray(t),
                                        name=f"b.{mode}.{nbytes}.{i}",
                                        op=hvd.Sum)
                return comp.decompress(hvd.synchronize(h), ctx)
            src = x_dev if mode == "device" else x_np
            h = hvd.allreduce_async(src, name=f"b.{mode}.{nbytes}.{i}",
                                    op=hvd.Sum)
            return hvd.synchronize(h)

        run_one(0)  # warm compile + negotiation caches
        t0 = time.perf_counter()
        out = None
        for i in range(1, iters + 1):
            out = run_one(i)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        rows.append({"mib": nbytes >> 20, "mode": mode,
                     "gbps": round(nbytes / dt / 1e9, 3),
                     "ms": round(dt * 1e3, 2)})

    for nbytes in (1 << 20, 16 << 20, 64 << 20):
        for mode in ("raw", "device", "bf16"):
            sweep(nbytes, mode)

    ctl = ctx_mod.context().runtime.controller
    stats = {"rounds": ctl.round, "fast_rounds": ctl.fast_rounds,
             "bytes_sent": ctl.bytes_sent,
             "bytes_per_round": round(ctl.bytes_sent / max(ctl.round, 1), 1)}
    if r == 0:
        result = {"rows": rows, "negotiation": stats}
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                f"eager_allreduce_{nproc}proc.json")
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
        print("BENCH-EAGER-RESULT " + json.dumps(result))


if __name__ == "__main__":
    if os.environ.get(_CHILD) == "1":
        main_worker()
    else:
        sys.exit(main_parent())
