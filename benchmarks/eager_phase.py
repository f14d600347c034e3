"""Real-chip eager-path measurements: fused eager allreduce GB/s —
device-resident, numpy-staged, and bf16-compressed — plus the
per-dispatch latency floor, all on one chip.

These are BASELINE.md's stated collective metric measured where it
counts: the silicon, not the CPU mesh. Single process (the eager fast
path with world size 1 still exercises staging + reduction + fetch;
cross-process adds the negotiated KV rounds, measured separately by
bench_eager_2proc.py). Rows land in benchmarks/eager_chip.jsonl for the
docs/benchmarks.md chip table.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _common import (enable_compilation_cache, make_recorder, require_tpu,
                     start_stall_watchdog)

_HERE = os.path.dirname(os.path.abspath(__file__))
record = make_recorder(os.path.join(_HERE, "eager_chip.jsonl"))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from bench import bench_eager_allreduce

    enable_compilation_cache()
    start_stall_watchdog(600)
    require_tpu()
    hvd.init()
    dev = jax.devices()[0].device_kind
    record(event="phase_start", device=dev)

    for mb in (1, 16, 64):
        nbytes = mb << 20
        for kw, tag in (
                (dict(device_resident=True), "device_resident"),
                (dict(), "numpy_staged"),
                (dict(compressed=True), "bf16_compressed")):
            try:
                gbps = bench_eager_allreduce(nbytes, iters=8, **kw)
                record(event="eager_allreduce", path=tag, mib=mb,
                       gbps=round(gbps, 3), device=dev)
            except Exception as e:  # keep measuring the other rows
                record(event="error", path=tag, mib=mb,
                       error=f"{type(e).__name__}: {e}"[:200])

    # transfer-guard leg ON SILICON: CPU backends skip some guard checks
    # (numpy<->host-buffer aliasing), so the real chip is the
    # authoritative verification that the device-resident eager paths
    # never transfer implicitly
    try:
        xg = jnp.ones((1 << 16,), jnp.float32)
        jax.block_until_ready(xg)
        with jax.transfer_guard("disallow"):
            o1 = hvd.allreduce(xg, average=True)
            o2 = hvd.allgather(xg.reshape(256, 256))
            o3, _ = hvd.alltoall(xg)
            o4 = hvd.reducescatter(xg, op=hvd.Sum)
            jax.block_until_ready((o1, o2, o3, o4))
        record(event="transfer_guard_ok", device=dev)
    except Exception as e:
        record(event="error", path="transfer_guard",
               error=f"{type(e).__name__}: {e}"[:200])

    # per-dispatch latency floor: a 4-byte eager allreduce round-trip —
    # the number that explained r3's 21.7%-MFU ceiling (~2.5-3 ms)
    try:
        x = jnp.zeros((1,), jnp.float32)
        jax.block_until_ready(x)
        for i in range(3):  # warm
            hvd.synchronize(hvd.allreduce_async(x, name=f"lat.w{i}"))
        t0 = time.perf_counter()
        n = 20
        for i in range(n):
            out = hvd.synchronize(hvd.allreduce_async(x, name=f"lat.{i}"))
        float(np.asarray(out)[0])
        record(event="dispatch_latency",
               ms=round((time.perf_counter() - t0) / n * 1e3, 3), device=dev)
    except Exception as e:
        record(event="error", path="latency",
               error=f"{type(e).__name__}: {e}"[:200])
    record(event="phase_done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
