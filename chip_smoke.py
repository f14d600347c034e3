"""chip_smoke.py — the quickest proof that horovod_tpu still starts on the chip.

    python3 chip_smoke.py              # one TPU chip, one process
    python3 chip_smoke.py --chips 4    # one host with four chips

With no arguments it drives the main path once on one chip, through the
entry points a user calls, and checks what comes out:

1. trainer  — ``hvd.init()``, ``hvd.DistributedOptimizer(optax.sgd(...,
   momentum=0.9))``, ``hvd.broadcast_parameters``,
   ``parallel.data_parallel_step``: ResNet-50 at full width (1000 classes,
   224x224x3, bf16, per-chip batch 128), weights and data from ``--seed``;
   loss finite and changing from step to step.
2. eager    — the negotiated path in the same process:
   ``allreduce_async``/``synchronize``, a grouped call, a broadcast and an
   allgather on a host array and on a committed 64 MiB device array;
   values checked, results on a TPU device, fused-plan hits on the repeat
   cycle.
3. kernel   — ``flash_attention`` and ``attention_stats``, forward and
   ``jax.grad``, at B=128, s in {1024, 2048}, d=128, bf16, against
   ``_reference_attention`` in float32, with ``tpu_custom_call`` in the
   lowered text (neither interpret mode nor ``scan_stats`` answered) and
   the three kernels by name (``hvd_flash_fwd``; ``hvd_flash_bwd_dq`` and
   ``hvd_flash_bwd_dkv`` behind ``jax.grad(flash_attention)``).

``--chips 4`` runs only what exists across chips, each in its own child,
one after the other, from a parent that never initialises a JAX backend
(a process that has holds the chips): (a) the ResNet-50 step on the
four-device mesh against a one-device reference, (b) ``hvdrun -np 4``
with one process per chip, (c) ring attention over a 4-way ``sp`` axis.

The last stdout line is ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": N}}`` with the device as JAX reports it — printed
only after every phase passed at full size on a TPU. Without a TPU the
script exits non-zero and prints no such line; ``--tiny`` is the
rehearsal (every phase at toy sizes on whatever JAX finds — CPU tests,
virtual devices), which also ends non-zero. Sizes are fixed in code; the
script reads no file git ignores.
"""

import argparse
import dataclasses
import functools
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

#: exit codes: 0 every phase passed on the chip; 1 a phase failed;
#: 2 no TPU; 3 the --tiny rehearsal finished (never a chip result)
EXIT_FAILED, EXIT_NO_TPU, EXIT_REHEARSAL = 1, 2, 3


class SmokeFailure(Exception):
    """A phase produced a wrong result."""


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Sizes:
    classes: int
    image: int
    batch: int            # per chip
    eager_bytes: int
    attn_batch: int       # B = batch * heads
    attn_seqs: tuple
    attn_dim: int
    attn_block: int
    ring_heads: int
    ring_seq: int         # total, over the sp axis

    def model(self):
        import jax.numpy as jnp

        from horovod_tpu.models import ResNet50
        from horovod_tpu.models.resnet import ResNet

        if self is FULL:
            return ResNet50(num_classes=self.classes, dtype=jnp.bfloat16)
        return ResNet(stage_sizes=[1, 1], num_filters=8,
                      num_classes=self.classes, dtype=jnp.bfloat16)


#: ResNet-50 as published (He et al. 2015; 1000 classes, 224x224x3), the
#: reference's synthetic-benchmark batch, and the 1.2B LM's attention
#: shapes (batch 8 x 16 heads, head width 128)
FULL = Sizes(classes=1000, image=224, batch=128, eager_bytes=64 << 20,
             attn_batch=128, attn_seqs=(1024, 2048), attn_dim=128,
             attn_block=512, ring_heads=8, ring_seq=8192)
TINY = Sizes(classes=10, image=32, batch=4, eager_bytes=64 << 10,
             attn_batch=2, attn_seqs=(256,), attn_dim=64, attn_block=128,
             ring_heads=2, ring_seq=512)


def device_info() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def start(args, want_chips: int):
    """Common start of every process that uses JAX: shared compile cache,
    ``hvd.init()``, and the no-accelerator exit. Returns (sizes, on_chip)."""
    import jax

    from horovod_tpu.utils import compile_cache

    compile_cache.enable_compilation_cache()
    import horovod_tpu as hvd

    hvd.init()
    dev = device_info()
    on_chip = dev["platform"] == "tpu"
    if not on_chip and not args.tiny:
        sys.stderr.write(
            f"chip_smoke: JAX found no TPU (platform {dev['platform']!r}, "
            f"kind {dev['kind']!r}); nothing was run\n")
        sys.exit(EXIT_NO_TPU)
    check(dev["count"] >= want_chips,
          f"need {want_chips} device(s), JAX found {dev['count']}")
    import jaxlib

    try:
        import libtpu
        tpu_lib = libtpu.__version__
    except ImportError:
        tpu_lib = "not installed"
    from horovod_tpu import _native

    say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {tpu_lib}"
        f" | device {dev} | hvd size {hvd.size()} processes "
        f"{hvd.cross_size()}")
    placed_by = ("JAX_COMPILATION_CACHE_DIR"
                 if os.environ.get(compile_cache.JAX_CACHE_DIR_ENV)
                 else "checkout default")
    say(f"compile cache {compile_cache.active_cache_dir()} ({placed_by}), "
        f"entries before: {compile_cache.cache_entries()}")
    say("native core: " + ("libhvdcore loaded" if _native.lib() is not None
                           else "not loaded, NumPy path in use"))
    return (TINY if args.tiny else FULL), on_chip


def cache_after() -> None:
    from horovod_tpu.utils import compile_cache

    say(f"compile cache entries after: {compile_cache.cache_entries()}")


# --------------------------------------------------------------------------
# trainer: ResNet-50 through DistributedOptimizer + data_parallel_step
# --------------------------------------------------------------------------

LEARNING_RATE = 0.05


def make_data(sz: Sizes, seed: int, n_chips: int):
    """Seeded host batch for ``n_chips`` chips and initial variables."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(seed)
    n = sz.batch * n_chips
    images = rng.randn(n, sz.image, sz.image, 3).astype(jnp.bfloat16)
    labels = rng.randint(0, sz.classes, (n,)).astype(np.int32)
    model = sz.model()
    # on the host: the training step donates its state, and the reference
    # run starts from the same values
    variables = jax.device_get(
        jax.jit(functools.partial(model.init, train=True))(
            jax.random.PRNGKey(seed), jnp.asarray(images[:2])))
    return model, images, labels, variables["params"], variables["batch_stats"]


def loss_and_grads(model, classes, params, batch_stats, images, labels):
    """What one chip computes on its shard (BatchNorm statistics are the
    shard's own, as in Horovod's per-GPU BN)."""
    import jax
    import jax.numpy as jnp

    def loss_fn(p):
        logits, upd = model.apply({"params": p, "batch_stats": batch_stats},
                                  images, train=True, mutable=["batch_stats"])
        onehot = jax.nn.one_hot(labels, classes)
        loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))
        return loss, upd["batch_stats"]

    (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    return loss, stats, grads


def train(sz: Sizes, data, steps: int):
    """The user's loop (examples/jax_synthetic_benchmark.py, bench.py
    bench_resnet) on ``make_data``'s output. Returns losses, final params,
    the sharded batch and seconds per call (the first one compiles)."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.parallel import data_parallel_step, shard_batch

    model, images, labels, params, batch_stats = data
    opt = hvd.DistributedOptimizer(optax.sgd(LEARNING_RATE, momentum=0.9))
    opt_state = opt.init(params)
    params = hvd.broadcast_parameters(params, root_rank=0)
    images, labels = shard_batch((images, labels))

    def step(state, opt_state, images, labels):  # per chip, on its shard
        params, batch_stats = state
        loss, stats, grads = loss_and_grads(model, sz.classes, params,
                                            batch_stats, images, labels)
        updates, opt_state = opt.update(grads, opt_state, params)
        return ((optax.apply_updates(params, updates), stats), opt_state,
                jax.lax.pmean(loss, "hvd"))

    compiled = data_parallel_step(step, batch_argnums=(2, 3))
    # replicated over the mesh, as the step returns them: state that
    # arrives without the mesh makes the second call trace and compile
    # the whole program again
    state, opt_state = jax.device_put(
        ((params, batch_stats), opt_state),
        NamedSharding(hvd.global_process_set().mesh, P()))
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, opt_state, loss = compiled(state, opt_state, images, labels)
        jax.block_until_ready((state, loss))
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return losses, state[0], (images, labels), secs


def check_losses(losses) -> None:
    import math

    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(all(a != b for a, b in zip(losses, losses[1:])),
          f"loss does not change from step to step: {losses}")


def phase_trainer(sz: Sizes, seed: int) -> None:
    import horovod_tpu as hvd

    losses, params, _, secs = train(
        sz, make_data(sz, seed, hvd.size()), steps=4)
    check_losses(losses)
    say(f"trainer: ResNet classes={sz.classes} image={sz.image} bf16 "
        f"per-chip batch={sz.batch} chips={hvd.size()} | compile+first step "
        f"{secs[0]:.1f} s | steps after it "
        f"{[round(s * 1e3, 1) for s in secs[1:]]} ms | losses "
        f"{[round(x, 4) for x in losses]}")


# --------------------------------------------------------------------------
# eager: the negotiated named-tensor path
# --------------------------------------------------------------------------

def eager_cycle(tag: str, x, n_proc: int, rank: int, on_chip: bool) -> bool:
    """One cycle of named collectives on ``x`` (host or device array);
    every rank contributes ``x * (rank + 1)`` where it matters. Returns
    whether the lone allreduce replayed a compiled fused plan: it is
    waited for before anything else is enqueued, so its chunk is itself
    on every cycle (what the background thread fuses from several
    pending tensors depends on when it drains them)."""
    import jax
    import numpy as np

    import horovod_tpu as hvd

    ref = np.asarray(x)
    tri = n_proc * (n_proc + 1) / 2  # sum of (rank + 1) over ranks
    mine = x * (rank + 1)
    hits = plan_hits()
    out_ar = hvd.synchronize(
        hvd.allreduce_async(mine, name=f"{tag}.ar", op=hvd.Sum))
    hit = plan_hits() > hits
    h_grp = hvd.grouped_allreduce_async([mine, mine * 2], name=f"{tag}.grp",
                                        op=hvd.Sum)
    h_bc = hvd.broadcast_async(mine, 0, name=f"{tag}.bc")
    h_ag = hvd.allgather_async(mine[:1024], name=f"{tag}.ag")
    outs = {"allreduce": (out_ar, ref * tri),
            "grouped[0]": (hvd.synchronize(h_grp[0]), ref * tri),
            "grouped[1]": (hvd.synchronize(h_grp[1]), ref * tri * 2),
            "broadcast": (hvd.synchronize(h_bc), ref),
            "allgather": (hvd.synchronize(h_ag), np.concatenate(
                [ref[:1024] * (r + 1) for r in range(n_proc)]))}
    for name, (out, want) in outs.items():
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6,
                                   err_msg=f"{tag} {name}")
        if on_chip:
            check(isinstance(out, jax.Array)
                  and {d.platform for d in out.devices()} == {"tpu"},
                  f"{tag} {name}: result does not live on a tpu device")
    return hit


def plan_hits() -> float:
    import horovod_tpu as hvd

    return sum(c["value"] for c in hvd.metrics_snapshot()["counters"]
               if c["name"] == "hvd_fused_plan_hits_total")


def phase_eager(sz: Sizes, seed: int, on_chip: bool, tag: str = "smoke"):
    import jax
    import numpy as np

    import horovod_tpu as hvd

    n_proc, rank = hvd.cross_size(), hvd.cross_rank()
    host = np.random.RandomState(seed).randn(
        sz.eager_bytes // 4).astype(np.float32)
    dev = jax.device_put(host, jax.local_devices()[0])  # committed
    jax.block_until_ready(dev)
    secs = {}
    for kind, x in (("host", host), ("device", dev)):
        for cycle in ("first", "repeat"):
            t0 = time.perf_counter()
            hit = eager_cycle(f"{tag}.{kind}", x, n_proc, rank, on_chip)
            secs[kind, cycle] = time.perf_counter() - t0
        check(hit, f"no fused-plan hit on the repeat cycle ({kind} array)")
    say(f"eager: {sz.eager_bytes >> 10} KiB x (allreduce, grouped x2, "
        f"broadcast, allgather) over {n_proc} process(es) | "
        + " | ".join(f"{k} {c} {s * 1e3:.0f} ms" for (k, c), s in secs.items())
        + f" | fused-plan hits {plan_hits():.0f}")


# --------------------------------------------------------------------------
# kernel: the Pallas flash attention against a float32 reference
# --------------------------------------------------------------------------

def f32_reference(fn, *arrays, chunk: int = 16):
    """``fn`` over float32 copies of ``arrays`` in true float32 (the
    TPU's default matmul precision is bf16 passes), ``chunk`` rows of the
    leading dimension at a time: a full [128, 2048, 2048] float32 score
    matrix and its gradient would not fit beside the rest."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(fn)
    outs = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, arrays[0].shape[0], chunk):
            outs.append(f(*(a[i:i + chunk].astype(jnp.float32)
                            for a in arrays)))
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *outs)


def close(name: str, got, want, tol: float) -> float:
    """Largest error relative to the reference's own scale."""
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    check(bool(jnp.isfinite(got).all()), f"{name}: not finite")
    check(got.shape == want.shape, f"{name}: shape {got.shape}")
    err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    check(err <= tol, f"{name}: error {err:.3g} of the reference's scale, "
                      f"limit {tol}")
    return err


def phase_kernel(sz: Sizes, seed: int, on_chip: bool) -> None:
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    F = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")
    blk = sz.attn_block
    # bf16 in, float32 accumulation: a few bf16 ulps of the output scale
    tol = 2e-2

    def flash_loss(q, k, v):
        return jnp.sum(F.flash_attention(q, k, v, True, blk, blk)
                       .astype(jnp.float32) ** 2)

    def stats_loss(q, k, v):  # o and the log-sum-exp m + log l
        o, m, l = F.attention_stats(q, k, v, True, blk, blk, 0)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(m + jnp.log(l))

    def ref_loss(q, k, v):
        return jnp.sum(F._reference_attention(q, k, v, True) ** 2)

    def ref_stats_loss(q, k, v):
        o, m, l = F._lax_stats(q, k, v, True)
        return jnp.sum(o ** 2) + jnp.sum(m + jnp.log(l))

    for s in sz.attn_seqs:
        rng = np.random.RandomState(seed + s)
        q, k, v = (jnp.asarray(rng.randn(sz.attn_batch, s, sz.attn_dim),
                               jnp.bfloat16) for _ in range(3))
        errs = {}
        lowered = []
        for causal in (True, False):
            fn = jax.jit(lambda q, k, v, c=causal: F.flash_attention(
                q, k, v, c, blk, blk))
            lowered.append(fn.lower(q, k, v).as_text())
            errs[f"flash causal={causal}"] = close(
                f"flash_attention s={s} causal={causal}", fn(q, k, v),
                f32_reference(lambda q, k, v, c=causal:
                              F._reference_attention(q, k, v, c), q, k, v),
                tol)
        for offset in (0, 1):
            fn = jax.jit(lambda q, k, v, o=offset: F.attention_stats(
                q, k, v, True, blk, blk, o))
            lowered.append(fn.lower(q, k, v).as_text())
            got = fn(q, k, v)
            want = f32_reference(lambda q, k, v, o=offset: F._lax_stats(
                q, k, v, True, o), q, k, v)
            # offset 1 masks row 0 entirely: its contract is m = NEG_INF
            # (o and l unconstrained, annihilated in the ring combine)
            check(bool((got[1][:, :offset] == F.NEG_INF).all()),
                  "attention_stats: masked row is not marked NEG_INF")
            for name, g, w in zip("oml", got, want):
                errs[f"stats offset={offset} {name}"] = close(
                    f"attention_stats s={s} offset={offset} {name}",
                    g[:, offset:], w[:, offset:], tol)
        for name, loss, ref in (("flash", flash_loss, ref_loss),
                                ("stats", stats_loss, ref_stats_loss)):
            fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            lowered.append(fn.lower(q, k, v).as_text())
            want = f32_reference(jax.grad(ref, argnums=(0, 1, 2)), q, k, v)
            for arg, g, w in zip("qkv", fn(q, k, v), want):
                errs[f"grad {name} d{arg}"] = close(
                    f"grad {name} s={s} d{arg}", g, w, tol)
        kernels = sorted({name for t in lowered
                          for name in re.findall(r"hvd_flash_\w+", t)})
        if on_chip:
            check(all("tpu_custom_call" in t for t in lowered),
                  "no tpu_custom_call in the lowered text: interpret mode "
                  "or scan_stats answered for the kernel")
            # jax.grad(flash_attention) is the two backward kernels
            check(kernels == ["hvd_flash_bwd_dkv", "hvd_flash_bwd_dq",
                              "hvd_flash_fwd"],
                  f"the lowered programs name the kernels {kernels}")
        say(f"kernel: B={sz.attn_batch} s={s} d={sz.attn_dim} bf16 block "
            f"{blk} | {len(lowered)} programs"
            + (f", tpu_custom_call in each ({', '.join(kernels)})"
               if on_chip else " (interpret mode off the chip)")
            + f" | largest error {max(errs.values()):.2e} "
            f"({max(errs, key=errs.get)}) of the reference's scale")


def run_one_chip(args) -> int:
    sz, on_chip = start(args, want_chips=1)
    phase_trainer(sz, args.seed)
    phase_eager(sz, args.seed, on_chip)
    phase_kernel(sz, args.seed, on_chip)
    cache_after()
    return finish(device_info(), on_chip and not args.tiny)


def finish(dev: dict, proven: bool) -> int:
    if not proven:
        say(f"rehearsal finished on {dev}: every phase ran, which is not a "
            "chip result")
        return EXIT_REHEARSAL
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


def result_line(text: str) -> dict:
    """The result a child printed (its last such line; a runtime may
    still log after it)."""
    lines = [ln for ln in text.splitlines() if ln.startswith('{"ok"')]
    check(lines, "child printed no result line")
    return json.loads(lines[-1])


# --------------------------------------------------------------------------
# --chips 4: (a) one process, four chips, against a one-device reference
# --------------------------------------------------------------------------

def reference_train(sz: Sizes, data, n_chips: int, steps: int):
    """The plain one-device reference: the same ``n_chips`` shards one
    after another (each with its own BatchNorm statistics, as the
    per-chip step sees them), their gradients averaged, the same optax
    update — no horovod_tpu in it."""
    import jax
    import jax.numpy as jnp
    import optax

    model, images, labels, params, batch_stats = data
    shards = (jnp.asarray(images).reshape((n_chips, sz.batch)
                                          + images.shape[1:]),
              jnp.asarray(labels).reshape(n_chips, sz.batch))
    opt = optax.sgd(LEARNING_RATE, momentum=0.9)
    opt_state = opt.init(params)
    init_params = params

    @jax.jit
    def step(params, opt_state, shards):
        def one(shard):
            loss, _, grads = loss_and_grads(model, sz.classes, params,
                                            batch_stats, *shard)
            return loss, grads

        losses, grads = jax.lax.map(one, shards)
        grads = jax.tree.map(lambda g: g.mean(0), grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, losses.mean()

    losses = []
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, shards)
        losses.append(float(loss))
    return losses, params, init_params


def phase_dp4(args) -> int:
    import jax
    import numpy as np
    from jax.flatten_util import ravel_pytree

    import horovod_tpu as hvd

    sz, on_chip = start(args, want_chips=4)
    n = hvd.size()
    check(n == 4, f"hvd.size() is {n}, want 4")
    data = make_data(sz, args.seed, n)
    losses, params, (images, labels), secs = train(sz, data, steps=2)
    check_losses(losses)
    # the batch and the work are spread over all four devices, not parked
    # on the first
    for name, x in (("images", images), ("labels", labels)):
        shards = x.addressable_shards
        check(len({s.device for s in shards}) == n
              and all(s.data.shape[0] == sz.batch for s in shards),
              f"{name} not sharded {sz.batch} per device over {n} devices: "
              f"{x.sharding}")
    check(len(jax.tree.leaves(params)[0].sharding.device_set) == n,
          "parameters are not replicated over the four devices")
    mem = [d.memory_stats() for d in jax.devices()[:n]]
    if on_chip:
        peaks = [m["peak_bytes_in_use"] for m in mem]
        check(min(peaks) > 0.5 * max(peaks),
              f"device memory is lopsided, work parked on one chip: {peaks}")
        mem_note = f"peak MiB per device {[p >> 20 for p in peaks]}"
    else:
        mem_note = "memory_stats not reported off the chip"
    ref_losses, ref_params, init_params = reference_train(sz, data, n, 2)
    # on the host: the two runs live on different sets of devices
    p, r, p0 = (np.asarray(ravel_pytree(t)[0], np.float64)
                for t in (params, ref_params, init_params))
    update_err = float(np.linalg.norm(p - r) / np.linalg.norm(r - p0))
    loss_err = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    say(f"dp4: ResNet classes={sz.classes} image={sz.image} per-chip batch "
        f"{sz.batch} on {n} devices | compile+first step {secs[0]:.1f} s, "
        f"second {secs[1] * 1e3:.1f} ms | {mem_note} | losses {losses} vs "
        f"one-device reference {ref_losses} (rel {loss_err:.2e}) | parameter"
        f" checksum (L2) {np.linalg.norm(p):.6f} vs "
        f"{np.linalg.norm(r):.6f} | two-step update differs by "
        f"{update_err:.2e} of its own size")
    # bf16 forward and backward: the two programs differ only in how XLA
    # fused the same per-shard arithmetic
    check(loss_err <= 2e-2, f"loss differs from the reference: {loss_err}")
    check(update_err <= 5e-2,
          f"parameters differ from the reference: {update_err}")
    cache_after()
    return finish(device_info(), on_chip and not args.tiny)


# --------------------------------------------------------------------------
# --chips 4: (b) hvdrun -np 4, one process per chip
# --------------------------------------------------------------------------

def child_cmd(phase: str, args) -> list:
    return [sys.executable, os.path.abspath(__file__), "--phase", phase,
            "--seed", str(args.seed)] + (["--tiny"] if args.tiny else [])


def phase_hvdrun4(args) -> int:
    """The launcher: this process never initialises a backend, so what
    its workers found comes from the result lines they printed."""
    import tempfile

    from horovod_tpu.runner.launch import run_commandline

    with tempfile.TemporaryDirectory() as out:
        rc = run_commandline(["-np", "4", "--output-filename", out]
                             + child_cmd("worker", args))
        check(rc == 0, f"hvdrun -np 4 exited with {rc}")
        from jax._src import xla_bridge

        check(not xla_bridge.backends_are_initialized(),
              "the launcher initialised a JAX backend")
        texts = []
        for r in range(4):
            with open(os.path.join(out, f"rank.{r}.out")) as f:
                texts.append(f.read())
    if args.tiny:
        return finish({"platform": "not asked", "count": 4}, False)
    results = [result_line(t) for t in texts]
    check(all(r["ok"] is True and r["device"] == results[0]["device"]
              for r in results), f"workers disagree: {results}")
    return finish(results[0]["device"], True)


def phase_worker(args) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd

    sz, on_chip = start(args, want_chips=1)
    n, rank = hvd.cross_size(), hvd.cross_rank()
    check(n == 4, f"{n} processes, want 4")
    if on_chip:  # one chip each, one four-device world
        check(len(jax.local_devices()) == 1,
              f"worker sees {jax.local_devices()}, want one chip")
        check(hvd.size() == 4 and hvd.local_size() == 4,
              f"size {hvd.size()} local_size {hvd.local_size()}, want 4, 4")
    say(f"worker: launcher rank {os.environ.get('HOROVOD_RANK')} is hvd "
        f"rank {hvd.rank()} of {hvd.size()}, local devices "
        f"{jax.local_devices()}, TPU_VISIBLE_CHIPS="
        f"{os.environ.get('TPU_VISIBLE_CHIPS')}")
    phase_eager(sz, args.seed, on_chip, tag="w")
    # two eager DistributedOptimizer steps: rank r's gradient is (r + 1)
    # times a fixed pattern, so every rank must land on the mean's update
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    pattern = {"w": jnp.arange(1024, dtype=jnp.float32) / 1024,
               "b": jnp.ones((16,), jnp.float32)}
    params = jax.tree.map(jnp.zeros_like, pattern)
    opt_state = opt.init(params)
    for step in (1, 2):
        grads = jax.tree.map(lambda g: g * (rank + 1) * step, pattern)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
    mean_rank = (n + 1) / 2  # mean of (r + 1)
    for key, g in pattern.items():
        np.testing.assert_allclose(
            np.asarray(params[key]), np.asarray(-0.1 * mean_rank * 3 * g),
            rtol=1e-6, err_msg=f"DistributedOptimizer eager steps, {key}")
    say(f"worker: rank {hvd.rank()} two DistributedOptimizer eager steps "
        "match the four-rank mean")
    cache_after()
    dev = device_info()
    hvd.shutdown()
    # exit 0 whenever the checks passed: hvdrun kills the job on the first
    # non-zero worker; only a chip run prints the result line
    finish(dev, on_chip and not args.tiny)
    return 0


# --------------------------------------------------------------------------
# --chips 4: (c) ring attention over a 4-way sp axis
# --------------------------------------------------------------------------

def phase_ring4(args) -> int:
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel import ring_attention

    sz, on_chip = start(args, want_chips=4)
    F = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    rng = np.random.RandomState(args.seed)
    shape = (1, sz.ring_seq, sz.ring_heads, sz.attn_dim)
    seq_sharded = NamedSharding(mesh, P(None, "sp"))
    q, k, v = (jax.device_put(jnp.asarray(rng.randn(*shape), jnp.bfloat16),
                              seq_sharded) for _ in range(3))
    ring = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp"), mesh=mesh,
        in_specs=P(None, "sp"), out_specs=P(None, "sp")))
    text = ring.lower(q, k, v).as_text()
    t0 = time.perf_counter()
    out = jax.block_until_ready(ring(q, k, v))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(ring(q, k, v))
    again = time.perf_counter() - t0
    check(len(out.sharding.device_set) == 4, f"output on {out.sharding}")
    if on_chip:
        check("tpu_custom_call" in text,
              "ring_attention did not take the Pallas kernel on TPU")

    def heads_first(x):  # [1, s, h, d] -> the kernel layout [h, s, d]
        return x[0].transpose(1, 0, 2)

    one = jax.devices()[0]
    want = f32_reference(
        lambda q, k, v: F._reference_attention(q, k, v, True),
        *(jax.device_put(heads_first(x), one) for x in (q, k, v)), chunk=2)
    err = close("ring_attention", jax.device_put(heads_first(out), one),
                want, 2e-2)
    say(f"ring4: causal ring attention, s={sz.ring_seq} over a 4-way sp "
        f"axis ({sz.ring_seq // 4} per device), heads {sz.ring_heads}, "
        f"d={sz.attn_dim}, bf16 | "
        + ("Pallas kernel (tpu_custom_call)" if on_chip
           else "XLA stats path off the chip")
        + f" | compile+first {first:.1f} s, again {again * 1e3:.1f} ms | "
        f"error {err:.2e} of the float32 single-device reference's scale")
    cache_after()
    return finish(device_info(), on_chip and not args.tiny)


# --------------------------------------------------------------------------
# --chips 4 parent: three children, no backend here
# --------------------------------------------------------------------------

CHILD_LIMIT_S = 900  # each child; the whole script answers inside 1200 s


def run_child(phase: str, args) -> dict:
    """Run one phase in its own process group, echo its output, and
    return the result line it printed last. The group is killed at the
    time limit and on the way out, so nothing this script starts
    outlives it."""
    say(f"--- child {phase}")
    proc = subprocess.Popen(child_cmd(phase, args), stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            start_new_session=True)

    def kill():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timer = threading.Timer(CHILD_LIMIT_S, kill)
    timer.start()
    lines = []
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            lines.append(line)
        rc = proc.wait()
    finally:
        timer.cancel()
        kill()
    check(rc == (EXIT_REHEARSAL if args.tiny else 0),
          f"child {phase} exited with {rc}")
    return {} if args.tiny else result_line("".join(lines))


def run_four_chips(args) -> int:
    results = [run_child(p, args) for p in ("dp4", "hvdrun4", "ring4")]
    check("jax" not in sys.modules, "the four-chip parent imported JAX")
    if args.tiny:
        say("rehearsal of --chips 4 finished: not a chip result")
        return EXIT_REHEARSAL
    check(all(r.get("ok") is True and r["device"] == results[0]["device"]
              for r in results), f"children disagree: {results}")
    return finish(results[0]["device"], True)


PHASES = {"dp4": phase_dp4, "hvdrun4": phase_hvdrun4,
          "worker": phase_worker, "ring4": phase_ring4}


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tiny", action="store_true",
                   help="rehearsal at toy sizes; never a chip result")
    p.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = p.parse_args()
    try:
        if args.phase:
            return PHASES[args.phase](args)
        if args.chips == 4:
            return run_four_chips(args)
        return run_one_chip(args)
    except (SmokeFailure, AssertionError) as e:
        sys.stderr.write(f"chip_smoke: FAILED: {e}\n")
        return EXIT_FAILED


if __name__ == "__main__":
    sys.exit(main())
