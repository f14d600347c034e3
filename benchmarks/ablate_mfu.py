"""MFU ablation microbenchmark (run on the real chip): isolates
forward / forward+backward / full-step costs per batch size."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from horovod_tpu.models import ResNet50

FWD = 2 * 4.09e9  # FLOPs (2 x MACs), matching bench.py round-5 correction
PEAK = 197e12


def timeit(f, *args, iters=20, warmup=3):
    for _ in range(warmup):
        out = f(*args)
    jax.block_until_ready(out)
    # value-fetch sync
    np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]
    return (time.perf_counter() - t0) / iters


def report(name, dt, batch, mult):
    mfu = batch * FWD * mult / dt / PEAK
    print(f"{name:40s} {dt*1e3:8.2f} ms  {batch/dt:9.1f} img/s  mfu={mfu:.3f}",
          flush=True)


def main():
    rng = jax.random.PRNGKey(0)
    for batch in (128, 256, 512):
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
        images = jnp.asarray(
            np.random.RandomState(0).randn(batch, 224, 224, 3), jnp.bfloat16)
        labels = jnp.asarray(
            np.random.RandomState(1).randint(0, 1000, (batch,)))
        variables = model.init(rng, images[:2], train=True)
        params, bstats = variables["params"], variables["batch_stats"]

        # forward only
        @jax.jit
        def fwd(p, b, x):
            out, _ = model.apply({"params": p, "batch_stats": b}, x,
                                 train=True, mutable=["batch_stats"])
            return out

        report(f"b{batch} fwd", timeit(fwd, params, bstats, images), batch, 1)

        # fwd+bwd (loss grad wrt params)
        def loss_fn(p, b, x, y):
            logits, upd = model.apply({"params": p, "batch_stats": b}, x,
                                      train=True, mutable=["batch_stats"])
            onehot = jax.nn.one_hot(y, 1000)
            return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1)), upd

        g = jax.jit(jax.grad(loss_fn, has_aux=True))
        report(f"b{batch} fwd+bwd", timeit(g, params, bstats, images, labels),
               batch, 3)

        # full step with sgd-momentum update, donated
        opt = optax.sgd(0.05, momentum=0.9)
        opt_state = opt.init(params)

        @jax.jit
        def full(p, b, s, x, y):
            grads, upd = jax.grad(loss_fn, has_aux=True)(p, b, x, y)
            updates, s = opt.update(grads, s, p)
            p = optax.apply_updates(p, updates)
            return p, upd["batch_stats"], s

        # donation: thread the returned state back in so donated buffers
        # are never reused after being consumed
        full_d = jax.jit(full, donate_argnums=(0, 1, 2))

        def full_loop(p, b, s):
            return full_d(p, b, s, images, labels)

        state = (params, bstats, opt_state)
        for _ in range(3):
            state = full_loop(*state)
        np.asarray(jax.tree.leaves(state)[0]).ravel()[:1]
        import time as _t
        t0 = _t.perf_counter()
        for _ in range(20):
            state = full_loop(*state)
        np.asarray(jax.tree.leaves(state)[0]).ravel()[:1]
        report(f"b{batch} full step", (_t.perf_counter() - t0) / 20, batch, 3)
        if batch == 256:
            # inference-mode fwd (no batch stats mutation)
            @jax.jit
            def fwd_eval(p, b, x):
                return model.apply({"params": p, "batch_stats": b}, x,
                                   train=False)

            report("b256 fwd eval", timeit(fwd_eval, params, bstats, images),
                   batch, 1)


if __name__ == "__main__":
    main()
