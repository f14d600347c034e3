"""Synthetic throughput benchmark — mirror of the reference's
examples/tensorflow2/tensorflow2_synthetic_benchmark.py (same flags,
same output format: "Img/sec per device" + total), on JAX/TPU.

Example:
    python examples/jax_synthetic_benchmark.py --model ResNet50 --batch-size 64
    python examples/jax_synthetic_benchmark.py --model InceptionV3 --image-size 299
    python examples/jax_synthetic_benchmark.py --model VGG16

Any registered model family works (ResNet50/101/152, InceptionV3,
VGG16/19, ViT_*): models without batch norm or with dropout are handled
uniformly.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import models
from horovod_tpu.parallel import data_parallel_step, shard_batch


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="ResNet50")
    p.add_argument("--image-size", type=int, default=224,
                   help="input resolution (299 is InceptionV3's canonical)")
    p.add_argument("--batch-size", type=int, default=64, help="per-chip")
    p.add_argument("--num-warmup-batches", type=int, default=3)
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--num-iters", type=int, default=3)
    p.add_argument("--fp16-allreduce", action="store_true")
    p.add_argument("--use-adasum", action="store_true")
    args = p.parse_args()

    hvd.init()
    model = getattr(models, args.model)(num_classes=1000, dtype=jnp.bfloat16)
    n = hvd.size()
    batch = args.batch_size * n
    sz = args.image_size
    # each process makes the shard of its own chips; shard_batch spreads it
    # over them (a bare jnp.asarray parks the whole batch on one device)
    local = args.batch_size * hvd.global_process_set().local_size
    host_images = np.random.RandomState(0).randn(
        local, sz, sz, 3).astype(jnp.bfloat16)
    images, labels = shard_batch(
        (host_images, np.random.RandomState(1).randint(0, 1000, (local,))))

    # extra rngs are ignored by models that take none (flax contract), so
    # one init/apply shape serves BN-only, dropout-only, and plain models
    rngs = {"params": jax.random.PRNGKey(0),
            "dropout": jax.random.PRNGKey(17)}
    variables = model.init(rngs, jnp.asarray(host_images[:2]), train=True)
    params = variables["params"]
    batch_stats = variables.get("batch_stats")
    compression = hvd.Compression.fp16 if args.fp16_allreduce else hvd.Compression.none
    opt = hvd.DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9), compression=compression,
        op=hvd.Adasum if args.use_adasum else hvd.Average)
    opt_state = opt.init(params)
    params = hvd.broadcast_parameters(params, root_rank=0)

    def step(state, opt_state, images, labels):
        params, batch_stats, rng_step = state
        rng_step, drop_key = jax.random.split(rng_step)

        def loss_fn(p):
            v = {"params": p}
            if batch_stats is not None:
                v["batch_stats"] = batch_stats
                logits, upd = model.apply(
                    v, images, train=True, mutable=["batch_stats"],
                    rngs={"dropout": drop_key})
                new_stats = upd["batch_stats"]
            else:
                logits = model.apply(v, images, train=True,
                                     rngs={"dropout": drop_key})
                new_stats = None
            onehot = jax.nn.one_hot(labels, 1000)
            loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))
            return loss, new_stats
        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return ((optax.apply_updates(params, updates), new_stats, rng_step),
                opt_state, jax.lax.pmean(loss, "hvd"))

    compiled = data_parallel_step(step, batch_argnums=(2, 3))
    # a fresh dropout key every step (folded through the carried state)
    state = (params, batch_stats, jax.random.PRNGKey(42))

    if hvd.rank() == 0:
        print(f"Model: {args.model}, Batch size: {args.batch_size} per chip, "
              f"Number of chips: {n}")
    for _ in range(args.num_warmup_batches):
        state, opt_state, loss = compiled(state, opt_state, images, labels)
    jax.block_until_ready(loss)

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            state, opt_state, loss = compiled(state, opt_state, images, labels)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        rate = batch * args.num_batches_per_iter / dt
        img_secs.append(rate)
        if hvd.rank() == 0:
            print(f"Iter #{i}: {rate / n:.1f} img/sec per chip")
    if hvd.rank() == 0:
        mean = np.mean(img_secs)
        print(f"Img/sec per chip: {mean / n:.1f} +-{1.96 * np.std(img_secs) / n:.1f}")
        print(f"Total img/sec on {n} chip(s): {mean:.1f}")


if __name__ == "__main__":
    main()
