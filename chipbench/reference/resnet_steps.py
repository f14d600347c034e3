"""Plain one-device reference for data-parallel training steps of a
model with per-chip BatchNorm (chip_smoke.py's ``reference_train``
pattern): the same per-chip shards one after another, each with its own
BatchNorm statistics, their gradients averaged, the plain optax update.
No horovod_tpu optimizer, no ``shard_map``, no collective. The function
of one shard is the caller's, the step's own: whether the model computes
what a ResNet should is reference/resnet.py's question, not this
file's."""

import jax
import optax


def train_steps(loss_and_grads, optimizer, params, batch_stats,
                shard_images, shard_labels, steps: int):
    """``steps`` optimizer steps on ``shard_images[chips, per_chip, ...]``.
    ``loss_and_grads(params, batch_stats, images, labels)`` gives one
    shard's loss, the BatchNorm statistics it leaves, and its gradients.

    As in the per-chip program, every step starts each shard from the
    *same* incoming BatchNorm statistics and keeps the first shard's
    update (the data-parallel step returns chip 0's, ``out_specs=P()``).
    Returns the per-step mean losses and the final parameters.
    """
    opt_state = optimizer.init(params)

    def one_step(carry, _):
        params, batch_stats, opt_state = carry

        def one_shard(shard):
            return loss_and_grads(params, batch_stats, *shard)

        losses, stats, grads = jax.lax.map(one_shard,
                                           (shard_images, shard_labels))
        grads = jax.tree.map(lambda g: g.mean(0), grads)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        stats = jax.tree.map(lambda s: s[0], stats)
        return (params, stats, opt_state), losses.mean()

    (params, _, _), losses = jax.lax.scan(
        one_step, (params, batch_stats, opt_state), None, length=steps)
    return losses, params
