"""Family ``dense_lm``: dense GPT-2-style decoders (models/transformer.py)
trained data-parallel through ``hvd.DistributedOptimizer`` +
``parallel.data_parallel_step`` on a resident batch of seeded token ids.

Configuration keys read: ``sizes`` (n_embd, n_head, n_inner,
n_positions, vocab_size, n_layer), ``model`` (compute_dtype, remat,
xent_chunk), ``optimizer``. Workload keys read: ``per_chip_batch``
(sequences a chip takes per step), ``sequence`` (positions predicted per
sequence; a sequence holds one token id more).
"""

import functools

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import flops
from chipbench.cell import (Cell, build_optimizer, dtype_of, pick, placed,
                            rel_l2, replica_on, seed_key)
from chipbench.reference import dense_lm as reference
from horovod_tpu.models import transformer as T
from horovod_tpu.parallel import data_parallel_step, shard_batch

#: Loss and gradients of the program's loss function (bf16 matmuls, f32
#: accumulation, f32 softmax and norms, remat) against the float32
#: reference on the first sequence of the cell's batch, at the cell's
#: widths and depth. bf16 carries 8 bits of mantissa (2**-8 = 3.9e-3 a
#: rounding); through eight blocks forward and backward the gradient of
#: an early leaf gathers a few of them: on the v5e the largest gradient
#: error was 1.5e-2 (blocks.0.wq) and the loss agreed to 3e-5 (PERF.md,
#: Findings, PR 23). The tolerances are ten and 2.7 times that; an
#: 8-bit float (2**-3 a rounding) or bf16 accumulation would miss them by
#: an order of magnitude.
LOSS_RTOL = 3e-4
GRAD_RTOL = 4e-2
#: the leaves whose gradients are compared: both ends of the depth, every
#: kind of weight, and the embedding, which the classifier shares
CHECK_LEAVES = ("embed", "pos", "ln_f.scale", "blocks.0.wq", "blocks.0.w1",
                "blocks.0.ln1.scale", "blocks.-1.wo", "blocks.-1.w2")
#: One real step of the program (remat inside the step, the in-graph
#: fused_tree_allreduce, the optimizer under DistributedOptimizer) from
#: the seeded state against the plain optax optimizer on the reference's
#: mean gradient over every sequence of the step: the step's loss, and
#: the update of UPDATE_LEAVES. Three copies of a compared leaf stay on
#: the chip while the step runs, so the embedding (412 MB a copy at the
#: published vocabulary) is left out; ``pos`` lies behind it in the
#: fused buffer. Adam's first update is -lr * g / (|g| + eps): the size
#: of g cancels and only its sign stays, so where g is small beside its
#: own bf16 error the two sides may differ by the whole 2 * lr and say
#: nothing. The comparison therefore keeps to the elements whose
#: reference gradient is at least the leaf's root mean square, where the
#: sign is the gradient's and not the rounding's. There the two sides
#: agreed to 3.1e-6 on the v5e, and the step's loss to 7e-6 (PERF.md,
#: Findings, PR 23). The tolerance is far above that and still under
#: what a learning rate off by a five-hundredth (2e-3) or a lost weight
#: decay (0.1 * |p| beside 1, so 2e-3 at the initial spread of 0.02)
#: would show; gradients that are wrong outright flip signs and are off
#: by more than 1.
UPDATE_LEAVES = tuple(p for p in CHECK_LEAVES if p != "embed")
UPDATE_RTOL = 1e-3
def make_cfg(config: dict) -> T.TransformerConfig:
    sz, m = config["sizes"], config["model"]
    return T.TransformerConfig(
        vocab_size=sz["vocab_size"], d_model=sz["n_embd"],
        n_heads=sz["n_head"], n_layers=sz["n_layer"], d_ff=sz["n_inner"],
        max_seq=sz["n_positions"], dtype=dtype_of(m["compute_dtype"]),
        remat=m["remat"], xent_chunk=m["xent_chunk"])


def make_step(cfg, opt, mesh):
    """The user's per-chip step, compiled data-parallel over ``mesh``."""
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(T.lm_loss)(
            params, tokens, cfg, use_constraints=False)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, "hvd"))

    return data_parallel_step(step, mesh=mesh, batch_argnums=(2,))


def train_flops_per_item(config: dict, sequence: int) -> float:
    sz = config["sizes"]
    return flops.TRAIN_FLOP_MULT * flops.lm_fwd_flops_per_token(
        sz["n_layer"], sz["n_embd"], sz["n_inner"], sz["vocab_size"],
        sequence)


def init_state(cfg, opt, key):
    """Seeded parameters and optimizer state, traced as one program."""
    params = T.init(key, cfg)
    return params, opt.init(params)


def build(config: dict, workload: dict, *, chips: int, seed: int,
          mesh) -> Cell:
    cfg = make_cfg(config)
    opt, plain_opt = build_optimizer(config["optimizer"])
    seq = workload["sequence"]
    if seq > cfg.max_seq:
        raise ValueError(f"sequence {seq} exceeds n_positions {cfg.max_seq}")
    n = workload["per_chip_batch"] * chips
    k_init, k_tok = jax.random.split(seed_key(seed), 2)

    @jax.jit
    def make_tokens(key):
        return jax.random.randint(key, (n, seq + 1), 0, cfg.vocab_size,
                                  jnp.int32)

    make_state = jax.jit(functools.partial(init_state, cfg, opt),
                         out_shardings=NamedSharding(mesh, P()))
    params, opt_state = make_state(k_init)
    batch = shard_batch((make_tokens(k_tok),), mesh=mesh)

    @jax.jit
    def loss_and_grad_errors(params, tokens, ref_grads):
        """The program's loss on the first sequence and, per leaf of
        CHECK_LEAVES, its gradient's distance from the reference's."""
        loss, grads = jax.value_and_grad(T.lm_loss)(
            params, tokens[:1], cfg, use_constraints=False)
        return loss, [rel_l2(pick(grads, p), r)
                      for p, r in zip(CHECK_LEAVES, ref_grads)]

    def reference_program(params, tokens):
        """The one reference program: sequence by sequence, the float32
        loss and the gradients of CHECK_LEAVES; then what the plain
        optimizer, from a fresh state, makes of their mean in
        UPDATE_LEAVES. An optimizer that acts leaf by leaf, as optax's
        adamw does, gives a leaf the same update alone as in the tree."""
        def one(sequence):
            loss, grads = jax.value_and_grad(reference.loss)(
                params, sequence[None])
            return loss, [pick(grads, p) for p in CHECK_LEAVES]

        losses, grads = jax.lax.map(one, tokens)
        mean = [g.mean(0) for p, g in zip(CHECK_LEAVES, grads)
                if p in UPDATE_LEAVES]
        old = [pick(params, p) for p in UPDATE_LEAVES]
        updates, _ = plain_opt.update(mean, plain_opt.init(old), old)
        sure = [jnp.abs(g) >= jnp.sqrt(jnp.mean(jnp.square(g))) for g in mean]
        return (losses, [g[0] for g in grads],
                optax.apply_updates(old, updates), updates, sure)

    @jax.jit
    def update_errors(params, new, updates, sure):
        """Per leaf of UPDATE_LEAVES, over the elements that are
        ``sure``: the L2 distance of the program's leaf from the
        reference's as a share of the reference update's L2 size."""
        def err(path, new, update, sure):
            off = jnp.where(sure, pick(params, path) - new, 0.0)
            return jnp.sqrt(jnp.sum(jnp.square(off))
                            / jnp.sum(jnp.square(jnp.where(sure, update, 0.0))))

        return [err(*each) for each in zip(UPDATE_LEAVES, new, updates, sure)]

    def check(cell: Cell) -> dict:
        """The program's loss function, then one real step, against the
        float32 reference and the plain optimizer. Starts again from the
        seeded state and leaves the cell one step on from it; the cell's
        own state goes first, two copies do not fit the chip."""
        first = mesh.devices.flat[0]
        cell.state = cell.opt_state = None
        # the reference runs beside the parameters alone (by the TPU
        # compiler's count it peaks at 12.7 GB with them): the optimizer
        # state, 4 GB more, is made again after it
        state, _ = make_state(k_init)
        params = replica_on(first, state)
        tokens = jax.device_put(cell.batch[0], first)
        with jax.default_matmul_precision("highest"):
            ref_losses, ref_grads, ref_new, ref_updates, sure = jax.jit(
                reference_program)(params, tokens)
        loss, grad_errs = jax.device_get(
            loss_and_grad_errors(params, tokens, ref_grads))
        grad_errs = {p: float(e) for p, e in zip(CHECK_LEAVES, grad_errs)}
        del params, ref_grads  # the step takes most of the chip

        _, opt_state = make_state(k_init)
        cell.state, cell.opt_state, step_loss = cell.step(
            state, opt_state, *cell.batch)
        update_errs, step_loss, ref_losses = jax.device_get((
            update_errors(replica_on(first, cell.state), ref_new,
                          ref_updates, sure),
            replica_on(first, step_loss), ref_losses))
        update_errs = {p: float(e) for p, e in zip(UPDATE_LEAVES, update_errs)}
        loss_err = abs(loss - ref_losses[0]) / ref_losses[0]
        step_loss_err = abs(step_loss - ref_losses.mean()) / ref_losses.mean()
        return {"ok": bool(loss_err <= LOSS_RTOL
                           and max(grad_errs.values()) <= GRAD_RTOL
                           and step_loss_err <= LOSS_RTOL
                           and max(update_errs.values()) <= UPDATE_RTOL),
                "loss": float(loss), "loss_rel_err": float(loss_err),
                "step_loss_rel_err": float(step_loss_err),
                "loss_rtol": LOSS_RTOL,
                "grad_rel_l2_err": grad_errs, "grad_rtol": GRAD_RTOL,
                "update_rel_l2_err": update_errs, "update_rtol": UPDATE_RTOL}

    return Cell(step=make_step(cfg, opt, mesh), state=params,
                opt_state=opt_state, batch=batch,
                items_per_step=n * seq,
                train_flops_per_item=train_flops_per_item(config, seq),
                check=check)


def abstract_step(config: dict, workload: dict, *, chips: int, mesh):
    """The step and the shapes it is called with, placed on ``mesh`` as
    ``build`` places them, with nothing on any device: what
    chipbench/aot_check.py compiles for a described chip."""
    cfg = make_cfg(config)
    opt, _ = build_optimizer(config["optimizer"])
    state = jax.eval_shape(functools.partial(init_state, cfg, opt),
                           jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct(
        (workload["per_chip_batch"] * chips, workload["sequence"] + 1),
        jnp.int32)
    return (make_step(cfg, opt, mesh),
            placed(mesh, state, P()) + placed(mesh, (tokens,), P("hvd")))
