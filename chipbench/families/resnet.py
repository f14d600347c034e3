"""Family ``resnet``: bottleneck ResNets (models/resnet.py) trained
data-parallel through ``hvd.DistributedOptimizer`` +
``parallel.data_parallel_step`` on a resident synthetic batch, the
reference's synthetic-benchmark convention.

Configuration keys read: ``sizes`` (stage_sizes, num_filters,
num_classes, image_size, channels), ``model`` (compute_dtype,
space_to_depth, conv_impl), ``optimizer``. Workload keys read:
``per_chip_batch``.
"""

import functools

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import flops
from chipbench.cell import (Cell, build_optimizer, dtype_of, pick, placed,
                            rel_l2, replica_on, seed_key)
from chipbench.reference import resnet as reference
from chipbench.reference import resnet_steps
from horovod_tpu.models.resnet import ResNet
from horovod_tpu.parallel import data_parallel_step, shard_batch

#: Two steps of the program (DistributedOptimizer, data_parallel_step, the
#: all-reduce) against the same per-chip function under plain optax, each
#: chip's shard in turn on one device. Both sides run the same bf16
#: model, so what differs is the order of the reductions (psum of shard
#: gradients against their mean taken in turn) and XLA's fusion choices,
#: each a bf16 rounding (2**-8) in some gradients: on the v5e the losses
#: agreed to 1e-5 or better and the two-step update to between 2e-5 and
#: 1.1e-2 of its own size, seed by seed, in 55 runs (PERF.md, Findings,
#: PR 23; PR 21 saw 1.7e-6 and 3.0e-3 at four chips). The update's
#: tolerance is three times the worst of them. A step that skipped the
#: all-reduce, averaged over the wrong count or lost the momentum is off
#: by tens of percent.
LOSS_RTOL = 1e-4
UPDATE_RTOL = 3e-2
CHECK_STEPS = 2

#: The program's model (bf16 convolutions, f32 BatchNorm statistics)
#: against reference/resnet.py (plain lax, float32) at the parameters the
#: two steps reached, on the first images of the first shard: logits,
#: loss and the gradients of MODEL_CHECK_LEAVES. After two steps because
#: at the seeded state every block's last BatchNorm scale is zero and no
#: gradient reaches the convolutions inside a block. On the v5e the
#: logits agreed to 0.43-0.46% and the loss to 5e-5 (PERF.md, Findings,
#: PR 23): the forward pass is held to 2%, which a convolution with the
#: wrong stride or padding, a missing layer or an 8-bit float misses by
#: far. The gradients agree much less well: 15-21% in the leaves with
#: most of the depth behind them, 7.5% in the last block, 0.4% in the
#: classifier (the CPU's bf16 gives the same, so it is bf16 against
#: float32 and not the chip: through fifty layers on sixteen images of
#: noise a rounding flips ReLU gates and the flips multiply). Their
#: tolerance is twice the worst seen and catches only a backward pass
#: that is wrong outright, which is off by 100% and more.
MODEL_CHECK_IMAGES = 16
MODEL_LOGIT_RTOL = 2e-2
MODEL_GRAD_RTOL = 4e-1


def model_check_leaves(stage_sizes) -> tuple:
    """The leaves whose gradients are compared: the stem, and in the
    first block, the first block that halves the image and the last block
    a 3x3, a projection, a 1x1, a BatchNorm scale; the classifier."""
    halves, last = stage_sizes[0], sum(stage_sizes) - 1
    return ("conv_init.kernel", "bn_init.scale",
            "BottleneckBlock_0.Conv_1.kernel",
            "BottleneckBlock_0.conv_proj.kernel",
            f"BottleneckBlock_{halves}.Conv_1.kernel",
            f"BottleneckBlock_{halves}.BatchNorm_2.scale",
            f"BottleneckBlock_{last}.Conv_2.kernel", "head.kernel")

def make_model(config: dict) -> ResNet:
    sz, m = config["sizes"], config["model"]
    return ResNet(stage_sizes=list(sz["stage_sizes"]),
                  num_filters=sz["num_filters"],
                  num_classes=sz["num_classes"],
                  dtype=dtype_of(m["compute_dtype"]),
                  space_to_depth=m["space_to_depth"],
                  conv_impl=m["conv_impl"], axis_name=None)


def shard_loss_and_grads(model, num_classes: int, params, batch_stats, images,
                         labels):
    """One chip's shard through the program's model: its softmax
    cross-entropy, the BatchNorm statistics it leaves, the gradients,
    and the logits."""
    def loss_fn(p):
        logits, upd = model.apply({"params": p, "batch_stats": batch_stats},
                                  images, train=True, mutable=["batch_stats"])
        onehot = jax.nn.one_hot(labels, num_classes)
        loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))
        return loss, (upd["batch_stats"], logits)

    (loss, (stats, logits)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params)
    return loss, stats, grads, logits


def make_step(model, num_classes: int, opt, mesh):
    """The user's per-chip step (examples/jax_synthetic_benchmark.py),
    compiled data-parallel over ``mesh``."""
    def step(state, opt_state, images, labels):
        params, batch_stats = state
        loss, stats, grads, _ = shard_loss_and_grads(
            model, num_classes, params, batch_stats, images, labels)
        updates, opt_state = opt.update(grads, opt_state, params)
        return ((optax.apply_updates(params, updates), stats), opt_state,
                jax.lax.pmean(loss, "hvd"))

    return data_parallel_step(step, mesh=mesh, batch_argnums=(2, 3))


def batch_shapes(config: dict, n: int):
    sz = config["sizes"]
    return ((n, sz["image_size"], sz["image_size"], sz["channels"]),
            dtype_of(config["model"]["compute_dtype"])), ((n,), jnp.int32)


def train_flops_per_item(config: dict) -> float:
    sz = config["sizes"]
    return flops.TRAIN_FLOP_MULT * flops.resnet_fwd_flops_per_image(
        sz["stage_sizes"], sz["num_filters"], sz["image_size"],
        sz["num_classes"])


def init_state(model, opt, config: dict, key):
    """Seeded variables and optimizer state, traced as one program."""
    (shape, dtype), _ = batch_shapes(config, 2)
    variables = model.init(key, jnp.zeros(shape, dtype), train=True)
    params = variables["params"]
    return (params, variables["batch_stats"]), opt.init(params)


def build(config: dict, workload: dict, *, chips: int, seed: int,
          mesh) -> Cell:
    model = make_model(config)
    num_classes = config["sizes"]["num_classes"]
    opt, plain_opt = build_optimizer(config["optimizer"])
    per_chip = workload["per_chip_batch"]
    n = per_chip * chips
    k_init, k_img, k_lab = jax.random.split(seed_key(seed), 3)

    # weights and data are made on the device from the seed, each in one
    # jitted call; the state comes out replicated over the mesh, as the
    # step returns it
    make_state = jax.jit(
        functools.partial(init_state, model, opt, config),
        out_shardings=NamedSharding(mesh, P()))

    @jax.jit
    def make_batch(k_img, k_lab):
        (ishape, idtype), (lshape, ldtype) = batch_shapes(config, n)
        return (jax.random.normal(k_img, ishape, idtype),
                jax.random.randint(k_lab, lshape, 0, num_classes, ldtype))

    state, opt_state = make_state(k_init)
    batch = shard_batch(make_batch(k_img, k_lab), mesh=mesh)
    step = make_step(model, num_classes, opt, mesh)

    stage_sizes = config["sizes"]["stage_sizes"]
    leaves = model_check_leaves(stage_sizes)

    def per_shard(params, batch_stats, images, labels):
        return shard_loss_and_grads(model, num_classes, params, batch_stats,
                                    images, labels)[:3]

    @jax.jit
    def reference_program(params, batch_stats, shard_images, shard_labels):
        """The one program the check adds: CHECK_STEPS plain steps, then
        the model against the float32 reference where they arrived."""
        ref_losses, ref_params = resnet_steps.train_steps(
            per_shard, plain_opt, params, batch_stats, shard_images,
            shard_labels, steps=CHECK_STEPS)
        images = shard_images[0, :MODEL_CHECK_IMAGES]
        labels = shard_labels[0, :MODEL_CHECK_IMAGES]
        loss, _, grads, logits = shard_loss_and_grads(
            model, num_classes, ref_params, batch_stats, images, labels)
        (want_loss, want_logits), want_grads = jax.value_and_grad(
            reference.loss_and_logits, has_aux=True)(
                ref_params, images, labels, stage_sizes)
        model_errs = (jnp.abs(loss - want_loss) / jnp.abs(want_loss),
                      rel_l2(logits, want_logits),
                      [rel_l2(pick(grads, p), pick(want_grads, p))
                       for p in leaves])
        return ref_losses, ref_params, model_errs

    def check(cell: Cell) -> dict:
        """CHECK_STEPS steps from the seeded state, program against plain
        optax; the model against its float32 reference."""
        first = mesh.devices.flat[0]
        on_first = functools.partial(replica_on, first)
        (p0, stats0), _ = on_first(make_state(k_init))
        images, labels = jax.device_put(cell.batch, first)
        ref_losses, ref_params, model_errs = reference_program(
            p0, stats0, images.reshape((chips, per_chip) + images.shape[1:]),
            labels.reshape(chips, per_chip))

        state, opt_state = make_state(k_init)
        losses = []
        for _ in range(CHECK_STEPS):
            state, opt_state, loss = cell.step(state, opt_state, *cell.batch)
            losses.append(loss)
        (loss_err, update_err), (model_loss_err, logit_err, grad_errs) = \
            jax.device_get((_compare(
                jnp.stack(on_first(losses)), ref_losses, on_first(state[0]),
                ref_params, p0), model_errs))
        grad_errs = {p: float(e) for p, e in zip(leaves, grad_errs)}
        return {"ok": bool(loss_err <= LOSS_RTOL
                           and update_err <= UPDATE_RTOL
                           and model_loss_err <= MODEL_LOGIT_RTOL
                           and logit_err <= MODEL_LOGIT_RTOL
                           and max(grad_errs.values()) <= MODEL_GRAD_RTOL),
                "loss_rel_err": float(loss_err), "loss_rtol": LOSS_RTOL,
                "update_rel_err": float(update_err),
                "update_rtol": UPDATE_RTOL,
                "losses": [float(x) for x in jax.device_get(losses)],
                "model_loss_rel_err": float(model_loss_err),
                "model_logit_rel_l2_err": float(logit_err),
                "model_logit_rtol": MODEL_LOGIT_RTOL,
                "model_grad_rel_l2_err": grad_errs,
                "model_grad_rtol": MODEL_GRAD_RTOL}

    return Cell(step=step, state=state, opt_state=opt_state, batch=batch,
                items_per_step=n,
                train_flops_per_item=train_flops_per_item(config),
                check=check)


def abstract_step(config: dict, workload: dict, *, chips: int, mesh):
    """The step and the shapes it is called with, placed on ``mesh`` as
    ``build`` places them, with nothing on any device: what
    chipbench/aot_check.py compiles for a described chip."""
    model = make_model(config)
    opt, _ = build_optimizer(config["optimizer"])
    state, opt_state = jax.eval_shape(
        functools.partial(init_state, model, opt, config),
        jax.random.PRNGKey(0))
    batch = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in
             batch_shapes(config, workload["per_chip_batch"] * chips)]
    return (make_step(model, config["sizes"]["num_classes"], opt, mesh),
            placed(mesh, (state, opt_state), P()) + placed(mesh, batch,
                                                          P("hvd")))


@jax.jit
def _compare(losses, ref_losses, params, ref_params, params0):
    """Largest relative loss error, and the error of the CHECK_STEPS-step
    parameter update as a share of the update's own L2 size."""
    def sq(tree):
        return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                   for x in jax.tree.leaves(tree))

    diff = jax.tree.map(jnp.subtract, params, ref_params)
    moved = jax.tree.map(jnp.subtract, ref_params, params0)
    return (jnp.max(jnp.abs(losses - ref_losses) / jnp.abs(ref_losses)),
            jnp.sqrt(sq(diff) / sq(moved)))
