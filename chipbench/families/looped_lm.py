"""Family ``looped_lm``: looped decoders (models/transformer.py with
``n_loops`` > 1: one stack of blocks with sandwich norms run
``total_ut_steps`` times over its own output as one ``lax.scan``, the
final norm inside the recurrence, an exit after every pass through the
one untied head and a learned gate, the loss an expectation over the
exits less an entropy term), trained data-parallel through
``hvd.DistributedOptimizer`` + ``parallel.data_parallel_step`` on a
resident batch of seeded token ids.

Configuration keys read: ``sizes`` (the model's ``config.json`` names,
and ``n_layer`` as run), ``model`` (compute_dtype, remat, exit_beta),
``optimizer``. Workload keys read: ``per_chip_batch``, ``sequence``
(positions predicted per sequence; a sequence holds one token id more,
drawn uniformly from the whole vocabulary).
"""

import functools

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import flops_looped
from chipbench.cell import (Cell, build_optimizer, pick, placed, rel_l2,
                            replica_on, seed_key)
from chipbench.reference import ouro as reference
from horovod_tpu.models import transformer as T
from horovod_tpu.parallel import data_parallel_step, shard_batch

#: *The model.* The program's loss function (bf16 matmuls with f32
#: accumulation, f32 softmax, norms, classifier and gate, the fused
#: attention kernels, remat, the recurrence as a scan) against the
#: float32 reference on the first sequence of the cell's batch, at the
#: cell's widths, depth and passes. Every limit is given with its two
#: readings on the v5e (PERF.md, PR 35): the largest over nine sound runs
#: of as many seeds, and the program in ``float8_e4m3fn``, the nearest
#: precision below the stated one (one run).
#:
#: The loss, the step's loss and each exit's mean loss: bf16 carries 8
#: bits of mantissa; over 4096 predictions at seeded weights (every exit
#: reads near log 49152 = 10.8) the roundings average out: the loss
#: agreed to at most 2.2e-5 and the four exits' mean losses to at most
#: 6.1e-5; the 8-bit float read 4.5e-4 in the loss and 6.1e-4 to 1.6e-3
#: in the exits. One limit for the five, the geometric middle of 6.1e-5
#: and 4.5e-4. It catches a forward pass wrong in the large (a pass left
#: out, the exits weighted otherwise, the entropy term dropped or of the
#: wrong sign: chipbench/tests/test_looped_lm.py).
LOSS_RTOL = 1.7e-4
#: The exit distribution (the mean over the tokens of each exit's
#: probability, four numbers that sum to one; the largest absolute
#: difference is held to this). **This limit does not tell the
#: precisions apart, and is not meant to**: a mean over 4096 tokens of a
#: smooth function of the gates hardly hangs on the rounding, so the
#: sound runs read 4.8e-4 to 3.2e-3 and the 8-bit float 8.4e-3 (on a
#: seed that read 3.0e-3 sound), and no limit between two readings a
#: factor of 2.6 apart would be safe from a fresh seed. It is set five times above
#: the largest sound reading, for what moves a share by hundredths: a
#: gate on the un-normed state, a gate without its bias, the last exit's
#: share taken from its own gate (the tests show each at the toy sizes).
#: The 8-bit float misses the other limits by factors of 2.6 and more.
EXIT_SHARE_ATOL = 1.5e-2
#: The gradients, through 24 applications of the blocks forward and
#: backward: a shared leaf's gradient is the sum of its four uses', each
#: with its own roundings: at most 3.63e-2 over the sound runs (the last
#: block's W_q; 2.9e-2 to 3.6e-2 by seed), where the eight applications
#: of the dense cell read 1.5e-2; the program in an 8-bit float read 1.0
#: in every leaf of a block and in the embedding, 0.63 in the final
#: norm, 0.32 in the head. The limit is the geometric middle of 3.63e-2
#: and 0.32: the other LM cells' 4e-2 would leave this depth a quarter
#: of room. A use left out of the sum (a quarter of a gradient), a
#: sandwich norm left out, the un-normed state carried to the next pass,
#: the rotary turn on half the columns: each is off by a large share of
#: the gradient itself.
GRAD_RTOL = 1e-1
#: the gate's gradient (``gate.w`` and ``gate.b`` as one vector: the
#: bias's is one number, which a seed can bring near zero) is a small
#: difference of large terms where the exits' losses are near equal, as
#: at seeded weights: the entropy term's pull towards the uniform
#: distribution, which is exact in float32 on both sides, less the
#: losses' pull, which carries the state's rounding. As one vector the
#: sound runs read at most 3.4e-2 (leaf by leaf 3.4e-2 for w, 8.7e-4 to
#: 0.59 for b), the 8-bit float 0.39 for w and 0.60 for b. Between
#: 3.4e-2 and 0.39, with the more room above the sound reading.
GATE_GRAD_RTOL = 1.5e-1
#: the leaves whose gradients are compared: both ends of the depth (the
#: first block's gradient passes through all 24 applications), every
#: kind of weight, each of the four norms of a block, the final norm
#: inside the recurrence, the gate, the embedding and the untied head
CHECK_LEAVES = ("embed", "head", "ln_f.scale", "gate.w", "gate.b",
                "blocks.0.wq", "blocks.0.wk", "blocks.0.ln1.scale",
                "blocks.0.ln1_post.scale", "blocks.0.mlp.gate",
                "blocks.0.mlp.down", "blocks.-1.wq", "blocks.-1.wv",
                "blocks.-1.wo", "blocks.-1.ln2.scale",
                "blocks.-1.ln2_post.scale", "blocks.-1.mlp.up",
                "blocks.-1.mlp.down")
#: *The step.* One real step of the program from the seeded state (remat
#: and the scan inside the step, the optimizer under
#: DistributedOptimizer) against the plain optax optimizer on the
#: reference's mean gradient over the step's sequences, as in the other
#: LM families and for their reasons: Adam's first update keeps only the
#: gradient's sign, so the comparison keeps to the elements whose
#: reference gradient is at least the leaf's root mean square. There the
#: two sides agreed to at most 3.9e-5 over the sound runs; the 8-bit
#: float read 0.46 (the final norm) to 1.0, as a state left unchanged
#: does. UPDATE_RTOL is the dense cell's, between the two with the more
#: room above the sound reading. Three copies of a compared leaf stay on the chip while
#: the step runs, and the step takes 91% of it: so not the embedding and
#: the head (403 MB a copy each at the published vocabulary) and one
#: matrix of each kind at each end of the depth (126 MB a copy in all);
#: EVERY_LEAF_STEP_MIN sees to the others.
UPDATE_LEAVES = ("ln_f.scale", "gate.w", "gate.b", "blocks.0.wq",
                 "blocks.0.ln1.scale", "blocks.0.ln1_post.scale",
                 "blocks.0.mlp.down", "blocks.-1.wo", "blocks.-1.ln2.scale",
                 "blocks.-1.ln2_post.scale", "blocks.-1.mlp.up")
UPDATE_RTOL = 1e-3
#: and every leaf of the tree has to have moved: Adam's first update is
#: the learning rate times the gradient's sign (and a tenth of the leaf
#: in weight decay), so a leaf's root-mean-square change over the
#: learning rate reads near one wherever most of its gradient is above
#: Adam's eps, 0 for a leaf the step left alone. On the v5e the stillest
#: leaf read 0.2818 to 0.2834 (the embedding: of 49,152 rows at most
#: 4096 meet a token in a step, the others move by their weight decay
#: alone, 0.1 x 0.02 beside 1: sqrt(0.08) = 0.283 whatever the seed);
#: with the program in an 8-bit float a block's W_k read 0.002. The
#: limit leaves the sound reading a factor of 2.8.
EVERY_LEAF_STEP_MIN = 0.1


def make_cfg(config: dict) -> T.TransformerConfig:
    sz, m = config["sizes"], config["model"]
    if sz["rms_norm_eps"] != 1e-6:
        raise ValueError("the decoder's norms take eps 1e-6")
    return T.TransformerConfig(
        vocab_size=sz["vocab_size"], d_model=sz["hidden_size"],
        n_heads=sz["num_attention_heads"],
        n_kv_heads=sz["num_key_value_heads"], n_layers=sz["n_layer"],
        d_ff=sz["intermediate_size"], max_seq=sz["max_position_embeddings"],
        dtype=getattr(jnp, m["compute_dtype"]), remat=m["remat"],
        d_head=sz["head_dim"], positions="layout", rope_layout=(1,),
        rope_theta=float(sz["rope_theta"]), tie_embeddings=False,
        mlp="gated", n_loops=sz["total_ut_steps"], sandwich_norms=True,
        exit_beta=float(m["exit_beta"]))


def arch_of(config: dict) -> dict:
    """What the reference needs of the configuration."""
    sz = config["sizes"]
    return {**{k: sz[k] for k in ("total_ut_steps", "rope_theta",
                                  "rms_norm_eps")},
            "exit_beta": config["model"]["exit_beta"]}


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
    return flops_looped.TRAIN_FLOP_MULT * flops_looped.fwd_flops_per_token(
        config["sizes"], sequence)


def init_state(cfg, opt, key):
    """Seeded parameters and optimizer state, traced as one program."""
    params = T.init(key, cfg)
    return params, opt.init(params)


def build(config: dict, workload: dict, *, chips: int, seed: int,
          mesh) -> Cell:
    cfg = make_cfg(config)
    arch = arch_of(config)
    opt, plain_opt = build_optimizer(config["optimizer"])
    seq = workload["sequence"]
    if seq > cfg.max_seq:
        raise ValueError(f"sequence {seq} exceeds max_position_embeddings "
                         f"{cfg.max_seq}")
    n = workload["per_chip_batch"] * chips
    k_init, k_tok = jax.random.split(seed_key(seed), 2)
    learning_rate = config["optimizer"]["args"]["learning_rate"]

    @jax.jit
    def make_tokens(key):
        return jax.random.randint(key, (n, seq + 1), 0, cfg.vocab_size,
                                  jnp.int32)

    # the seeded state a half at a time, two programs for the window's
    # state and for the check: there the optimizer's 4.9 GB is made only
    # once the reference is done
    make_params, make_opt_state = (jax.jit(
        lambda key, half=half: init_state(cfg, opt, key)[half],
        out_shardings=NamedSharding(mesh, P())) for half in (0, 1))
    params, opt_state = make_params(k_init), make_opt_state(k_init)
    batch = shard_batch((make_tokens(k_tok),), mesh=mesh)

    @jax.jit
    def program(params, tokens):
        """The program's loss on the first sequence, each exit's mean
        loss and the mean exit distribution, and its gradients in
        CHECK_LEAVES."""
        (loss, exits), grads = jax.value_and_grad(T.lm_loss, has_aux=True)(
            params, tokens[:1], cfg, use_constraints=False,
            return_exits=True)
        return loss, exits, [pick(grads, p) for p in CHECK_LEAVES]

    def reference_program(params, tokens):
        """The one reference program: sequence by sequence, the float32
        loss, its exits and the gradients of CHECK_LEAVES; then what the
        plain optimizer, from a fresh state, makes of the gradients'
        mean in UPDATE_LEAVES (an optimizer that acts leaf by leaf gives
        a leaf the same update alone as in the tree)."""
        losses, exits, grads = jax.lax.map(
            lambda sequence: reference.loss_and_grads(
                params, sequence, arch, CHECK_LEAVES), tokens)
        mean = [g.mean(0) for p, g in zip(CHECK_LEAVES, grads)
                if p in UPDATE_LEAVES]
        old = [pick(params, p) for p in UPDATE_LEAVES]
        updates, _ = plain_opt.update(mean, plain_opt.init(old), old)
        sure = [jnp.abs(g) >= jnp.sqrt(jnp.mean(jnp.square(g))) for g in mean]
        return (losses, [e[0] for e in exits], [g[0] for g in grads],
                optax.apply_updates(old, updates), updates, sure)

    @jax.jit
    def model_errors(exits, grads, ref_exits, ref_grads):
        """Each exit's mean loss as a relative error, the exit
        distribution's largest absolute difference, per leaf of
        CHECK_LEAVES the distance of the program's gradient from the
        reference's, and that distance for the gate's two leaves as one
        vector."""
        (each, share), (ref_each, ref_share) = exits, ref_exits
        gate = [i for i, p in enumerate(CHECK_LEAVES) if p.startswith("gate")]
        return (jnp.abs(each - ref_each) / ref_each,
                jnp.max(jnp.abs(share - ref_share)),
                [rel_l2(g, r) for g, r in zip(grads, ref_grads)],
                rel_l2(*(jnp.concatenate([each[i].ravel() for i in gate])
                         for each in (grads, ref_grads))))

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

    @jax.jit
    def leaf_steps(old, new):
        """Per leaf of the whole tree, the root-mean-square change of
        one step over the learning rate."""
        return jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.mean(jnp.square(b - a)))
            / learning_rate, old, new)

    def check(cell: Cell) -> dict:
        """The program's loss function, then one real step, against the
        float32 reference and the plain optimizer. Starts again from the
        seeded state and leaves the cell one step on from it; the cell's
        own state goes first, two copies do not fit the chip."""
        first = mesh.devices.flat[0]
        peaks = {}

        def peak_after(phase):  # the runtime's counter, where it has one
            peaks[phase] = (first.memory_stats() or {}).get(
                "peak_bytes_in_use")

        cell.state = cell.opt_state = None
        state = make_params(k_init)  # the optimizer state comes later
        params = replica_on(first, state)
        tokens = jax.device_put(cell.batch[0], first)
        loss, exits, grads = program(params, tokens)
        jax.block_until_ready(grads)
        peak_after("program")
        with jax.default_matmul_precision("highest"):
            (ref_losses, ref_exits, ref_grads, ref_new, ref_updates,
             sure) = jax.jit(reference_program)(params, tokens)
        loss, exits, (exit_errs, share_err, grad_errs, gate_err), ref_exits = (
            jax.device_get((loss, exits, model_errors(
                exits, grads, ref_exits, ref_grads), ref_exits)))
        grad_errs = {p: float(e) for p, e in zip(CHECK_LEAVES, grad_errs)}
        peak_after("reference")
        del params, grads, ref_grads  # the step takes most of the chip

        cell.state, cell.opt_state, step_loss = cell.step(
            state, make_opt_state(k_init), *cell.batch)
        update_errs, step_loss, ref_losses = jax.device_get((
            update_errors(replica_on(first, cell.state), ref_new,
                          ref_updates, sure),
            replica_on(first, step_loss), ref_losses))
        update_errs = {p: float(e) for p, e in zip(UPDATE_LEAVES, update_errs)}
        peak_after("step")
        # the seeded state once more (the step took the first as its own)
        steps = jax.device_get(leaf_steps(make_params(k_init), cell.state))
        steps = {jax.tree_util.keystr(path, simple=True, separator="."):
                 float(x) for path, x in jax.tree.leaves_with_path(steps)}
        stillest = min(steps, key=steps.get)
        loss_err = abs(loss - ref_losses[0]) / ref_losses[0]
        step_loss_err = abs(step_loss - ref_losses.mean()) / ref_losses.mean()

        return {"ok": bool(loss_err <= LOSS_RTOL
                           and float(exit_errs.max()) <= LOSS_RTOL
                           and float(share_err) <= EXIT_SHARE_ATOL
                           and max(e for p, e in grad_errs.items()
                                   if "gate" not in p) <= GRAD_RTOL
                           and float(gate_err) <= GATE_GRAD_RTOL
                           and step_loss_err <= LOSS_RTOL
                           and max(update_errs.values()) <= UPDATE_RTOL
                           and steps[stillest] >= EVERY_LEAF_STEP_MIN),
                "loss": float(loss), "loss_rel_err": float(loss_err),
                "step_loss_rel_err": float(step_loss_err),
                "exit_losses": [float(x) for x in exits[0]],
                "exit_loss_rel_err": [float(x) for x in exit_errs],
                "loss_rtol": LOSS_RTOL,
                "exit_shares": [float(x) for x in exits[1]],
                "exit_shares_reference": [float(x) for x in ref_exits[1]],
                "exit_share_abs_err": float(share_err),
                "exit_share_atol": EXIT_SHARE_ATOL,
                "grad_rel_l2_err": grad_errs, "grad_rtol": GRAD_RTOL,
                "gate_grad_rel_l2_err": float(gate_err),
                "gate_grad_rtol": GATE_GRAD_RTOL,
                "update_rel_l2_err": update_errs, "update_rtol": UPDATE_RTOL,
                "leaves": len(steps), "stillest_leaf": stillest,
                "leaf_step_over_lr": [steps[stillest], max(steps.values())],
                "every_leaf_step_min": EVERY_LEAF_STEP_MIN,
                "peak_bytes_in_use_after": peaks}

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
