"""Family ``mla_moe_lm``: DeepSeek-V3-shaped decoders (models/transformer.py
composed per layer: latent attention through the ``hvd_mla_*`` kernels
of ops/pallas/flash_attention.py, a leading dense gated layer, then
parallel/moe.py's dropless expert layer under a sigmoid, bias-corrected
router beside shared experts), trained data-parallel through
``hvd.DistributedOptimizer`` + ``parallel.data_parallel_step`` on a
resident batch of seeded token ids: one chip's share of a deployment in
which several chips share each layer (the configuration file says how).

Configuration keys read: ``sizes`` (the model's ``config.json`` names,
and ``n_layer``, ``experts_held``, ``embedding_rows`` as run), ``model``
(compute_dtype, remat, xent_chunk, first_expert_held,
embedding_init_std, router_bias_init_std), ``optimizer`` (AdamW: the
router's correction bias is masked out of its weight decay, and no
gradient reaches it, so the optimizer leaves it alone). Workload keys
read: ``per_chip_batch``, ``sequence`` (positions predicted per
sequence; a sequence holds one token id more, drawn uniformly from the
rows held).
"""

import functools

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from chipbench import flops_mla
from chipbench.cell import (Cell, pick, placed, rel_l2, replica_on,
                            seed_key)
from chipbench.reference import kanana2 as reference
from horovod_tpu.models import transformer as T
from horovod_tpu.parallel import data_parallel_step, shard_batch

#: *The model.* Loss and gradients of the program's loss function (bf16
#: matmuls with f32 accumulation, f32 softmax, norms and router, the
#: fused latent kernels, remat) against the float32 reference on the
#: first sequence of the cell's batch, at the cell's widths and depth.
#:
#: The loss: bf16 carries 8 bits of mantissa; over 8192 predictions the
#: roundings average out: over the sound runs on the v5e the first
#: sequence's loss agreed to at most 1.6e-5 and the step's to at most
#: 3.1e-5, and the program in an 8-bit float read 1.45e-4 (PERF.md,
#: PR 33). LOSS_RTOL, which both are held to, is the geometric middle
#: of 3.1e-5 and 1.45e-4: this cell's own readings, not the other LM
#: cells' limit (3e-4 would pass the 8-bit float's forward pass). It
#: catches a forward pass wrong in the large, whatever its gradients
#: read: bf16 where f32 accumulation is stated, the softmax scale
#: 1/sqrt(128) for 1/sqrt(192), the 2.448 left out, the shared experts
#: left out or weighted (chipbench/tests/test_mla_moe_lm.py shows each
#: at the toy sizes).
LOSS_RTOL = 7e-5
#: The gradients: through five blocks forward and backward a leaf's
#: gradient gathers a few roundings each way: at most 1.23e-2 over nine
#: sound runs (W_q of an expert layer); the program in an 8-bit float
#: read 1.0 in every leaf below the head (4.8e-2 in the final norm,
#: 1.0e-1 in the head). GRAD_RTOL is the other LM cells' 4e-2, between
#: the two. The rotary turn on the wrong 64 columns or missing on the
#: shared key, ``norm_kv`` left out, a wrong scale: each moves W_q's,
#: W_kva's or W_kvb's gradient by a large share of itself. And a routing
#: that a checkpointed block's backward pass makes anew from recomputed
#: activations (PERF.md, PR 33: the sigmoid choice is a kept residual
#: since) read 1.0e-1 in the held expert's three matrices.
GRAD_RTOL = 4e-2
#: a router's gradient is a small difference of large terms (the
#: derivative of six normalised weights whose expert outputs nearly
#: cancel): its limit is wider, the geometric middle of 1.27e-2 (the
#: largest of nine sound runs) and the 1.0 of an 8-bit float, and far
#: under what a router reading another input, softmax for sigmoid, or
#: weights taken from ``s + b`` would show; the backward pass routed
#: anew (above) read 1.1e-1 to 1.3e-1
ROUTER_GRAD_RTOL = 1e-1
#: *The routing.* The program's residual stream is bf16 and the
#: reference's float32, so where a token's 6th and 7th biased scores lie
#: within that rounding of each other the two sides choose different
#: experts, though the router itself is float32 at the highest precision
#: on both sides (PERF.md, PR 28). So the sequence the gradients are
#: compared on is routed in the reference as the program routed it, and
#: the routing is judged by itself: the share of (token, layer) pairs
#: whose chosen set is not the reference's own may be at most
#: ROUTING_DIFFERS_MAX, and no imposed pick may lie further under the
#: reference's own 6th *biased* score than ROUTING_SHORT_MAX standard
#: deviations of that token's 128 biased scores. A router in bf16, one
#: reading another input, a choice by ``s`` without ``b``: each differs
#: on a large share of the tokens, and a wrong pick falls of the order
#: of one short. Each limit is the geometric middle of its two readings
#: on the v5e (PERF.md, PR 33): over nine sound runs the share was
#: 0.030 to 0.054 (it grows with the depth) and the largest shortfall
#: 0.028; the program in an 8-bit float read at least 0.357 and 0.235.
ROUTING_DIFFERS_MAX = 0.14
ROUTING_SHORT_MAX = 0.08
#: the leaves whose gradients are compared: W_q, W_kva, norm_kv's scale
#: and W_kvb of the dense layer (0) and of an expert layer (1), the last
#: W_o, the dense layer's gate matrix, a router, one held expert's three
#: matrices, the shared expert's gate and down, the final norm, the
#: embedding's rows held and the untied head
CHECK_LEAVES = ("embed", "head", "ln_f.scale",
                "blocks.0.wq", "blocks.0.wkva", "blocks.0.ln_kv.scale",
                "blocks.0.wkvb", "blocks.1.wq", "blocks.1.wkva",
                "blocks.1.ln_kv.scale", "blocks.1.wkvb", "blocks.-1.wo",
                "blocks.0.mlp.gate", "blocks.1.router",
                "blocks.1.experts.gate.3", "blocks.1.experts.up.3",
                "blocks.1.experts.down.3", "blocks.1.shared.gate",
                "blocks.1.shared.down")
#: *The step.* One real step of the program from the seeded state
#: against the plain optax optimizer on the reference's mean gradient
#: over the step's sequences, as in the other LM families and for their
#: reasons: Adam's first update keeps only the gradient's sign, so the
#: comparison keeps to the elements whose reference gradient is at least
#: the leaf's root mean square. UPDATE_RTOL is the dense cell's (the
#: largest of nine sound runs 3.1e-4, W_q of an expert layer; an 8-bit
#: float and a state left unchanged read 1.0). Not among these leaves: the embedding and the head (131 MB a copy each)
#: and the router (whose small gradient's signs hang on the routing);
#: EVERY_LEAF_STEP_MIN sees to them.
UPDATE_LEAVES = tuple(p for p in CHECK_LEAVES
                      if p not in ("embed", "head") and "router" not in p)
UPDATE_RTOL = 1e-3
#: the held expert's three matrices have a limit of their own in this
#: half, as in the ``moe_lm`` family and for its reason: their gradients
#: are sums over the few hundred rows routed to one expert, and whole
#: terms of those sums differ between the two sides where a token of the
#: sequence the reference routes by its own choice ties; single elements
#: above the leaf's root mean square then change sign: 2.3e-4 to 1.41e-2
#: over nine sound runs. The program in an 8-bit float, and a state left
#: unchanged, read 1.0 (PERF.md, PR 33); the limit leaves the more room
#: above the sound reading, since fresh seeds read higher.
EXPERT_UPDATE_RTOL = 5e-2
#: and every leaf of the tree but the router's correction bias has to
#: have moved: Adam's first update is the learning rate times the
#: gradient's sign (and a tenth of the leaf in weight decay), so a
#: leaf's root-mean-square change over the learning rate reads near one
#: wherever most of its gradient is above Adam's eps, 0 for a leaf the
#: step left alone. On the v5e the stillest leaf read 0.787 (the
#: embedding: a third of its rows meet no token in a step and move by
#: their weight decay alone) and, with the program in an 8-bit float,
#: 0.002 (a router). The bias has to read exactly 0: no gradient reaches
#: it and the optimizer's decay is masked from it.
EVERY_LEAF_STEP_MIN = 0.2
BIAS = "router_bias"


def make_cfg(config: dict) -> T.TransformerConfig:
    sz, m = config["sizes"], config["model"]
    if sz["v_head_dim"] != sz["qk_nope_head_dim"]:
        raise ValueError("the decoder's latent heads take a value as wide "
                         "as a head's no-rope columns")
    return T.TransformerConfig(
        vocab_size=sz["embedding_rows"], d_model=sz["hidden_size"],
        n_heads=sz["num_attention_heads"], n_layers=sz["n_layer"],
        d_ff=sz["intermediate_size"], max_seq=sz["max_position_embeddings"],
        dtype=getattr(jnp, m["compute_dtype"]), remat=m["remat"],
        xent_chunk=m["xent_chunk"], d_head=sz["qk_nope_head_dim"],
        positions="layout", rope_theta=float(sz["rope_theta"]),
        n_experts=sz["n_routed_experts"],
        experts_per_token=sz["num_experts_per_tok"],
        d_expert=sz["moe_intermediate_size"],
        experts_held=(m["first_expert_held"], sz["experts_held"]),
        tie_embeddings=False, kv_latent=sz["kv_lora_rank"],
        d_rope=sz["qk_rope_head_dim"], mlp="gated",
        n_dense_layers=sz["first_k_dense_replace"],
        n_shared_experts=sz["n_shared_experts"], router_scoring="sigmoid",
        router_input="normed",
        routed_scale=float(sz["routed_scaling_factor"]),
        expert_activation="silu")


def arch_of(config: dict) -> dict:
    """What the reference needs of the configuration."""
    sz = config["sizes"]
    return {**{k: sz[k] for k in (
        "qk_nope_head_dim", "kv_lora_rank", "rope_theta",
        "num_experts_per_tok", "routed_scaling_factor")},
        "first_held": config["model"]["first_expert_held"]}


def decays(params):
    """AdamW's weight-decay mask: every leaf but the routers' correction
    biases."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: BIAS not in jax.tree_util.keystr(path), params)


def build_optimizer(spec: dict):
    """The configuration's AdamW with the bias masked from its decay,
    under ``hvd.DistributedOptimizer`` (the program under test) and
    plain (the reference's)."""
    if spec["name"] != "adamw":
        raise ValueError("this family masks AdamW's weight decay")
    plain = optax.adamw(**spec["args"], mask=decays)
    return hvd.DistributedOptimizer(plain), plain


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
    return flops_mla.TRAIN_FLOP_MULT * flops_mla.fwd_flops_per_token(
        config["sizes"], sequence)


def init_state(cfg, opt, model: dict, key):
    """Seeded parameters and optimizer state, traced as one program. The
    embedding's rows are drawn with a spread of their own, and the
    routers' correction biases, which ``T.init`` leaves at zero, at a
    small one (the configuration's ``assumed`` says why)."""
    params = T.init(key, cfg)
    params["embed"] = model["embedding_init_std"] * jax.random.normal(
        jax.random.fold_in(key, 1), params["embed"].shape, jnp.float32)
    for i, blk in enumerate(params["blocks"]):
        if BIAS in blk:
            blk[BIAS] = model["router_bias_init_std"] * jax.random.normal(
                jax.random.fold_in(key, 2 + i), blk[BIAS].shape, jnp.float32)
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
    first_held, held = cfg.held
    bound = seq * min(cfg.experts_per_token, held)
    expected = seq * cfg.experts_per_token * held / cfg.n_experts
    learning_rate = config["optimizer"]["args"]["learning_rate"]

    @jax.jit
    def make_tokens(key):
        return jax.random.randint(key, (n, seq + 1), 0, cfg.vocab_size,
                                  jnp.int32)

    # the seeded state a half at a time, two programs for the window's
    # state and for the check: there the other half's 2.3 or 4.6 GB is
    # then never made (both halves at once beside a stepped state would
    # fill the chip)
    make_params, make_opt_state = (jax.jit(
        lambda key, half=half: init_state(cfg, opt, config["model"],
                                          key)[half],
        out_shardings=NamedSharding(mesh, P())) for half in (0, 1))
    params, opt_state = make_params(k_init), make_opt_state(k_init)
    batch = shard_batch((make_tokens(k_tok),), mesh=mesh)

    @jax.jit
    def program(params, tokens):
        """The program's loss on the first sequence, its gradients in
        CHECK_LEAVES and, per expert layer, the experts each token
        chose, as a mask [tokens, experts]."""
        (loss, routing), grads = jax.value_and_grad(
            T.lm_loss, has_aux=True)(params, tokens[:1], cfg,
                                     use_constraints=False,
                                     return_routing=True)
        masks = [jnp.zeros((seq, cfg.n_experts), bool).at[
            jnp.arange(seq)[:, None], chosen].set(True) for chosen in routing]
        return loss, [pick(grads, p) for p in CHECK_LEAVES], masks

    def reference_program(params, tokens, masks):
        """The one reference program: sequence by sequence, the float32
        loss, the gradients of CHECK_LEAVES and what the routers did;
        then what the plain optimizer, from a fresh state, makes of the
        gradients' mean in UPDATE_LEAVES (an optimizer that acts leaf by
        leaf gives a leaf the same update alone as in the tree). The
        first sequence, which the program's gradients are compared on,
        is routed as the program routed it (``masks``; the reference's
        own choice and the imposed picks' shortfall come back with it:
        reference/kanana2.py ``router_weights``); the others by the
        reference's own choice."""
        def one(each):
            sequence, first = each
            (loss, routing), grads = jax.value_and_grad(
                reference.loss, has_aux=True)(
                    params, sequence, arch, [m & first for m in masks])
            return loss, [pick(grads, p) for p in CHECK_LEAVES], routing

        losses, grads, routing = jax.lax.map(
            one, (tokens, jnp.arange(n) == 0))
        mean = [g.mean(0) for p, g in zip(CHECK_LEAVES, grads)
                if p in UPDATE_LEAVES]
        old = [pick(params, p) for p in UPDATE_LEAVES]
        updates, _ = plain_opt.update(mean, plain_opt.init(old), old)
        sure = [jnp.abs(g) >= jnp.sqrt(jnp.mean(jnp.square(g))) for g in mean]
        return (losses, [g[0] for g in grads],
                [(own[0], short[0]) for own, short in routing],
                optax.apply_updates(old, updates), updates, sure)

    @jax.jit
    def model_errors(grads, ref_grads, masks, ref_routing):
        """Per leaf of CHECK_LEAVES the distance of the program's
        gradient from the reference's; per expert layer the share of
        tokens whose chosen set differs from the reference's own, the
        rows routed to held experts, the fullest held expert's rows and
        the rows past the buffers' bound (dropped: none, by the bound's
        construction)."""
        errs = [rel_l2(g, r) for g, r in zip(grads, ref_grads)]
        differs, routed, fullest, dropped = [], [], [], []
        for mask, (own, _) in zip(masks, ref_routing):
            differs.append(jnp.mean(jnp.any(mask != own, axis=-1)))
            load = mask[:, first_held:first_held + held].sum(axis=0)
            routed.append(load.sum())
            fullest.append(load.max())
            dropped.append(jnp.maximum(load.sum() - bound, 0))
        return errs, (differs, routed, fullest, dropped,
                      [short for _, short in ref_routing])

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
        loss, grads, masks = program(params, tokens)
        jax.block_until_ready(grads)
        peak_after("program")
        with jax.default_matmul_precision("highest"):
            (ref_losses, ref_grads, ref_routing, ref_new, ref_updates,
             sure) = jax.jit(reference_program)(params, tokens, masks)
        loss, (grad_errs, routers) = jax.device_get(
            (loss, model_errors(grads, ref_grads, masks, ref_routing)))
        grad_errs = {p: float(e) for p, e in zip(CHECK_LEAVES, grad_errs)}
        differs, routed, fullest, dropped, short = (
            [float(x) for x in each] for each in routers)
        peak_after("reference")
        # the step takes most of the chip
        del params, grads, masks, ref_grads, ref_routing

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
        bias_moved = max(x for p, x in steps.items() if BIAS in p)
        moved = {p: x for p, x in steps.items() if BIAS not in p}
        stillest = min(moved, key=moved.get)
        loss_err = abs(loss - ref_losses[0]) / ref_losses[0]
        step_loss_err = abs(step_loss - ref_losses.mean()) / ref_losses.mean()

        def worst(errs, word, among=True):
            return max(e for p, e in errs.items() if (word in p) == among)

        return {"ok": bool(loss_err <= LOSS_RTOL
                           and worst(grad_errs, "router", False) <= GRAD_RTOL
                           and worst(grad_errs, "router") <= ROUTER_GRAD_RTOL
                           and max(differs) <= ROUTING_DIFFERS_MAX
                           and max(short) <= ROUTING_SHORT_MAX
                           and max(dropped) == 0
                           and step_loss_err <= LOSS_RTOL
                           and worst(update_errs, "experts", False)
                           <= UPDATE_RTOL
                           and worst(update_errs, "experts")
                           <= EXPERT_UPDATE_RTOL
                           and moved[stillest] >= EVERY_LEAF_STEP_MIN
                           and bias_moved == 0.0),
                "loss": float(loss), "loss_rel_err": float(loss_err),
                "step_loss_rel_err": float(step_loss_err),
                "loss_rtol": LOSS_RTOL,
                "grad_rel_l2_err": grad_errs, "grad_rtol": GRAD_RTOL,
                "router_grad_rtol": ROUTER_GRAD_RTOL,
                "routing_differs_share": differs,
                "routing_differs_max": ROUTING_DIFFERS_MAX,
                "routing_short_of_kth_in_sd": short,
                "routing_short_max": ROUTING_SHORT_MAX,
                "rows_routed_to_held": routed, "rows_expected": expected,
                "buffer_rows": bound,
                "fullest_expert_over_mean": [
                    f * held / r if r else 0.0
                    for f, r in zip(fullest, routed)],
                "dropped_rows": dropped,
                "update_rel_l2_err": update_errs, "update_rtol": UPDATE_RTOL,
                "expert_update_rtol": EXPERT_UPDATE_RTOL,
                "leaves": len(steps), "stillest_leaf": stillest,
                "leaf_step_over_lr": [moved[stillest], max(moved.values())],
                "every_leaf_step_min": EVERY_LEAF_STEP_MIN,
                "router_bias_step_over_lr": bias_moved,
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
    state = jax.eval_shape(
        functools.partial(init_state, cfg, opt, config["model"]),
        jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct(
        (workload["per_chip_batch"] * chips, workload["sequence"] + 1),
        jnp.int32)
    return (make_step(cfg, opt, mesh),
            placed(mesh, state, P()) + placed(mesh, (tokens,), P("hvd")))
