"""Family ``moe_lm``: sparse-expert decoders with window and global layers
(models/transformer.py composed per layer, parallel/moe.py's dropless
expert layer, the windowed grouped-head kernels of
ops/pallas/flash_attention.py), trained data-parallel through
``hvd.DistributedOptimizer`` + ``parallel.data_parallel_step`` on a
resident batch of seeded token ids: one chip's share of a deployment in
which several chips share each layer (the configuration file says how).

Configuration keys read: ``sizes`` (the model's ``config.json`` names,
and ``n_layer``, ``experts_held``, ``embedding_rows`` as run),
``model`` (compute_dtype, remat, xent_chunk, first_expert_held,
embedding_init_std), ``optimizer``. Workload keys read: ``per_chip_batch``, ``sequence``
(positions predicted per sequence; a sequence holds one token id more,
drawn uniformly from the rows held).
"""

import functools

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import flops_moe
from chipbench.cell import (Cell, build_optimizer, dtype_of, pick, placed,
                            rel_l2, replica_on, seed_key)
from chipbench.reference import smallthinker as reference
from horovod_tpu.models import transformer as T
from horovod_tpu.parallel import data_parallel_step, shard_batch

#: *The model.* Loss and gradients of the program's loss function (bf16
#: matmuls with f32 accumulation, f32 softmax, norms and router, the
#: fused kernels, remat) against the float32 reference on the first
#: sequence of the cell's batch, at the cell's widths and depth.
#:
#: The loss: bf16 carries 8 bits of mantissa (2**-8 = 3.9e-3 a
#: rounding); over 8192 predictions the roundings average out and the
#: loss agrees to a few 1e-5 (dense cell: 3e-5; PERF.md has this
#: cell's). LOSS_RTOL is about ten times that. It is the tolerance that
#: catches a forward pass wrong in the large: bf16 where f32
#: accumulation is stated (1e-3 and more over 8192-long sums), a window
#: off by one 1024-tile (a windowed layer's late queries lose or gain a
#: quarter of their keys), a key/value head mis-grouped (every query
#: head but one of a group reads another head's keys: the attention
#: output is another function altogether).
LOSS_RTOL = 3e-4
#: The gradients: through four blocks forward and backward a leaf's
#: gradient gathers a few roundings each way. GRAD_RTOL is the dense
#: cell's 4e-2 (its largest reading 1.5e-2; this cell's in PERF.md). A
#: weight normalised over the held experts instead of the chosen scales
#: every expert's output by the inverse of the share of a token's
#: weight that is held (an eighth on average here) and misses every
#: expert leaf's tolerance by an order of magnitude, as a window off by
#: a tile and a mis-grouped key/value head miss W_q's and W_k's, and an
#: 8-bit float (2**-3 a rounding) most of them
#: (chipbench/tests/test_moe_lm.py shows all four at the toy sizes).
GRAD_RTOL = 4e-2
#: a router's gradient is a small difference of large terms (the
#: derivative of a softmax over six scores whose expert outputs nearly
#: cancel): its tolerance is wider, and still far under the 1.0 and
#: more that a router reading the normed input, or weights normalised
#: over the wrong set, would show
ROUTER_GRAD_RTOL = 1.5e-1
#: an expert's gate matrix meets ReLU's kink: where a pre-activation
#: ``u G_e`` lies within bf16's rounding of zero the two sides disagree on
#: whether that element passes, and each such element's whole
#: contribution is in one gradient and not in the other (its up and down
#: matrices, which see the product after the gate, read a third of it):
#: 4.1e-2 on the v5e with the routing imposed (PERF.md). Gelu for relu,
#: or gate and up exchanged, read of the order of one.
GATE_GRAD_RTOL = 1e-1
#: *The routing.* The program's residual stream is bf16 and the
#: reference's float32, so where a token's 6th and 7th router scores
#: lie within that rounding of each other the two sides choose
#: different experts, though the router itself is float32 at the
#: highest precision on both sides: on the v5e 1% of the tokens in the
#: first layer and 4.4-5.1% in the later ones (PERF.md). One expert of
#: six then differs, with the smallest weight, and an expert's
#: gradients read 4-5e-2 off for that alone. So the sequence the
#: gradients are compared on is routed in the reference as the program
#: routed it, and the routing is judged by itself, not hidden in a
#: wider gradient tolerance: the share of (token, layer) pairs whose
#: chosen set is not the reference's own may be at most
#: ROUTING_DIFFERS_MAX, and no imposed pick may lie further under the
#: reference's own 6th score than ROUTING_SHORT_MAX standard deviations
#: of that token's 64 scores. Each limit is the geometric middle of its
#: two readings on the v5e (PERF.md): over twenty sound runs the largest
#: share was 0.027 and the largest shortfall 0.023; the program in an
#: 8-bit float read at least 0.14 and 0.109 (a router in bf16, or
#: reading another input, differs on every other token, and a wrong
#: pick falls of the order of one short).
ROUTING_DIFFERS_MAX = 0.06
ROUTING_SHORT_MAX = 0.05
#: the leaves whose gradients are compared: the router, W_q and W_k of a
#: global layer (0) and of a window layer (1), one held expert's three
#: matrices, the last W_o, the final norm, the embedding's rows held and
#: the untied head
CHECK_LEAVES = ("embed", "head", "ln_f.scale", "blocks.0.router",
                "blocks.1.router", "blocks.0.wq", "blocks.0.wk",
                "blocks.1.wq", "blocks.1.wk", "blocks.-1.wo",
                "blocks.0.experts.gate.3", "blocks.0.experts.up.3",
                "blocks.0.experts.down.3")
#: *The step.* One real step of the program from the seeded state
#: against the plain optax optimizer on the reference's mean gradient
#: over the step's sequences, as in the dense family and for its reasons:
#: Adam's first update keeps only the gradient's sign, so the comparison
#: keeps to the elements whose reference gradient is at least the leaf's
#: root mean square. UPDATE_RTOL is the dense cell's: far above
#: agreement to 1e-5, under what a learning rate off by 2e-3 or a lost
#: weight decay would show. Not among these leaves: the embedding and
#: the head (194 MB a copy each) and the routers (whose small gradients'
#: signs hang on the routing); EVERY_LEAF_STEP_MIN sees to them.
UPDATE_LEAVES = tuple(p for p in CHECK_LEAVES
                      if p not in ("embed", "head") and "router" not in p)
UPDATE_RTOL = 1e-3
#: the held expert's three matrices have a limit of their own in this
#: half: their gradients are sums over the few hundred rows routed to
#: one expert, and whole terms of those sums differ between the two
#: sides: in the gate's, where a pre-activation crosses ReLU's kink
#: under bf16; in all three, where a token of the sequences the
#: reference routes by its own choice ties (above). A single element
#: above the leaf's root mean square then changes sign, and n of the
#: 620,000 compared read 2 sqrt(n / 620,000) = 2.5e-3 sqrt(n): over 32
#: runs on the v5e 1.0e-4 to 2.0e-4 with none, 2.5e-3 to 5.1e-3 with
#: one to four (the gate's in four runs, the up matrix's in one). The
#: program in an 8-bit float, and a state left unchanged, read 1.0
#: (PERF.md). 3e-2 is 140 such elements.
EXPERT_UPDATE_RTOL = 3e-2
#: and every leaf of the tree, the embedding, the head and the routers
#: among them, has to have moved: Adam's first update is the learning
#: rate times the gradient's sign (and a tenth of the leaf in weight
#: decay), so a leaf's root-mean-square change over the learning rate
#: reads near one wherever most of its gradient is above Adam's eps, 0
#: for a leaf the step left alone, and a few thousandths (the weight
#: decay of a 0.02 spread) where the gradient is lost. On the v5e the
#: smallest leaf reads 0.746 (the embedding: 42% of its rows meet no
#: token in a step and move by their weight decay alone) and, with the
#: program in an 8-bit float, 0.002 (a router; PERF.md).
EVERY_LEAF_STEP_MIN = 0.2


def make_cfg(config: dict) -> T.TransformerConfig:
    sz, m = config["sizes"], config["model"]
    layers = sz["n_layer"]
    return T.TransformerConfig(
        vocab_size=sz["embedding_rows"], d_model=sz["hidden_size"],
        n_heads=sz["num_attention_heads"], n_layers=layers, d_ff=0,
        max_seq=sz["max_position_embeddings"],
        dtype=dtype_of(m["compute_dtype"]), remat=m["remat"],
        xent_chunk=m["xent_chunk"],
        n_kv_heads=sz["num_key_value_heads"], d_head=sz["head_dim"],
        positions="layout", rope_layout=tuple(sz["rope_layout"][:layers]),
        rope_theta=float(sz["rope_theta"]),
        window=sz["sliding_window_size"],
        window_layout=tuple(sz["sliding_window_layout"][:layers]),
        n_experts=sz["moe_num_primary_experts"],
        experts_per_token=sz["moe_num_active_primary_experts"],
        d_expert=sz["moe_ffn_hidden_size"],
        experts_held=(m["first_expert_held"], sz["experts_held"]),
        tie_embeddings=False)


def arch_of(config: dict) -> dict:
    """What the reference needs of the configuration."""
    sz = config["sizes"]
    return {**{k: sz[k] for k in (
        "sliding_window_size", "sliding_window_layout", "rope_layout",
        "rope_theta", "moe_num_active_primary_experts")},
        "first_held": config["model"]["first_expert_held"]}


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
    return flops_moe.TRAIN_FLOP_MULT * flops_moe.fwd_flops_per_token(
        config["sizes"], sequence)


def init_state(cfg, opt, embedding_std, key):
    """Seeded parameters and optimizer state, traced as one program. The
    embedding's rows are drawn with a spread of their own (the
    configuration's ``assumed.initialisation`` says why)."""
    params = T.init(key, cfg)
    params["embed"] = embedding_std * jax.random.normal(
        jax.random.fold_in(key, 1), params["embed"].shape, jnp.float32)
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

    make_state = jax.jit(
        functools.partial(init_state, cfg, opt,
                          config["model"]["embedding_init_std"]),
        out_shardings=NamedSharding(mesh, P()))
    params, opt_state = make_state(k_init)
    batch = shard_batch((make_tokens(k_tok),), mesh=mesh)

    @jax.jit
    def program(params, tokens):
        """The program's loss on the first sequence, its gradients in
        CHECK_LEAVES and, per layer, the experts each token chose, as a
        mask [tokens, experts]."""
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
        reference/smallthinker.py ``router_weights``); the others by the
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
        gradient from the reference's; per layer the share of tokens
        whose chosen set differs from the reference's own, the rows
        routed to held experts, the fullest held expert's rows and the
        rows past the buffers' bound (dropped: none, by the bound's
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
        cell.state = cell.opt_state = None
        state, _ = make_state(k_init)  # the optimizer state comes later
        params = replica_on(first, state)
        tokens = jax.device_put(cell.batch[0], first)
        loss, grads, masks = program(params, tokens)
        with jax.default_matmul_precision("highest"):
            (ref_losses, ref_grads, ref_routing, ref_new, ref_updates,
             sure) = jax.jit(reference_program)(params, tokens, masks)
        loss, (grad_errs, routers) = jax.device_get(
            (loss, model_errors(grads, ref_grads, masks, ref_routing)))
        grad_errs = {p: float(e) for p, e in zip(CHECK_LEAVES, grad_errs)}
        differs, routed, fullest, dropped, short = (
            [float(x) for x in each] for each in routers)
        # the step takes most of the chip
        del params, grads, masks, ref_grads, ref_routing

        _, opt_state = make_state(k_init)
        cell.state, cell.opt_state, step_loss = cell.step(
            state, opt_state, *cell.batch)
        update_errs, step_loss, ref_losses = jax.device_get((
            update_errors(replica_on(first, cell.state), ref_new,
                          ref_updates, sure),
            replica_on(first, step_loss), ref_losses))
        update_errs = {p: float(e) for p, e in zip(UPDATE_LEAVES, update_errs)}
        # the seeded state once more (the step took the first as its own)
        steps = jax.device_get(leaf_steps(make_state(k_init)[0], cell.state))
        steps = {jax.tree_util.keystr(path, simple=True, separator="."):
                 float(x) for path, x in jax.tree.leaves_with_path(steps)}
        stillest = min(steps, key=steps.get)
        loss_err = abs(loss - ref_losses[0]) / ref_losses[0]
        step_loss_err = abs(step_loss - ref_losses.mean()) / ref_losses.mean()
        def worst(errs, word, among=True):
            return max(e for p, e in errs.items() if (word in p) == among)

        others = max(e for p, e in grad_errs.items()
                     if "router" not in p and "gate" not in p)
        return {"ok": bool(loss_err <= LOSS_RTOL
                           and others <= GRAD_RTOL
                           and worst(grad_errs, "gate") <= GATE_GRAD_RTOL
                           and worst(grad_errs, "router") <= ROUTER_GRAD_RTOL
                           and max(differs) <= ROUTING_DIFFERS_MAX
                           and max(short) <= ROUTING_SHORT_MAX
                           and max(dropped) == 0
                           and step_loss_err <= LOSS_RTOL
                           and worst(update_errs, "experts", False)
                           <= UPDATE_RTOL
                           and worst(update_errs, "experts")
                           <= EXPERT_UPDATE_RTOL
                           and steps[stillest] >= EVERY_LEAF_STEP_MIN),
                "loss": float(loss), "loss_rel_err": float(loss_err),
                "step_loss_rel_err": float(step_loss_err),
                "loss_rtol": LOSS_RTOL,
                "grad_rel_l2_err": grad_errs, "grad_rtol": GRAD_RTOL,
                "router_grad_rtol": ROUTER_GRAD_RTOL,
                "gate_grad_rtol": GATE_GRAD_RTOL,
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
                "leaf_step_over_lr": [steps[stillest], max(steps.values())],
                "every_leaf_step_min": EVERY_LEAF_STEP_MIN}

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
        functools.partial(init_state, cfg, opt,
                          config["model"]["embedding_init_std"]),
        jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct(
        (workload["per_chip_batch"] * chips, workload["sequence"] + 1),
        jnp.int32)
    return (make_step(cfg, opt, mesh),
            placed(mesh, state, P()) + placed(mesh, (tokens,), P("hvd")))
