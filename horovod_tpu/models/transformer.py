"""Decoder-only transformer LM, written for mesh sharding.

Greenfield relative to the reference (Horovod is model-agnostic), but
required by SURVEY.md §2.3/§5.7: TP/SP/PP must be first-class in the TPU
framework. The model is pure-functional (params pytree + apply) with an
explicit `param_specs`/`act_spec` sharding map so the same code runs:

- single-chip,
- dp×tp×sp under `jit` with GSPMD sharding constraints (XLA inserts the
  psum for row-parallel matmuls and the reshards around attention),
- under `shard_map` for the explicit ring-attention / Ulysses paths in
  `horovod_tpu.parallel.sp`.

Sharding layout (Megatron-style column→row pairs so each block needs one
psum over 'tp'):
  wq/wk/wv: (d_model, n_heads, head_dim)  heads sharded over 'tp'
  wo:       (n_heads, head_dim, d_model)  heads sharded over 'tp'
  w1:       (d_model, d_ff)               d_ff sharded over 'tp'
  w2:       (d_ff, d_model)               d_ff sharded over 'tp'
  activations: (batch, seq, d_model) — batch over 'dp', seq over 'sp'

One decoder, composed per layer from the configuration. The defaults are
the GPT-2-style block (learned positions, as many key/value heads as
query heads, full causal attention, a GELU MLP, the classifier tied to
the embedding). A configuration may instead give: grouped-query heads
(``n_kv_heads``) of a width of their own (``d_head``); per layer, by
patterns that repeat with their period, a sliding ``window`` over the
keys (``window_layout``) and rotary positions or none at all
(``rope_layout``; ``positions="layout"`` drops the learned table); a
sparse gated feed-forward (``n_experts``, ``experts_per_token``,
``d_expert``: a softmax router over all the experts that reads the
block's *input*, ReGLU experts, of which this model holds
``experts_held``: ``parallel/moe.py``); and an untied classifier
(``tie_embeddings=False``). ``vocab_size`` is the rows held: a sliced
vocabulary is a smaller vocabulary.

Further settings of the same block (a DeepSeek-V3-shaped decoder takes
them all): **latent attention** (``kv_latent`` > 0: keys and values are
expanded per head from one normed latent of that width; a head's
query/key is ``d_head`` columns without positions and ``d_rope`` rotary
ones, the rotary key being one array all heads share; its value is
``d_head`` wide); a **gated dense feed-forward** (``mlp="gated"``: SiLU
gate, up, down, ``d_ff`` wide); **leading dense layers** before the
expert layers (``n_dense_layers``); **shared experts** (one gated MLP of
``n_shared_experts * d_expert`` on every token, beside the routed ones);
and the router's **scoring** (``router_scoring="sigmoid"``: a
correction bias enters the choice only, the weights are the sigmoids
normalised over the chosen times ``routed_scale``), its **input**
(``router_input="normed"``: the expert layer's normed input) and the
routed experts' **activation** (``expert_activation="silu"``).

A **looped** decoder (``n_loops`` > 1; plain heads and a dense
feed-forward) runs the one stack of blocks ``n_loops`` times over its
own output with the same weights, as one ``lax.scan`` over the passes
whose body is the stack: a weight's gradient is a sum over its uses.
The final norm lies inside the recurrence: its output is what a pass's
**exit** reads (the classifier, and a learned gate ``gate.w``,
``gate.b`` on the normed state) and what the next pass starts from.
`apply` returns the last pass's logits; `lm_loss` is the expected loss
over the exits under the gates' exit distribution less ``exit_beta``
times that distribution's entropy, each exit's classifier and
cross-entropy running inside the recurrence so that only ``[n_loops,
tokens]`` losses and gate logits leave it. **Sandwich norms**
(``sandwich_norms=True``) put a second RMSNorm on each sub-layer's
output before the residual add (``ln1_post``, ``ln2_post``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..utils import scopes


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq: int = 2048
    dtype: Any = jnp.bfloat16
    # mesh axis names (None disables that sharding dimension)
    dp_axis: Optional[str] = "dp"
    tp_axis: Optional[str] = "tp"
    sp_axis: Optional[str] = "sp"
    # rematerialize each block in the backward pass (jax.checkpoint):
    # activation memory drops from O(layers) to O(1) blocks at ~1/3 extra
    # FLOPs — the standard TPU trade when HBM, not MXU, is the binding
    # constraint (long sequences, big batches)
    remat: bool = False
    # lm_loss streams the classifier over vocab chunks of this size
    # (ops/xent.py) instead of materializing float32 logits [tokens,
    # vocab] — the biggest tensor in long-context training. None = dense.
    xent_chunk: Optional[int] = None
    # -- the block's composition (module docstring); defaults: GPT-2's ----
    n_kv_heads: Optional[int] = None       # None: n_heads
    d_head: Optional[int] = None           # None: d_model // n_heads
    positions: str = "learned"             # or "layout": rope_layout says
    rope_layout: tuple = ()                # per layer, periodic: 1 = rotary
    rope_theta: float = 10000.0
    window: Optional[int] = None           # keys a windowed layer sees
    window_layout: tuple = ()              # per layer, periodic: 1 = windowed
    n_experts: int = 0                     # router width; 0: the dense MLP
    experts_per_token: int = 0
    d_expert: int = 0
    experts_held: Optional[tuple] = None   # (first, count); None: all
    tie_embeddings: bool = True
    kv_latent: int = 0                     # latent heads: the latent's width
    d_rope: int = 0                        # a latent head's rotary columns
    mlp: str = "gelu"                      # dense feed-forward; or "gated"
    n_dense_layers: int = 0                # leading layers without experts
    n_shared_experts: int = 0              # of d_expert each, as one MLP
    router_scoring: str = "softmax"        # or "sigmoid" (bias-corrected)
    router_input: str = "block"            # or "normed": as the experts'
    routed_scale: float = 1.0              # on the sigmoid scoring's weights
    expert_activation: str = "relu"        # or "silu"
    n_loops: int = 1                       # passes over the one stack
    sandwich_norms: bool = False           # a norm on each sub-layer's output
    exit_beta: float = 0.0                 # entropy's weight in a looped loss

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def held(self) -> tuple:
        """``(first, count)`` of the experts this model holds."""
        return self.experts_held or (0, self.n_experts)

    def has_experts(self, i: int) -> bool:
        """Is layer ``i`` an expert layer (after the leading dense ones)?"""
        return bool(self.n_experts) and i >= self.n_dense_layers

    def layer_kind(self, i: int) -> tuple:
        """Layer ``i``'s ``(window or None, rotary positions?)``."""
        def at(layout):
            return bool(layout) and bool(layout[i % len(layout)])

        return (self.window if at(self.window_layout) else None,
                self.positions == "layout" and at(self.rope_layout))


def init(rng, cfg: TransformerConfig):
    keys = jax.random.split(rng, 4 + cfg.n_layers)
    s = 0.02

    def normal(key, *shape):
        return s * jax.random.normal(key, shape, jnp.float32)

    def gated(k, width):
        return {"gate": normal(k[0], cfg.d_model, width),
                "up": normal(k[1], cfg.d_model, width),
                "down": normal(k[2], width, cfg.d_model)}

    params = {
        "embed": normal(keys[0], cfg.vocab_size, cfg.d_model),
        "ln_f": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
        "blocks": [],
    }
    if cfg.positions == "learned":
        params["pos"] = normal(keys[1], cfg.max_seq, cfg.d_model)
    if not cfg.tie_embeddings:
        params["head"] = normal(keys[2], cfg.d_model, cfg.vocab_size)
    _check_looped(cfg)
    if cfg.n_loops > 1:  # the exits' gate, from the one key no older leaf drew
        params["gate"] = {"w": normal(keys[3], cfg.d_model),
                          "b": jnp.zeros((), jnp.float32)}
    held = cfg.held[1]
    # as many keys a layer as the first decoders drew, so that a seed
    # still gives them the weights it gave; the further settings draw 18
    further = (cfg.kv_latent or cfg.mlp != "gelu" or cfg.n_dense_layers
               or cfg.n_shared_experts)
    for i in range(cfg.n_layers):
        k = jax.random.split(
            keys[4 + i], 18 if further else 10 if cfg.n_experts else 6)
        blk = {
            "ln1": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
            "ln2": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
            "wo": normal(k[3], cfg.n_heads, cfg.head_dim, cfg.d_model),
        }
        if cfg.kv_latent:
            blk.update(
                wq=normal(k[0], cfg.d_model, cfg.n_heads,
                          cfg.head_dim + cfg.d_rope),
                wkva=normal(k[1], cfg.d_model, cfg.kv_latent + cfg.d_rope),
                ln_kv={"scale": jnp.ones((cfg.kv_latent,), jnp.float32)},
                wkvb=normal(k[2], cfg.kv_latent, cfg.n_heads,
                            2 * cfg.head_dim))
        else:
            blk.update(
                wq=normal(k[0], cfg.d_model, cfg.n_heads, cfg.head_dim),
                wk=normal(k[1], cfg.d_model, cfg.kv_heads, cfg.head_dim),
                wv=normal(k[2], cfg.d_model, cfg.kv_heads, cfg.head_dim))
        if cfg.has_experts(i):
            blk["router"] = normal(k[6], cfg.d_model, cfg.n_experts)
            if cfg.router_scoring == "sigmoid":
                # the choice's correction bias: no gradient reaches it
                blk["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
            blk["experts"] = {
                "gate": normal(k[7], held, cfg.d_model, cfg.d_expert),
                "up": normal(k[8], held, cfg.d_model, cfg.d_expert),
                "down": normal(k[9], held, cfg.d_expert, cfg.d_model),
            }
            if cfg.n_shared_experts:
                blk["shared"] = gated(
                    k[10:13], cfg.n_shared_experts * cfg.d_expert)
        elif cfg.mlp == "gated":
            blk["mlp"] = gated(k[13:16], cfg.d_ff)
        else:
            blk["w1"] = normal(k[4], cfg.d_model, cfg.d_ff)
            blk["w2"] = normal(k[5], cfg.d_ff, cfg.d_model)
        if cfg.sandwich_norms:
            for name in ("ln1_post", "ln2_post"):
                blk[name] = {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
        params["blocks"].append(blk)
    return params


def _check_looped(cfg: TransformerConfig) -> None:
    """The recurrence and the sandwich norms are built for plain heads
    and a dense feed-forward: no cell needs them elsewhere."""
    if (cfg.n_loops > 1 or cfg.sandwich_norms) and (cfg.kv_latent
                                                    or cfg.n_experts):
        raise ValueError("n_loops > 1 and sandwich_norms take plain heads "
                         "and a dense feed-forward: no latent heads, no "
                         "experts")
    if cfg.n_loops > 1 and cfg.xent_chunk:
        raise ValueError("a looped decoder's exits take the dense loss, one "
                         "exit at a time: no xent_chunk")


def param_specs(cfg: TransformerConfig):
    """PartitionSpec pytree matching `init` (for jit in_shardings)."""
    tp = cfg.tp_axis
    gated = {"gate": P(None, tp), "up": P(None, tp), "down": P(tp, None)}

    def block(i):
        blk = {
            "ln1": {"scale": P()},
            "ln2": {"scale": P()},
            "wq": P(None, tp, None),
            "wo": P(tp, None, None),
        }
        if cfg.kv_latent:  # the latent is every head's: replicated
            blk.update(wkva=P(None, None), ln_kv={"scale": P()},
                       wkvb=P(None, tp, None))
        else:
            blk.update(wk=P(None, tp, None), wv=P(None, tp, None))
        if cfg.has_experts(i):  # the experts held are replicated: no GSPMD split
            blk["router"] = P(None, None)
            if cfg.router_scoring == "sigmoid":
                blk["router_bias"] = P(None)
            blk["experts"] = {w: P(None, None, None)
                              for w in ("gate", "up", "down")}
            if cfg.n_shared_experts:
                blk["shared"] = dict(gated)
        elif cfg.mlp == "gated":
            blk["mlp"] = dict(gated)
        else:
            blk.update(w1=P(None, tp), w2=P(tp, None))
        if cfg.sandwich_norms:
            blk.update(ln1_post={"scale": P()}, ln2_post={"scale": P()})
        return blk

    specs = {
        "embed": P(None, None),
        "ln_f": {"scale": P()},
        "blocks": [block(i) for i in range(cfg.n_layers)],
    }
    if cfg.positions == "learned":
        specs["pos"] = P(None, None)
    if not cfg.tie_embeddings:
        specs["head"] = P(None, None)
    if cfg.n_loops > 1:
        specs["gate"] = {"w": P(None), "b": P()}
    return specs


def act_spec(cfg: TransformerConfig) -> P:
    return P(cfg.dp_axis, cfg.sp_axis, None)


def _rmsnorm(x, scale):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)
    return (y * scale).astype(x.dtype)


def _constrain(x, spec, use_constraints):
    if use_constraints:
        return jax.lax.with_sharding_constraint(x, spec)
    return x


def apply(params, tokens, cfg: TransformerConfig, *, use_constraints: bool = True,
          attn_fn=None, positions=None,
          return_hidden: bool = False, return_routing: bool = False,
          per_pass=None):
    """Forward pass → logits (float32), or — with ``return_hidden=True``
    — the pre-projection hidden states [b, s, d] in ``cfg.dtype`` for
    the chunked LM loss (lm_loss with cfg.xent_chunk).

    ``attn_fn(q, k, v)`` hook (q/k/v: [b, s, h, hd]) lets
    `horovod_tpu.parallel.sp` substitute ring attention or Ulysses
    attention; default is full causal attention: the fused Pallas
    kernels where `fused_attention_blocks` picks them (a TPU, a call XLA
    need not partition, a long enough sequence that tiles), else
    `causal_attention` (XLA reshards over 'sp' automatically under GSPMD).

    ``positions`` ([s] global position ids) must be supplied when running
    inside a shard_map with the sequence sharded (ring attention): each
    chip's block starts at ``axis_index * s_local``, not 0.

    ``return_routing=True`` (a sparse-expert decoder) returns ``(result,
    [chosen experts [b*s, k] of each layer])``: what a comparison with a
    reference needs to tell a tie in the router from a fault.

    A looped decoder (``cfg.n_loops`` > 1) gives the last pass's result;
    with ``per_pass(s)``, a function of a pass's normed state [b, s, d],
    it returns what that makes of every pass instead, stacked over the
    passes (`lm_loss`'s exits).
    """
    _check_looped(cfg)
    if cfg.n_loops > 1 and attn_fn is not None:
        raise ValueError("a looped decoder runs the default attention: "
                         "no attn_fn")
    aspec = act_spec(cfg)
    if positions is None:
        positions = jnp.arange(tokens.shape[1])
    with jax.named_scope(scopes.EMBED):
        x = params["embed"][tokens].astype(cfg.dtype)
        if cfg.positions == "learned":
            x = x + params["pos"][positions].astype(cfg.dtype)[None]
    x = _constrain(x, aspec, use_constraints)

    # the fused kernels' tile shape, where the default attention takes them
    blocks = None if attn_fn else fused_attention_blocks(
        tokens.shape[1], cfg.head_dim, use_constraints)
    # a checkpointed block keeps what the fused backward kernels read (the
    # arrays `flash_attention` names) and, of an expert layer, its router's
    # choice (`_route`) and what it sorted out of it, and recomputes the
    # rest; where no kernel runs those are all that carry a name
    keeps = cfg.remat and blocks is not None
    kinds = [cfg.layer_kind(i) for i in range(cfg.n_layers)]
    if cfg.kv_latent:  # a latent head's rotary columns, in every layer
        rotary = _rope_tables(positions, cfg.d_rope, cfg.rope_theta)
    else:
        rotary = (_rope_tables(positions, cfg.head_dim, cfg.rope_theta)
                  if any(rope for _, rope in kinds) else None)
    if any(window for window, _ in kinds) and (attn_fn or cfg.kv_latent):
        raise ValueError("attn_fn and latent heads take no window: a "
                         "windowed layer runs the default attention")

    def _attend(x, blk, window, rope):
        """x + the block's attention over heads of one width."""
        with jax.named_scope(scopes.ATTENTION):
            h = _rmsnorm(x, blk["ln1"]["scale"])
            if blocks is not None:
                o = _fused_attention(h, blk, cfg, blocks, window,
                                     rotary if rope else None)
            else:
                q, k, v = (jnp.einsum("bsd,dhk->bshk", h,
                                      blk[w].astype(cfg.dtype))
                           for w in ("wq", "wk", "wv"))
                if rope:
                    q, k = _rope(q, rotary), _rope(k, rotary)
                if attn_fn is None:
                    o = causal_attention(q, k, v, window)
                else:
                    o = attn_fn(q, *(_repeat_kv(a, cfg) for a in (k, v)))
                o = jnp.einsum("bshk,hkd->bsd", o, blk["wo"].astype(cfg.dtype))
            return x + _normed_if(blk, "ln1_post", o)

    # the scopes sit inside the block, so they survive jax.checkpoint; what
    # a layer's feed-forward is follows from the parameters it was given
    def _block(x, blk, window=None, rope=False):
        sparse = "experts" in blk
        if sparse and cfg.router_input == "block":
            with jax.named_scope(scopes.ROUTER):
                routed = _route(x, blk, cfg)
        if cfg.kv_latent:
            x = _latent_attention(x, blk, cfg, blocks, rotary, attn_fn)
        else:
            x = _attend(x, blk, window, rope)
        x = _constrain(x, aspec, use_constraints)
        if sparse:
            from ..parallel import moe

            with jax.named_scope(scopes.MOE):
                h = _rmsnorm(x, blk["ln2"]["scale"])
            if cfg.router_input != "block":  # reads what the experts read
                with jax.named_scope(scopes.ROUTER):
                    routed = _route(h, blk, cfg)
            with jax.named_scope(scopes.MOE):
                ff = moe.expert_layer(h.reshape(-1, h.shape[-1]), *routed,
                                      blk["experts"], cfg.held,
                                      activation=cfg.expert_activation)
                x = x + ff.reshape(h.shape)
            if "shared" in blk:  # every token's, beside the routed ones
                with jax.named_scope(scopes.SHARED_EXPERT):
                    x = x + _gated_mlp(h, blk["shared"], cfg.dtype)
            return _constrain(x, aspec, use_constraints), routed[0]
        with jax.named_scope(scopes.MLP):
            h = _rmsnorm(x, blk["ln2"]["scale"])
            if "mlp" in blk:
                ff = _gated_mlp(h, blk["mlp"], cfg.dtype)
            else:
                ff = jax.nn.gelu(
                    jnp.einsum("bsd,df->bsf", h, blk["w1"].astype(cfg.dtype)))
                ff = jnp.einsum("bsf,fd->bsd", ff,
                                blk["w2"].astype(cfg.dtype))
            x = x + _normed_if(blk, "ln2_post", ff)
        return _constrain(x, aspec, use_constraints)

    # one (checkpointed) function per kind of layer the pattern has
    block_fns, routing = {}, []

    def _stack(x):
        """The blocks, one after the other."""
        for kind, blk in zip(kinds, params["blocks"]):
            if kind not in block_fns:
                fn = _block if kind == (None, False) else functools.partial(
                    _block, window=kind[0], rope=kind[1])
                block_fns[kind] = jax.checkpoint(
                    fn, policy=_KEEP_KERNEL_RESIDUALS) if cfg.remat else fn
            x = block_fns[kind](x, blk)
            if attn_fn is None:  # out here a layer counts, traced or not
                scopes.note_attention(
                    kernel=blocks is not None, window=kind[0] is not None,
                    kept=keeps, latent=bool(cfg.kv_latent))
            kept = _kept_bytes(tokens.shape, cfg, keeps,
                               cfg.remat and "experts" in blk,
                               cfg.remat and "experts" in blk)
            if kept:
                scopes.note_kept(kept)
            if "experts" in blk:
                x, chosen = x
                routing.append(chosen)
                from ..parallel import moe

                rows = x.shape[0] * x.shape[1]   # the tokens a chip has
                bound = rows * min(cfg.experts_per_token, cfg.held[1])
                scopes.note_moe(cfg.held[1], cfg.n_experts,
                                cfg.experts_per_token, bound,
                                moe.chunk_rows(rows, bound))
                if "shared" in blk:
                    scopes.note_layer("shared_experts")
            elif cfg.n_experts:
                scopes.note_layer("dense_layers")
        return x

    def _final_norm(x):
        with jax.named_scope(scopes.HEAD):
            return _rmsnorm(x, params["ln_f"]["scale"])

    if cfg.n_loops > 1:
        # the recurrence: one scan over the passes, its body the stack and
        # the final norm, whose output an exit reads and the next pass takes
        def one_pass(s, _):
            s = _final_norm(_stack(s))
            return s, per_pass(s) if per_pass else None

        with scopes.note_loop(cfg.n_loops, cfg.n_layers,
                              cfg.n_loops if per_pass else 1):
            x, passes = jax.lax.scan(one_pass, x, None, length=cfg.n_loops)
        if per_pass:
            return passes
    else:
        x = _final_norm(_stack(x))
    with jax.named_scope(scopes.HEAD):
        # pre-projection activations for the chunked LM loss, or logits
        out = x if return_hidden else _classify(x, params, cfg)
    return (out, routing) if return_routing else out


def _classify(x, params, cfg: TransformerConfig):
    """Float32 logits [b, s, rows] of normed states [b, s, d]."""
    if not cfg.tie_embeddings:
        return jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                          params["head"])
    return jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32), params["embed"])


def _target_logp(logits, targets):
    """Each token's log-probability of its target, [b, s] float32."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def _normed_if(blk, name: str, out):
    """A sub-layer's output on its way to the residual add: through the
    sandwich norm ``name`` where the block was given one."""
    return _rmsnorm(out, blk[name]["scale"]) if name in blk else out


def _route(x, blk, cfg: TransformerConfig):
    """A sparse-expert block's router on its input [b, s, d]: scores in
    float32 at the highest matmul precision (on a TPU a float32 product
    is bfloat16 passes unless told otherwise, and a choice between two
    near-equal scores should hang on as little rounding as it can), then
    ``parallel.moe.route`` by the configuration's scoring: (chosen,
    weights), [b*s, k] each.

    Its choice is named (`scopes.KEPT_CHOICE`), whatever the scoring and
    the input, so that a checkpointed block's policy keeps it and both
    passes route alike: the expert layer keeps what it sorted out of the
    forward pass's choice (`scopes.KEPT_ROUTING`), and the router's
    gradient has to be taken at that choice. A recomputed choice is not
    that one on the chip: a normed input is recomputed not bit for bit
    (XLA fuses it otherwise), and a router on the block's own input whose
    choice was recomputed read its gradient 2-3 times worse beside the
    kept indices, never on the CPU (PERF.md §6, PRs 33 and 37)."""
    from ..parallel import moe

    scores = jnp.einsum("td,de->te",
                        x.reshape(-1, x.shape[-1]).astype(jnp.float32),
                        blk["router"], precision=jax.lax.Precision.HIGHEST)
    if cfg.router_scoring == "softmax":
        return moe.route(scores, cfg.experts_per_token,
                         name=scopes.KEPT_CHOICE)
    return moe.route(scores, cfg.experts_per_token,
                     scoring=cfg.router_scoring, bias=blk["router_bias"],
                     scale=cfg.routed_scale, name=scopes.KEPT_CHOICE)


def _gated_mlp(h, weights, dtype):
    """``(silu(h G) * (h U)) D``: the dense gated feed-forward and the
    shared experts."""
    gate, up, down = (weights[w].astype(dtype) for w in ("gate", "up", "down"))
    ff = (jax.nn.silu(jnp.einsum("bsd,df->bsf", h, gate))
          * jnp.einsum("bsd,df->bsf", h, up))
    return jnp.einsum("bsf,fd->bsd", ff, down)


def _latent_attention(x, blk, cfg: TransformerConfig, blocks, rotary,
                      attn_fn=None):
    """``x`` + its attention over latent (MLA) heads, [b, s, d]. Keys and
    values are expanded per head from one normed latent ``c``; a head's
    score is its ``d_head`` columns without positions against its own
    key plus its ``d_rope`` rotary columns against the one rotary key all
    heads share, over ``sqrt(d_head + d_rope)``. With ``blocks`` through
    the fused kernels (``ops/pallas/flash_attention.py
    latent_attention``: the shared key is never copied per head), else
    `causal_attention` (or ``attn_fn``) on heads put together. What
    makes the latent and expands it runs under ``hvd.model/latent``;
    the queries, the kernels and the output projection under
    ``hvd.model/attention`` (side by side: a nested scope would be filed
    under its parent)."""
    dt, hd, latent = cfg.dtype, cfg.head_dim, cfg.kv_latent
    with jax.named_scope(scopes.ATTENTION):
        h = _rmsnorm(x, blk["ln1"]["scale"])
    with jax.named_scope(scopes.LATENT):
        kva = jnp.einsum("bsd,de->bse", h, blk["wkva"].astype(dt))
        c = _rmsnorm(kva[..., :latent], blk["ln_kv"]["scale"])
        k_rope = _rope(kva[:, :, None, latent:], rotary)[:, :, 0]
        wkvb = blk["wkvb"].astype(dt)
        if blocks is not None:  # the heads side by side, as the kernels take
            k, v = (jnp.einsum("bsc,ce->bse", c, w.reshape(latent, -1))
                    for w in (wkvb[..., :hd], wkvb[..., hd:]))
        else:
            kv = jnp.einsum("bsc,chk->bshk", c, wkvb)
            k, v = kv[..., :hd], kv[..., hd:]
    with jax.named_scope(scopes.ATTENTION):
        wq = blk["wq"].astype(dt)
        if blocks is not None:
            from ..ops.pallas.flash_attention import latent_attention

            q = jnp.einsum("bsd,de->bse", h,
                           wq[..., :hd].reshape(cfg.d_model, -1))
            q_rope = _rope(jnp.einsum("bsd,dhk->bhsk", h, wq[..., hd:]),
                           rotary, heads_first=True)
            o = latent_attention(q, q_rope, k, k_rope, v, *blocks,
                                 cfg.n_heads)
            o = jnp.einsum("bse,ed->bsd", o,
                           blk["wo"].astype(dt).reshape(-1, cfg.d_model))
        else:
            q = jnp.einsum("bsd,dhk->bshk", h, wq)
            q = jnp.concatenate([q[..., :hd], _rope(q[..., hd:], rotary)],
                                axis=-1)
            k = jnp.concatenate([k, jnp.broadcast_to(
                k_rope[:, :, None], (*k.shape[:3], cfg.d_rope))], axis=-1)
            o = (attn_fn or causal_attention)(q, k, v)
            o = jnp.einsum("bshk,hkd->bsd", o, blk["wo"].astype(dt))
        return x + o


def _rope_tables(positions, head_dim: int, theta: float):
    """(cos, sin), [s, head_dim] float32 each, of rotary positions over
    the whole head: frequency ``theta ** (-2i / head_dim)`` for the pair
    ``(i, i + head_dim/2)`` (the rotate-half pairing)."""
    inv_freq = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                         / head_dim)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


def _rope(x, tables, heads_first: bool = False):
    """Rotary positions on [b, s, h, hd], or ``heads_first`` on [b, h, s,
    hd] (in float32, back in x's type)."""
    cos, sin = (t[None, None] if heads_first else t[None, :, None, :]
                for t in tables)
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos + turned * sin).astype(x.dtype)


def _rope_side_by_side(x, tables, head_dim: int):
    """`_rope` on [b, s, h*hd], the heads side by side as the fused
    kernels take them, without going through [b, s, h, hd]: on a TPU
    that reshape puts the heads on a tiled dimension of the layout and
    is a copy of the array each way. A pair's partner is ``hd/2``
    columns on, or back, within its head: two rolls and a select."""
    cos, sin = (jnp.tile(t, x.shape[-1] // head_dim)[None] for t in tables)
    x32 = x.astype(jnp.float32)
    half = head_dim // 2
    first = (jnp.arange(x.shape[-1]) % head_dim) < half
    turned = jnp.where(first, -jnp.roll(x32, -half, axis=-1),
                       jnp.roll(x32, half, axis=-1))
    return (x32 * cos + turned * sin).astype(x.dtype)


def _repeat_kv(a, cfg: TransformerConfig):
    """Key/value heads [b, s, kv, hd] as one per query head, for an
    ``attn_fn`` that knows no grouped heads."""
    group = cfg.n_heads // cfg.kv_heads
    return a if group == 1 else jnp.repeat(a, group, axis=2)


#: the shortest sequence at which the fused kernels beat the einsum path
#: on the chip (PERF.md, PR 26: the v5e measurements that set it)
FUSED_ATTENTION_MIN_SEQ = 512


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _partitioned_by_xla() -> bool:
    """Would XLA have to partition a kernel called here? It cannot (a
    Mosaic call lowers only where every mesh axis is manual, or for one
    device). Inside a ``shard_map`` over every axis of its mesh, as
    ``data_parallel_step`` runs its per-chip body, the answer is no;
    under a bare ``jit`` the trace does not say what the arguments are
    sharded over (``fsdp_train_step``: GSPMD, no constraints), so only
    a process with one device is sure."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return jax.device_count() > 1
    return not mesh.are_all_axes_manual


def fused_attention_blocks(s: int, head_dim: int, use_constraints: bool):
    """The fused kernels' ``(block_q, block_k)`` where the default
    attention takes them, else None (`causal_attention`). From what the
    call can see and nothing else: a TPU, nothing for XLA to partition
    (no GSPMD constraints asked for, and `_partitioned_by_xla` says
    no), a sequence long enough for the kernels to win, and one they
    tile."""
    if (use_constraints or not _on_tpu() or s < FUSED_ATTENTION_MIN_SEQ
            or _partitioned_by_xla()):
        return None
    from ..ops.pallas.flash_attention import block_sizes

    return block_sizes(s, head_dim)


_KEEP_KERNEL_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    # `KEPT_BY_REMAT` and the rotary parts; a router's choice (`_route`);
    # what an expert layer sorted out of its routing
    *scopes.KEPT_BY_REMAT_LATENT, scopes.KEPT_CHOICE, scopes.KEPT_ROUTING)


def _kept_bytes(shape, cfg: TransformerConfig, kernels: bool = True,
                choice: bool = False, routing: bool = False) -> int:
    """Bytes a checkpointed block keeps on ``shape`` = (b, s) tokens.
    ``kernels``: `flash_attention`'s residuals, q and o [b, s, heads*hd],
    k and v [b, s, kv*hd] in ``cfg.dtype``, lse [b*heads, 1, s] float32;
    of `latent_attention`'s also q_rope [b, heads, s, d_rope] and the one
    k_rope [b, s, d_rope]. ``choice``: its router's, [b*s, k] int32.
    ``routing``: what its expert layer sorted out of the routing
    (``parallel.moe.index_bytes``)."""
    b, s = shape
    if routing:
        from ..parallel import moe

        return (_kept_bytes(shape, cfg, kernels, choice)
                + moe.index_bytes(b * s, cfg.experts_per_token, cfg.held[1]))
    wide = 2 * (cfg.n_heads + cfg.kv_heads) * cfg.head_dim
    if cfg.kv_latent:
        wide += (cfg.n_heads + 1) * cfg.d_rope
    per_token = kernels * (wide * jnp.dtype(cfg.dtype).itemsize
                           + 4 * cfg.n_heads)
    return b * s * (per_token + choice * 4 * cfg.experts_per_token)


def _fused_attention(h, blk, cfg: TransformerConfig, blocks, window=None,
                     rotary=None):
    """`causal_attention` between its projections, through the Pallas
    kernels (ops/pallas/flash_attention.py: the scores stay in VMEM,
    forward and backward). The kernels take the heads side by side,
    [b, s, h*hd], and pick a head by block index, so no transpose of q,
    k, v or o is paid. The projections are plain [d, h*hd] matmuls
    here, and here only: the TPU compiler lays a ``dhk`` dot's output
    out sequence-minor and then copies it for the kernels, while on the
    einsum path the plain form costs a copy of every converted weight
    instead (both seen in the step compiled for a v5e; PERF.md, PR 26)."""
    from ..ops.pallas.flash_attention import flash_attention

    q, k, v = (
        jnp.einsum("bsd,de->bse", h,
                   blk[w].astype(cfg.dtype).reshape(cfg.d_model, -1))
        for w in ("wq", "wk", "wv"))
    if rotary is not None:
        q, k = (_rope_side_by_side(a, rotary, cfg.head_dim) for a in (q, k))
    o = flash_attention(q, k, v, True, *blocks, blk["wq"].shape[1], window,
                        blk["wk"].shape[1])
    return jnp.einsum("bse,ed->bsd", o,
                      blk["wo"].astype(cfg.dtype).reshape(-1, cfg.d_model))


def causal_attention(q, k, v, window=None, kv_heads=None):
    """Plain causal attention, [b, s, h, hd] layout, f32 softmax: an
    einsum writes the [b, h, s, s] float32 logits and XLA's softmax
    passes over them. What ``apply`` runs wherever
    `fused_attention_blocks` says None (short or untileable sequences,
    off the TPU, under GSPMD), and the numerics the fused kernels are
    held to. With a ``window`` query ``i`` sees the keys ``i - window <
    j <= i``; ``k`` and ``v`` may have fewer heads than ``q``
    (``kv_heads``, default: as many as they come with): query head ``h``
    reads key/value head ``h // (heads // kv_heads)``."""
    kv_heads = kv_heads or k.shape[2]
    if kv_heads != k.shape[2] or q.shape[2] % kv_heads:
        raise ValueError(f"{q.shape[2]} query heads over {kv_heads} "
                         f"key/value heads, but k has {k.shape[2]}")
    if kv_heads != q.shape[2]:
        k, v = (jnp.repeat(a, q.shape[2] // kv_heads, axis=2)
                for a in (k, v))
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bshk,bthk->bhst", q, k).astype(jnp.float32) * scale
    s, t = logits.shape[-2], logits.shape[-1]
    mask = jnp.tril(jnp.ones((s, t), bool))
    if window is not None:
        mask = mask & ~jnp.tril(jnp.ones((s, t), bool), k=-window)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthk->bshk", probs, v)


def lm_loss(params, tokens, cfg: TransformerConfig, *,
            return_routing: bool = False, **kw):
    """Next-token cross-entropy (mean over tokens).

    With ``cfg.xent_chunk`` set, the classifier streams over vocab
    chunks (ops/xent.py chunked_softmax_xent) and float32 logits
    [tokens, vocab] are never materialized. ``return_routing=True``
    returns ``(loss, routing)`` with `apply`'s routing (``has_aux``).

    A looped decoder (``cfg.n_loops`` > 1): the expected loss over its
    exits less ``cfg.exit_beta`` times the exit distribution's entropy
    (`exit_loss`); ``return_exits=True`` returns ``(loss, (each exit's
    mean loss [n_loops], the mean exit distribution [n_loops]))``."""
    targets = tokens[:, 1:]
    if cfg.n_loops > 1:
        if return_routing:
            raise ValueError("a looped decoder has no routing to return")
        return _looped_loss(params, tokens[:, :-1], targets, cfg, **kw)
    out = apply(params, tokens[:, :-1], cfg, return_hidden=bool(cfg.xent_chunk),
                return_routing=return_routing, **kw)
    out, routing = out if return_routing else (out, None)
    with jax.named_scope(scopes.HEAD):
        if cfg.xent_chunk:
            from ..ops.xent import chunked_softmax_xent

            b, s, d = out.shape
            w = params["embed"] if cfg.tie_embeddings else params["head"].T
            loss = chunked_softmax_xent(out.reshape(b * s, d), w,
                                        targets.reshape(-1), cfg.xent_chunk)
        else:
            loss = -jnp.mean(_target_logp(out, targets))
    return (loss, routing) if return_routing else loss


def _looped_loss(params, tokens, targets, cfg: TransformerConfig, *,
                 return_exits: bool = False, **kw):
    """`lm_loss` of a looped decoder. Each pass's exit runs inside the
    recurrence, under a checkpoint that keeps nothing: the float32
    logits [tokens, rows] of one exit at a time exist, forward and
    backward, and only each token's loss and gate logit leave a pass.
    (Taking an exit's positions a piece at a time, or picking the
    target's logit by a comparison for the gather, made the TPU
    compiler's count of the step larger, not smaller: PERF.md, PR 35.)"""
    @jax.checkpoint
    def exit_of(s, head, gate):
        with jax.named_scope(scopes.HEAD):
            losses = -_target_logp(_classify(s, head, cfg), targets)
        with jax.named_scope(scopes.EXIT):
            logit = jnp.einsum("bsd,d->bs", s.astype(jnp.float32),
                               gate["w"]) + gate["b"]
        return losses, logit

    head = {w: params[w] for w in ("embed", "head") if w in params}
    losses, gate_logits = apply(
        params, tokens, cfg, **kw,
        per_pass=lambda s: exit_of(s, head, params["gate"]))
    with jax.named_scope(scopes.EXIT):
        loss, exits = exit_loss(losses, gate_logits, cfg.exit_beta)
    return (loss, exits) if return_exits else loss


def exit_loss(losses, gate_logits, beta: float):
    """The expected loss over a looped decoder's exits less ``beta``
    times the exit distribution's entropy, from each exit's token losses
    and gate logits, [passes, ...tokens] float32 each. With ``lam_t =
    sigmoid(gate_logits[t])`` a token leaves at exit ``t`` with
    probability ``p_t = lam_t * prod_{j<t}(1 - lam_j)`` and at the last
    with what is left, ``prod_{j<last}(1 - lam_j)`` (the last gate is
    not read). In logarithms: ``log(1 - lam) = log_sigmoid(-logit)``.
    → (loss, (each exit's mean loss, the mean exit distribution))."""
    stays = jax.nn.log_sigmoid(-gate_logits[:-1])
    reached = jnp.concatenate(  # log prod_{j<t}(1 - lam_j), t = 0..last
        [jnp.zeros_like(stays[:1]), jnp.cumsum(stays, axis=0)])
    log_p = reached + jnp.concatenate(
        [jax.nn.log_sigmoid(gate_logits[:-1]), jnp.zeros_like(stays[:1])])
    p = jnp.exp(log_p)
    per_token = jnp.sum(p * losses, axis=0) + beta * jnp.sum(p * log_p, axis=0)
    over_tokens = tuple(range(1, losses.ndim))
    return jnp.mean(per_token), (jnp.mean(losses, axis=over_tokens),
                                 jnp.mean(p, axis=over_tokens))
