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

**QK-norm** (``qk_norm=True``) puts a per-head RMSNorm on the queries and
the keys before their rotary turn (``q_norm``, ``k_norm``). **Learned
sparse attention** (``sparse_topk`` > 0; plain or grouped heads, no
window, no recurrence): an indexer of its own (``indexer``: DeepSeek
Sparse Attention's lightning indexer) reads the block's normed input,
detached, and scores every earlier key of a query, ``I(t, s) = sum_j
w_j(t) relu(q_j(t) . k(s))`` over ``index_heads`` queries of
``index_dim`` and one key (a LayerNorm on it, rotary positions on the
leading ``index_rope`` columns of both, ``w`` scaled by ``index_heads **
-0.5`` and the products by ``index_dim ** -0.5``); the query then
attends over the ``sparse_topk`` keys of the largest scores only (all
of them while it has fewer), through the ``hvd_dsa_*`` kernels where
the fused kernels are taken. The indexer learns from a term of its own,
which `lm_loss` adds: per layer, the mean over the tokens of the KL
divergence of the indexer's softmax over a query's selection from the
attention's probabilities there, averaged over the heads and taken as
a constant; the trunk learns from the LM loss alone.

**Gated short-convolution layers** (``layer_types``, a kind for each
layer as given, not a period: ``"conv"`` or ``"full_attention"``) take
LFM2's mixer in place of attention: the normed input projected to two
gates and a stream, ``[B | C | x~] = u W_in``, a causal filter of
``conv_taps`` taps per channel over ``B * x~``, then ``(C * y) W_out``
(``in_proj``, ``conv``, ``out_proj``; `_short_conv`). Every RMSNorm
takes ``norm_eps``. Heads of 64 (``d_head``) run through the fused
kernels heads first.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import ad_checkpoint
from jax.sharding import PartitionSpec as P

from ..utils import scopes

NORM_EPS = 1e-6                            # an RMSNorm's epsilon by default

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
    qk_norm: bool = False                  # a per-head norm on q and k
    sparse_topk: int = 0                   # keys a query selects; 0: all
    index_heads: int = 0                   # the indexer's queries a token
    index_dim: int = 0                     # their width, and its key's
    index_rope: int = 0                    # of it, the rotary columns
    index_chunk: int = 512                 # queries it scores at a time
    norm_eps: float = NORM_EPS             # every RMSNorm's epsilon
    layer_types: tuple = ()                # per layer, as given: "conv" or
    #                                        "full_attention"; (): attention
    conv_taps: int = 3                     # a conv layer's causal filter
    expert_chunk: int = 1                  # read by nothing: the chunks
    #                                        follow from the expected load
    #                                        (`moe.chunk_rows`); accepted
    #                                        while the benchmark's conv
    #                                        family still passes it

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

    def is_conv(self, i: int) -> bool:
        """Is layer ``i`` a gated short-convolution layer?"""
        return bool(self.layer_types) and self.layer_types[i] == "conv"

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
        }
        wo = functools.partial(normal, k[3], cfg.n_heads, cfg.head_dim,
                               cfg.d_model)
        if cfg.is_conv(i):  # from a key of its own, as the indexer's
            kc = jax.random.split(jax.random.fold_in(keys[4 + i], 2), 3)
            blk.update(in_proj=normal(kc[0], cfg.d_model, 3 * cfg.d_model),
                       conv=normal(kc[1], cfg.conv_taps, cfg.d_model),
                       out_proj=normal(kc[2], cfg.d_model, cfg.d_model))
        elif cfg.kv_latent:
            blk.update(
                wo=wo(),
                wq=normal(k[0], cfg.d_model, cfg.n_heads,
                          cfg.head_dim + cfg.d_rope),
                wkva=normal(k[1], cfg.d_model, cfg.kv_latent + cfg.d_rope),
                ln_kv={"scale": jnp.ones((cfg.kv_latent,), jnp.float32)},
                wkvb=normal(k[2], cfg.kv_latent, cfg.n_heads,
                            2 * cfg.head_dim))
        else:
            blk.update(
                wo=wo(),
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
        if cfg.qk_norm and not cfg.is_conv(i):
            for name in ("q_norm", "k_norm"):
                blk[name] = {"scale": jnp.ones((cfg.head_dim,), jnp.float32)}
        if cfg.sparse_topk:  # from a key of its own: the others stay as drawn
            ki = jax.random.split(jax.random.fold_in(keys[4 + i], 1), 3)
            blk["indexer"] = {
                "wq": normal(ki[0], cfg.d_model, cfg.index_heads,
                             cfg.index_dim),
                "wk": normal(ki[1], cfg.d_model, cfg.index_dim),
                "k_norm": {"scale": jnp.ones((cfg.index_dim,), jnp.float32),
                           "bias": jnp.zeros((cfg.index_dim,), jnp.float32)},
                "w": normal(ki[2], cfg.d_model, cfg.index_heads)}
        params["blocks"].append(blk)
    return params


def _check_looped(cfg: TransformerConfig) -> None:
    """The recurrence and the sandwich norms are built for plain heads
    and a dense feed-forward, learned sparse attention for plain or
    grouped heads in every layer: no cell needs them elsewhere."""
    if (cfg.n_loops > 1 or cfg.sandwich_norms) and (cfg.kv_latent
                                                    or cfg.n_experts):
        raise ValueError("n_loops > 1 and sandwich_norms take plain heads "
                         "and a dense feed-forward: no latent heads, no "
                         "experts")
    if cfg.n_loops > 1 and cfg.xent_chunk:
        raise ValueError("a looped decoder's exits take the dense loss, one "
                         "exit at a time: no xent_chunk")
    if cfg.sparse_topk and (cfg.kv_latent or cfg.window_layout
                            or cfg.n_loops > 1 or not cfg.index_heads
                            or not cfg.index_dim or cfg.index_rope % 2
                            or cfg.index_rope > cfg.index_dim):
        raise ValueError("sparse_topk takes plain or grouped heads with no "
                         "latent, no window and no recurrence, and an indexer "
                         "of index_heads x index_dim whose index_rope rotary "
                         "columns are even and no more than index_dim")
    if cfg.layer_types and (
            len(cfg.layer_types) != cfg.n_layers
            or not set(cfg.layer_types) <= {"conv", "full_attention"}
            or cfg.kv_latent or cfg.sparse_topk or cfg.n_loops > 1
            or cfg.sandwich_norms):
        raise ValueError("layer_types names each of the n_layers layers "
                         "conv or full_attention; conv layers sit beside "
                         "plain or grouped heads: no latent, no selection, "
                         "no recurrence, no sandwich norms")


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
        if cfg.is_conv(i):  # replicated: W_in's column groups are not split
            blk = {"ln1": {"scale": P()}, "ln2": {"scale": P()},
                   "in_proj": P(None, None), "conv": P(None, None),
                   "out_proj": P(None, None)}
        elif cfg.kv_latent:  # the latent is every head's: replicated
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
        if cfg.qk_norm and not cfg.is_conv(i):
            blk.update(q_norm={"scale": P()}, k_norm={"scale": P()})
        if cfg.sparse_topk:  # every head's: replicated
            blk["indexer"] = {"wq": P(None, None, None), "wk": P(None, None),
                              "k_norm": {"scale": P(), "bias": P()},
                              "w": P(None, None)}
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


def _eps(cfg: TransformerConfig) -> tuple:
    """The configuration's epsilon as trailing arguments of `_rmsnorm`,
    `_normed_if` and `_head_norm`: none at `NORM_EPS`, so a configuration
    at the default calls them in the forms without it, the forms the
    benchmark's planted-fault tests replace."""
    return () if cfg.norm_eps == NORM_EPS else (cfg.norm_eps,)


def _rmsnorm(x, scale, eps: float = NORM_EPS):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _constrain(x, spec, use_constraints):
    if use_constraints:
        return jax.lax.with_sharding_constraint(x, spec)
    return x


def apply(params, tokens, cfg: TransformerConfig, *,
          return_routing: bool = False, return_selection: bool = False,
          **kw):
    """Forward pass → logits (float32), or — with ``return_hidden=True``
    — the pre-projection hidden states [b, s, d] in ``cfg.dtype`` for
    the chunked LM loss (lm_loss with cfg.xent_chunk). The keywords but
    the two ``return_*`` are `_forward`'s, which holds their defaults;
    ``use_constraints`` (True) pins activations to `act_spec`.

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
    ``return_selection=True`` (learned sparse attention) returns the
    keys each query of each layer selected, packed by query
    (``ops/selection.py``: [b, s/32, s] int32 a layer); both asked for,
    ``(result, (routing, selections))``.

    A looped decoder (``cfg.n_loops`` > 1) gives the last pass's result;
    with ``per_pass(s)``, a function of a pass's normed state [b, s, d],
    it returns what that makes of every pass instead, stacked over the
    passes (`lm_loss`'s exits).
    """
    out, routing, selections, _ = _forward(params, tokens, cfg, **kw)
    if kw.get("per_pass"):
        return out
    return _with_aux(out, routing, selections, return_routing,
                     return_selection)


def _with_aux(out, routing, selections, return_routing, return_selection):
    """``out``, or ``(out, aux)``: ``aux`` the routing or the selections
    asked for, or both as ``(routing, selections)``."""
    aux = [a for a, asked in ((routing, return_routing),
                              (selections, return_selection)) if asked]
    return (out, aux[0] if len(aux) == 1 else tuple(aux)) if aux else out


def _forward(params, tokens, cfg: TransformerConfig, *,
             use_constraints: bool = True, attn_fn=None, positions=None,
             return_hidden: bool = False, per_pass=None):
    """`apply`'s work, its keywords `apply`'s own: (its result, the
    routing, the selections, the sum over the layers of the indexer's KL
    term, 0.0 without one)."""
    _check_looped(cfg)
    if (cfg.n_loops > 1 or cfg.sparse_topk) and attn_fn is not None:
        raise ValueError("a looped decoder and learned sparse attention run "
                         "the default attention: no attn_fn")
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
    index_rotary = (_rope_tables(positions, cfg.index_rope, cfg.rope_theta)
                    if cfg.sparse_topk and cfg.index_rope else None)
    index_blocks = (_index_kernel_blocks(cfg, tokens.shape[1], blocks)
                    if cfg.sparse_topk else None)
    mask_heads = 0   # the query heads a sparse forward step takes
    if cfg.sparse_topk and blocks is not None:
        from ..ops.pallas.flash_attention import mask_heads as heads_a_step

        # the Q block and dtype `sparse_attention`'s forward kernel takes
        mask_heads = heads_a_step(cfg.n_heads, cfg.kv_heads,
                                  min(blocks[0], tokens.shape[1]),
                                  cfg.head_dim, cfg.dtype)

    def _attend(x, blk, window, rope):
        """x + the block's attention over heads of one width."""
        with jax.named_scope(scopes.ATTENTION):
            h = _rmsnorm(x, blk["ln1"]["scale"], *_eps(cfg))
            if blocks is not None:
                o = _fused_attention(h, blk, cfg, blocks, window,
                                     rotary if rope else None)
            else:
                q, k, v = (jnp.einsum("bsd,dhk->bshk", h,
                                      blk[w].astype(cfg.dtype))
                           for w in ("wq", "wk", "wv"))
                if cfg.qk_norm:
                    q, k = (_rmsnorm(a, blk[n]["scale"], *_eps(cfg))
                            for a, n in ((q, "q_norm"), (k, "k_norm")))
                if rope:
                    q, k = _rope(q, rotary), _rope(k, rotary)
                if attn_fn is None:
                    o = causal_attention(q, k, v, window)
                else:
                    o = attn_fn(q, *(_repeat_kv(a, cfg) for a in (k, v)))
                o = jnp.einsum("bshk,hkd->bsd", o, blk["wo"].astype(cfg.dtype))
            return x + _normed_if(blk, "ln1_post", o, *_eps(cfg))

    # the scopes sit inside the block, so they survive jax.checkpoint; what
    # a layer's feed-forward is follows from the parameters it was given
    def _block(x, blk, window=None, rope=False):
        """The block's output, with the chosen experts of an expert layer
        and, after them, the indexer's (KL term, selection) of a learned
        sparse attention."""
        sparse, indexed = "experts" in blk, ()
        if sparse and cfg.router_input == "block":
            with jax.named_scope(scopes.ROUTER):
                routed = _route(x, blk, cfg)
        if "conv" in blk:
            x = _short_conv(x, blk, cfg)
        elif cfg.kv_latent:
            x = _latent_attention(x, blk, cfg, blocks, rotary, attn_fn)
        elif cfg.sparse_topk:
            x, indexed = _sparse_attention(x, blk, cfg, blocks, index_blocks,
                                           rotary if rope else None,
                                           index_rotary)
            indexed = (indexed,)
        else:
            x = _attend(x, blk, window, rope)
        x = _constrain(x, aspec, use_constraints)
        if sparse:
            from ..parallel import moe

            with jax.named_scope(scopes.MOE):
                h = _rmsnorm(x, blk["ln2"]["scale"], *_eps(cfg))
            if cfg.router_input != "block":  # reads what the experts read
                with jax.named_scope(scopes.ROUTER):
                    routed = _route(h, blk, cfg)
            with jax.named_scope(scopes.MOE):
                ff = moe.expert_layer(h.reshape(-1, h.shape[-1]), *routed,
                                      blk["experts"], cfg.held,
                                      activation=cfg.expert_activation,
                                      experts=cfg.n_experts)
                x = x + ff.reshape(h.shape)
            if "shared" in blk:  # every token's, beside the routed ones
                with jax.named_scope(scopes.SHARED_EXPERT):
                    x = x + _gated_mlp(h, blk["shared"], cfg.dtype)
            return _constrain(x, aspec, use_constraints), routed[0], *indexed
        with jax.named_scope(scopes.MLP):
            h = _rmsnorm(x, blk["ln2"]["scale"], *_eps(cfg))
            if "mlp" in blk:
                ff = _gated_mlp(h, blk["mlp"], cfg.dtype)
            else:
                ff = jax.nn.gelu(
                    jnp.einsum("bsd,df->bsf", h, blk["w1"].astype(cfg.dtype)))
                ff = jnp.einsum("bsf,fd->bsd", ff,
                                blk["w2"].astype(cfg.dtype))
            x = x + _normed_if(blk, "ln2_post", ff, *_eps(cfg))
        x = _constrain(x, aspec, use_constraints)
        return (x, *indexed) if indexed else x

    # one (checkpointed) function per kind of layer the pattern has
    block_fns, routing, selections, kls = {}, [], [], []

    def _stack(x):
        """The blocks, one after the other."""
        for kind, blk in zip(kinds, params["blocks"]):
            if kind not in block_fns:
                fn = _block if kind == (None, False) else functools.partial(
                    _block, window=kind[0], rope=kind[1])
                block_fns[kind] = jax.checkpoint(
                    fn, policy=_KEEP_KERNEL_RESIDUALS) if cfg.remat else fn
            x = block_fns[kind](x, blk)
            conv = "conv" in blk
            if conv:  # out here a layer counts, traced or not
                scopes.note_conv(tokens.size, cfg.d_model)
            elif attn_fn is None:
                scopes.note_attention(
                    kernel=blocks is not None, window=kind[0] is not None,
                    kept=keeps, latent=bool(cfg.kv_latent),
                    sparse=bool(cfg.sparse_topk),
                    indexer_kernel=index_blocks is not None,
                    mask_heads=mask_heads)
            kept = _kept_bytes(tokens.shape, cfg, keeps and not conv,
                               cfg.remat and "experts" in blk,
                               cfg.remat and "experts" in blk)
            if cfg.sparse_topk:
                kl, selected = x[-1]
                x = x[:-1] if "experts" in blk else x[0]
                kls.append(kl)
                selections.append(selected)
                if cfg.remat:
                    kept += _kept_selection_bytes(tokens.shape, cfg,
                                                  blocks is not None)
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
                                moe.chunk_rows(rows, cfg.experts_per_token,
                                               cfg.held[1], cfg.n_experts))
                if "shared" in blk:
                    scopes.note_layer("shared_experts")
            elif cfg.n_experts:
                scopes.note_layer("dense_layers")
        return x

    def _final_norm(x):
        with jax.named_scope(scopes.HEAD):
            return _rmsnorm(x, params["ln_f"]["scale"], *_eps(cfg))

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
            return passes, routing, selections, 0.0
    else:
        x = _final_norm(_stack(x))
    with jax.named_scope(scopes.HEAD):
        # pre-projection activations for the chunked LM loss, or logits
        out = x if return_hidden else _classify(x, params, cfg)
    return out, routing, selections, sum(kls) if kls else 0.0


def _classify(x, params, cfg: TransformerConfig):
    """Float32 logits [b, s, rows] of normed states [b, s, d]."""
    if not cfg.tie_embeddings:
        return jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                          params["head"])
    return jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32), params["embed"])


@jax.custom_vjp
def _target_logp(logits, targets):
    """Each token's log-probability of its target, [b, s] float32: the
    target's logit less its row's log-sum-exp.

    A rule of its own: the forward pass keeps the logits, each row's max
    and its sum of exponentials, and writes no log-softmax of the logits'
    size; the backward pass is one elementwise pass, ``g * (onehot -
    softmax)``, with no scatter into zeros. Both round as
    ``jax.nn.log_softmax`` and its gather do. Ids as `jnp.take_along_axis`
    takes them: a negative one counts from the end, one out of range
    reads NaN and takes no gradient."""
    return _target_logp_fwd(logits, targets)[0]


def _target_logp_fwd(logits, targets):
    last = logits.ndim - 1
    batch = tuple(range(last))
    top = jax.lax.reduce_max(logits, (last,))
    total = jax.lax.reduce_sum(
        jax.lax.exp(logits - _along_rows(top, logits)), (last,))
    ids = jax.lax.select(targets < 0, targets + logits.shape[-1], targets)
    picked = jax.lax.gather(
        logits, ids[..., None], jax.lax.GatherDimensionNumbers(
            offset_dims=(), collapsed_slice_dims=(last,),
            start_index_map=(last,), operand_batching_dims=batch,
            start_indices_batching_dims=batch),
        slice_sizes=(1,) * logits.ndim,
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP, fill_value=jnp.nan)
    top, total = map(ad_checkpoint.checkpoint_name, (top, total),
                     scopes.KEPT_XENT)
    return (picked - top) - jax.lax.log(total), (logits, ids, top, total)


def _target_logp_bwd(res, g):
    logits, ids, top, total = res
    g = jax.lax.select((ids >= 0) & (ids < logits.shape[-1]), g,
                       jnp.zeros_like(g))
    onehot = jax.lax.broadcasted_iota(
        ids.dtype, logits.shape, logits.ndim - 1) == _along_rows(ids, logits)
    softmax_g = _along_rows(g / total, logits) * jax.lax.exp(
        logits - _along_rows(top, logits))
    return (_along_rows(g, logits) * onehot.astype(logits.dtype) - softmax_g,
            None)


def _along_rows(a, logits):
    """``a``, one value a row of ``logits``, broadcast along the row."""
    return jax.lax.broadcast_in_dim(a, logits.shape,
                                    tuple(range(logits.ndim - 1)))


_target_logp.defvjp(_target_logp_fwd, _target_logp_bwd)


def _normed_if(blk, name: str, out, *eps):
    """A sub-layer's output on its way to the residual add: through the
    sandwich norm ``name`` where the block was given one."""
    return _rmsnorm(out, blk[name]["scale"], *eps) if name in blk else out


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
        h = _rmsnorm(x, blk["ln1"]["scale"], *_eps(cfg))
    with jax.named_scope(scopes.LATENT):
        kva = jnp.einsum("bsd,de->bse", h, blk["wkva"].astype(dt))
        c = _rmsnorm(kva[..., :latent], blk["ln_kv"]["scale"], *_eps(cfg))
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
    *scopes.KEPT_BY_REMAT_LATENT, scopes.KEPT_CHOICE, scopes.KEPT_ROUTING,
    # a learned sparse attention's selection and its indexer's gradients
    *scopes.KEPT_SELECTION)

_KEEP_XENT_ROWS = jax.checkpoint_policies.save_only_these_names(
    *scopes.KEPT_XENT)


def _kept_bytes(shape, cfg: TransformerConfig, kernels: bool = True,
                choice: bool = False, routing: bool = False) -> int:
    """Bytes a checkpointed block keeps on ``shape`` = (b, s) tokens.
    ``kernels``: `flash_attention`'s residuals, q and o [b, s, heads*hd],
    k and v [b, s, kv*hd] in ``cfg.dtype``, lse [b*heads, 1, s] float32;
    of `latent_attention`'s also q_rope [b, heads, s, d_rope] and the one
    k_rope [b, s, d_rope]. ``choice``: its router's, [b*s, k] int32.
    ``routing``: what its expert layer sorted out of the routing
    (``parallel.moe.index_bytes``). A conv layer's mixer keeps nothing
    (``kernels`` False): its recomputation starts from the block's input,
    which every checkpointed block keeps and none counts here."""
    b, s = shape
    if routing:
        from ..parallel import moe

        return (_kept_bytes(shape, cfg, kernels, choice)
                + moe.index_bytes(b * s, cfg.experts_per_token, cfg.held[1],
                                  cfg.n_experts))
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
    instead (both seen in the step compiled for a v5e; PERF.md, PR 26).
    Heads too narrow to stand side by side in a lane tile (the kernels'
    ``NARROW_HEAD``) go heads first, [b, heads, s, hd] (`_heads_first`),
    and come back through a ``hkd`` projection."""
    from ..ops.pallas.flash_attention import LANES, flash_attention

    if cfg.head_dim % LANES:
        q, k, v = _heads_first(h, blk, cfg, rotary)
        o = flash_attention(q, k, v, True, *blocks, cfg.n_heads, window,
                            cfg.kv_heads)
        return jnp.einsum("bhsk,hkd->bsd", o, blk["wo"].astype(cfg.dtype))
    q, k, v = _heads_side_by_side(h, blk, cfg, rotary)
    o = flash_attention(q, k, v, True, *blocks, blk["wq"].shape[1], window,
                        blk["wk"].shape[1])
    return jnp.einsum("bse,ed->bsd", o,
                      blk["wo"].astype(cfg.dtype).reshape(-1, cfg.d_model))


def _heads_side_by_side(h, blk, cfg: TransformerConfig, rotary=None):
    """q, k, v [b, s, heads*hd] as the fused kernels take them, the
    heads side by side: the projections, QK-norm where the block has it,
    the rotary turn where ``rotary`` is given."""
    q, k, v = (
        jnp.einsum("bsd,de->bse", h,
                   blk[w].astype(cfg.dtype).reshape(cfg.d_model, -1))
        for w in ("wq", "wk", "wv"))
    if "q_norm" in blk:
        q, k = (_head_norm(a, blk[n]["scale"], cfg.head_dim, *_eps(cfg))
                for a, n in ((q, "q_norm"), (k, "k_norm")))
    if rotary is not None:
        q, k = (_rope_side_by_side(a, rotary, cfg.head_dim) for a in (q, k))
    return q, k, v


def _heads_first(h, blk, cfg: TransformerConfig, rotary=None):
    """q, k, v [b, heads, s, hd], heads first, as the fused kernels take
    heads narrower than a lane tile: the projections, QK-norm where the
    block has it, the rotary turn where ``rotary`` is given."""
    q, k, v = (jnp.einsum("bsd,dhk->bhsk", h, blk[w].astype(cfg.dtype))
               for w in ("wq", "wk", "wv"))
    if "q_norm" in blk:
        q, k = (_rmsnorm(a, blk[n]["scale"], *_eps(cfg))
                for a, n in ((q, "q_norm"), (k, "k_norm")))
    if rotary is not None:
        q, k = (_rope(a, rotary, heads_first=True) for a in (q, k))
    return q, k, v


def _short_conv(x, blk, cfg: TransformerConfig):
    """``x`` + its gated short convolution (LFM2's), [b, s, d], under
    ``hvd.model/conv``: ``[B | C | x~] = u W_in`` of the normed input
    ``u``, ``z = B * x~``, a causal filter of ``cfg.conv_taps`` taps per
    channel, ``y_t = sum_j w_j z_{t - taps + 1 + j}`` (zero before
    position 0, no bias), and ``(C * y) W_out``. The filter is shifted
    multiply-adds on ``z``, in float32, which XLA fuses with the two
    gates into one pass; no convolution op. Nothing in it carries a
    name, so a checkpointed block keeps its input alone and recomputes
    the rest."""
    d, dt = cfg.d_model, cfg.dtype
    with jax.named_scope(scopes.CONV):
        u = _rmsnorm(x, blk["ln1"]["scale"], *_eps(cfg))
        bcx = jnp.einsum("bsd,de->bse", u, blk["in_proj"].astype(dt))
        b, c, xt = (bcx[..., i * d:(i + 1) * d].astype(jnp.float32)
                    for i in range(3))
        z, taps = b * xt, blk["conv"]
        y = z * taps[-1]
        for back in range(1, taps.shape[0]):  # tap -1 - back: back steps
            earlier = jnp.pad(z[:, :-back], ((0, 0), (back, 0), (0, 0)))
            y = y + earlier * taps[-1 - back]
        o = jnp.einsum("bsd,de->bse", (c * y).astype(dt),
                       blk["out_proj"].astype(dt))
        return x + o


def _head_norm(x, scale, head_dim: int, *eps):
    """`_rmsnorm` of each head of [b, s, h*hd] by itself."""
    b, s, width = x.shape
    return _rmsnorm(x.reshape(b, s, width // head_dim, head_dim),
                    scale, *eps).reshape(b, s, width)


# -- learned sparse attention ------------------------------------------------

def _sparse_attention(x, blk, cfg: TransformerConfig, blocks, index_blocks,
                      rotary, index_rotary):
    """``x`` + its learned sparse attention, and the indexer's ``(KL
    term, selection packed by query)``. The main heads' projections, the
    kernels (``hvd_dsa_*``, where ``blocks`` says the fused kernels are
    taken; else `_selected_attention`) and the output projection run
    under ``hvd.model/attention``; the indexer, from its projections to
    its KL term, under ``hvd.model/indexer`` (side by side, as the latent
    part is). The indexer reads the normed input and the main heads'
    queries and keys detached: what it does moves no other leaf. The
    indexer's scores and their gradient are kernels too where
    ``index_blocks`` (`_index_kernel_blocks`) is given
    (``hvd_dsa_index_*``; else `_index_scores`)."""
    with jax.named_scope(scopes.ATTENTION):
        h = _rmsnorm(x, blk["ln1"]["scale"], *_eps(cfg))
        q, k, v = _heads_side_by_side(h, blk, cfg, rotary)
    spec = (cfg.sparse_topk, cfg.index_chunk, cfg.n_heads, blocks,
            index_blocks)
    with jax.named_scope(scopes.INDEXER):
        iq, ik, iw = _index_inputs(h, blk["indexer"], cfg, index_rotary)
        selected = _index_select(iq, ik, iw, spec)
    with jax.named_scope(scopes.ATTENTION):
        if blocks is not None:
            from ..ops.pallas.flash_attention import sparse_attention

            o, lse = sparse_attention(q, k, v, selected, *blocks, cfg.n_heads,
                                      cfg.kv_heads, cfg.sparse_topk)
        else:
            from ..ops import selection

            o, lse = _selected_attention(
                q, k, v, selection.unpack(selected[0]), cfg.n_heads)
        o = jnp.einsum("bse,ed->bsd", o,
                       blk["wo"].astype(cfg.dtype).reshape(-1, cfg.d_model))
    with jax.named_scope(scopes.INDEXER):
        kl = _indexer(iq, ik, iw, *map(jax.lax.stop_gradient, (q, k, lse)),
                      selected, spec)
    return x + o, (kl, selected[0])


def _layernorm(x, params):
    """LayerNorm over the last axis with a scale and a bias (the
    indexer's key, float32)."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    centred = x - mean
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    return (centred * jax.lax.rsqrt(var + 1e-6) * params["scale"]
            + params["bias"])


def _index_inputs(h, idx, cfg: TransformerConfig, tables):
    """The indexer's queries [b, s, heads, dim], its one key [b, s, dim]
    and its head weights [b, s, heads], float32 (bf16 products with a
    float32 result), from the block's normed input ``h`` detached: a
    LayerNorm on the key, rotary positions on the leading
    ``cfg.index_rope`` columns of both."""
    x, dt = jax.lax.stop_gradient(h), cfg.dtype
    f32 = jnp.float32
    q = jnp.einsum("bsd,dhk->bshk", x, idx["wq"].astype(dt),
                   preferred_element_type=f32)
    k = _layernorm(jnp.einsum("bsd,dk->bsk", x, idx["wk"].astype(dt),
                              preferred_element_type=f32), idx["k_norm"])
    w = jnp.einsum("bsd,dh->bsh", x, idx["w"].astype(dt),
                   preferred_element_type=f32)
    r = cfg.index_rope
    if r:
        q = jnp.concatenate([_rope(q[..., :r], tables), q[..., r:]], axis=-1)
        k = jnp.concatenate([_rope(k[:, :, None, :r], tables)[:, :, 0],
                             k[..., r:]], axis=-1)
    return q, k, w


def _index_scores(iq, iw, ik):
    """``I(t, s) = sum_j w_j(t) heads**-1/2 relu(q_j(t) . k(s)) dim**-1/2``,
    [b, c, s] float32, of queries ``iq`` [b, c, heads, dim] with weights
    ``iw`` [b, c, heads] against keys ``ik`` [b, s, dim]: the einsum
    path's, and what the kernels' (``flash_attention.index_scores``)
    are held to."""
    heads, dim = iq.shape[-2:]
    dots = jnp.einsum("bchk,bsk->bchs", iq, ik,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bchs,bch->bcs", jax.nn.relu(dots),
                      iw) * (heads * dim) ** -0.5


def _index_kernel_blocks(cfg: TransformerConfig, s: int, blocks):
    """``(group, block_k)`` of the indexer's kernels over ``s`` positions
    (``flash_attention.index_blocks``), or None: they are taken where the
    attention kernels are (``blocks``) and tile the indexer's chunks."""
    if blocks is None:
        return None
    from ..ops.pallas.flash_attention import index_blocks

    return index_blocks(_chunks(s, cfg.index_chunk), s, cfg.index_heads,
                        cfg.index_dim)


def _chunk_scores(iq, iw, ik, first, kernel):
    """The scores of the chunk of queries from ``first`` (`_index_scores`'
    arguments), through the kernels where ``kernel`` (`_index_kernel_blocks`)
    is given: one function for the selection and the KL term, so both see
    the same scores."""
    if kernel is None:
        return _index_scores(iq, iw, ik)
    from ..ops.pallas.flash_attention import index_scores

    return index_scores(iq, iw, ik, first, *kernel)


def _select(scores, first, topk: int):
    """Which keys each query selects, [b, c, s] bool: query ``first +
    i`` takes key ``j <= first + i`` iff its score is at least the
    query's ``topk``-th largest, or all of them where it has fewer. The
    threshold exactly, without a sort: the scores as unsigned integers of
    the same order, then the largest ``T`` with at least ``topk`` of them
    ``>= T``, two bits at a time from the top (three counts a round,
    sixteen rounds; a later key outside the causal range counts as 0).
    Four bits a round, fifteen counts, cost 2.4 times as much by the TPU
    compiler's estimate at the cell's sizes (PERF.md §6)."""
    c, s = scores.shape[-2:]
    causal = jnp.arange(s)[None, :] <= first + jnp.arange(c)[:, None]
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    ordered = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    ordered = jnp.where(causal, ordered, jnp.uint32(0))
    floor = jnp.zeros(scores.shape[:-1], jnp.uint32)
    digits = jnp.arange(1, 4, dtype=jnp.uint32)
    for shift in range(30, -1, -2):
        tried = floor[..., None] + (digits << shift)
        above = jnp.sum(ordered[..., None, :] >= tried[..., None], axis=-1)
        floor = floor + (jnp.sum(above >= topk, axis=-1).astype(jnp.uint32)
                         << shift)
    return causal & (ordered >= floor[..., None])


def _target(q, k, lse, keep, heads: int):
    """The main attention's probabilities over each query's selection,
    averaged over the heads, [b, c, s] float32: the indexer's target, on
    the einsum path (the kernels' is ``flash_attention.sparse_target``).
    q [b, c, heads*hd], k [b, s, kv*hd], ``lse`` [b*heads, 1, c] each
    head's log-sum-exp over the selection, one key/value head's queries
    at a time."""
    b, c, width = q.shape
    hd = width // heads
    kv = k.shape[-1] // hd
    group = heads // kv
    lse = lse.reshape(b, heads, c)
    total = 0.0
    for g in range(kv):
        qg = q[..., g * group * hd:(g + 1) * group * hd].reshape(
            b, c, group, hd)
        logits = jnp.einsum("bcjd,bsd->bjcs", qg, k[..., g * hd:(g + 1) * hd],
                            preferred_element_type=jnp.float32) * hd ** -0.5
        p = jnp.exp(logits - lse[:, g * group:(g + 1) * group, :, None])
        total = total + jnp.sum(jnp.where(keep[:, None], p, 0.0), axis=1)
    return total / heads


def _kl(scores, keep, target):
    """``sum_t KL(target_t || softmax over the selection of scores_t)``
    and its gradient in the scores, ``softmax - target`` on the
    selection (the target sums to one there)."""
    logits = jnp.where(keep, scores, -jnp.inf)
    log_q = scores - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    terms = jnp.where(target > 0, target * jnp.log(target), 0.0) \
        - target * log_q
    return (jnp.sum(jnp.where(keep, terms, 0.0)),
            jnp.where(keep, jnp.exp(log_q) - target, 0.0))


def _chunks(s: int, chunk: int) -> int:
    """The queries the indexer takes at a time over ``s`` positions."""
    from ..ops import selection

    c = min(chunk, s)
    if s % c or c % selection.WORD:
        raise ValueError(f"learned sparse attention over {s} positions "
                         f"takes chunks of {c} queries that tile them, "
                         f"each a multiple of {selection.WORD}")
    return c


def _index_select(iq, ik, iw, spec):
    """Each query's selection (`_select` of `_index_scores`), chunk by
    chunk in one ``lax.scan``, packed by query and, for the kernels, by
    key and by tile; named (`scopes.KEPT_SELECTION`), so that a
    checkpointed block keeps it: its backward pass attends over the keys
    its forward pass selected, and selects no second time. Takes no
    gradient."""
    from ..ops import selection

    iq, ik, iw = map(jax.lax.stop_gradient, (iq, ik, iw))
    topk, chunk, _, blocks, kernel = spec
    b, s = iq.shape[:2]
    c = _chunks(s, chunk)

    def one(_, first):
        iq_c, iw_c = (jax.lax.dynamic_slice_in_dim(a, first, c, axis=1)
                      for a in (iq, iw))
        keep = _select(_chunk_scores(iq_c, iw_c, ik, first, kernel), first,
                       topk)
        out = [selection.pack(keep)]
        if blocks is not None:
            out.append(selection.pack(jnp.swapaxes(keep, 1, 2)))
        return None, out

    _, outs = jax.lax.scan(one, None, jnp.arange(0, s, c))
    by_query = jnp.moveaxis(outs[0], 0, 1).reshape(b, s // selection.WORD, s)
    named = [by_query]
    if blocks is not None:  # chunks of keys' words [n, b, s/32, c]
        named += [jnp.transpose(outs[1], (1, 2, 0, 3)).reshape(
            b, s // selection.WORD, s), selection.tiles(by_query, *blocks)]
    return tuple(ad_checkpoint.checkpoint_name(a, name)
                 for a, name in zip(named, scopes.KEPT_SELECTION))


def _kl_pass(iq, ik, iw, q, k, lse, selected, spec, grads: bool):
    """The indexer's KL term over chunks of queries, one ``lax.scan``:
    each chunk's scores, the target (the kernels' ``sparse_target``, or
    `_target`) and `_kl`; with ``grads``, also the term's gradients in
    the indexer's queries, key and weights, taken here (``jax.vjp`` of
    the scores alone). → (the term's mean over the tokens, the gradients
    or None)."""
    from ..ops import selection

    topk, chunk, heads, blocks, kernel = spec
    b, s = iq.shape[:2]
    c = _chunks(s, chunk)
    by_query = selected[0]

    def one(carry, first):
        iq_c, iw_c, q_c = (jax.lax.dynamic_slice_in_dim(a, first, c, axis=1)
                           for a in (iq, iw, q))
        lse_c = jax.lax.dynamic_slice_in_dim(lse, first, c, axis=2)
        words = jax.lax.dynamic_slice_in_dim(
            by_query, first // selection.WORD, c // selection.WORD, axis=1)
        keep = selection.unpack(words)
        scores_of = functools.partial(_chunk_scores, first=first,
                                      kernel=kernel)
        if grads:
            scores, back = jax.vjp(scores_of, iq_c, iw_c, ik)
        else:
            scores = scores_of(iq_c, iw_c, ik)
        if blocks is not None:
            from ..ops.pallas.flash_attention import sparse_target

            target = sparse_target(q_c, k, lse_c, words, first, *blocks,
                                   heads, k.shape[-1] * heads // q.shape[-1],
                                   topk)
        else:
            target = _target(q_c, k, lse_c, keep, heads)
        value, d_scores = _kl(scores, keep, target)
        if not grads:
            return carry + value, None
        d_iq, d_iw, d_ik = back(d_scores / (b * s))
        total, k_grad = carry
        return (total + value, k_grad + d_ik), (d_iq, d_iw)

    zero = jnp.zeros((), jnp.float32)
    carry = (zero, jnp.zeros_like(ik)) if grads else zero
    carry, outs = jax.lax.scan(one, carry, jnp.arange(0, s, c))
    if not grads:
        return carry / (b * s), None
    d_iq, d_iw = (jnp.moveaxis(a, 0, 1).reshape(b, s, *a.shape[3:])
                  for a in outs)
    found = tuple(ad_checkpoint.checkpoint_name(g, scopes.KEPT_SELECTION[3])
                  for g in (d_iq, carry[1], d_iw))
    return carry[0] / (b * s), found


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _indexer(iq, ik, iw, q, k, lse, selected, spec):
    """A learned sparse attention's indexer KL term: the mean over the
    tokens of ``KL(p || softmax over the selection of I)``, ``I`` the
    scores of the indexer's queries ``iq`` [b, s, heads, dim], key ``ik``
    [b, s, dim] and weights ``iw`` [b, s, heads], ``p`` the main heads'
    mean probabilities over the selection, from their queries ``q`` [b,
    s, heads*hd], keys ``k`` [b, s, kv*hd] and log-sum-exps ``lse``
    [b*heads, 1, s] (a constant), ``selected`` `_index_select`'s
    (``spec``: the keys a query selects, the queries a chunk, the main
    heads, the kernels' blocks or None, the indexer kernels' or None).

    A rule of its own: the forward pass takes the term's gradients where
    it has the scores (one pass over them, not two), and the backward
    pass scales them; ``q``, ``k``, ``lse`` and the selection take none.
    The gradients are named (`scopes.KEPT_SELECTION`): a checkpointed
    block keeps them and runs this once."""
    return _kl_pass(iq, ik, iw, q, k, lse, selected, spec, grads=False)[0]


def _indexer_fwd(iq, ik, iw, q, k, lse, selected, spec):
    return _kl_pass(iq, ik, iw, q, k, lse, selected, spec, grads=True)


def _indexer_bwd(spec, found, g):
    d_iq, d_ik, d_iw = found
    return g * d_iq, g * d_ik, g * d_iw, None, None, None, None


_indexer.defvjp(_indexer_fwd, _indexer_bwd)


def _selected_attention(q, k, v, keep, heads: int):
    """Attention of each query over the keys ``keep`` [b, s, s] says it
    selected, q [b, s, heads*hd], k/v [b, s, kv*hd], float32 softmax:
    the einsum path of `_sparse_attention`, and the numerics its kernels
    are held to. → (o, each head's log-sum-exp [b*heads, 1, s], as the
    kernels give it)."""
    b, s, width = q.shape
    hd = width // heads
    group = heads // (k.shape[-1] // hd)
    q = q.reshape(b, s, heads, hd)
    k, v = (jnp.repeat(a.reshape(b, s, -1, hd), group, axis=2)
            for a in (k, v))
    logits = jnp.einsum("bshk,bthk->bhst", q, k).astype(jnp.float32)
    logits = jnp.where(keep[:, None], logits * hd ** -0.5, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    lse = jax.nn.logsumexp(logits, axis=-1).reshape(b * heads, 1, s)
    return (jnp.einsum("bhst,bthk->bshk", probs, v).reshape(b, s, width),
            lse)


def _kept_selection_bytes(shape, cfg: TransformerConfig, kernels: bool) -> int:
    """Bytes a checkpointed learned-sparse block keeps beside
    `_kept_bytes`' on ``shape`` = (b, s) tokens (`scopes.KEPT_SELECTION`):
    the selection packed by query, [b, s/32, s] int32, and for the
    kernels by key too and a word a tile; the indexer's gradients,
    float32, as its queries, key and weights."""
    b, s = shape
    words = b * s * s // 8
    tiles = 0
    if kernels:
        from ..ops.pallas.flash_attention import block_sizes

        bq, bk = block_sizes(s, cfg.head_dim)
        tiles = 4 * b * (s // bq) * (s // bk)
    per_token = cfg.index_heads * cfg.index_dim + cfg.index_dim + cfg.index_heads
    return words * (2 if kernels else 1) + tiles + 4 * b * s * per_token


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
            return_routing: bool = False, return_selection: bool = False,
            **kw):
    """Next-token cross-entropy (mean over tokens).

    With ``cfg.xent_chunk`` set, the classifier streams over vocab
    chunks (ops/xent.py chunked_softmax_xent) and float32 logits
    [tokens, vocab] are never materialized. ``return_routing=True``
    returns ``(loss, routing)`` with `apply`'s routing (``has_aux``),
    ``return_selection=True`` ``(loss, selections)``, and both ``(loss,
    (routing, selections))``.

    With learned sparse attention (``cfg.sparse_topk``) the loss is the
    cross-entropy plus each layer's indexer KL term (``_indexer``): its
    gradient reaches the indexers alone, the cross-entropy's every other
    leaf.

    A looped decoder (``cfg.n_loops`` > 1): the expected loss over its
    exits less ``cfg.exit_beta`` times the exit distribution's entropy
    (`exit_loss`); ``return_exits=True`` returns ``(loss, (each exit's
    mean loss [n_loops], the mean exit distribution [n_loops]))``."""
    targets = tokens[:, 1:]
    if cfg.n_loops > 1:
        if return_routing or return_selection:
            raise ValueError("a looped decoder has no routing to return")
        return _looped_loss(params, tokens[:, :-1], targets, cfg, **kw)
    out, routing, selections, kl = _forward(
        params, tokens[:, :-1], cfg, return_hidden=bool(cfg.xent_chunk), **kw)
    with jax.named_scope(scopes.HEAD):
        if cfg.xent_chunk:
            from ..ops.xent import chunked_softmax_xent

            b, s, d = out.shape
            w = params["embed"] if cfg.tie_embeddings else params["head"].T
            loss = chunked_softmax_xent(out.reshape(b * s, d), w,
                                        targets.reshape(-1), cfg.xent_chunk)
        else:
            scopes.note_xent(targets.size)
            loss = -jnp.mean(_target_logp(out, targets))
    if cfg.sparse_topk:
        with jax.named_scope(scopes.INDEXER):
            loss = loss + kl
    return _with_aux(loss, routing, selections, return_routing,
                     return_selection)


def _looped_loss(params, tokens, targets, cfg: TransformerConfig, *,
                 return_exits: bool = False, **kw):
    """`lm_loss` of a looped decoder. Each pass's exit runs inside the
    recurrence, under a checkpoint that keeps only the loss rule's two
    rows (`scopes.KEPT_XENT`): the float32 logits [tokens, rows] of one
    exit at a time exist, forward and backward (which recomputes the
    classifier and makes no pass over its logits but the rule's one),
    and only each token's loss and gate logit leave a pass.
    (Taking an exit's positions a piece at a time, or picking the
    target's logit by a comparison for the gather, made the TPU
    compiler's count of the step larger, not smaller: PERF.md, PR 35.)"""
    @functools.partial(jax.checkpoint, policy=_KEEP_XENT_ROWS)
    def exit_of(s, head, gate):
        with jax.named_scope(scopes.HEAD):
            losses = -_target_logp(_classify(s, head, cfg), targets)
        with jax.named_scope(scopes.EXIT):
            logit = jnp.einsum("bsd,d->bs", s.astype(jnp.float32),
                               gate["w"]) + gate["b"]
        return losses, logit

    def per_pass(s):
        scopes.note_xent(targets.size)  # out here a pass counts once
        return exit_of(s, head, params["gate"])

    head = {w: params[w] for w in ("embed", "head") if w in params}
    losses, gate_logits = apply(params, tokens, cfg, **kw, per_pass=per_pass)
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
