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
"""

from __future__ import annotations

import dataclasses
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

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def init(rng, cfg: TransformerConfig):
    keys = jax.random.split(rng, 4 + cfg.n_layers)
    s = 0.02
    params = {
        "embed": s * jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model), jnp.float32),
        "pos": s * jax.random.normal(keys[1], (cfg.max_seq, cfg.d_model), jnp.float32),
        "ln_f": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
        "blocks": [],
    }
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[4 + i], 6)
        params["blocks"].append({
            "ln1": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
            "ln2": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
            "wq": s * jax.random.normal(k[0], (cfg.d_model, cfg.n_heads, cfg.head_dim), jnp.float32),
            "wk": s * jax.random.normal(k[1], (cfg.d_model, cfg.n_heads, cfg.head_dim), jnp.float32),
            "wv": s * jax.random.normal(k[2], (cfg.d_model, cfg.n_heads, cfg.head_dim), jnp.float32),
            "wo": s * jax.random.normal(k[3], (cfg.n_heads, cfg.head_dim, cfg.d_model), jnp.float32),
            "w1": s * jax.random.normal(k[4], (cfg.d_model, cfg.d_ff), jnp.float32),
            "w2": s * jax.random.normal(k[5], (cfg.d_ff, cfg.d_model), jnp.float32),
        })
    return params


def param_specs(cfg: TransformerConfig):
    """PartitionSpec pytree matching `init` (for jit in_shardings)."""
    tp = cfg.tp_axis
    block = {
        "ln1": {"scale": P()},
        "ln2": {"scale": P()},
        "wq": P(None, tp, None),
        "wk": P(None, tp, None),
        "wv": P(None, tp, None),
        "wo": P(tp, None, None),
        "w1": P(None, tp),
        "w2": P(tp, None),
    }
    return {
        "embed": P(None, None),
        "pos": P(None, None),
        "ln_f": {"scale": P()},
        "blocks": [dict(block) for _ in range(cfg.n_layers)],
    }


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
          return_hidden: bool = False):
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
    """
    aspec = act_spec(cfg)
    if positions is None:
        positions = jnp.arange(tokens.shape[1])
    with jax.named_scope(scopes.EMBED):
        x = params["embed"][tokens].astype(cfg.dtype)
        x = x + params["pos"][positions].astype(cfg.dtype)[None]
    x = _constrain(x, aspec, use_constraints)

    # the fused kernels' tile shape, where the default attention takes them
    blocks = None if attn_fn else fused_attention_blocks(
        tokens.shape[1], cfg.head_dim, use_constraints)

    # the scopes sit inside the block, so they survive jax.checkpoint
    def _block(x, blk):
        with jax.named_scope(scopes.ATTENTION):
            h = _rmsnorm(x, blk["ln1"]["scale"])
            if attn_fn is None:
                scopes.note_attention(kernel=blocks is not None)
            if blocks is not None:
                o = _fused_attention(h, blk, cfg, blocks)
            else:
                q, k, v = (jnp.einsum("bsd,dhk->bshk", h,
                                      blk[w].astype(cfg.dtype))
                           for w in ("wq", "wk", "wv"))
                o = (attn_fn or causal_attention)(q, k, v)
                o = jnp.einsum("bshk,hkd->bsd", o, blk["wo"].astype(cfg.dtype))
            x = x + o
        x = _constrain(x, aspec, use_constraints)
        with jax.named_scope(scopes.MLP):
            h = _rmsnorm(x, blk["ln2"]["scale"])
            ff = jax.nn.gelu(
                jnp.einsum("bsd,df->bsf", h, blk["w1"].astype(cfg.dtype)))
            ff = jnp.einsum("bsf,fd->bsd", ff, blk["w2"].astype(cfg.dtype))
            x = x + ff
        return _constrain(x, aspec, use_constraints)

    block_fn = jax.checkpoint(_block) if cfg.remat else _block
    for blk in params["blocks"]:
        x = block_fn(x, blk)
    with jax.named_scope(scopes.HEAD):
        x = _rmsnorm(x, params["ln_f"]["scale"])
        if return_hidden:
            return x  # pre-projection activations for the chunked LM loss
        return jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                          params["embed"])


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


def _fused_attention(h, blk, cfg: TransformerConfig, blocks):
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
    o = flash_attention(q, k, v, True, *blocks, blk["wq"].shape[1])
    return jnp.einsum("bse,ed->bsd", o,
                      blk["wo"].astype(cfg.dtype).reshape(-1, cfg.d_model))


def causal_attention(q, k, v):
    """Plain causal attention, [b, s, h, hd] layout, f32 softmax: an
    einsum writes the [b, h, s, s] float32 logits and XLA's softmax
    passes over them. What ``apply`` runs wherever
    `fused_attention_blocks` says None (short or untileable sequences,
    off the TPU, under GSPMD), and the numerics the fused kernels are
    held to."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bshk,bthk->bhst", q, k).astype(jnp.float32) * scale
    s, t = logits.shape[-2], logits.shape[-1]
    mask = jnp.tril(jnp.ones((s, t), bool))
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthk->bshk", probs, v)


def lm_loss(params, tokens, cfg: TransformerConfig, **kw):
    """Next-token cross-entropy (mean over tokens).

    With ``cfg.xent_chunk`` set, the classifier streams over vocab
    chunks (ops/xent.py chunked_softmax_xent) and float32 logits
    [tokens, vocab] are never materialized."""
    targets = tokens[:, 1:]
    if cfg.xent_chunk:
        from ..ops.xent import chunked_softmax_xent

        h = apply(params, tokens[:, :-1], cfg, return_hidden=True, **kw)
        b, s, d = h.shape
        with jax.named_scope(scopes.HEAD):
            return chunked_softmax_xent(h.reshape(b * s, d), params["embed"],
                                        targets.reshape(-1), cfg.xent_chunk)
    logits = apply(params, tokens[:, :-1], cfg, **kw)
    with jax.named_scope(scopes.HEAD):
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return -jnp.mean(ll)
