"""Plain float32 reference of a SmallThinker decoder's training loss.

Written from the model's ``config.json`` (PowerInfer/SmallThinker-21BA3B-
Instruct) and the family's description, as the configuration file's
``assumed`` lists: pre-norm blocks of grouped-query causal attention and
a sparse ReGLU feed-forward whose router reads the block's *input*;
layers alternate by ``sliding_window_layout`` and ``rope_layout`` between
global attention without positions and attention over a sliding window
with rotary positions (rotate-half pairing over the whole head); RMSNorm
with a scale, no bias anywhere, an untied classifier, no auxiliary loss.

For layer ``l`` with input ``x`` [T, d]:

    r = x @ W_r;  S = the k largest of r;  w_e = exp(r_e) / sum_{j in S} exp(r_j)
    h = norm_1(x);  q, k, v = h W_q, h W_k, h W_v;  rotary on q, k if rope_layout[l]
    scores = q k^T / sqrt(head);  key j visible to query i iff j <= i
                                  and (no window on this layer or i - j < window)
    y = x + softmax(scores) v W_o;  query head h reads key/value head h // group
    u = norm_2(y);  E_e(u) = (relu(u G_e) * (u U_e)) D_e
    out = y + sum_{e in S and held} w_e E_e(u)

The experts held are a share of the router's (``first_held`` and as many
as the parameters carry): what the others would add is left out, as in
the program. The weights stay normalised over all the chosen.

Straightforward ``jax.numpy`` in float32, nothing of the program, no
kernels: attention by blocks of queries against the span of keys a block
can see, so that the scores of one block ([heads, block, keys]) fit a
chip at 8192 positions; every held expert on every token, times a weight
that is zero where the token did not choose it; dense softmax
cross-entropy over the rows held. The blocks of queries and the experts
are loops (``lax.map``, ``lax.scan``: one body to compile, not sixteen),
and each layer, and each block of queries in it, is a
``jax.checkpoint``: the gradient keeps one block's scores and one
layer's expert activations at a time. On a TPU callers run this
under ``jax.default_matmul_precision("highest")``.

The parameter tree is the program's (``models/transformer.py init``):
``embed [rows, d]``, ``head [d, rows]``, ``ln_f.scale`` and per block
``ln1.scale``, ``ln2.scale``, ``wq [d, heads, hd]``, ``wk/wv [d,
kv_heads, hd]``, ``wo [heads, hd, d]``, ``router [d, experts]``,
``experts.gate/up [held, d, f]``, ``experts.down [held, f, d]``.
"""

import functools
import math

import jax
import jax.numpy as jnp

NORM_EPS = 1e-6
QUERY_BLOCK = 512


def rmsnorm(x, scale):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + NORM_EPS)) * scale


def rotary(x, theta: float):
    """Rotary positions 0..s-1 on [s, heads, hd]: the pair (i, i + hd/2)
    turns by ``position * theta ** (-2i / hd)``."""
    s, _, hd = x.shape
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.cos(angle)[:, None, :]
    sin = jnp.sin(angle)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(h, blk, window, rope: bool, theta: float):
    """[s, d] -> [s, d], one sequence: block by block of QUERY_BLOCK
    queries, each against the span of keys it can see (all of them in a
    global layer, the last ``window + block - 1`` up to the block's end
    in a windowed one; the mask does the rest)."""
    s = h.shape[0]
    q = jnp.einsum("sd,dhk->shk", h, blk["wq"])
    k = jnp.einsum("sd,dhk->shk", h, blk["wk"])
    v = jnp.einsum("sd,dhk->shk", h, blk["wv"])
    if rope:
        q, k = rotary(q, theta), rotary(k, theta)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)
    span = s if window is None else min(s, window + block - 1)

    @jax.checkpoint
    def rows(start):
        """Queries ``start .. start + block - 1``."""
        first_key = jnp.clip(start + block - span, 0, s - span)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)
        kb = jax.lax.dynamic_slice_in_dim(k, first_key, span)
        vb = jax.lax.dynamic_slice_in_dim(v, first_key, span)
        scores = jnp.einsum("shk,thk->hst", qb, kb) / math.sqrt(q.shape[-1])
        i = start + jnp.arange(block)[:, None]
        j = first_key + jnp.arange(span)[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (i - j < window)
        scores = jnp.where(seen[None], scores, -jnp.inf)
        scores = scores - scores.max(axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        probs = probs / probs.sum(axis=-1, keepdims=True)
        return jnp.einsum("hst,thk->shk", probs, vb)

    out = jax.lax.map(rows, jnp.arange(0, s, block))
    return jnp.einsum("shk,hkd->sd", out.reshape(q.shape), blk["wo"])


def router_weights(x, router, k: int, imposed=None):
    """[T, experts] weights: the softmax over the ``k`` largest scores of
    each token, zero elsewhere; which they are, as a mask; and 0.0.

    ``imposed`` (a mask like the one returned; a token's row all False
    leaves that token to the router) makes the weights a softmax of
    this router's own scores over the imposed sets instead.
    That is how a comparison tells a tie from a fault: a program whose
    activations are rounded differently picks another expert where a
    token's k-th and (k+1)-th scores nearly tie, and is then held to
    this reference *on its own picks*, together with the third result:
    the largest amount, over the tokens, by which an imposed pick's
    score lies under this router's own k-th largest, in units of the
    spread (standard deviation) of that token's scores. A tie reads a
    small fraction; a wrong router reads of the order of one."""
    r = x @ router
    kth = jnp.sort(r, axis=-1)[:, -k][:, None]
    own = r >= kth
    chosen = own if imposed is None else jnp.where(
        imposed.any(axis=-1, keepdims=True), imposed, own)
    e = jnp.where(chosen, jnp.exp(r - r.max(axis=-1, keepdims=True)), 0.0)
    short = jnp.where(chosen, kth - r, 0.0).max(axis=-1)
    return (e / e.sum(axis=-1, keepdims=True), own,
            (short / r.std(axis=-1)).max())


def experts(u, params, weights, first_held: int):
    """Every held expert on every token, weighted; one expert after the
    other."""
    held = params["gate"].shape[0]

    def add(y, each):
        gate, up, down, w = each
        hidden = jax.nn.relu(u @ gate) * (u @ up)
        return y + w[:, None] * (hidden @ down), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(u), (
        params["gate"], params["up"], params["down"],
        weights[:, first_held:first_held + held].T))
    return y


def layer(x, blk, imposed, window, rope: bool, arch: dict):
    weights, chosen, short = router_weights(
        x, blk["router"], arch["moe_num_active_primary_experts"], imposed)
    y = x + attention(rmsnorm(x, blk["ln1"]["scale"]), blk, window, rope,
                      float(arch["rope_theta"]))
    out = y + experts(rmsnorm(y, blk["ln2"]["scale"]), blk["experts"],
                      weights, arch["first_held"])
    return out, (chosen, short)


def loss(params, tokens, arch: dict, imposed=None):
    """Mean next-token cross-entropy of one sequence ``tokens`` [s + 1]
    over the rows held; and per layer the router's own chosen-expert
    mask [s, experts] and, where ``imposed`` gives each layer a mask to
    use instead, by how much those picks fall short (`router_weights`).
    ``arch``: ``sliding_window_size``, ``sliding_window_layout``,
    ``rope_layout``, ``rope_theta``, ``moe_num_active_primary_experts``,
    ``first_held``."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    x = params["embed"][tokens[:-1]]
    routing = []
    for i, blk in enumerate(params["blocks"]):
        window = (arch["sliding_window_size"]
                  if arch["sliding_window_layout"][i] else None)
        step = jax.checkpoint(functools.partial(
            layer, window=window, rope=bool(arch["rope_layout"][i]),
            arch=arch))
        x, chosen = step(x, blk, None if imposed is None else imposed[i])
        routing.append(chosen)
    logits = rmsnorm(x, params["ln_f"]["scale"]) @ params["head"]
    logits = logits - logits.max(axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.exp(logits).sum(axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return -picked.mean(), routing
