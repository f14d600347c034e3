"""Plain float32 reference of a Kanana-2 (DeepSeek-V3-shaped) decoder's
training loss.

Written from the model's ``config.json`` (kakaocorp/kanana-2-30b-a3b-
instruct-2601, ``model_type`` ``deepseek_v3``) as the configuration
file's ``assumed`` lists: pre-norm blocks of latent (MLA) causal
attention without query compression and a feed-forward that is a dense
gated MLP in the leading layer(s) and, after them, routed experts chosen
by a sigmoid, bias-corrected router beside shared experts; RMSNorm with a
scale, no bias on any projection, an untied classifier, no auxiliary
loss. For layer ``l`` with input ``x`` [T, d] (``n``: the no-rope
columns of a head, ``r``: its rotary columns, ``L``: the latent's width):

    h = norm_1(x);  q = h W_q, per head [q_n (n) ; q_r (r)]
    [c ; k_r] = h W_kva;  c <- norm_kv(c);  k_r (r) is ONE key for all heads
    [k_n ; v] = c W_kvb, per head k_n (n) and v (n)
    rotary positions on q_r of every head and on k_r (rotate-half pairing)
    score of head a, query i, key j <= i:
        (q_n[i,a] . k_n[j,a] + q_r[i,a] . k_r[j]) / sqrt(n + r)
    y = x + concat_a(softmax(scores) v[:, a]) W_o
    u = norm_2(y)
    l < first_k_dense_replace:  out = y + (silu(u G) * (u U)) D
    else:  s = sigmoid(u W_r);  S = the k largest of s + b
           w_e = scale * s_e / (sum_{j in S} s_j + 1e-20)  for e in S
           E_e(u) = (silu(u G_e) * (u U_e)) D_e;  Sh(u) likewise, unweighted
           out = y + sum_{e in S and held} w_e E_e(u) + Sh(u)

The bias ``b`` enters the choice and nothing else. The experts held are
a share of the router's (``first_held`` and as many as the parameters
carry): what the others would add is left out, as in the program; the
weights stay normalised over all the chosen.

Straightforward ``jax.numpy`` in float32, nothing of the program, no
kernels, no absorbed form: keys and values are expanded from the latent
as the equations say; attention by blocks of queries, so that the scores
of one block ([heads, block, keys]) fit a chip at 8192 positions; every
held expert on every token, times a weight that is zero where the token
did not choose it; dense softmax cross-entropy over the rows held. The
blocks of queries and the experts are loops (``lax.map``, ``lax.scan``),
and each layer, and each block of queries in it, is a
``jax.checkpoint``. On a TPU callers run this under
``jax.default_matmul_precision("highest")``.

The parameter tree is the program's (``models/transformer.py init``):
``embed [rows, d]``, ``head [d, rows]``, ``ln_f.scale`` and per block
``ln1.scale``, ``ln2.scale``, ``wq [d, heads, n + r]``, ``wkva [d, L +
r]``, ``ln_kv.scale [L]``, ``wkvb [L, heads, 2n]``, ``wo [heads, n,
d]``; a dense layer ``mlp.gate/up [d, f]``, ``mlp.down [f, d]``; an
expert layer ``router [d, experts]``, ``router_bias [experts]``,
``experts.gate/up [held, d, f]``, ``experts.down [held, f, d]``,
``shared.gate/up/down``.
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
    """Rotary positions 0..s-1 on [s, heads, r]: the pair (i, i + r/2)
    turns by ``position * theta ** (-2i / r)``."""
    s, _, r = x.shape
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.cos(angle)[:, None, :]
    sin = jnp.sin(angle)[:, None, :]
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(h, blk, arch: dict):
    """[s, d] -> [s, d], one sequence: keys and values expanded from the
    latent, then block by block of QUERY_BLOCK queries against all the
    keys (the mask does the rest)."""
    s = h.shape[0]
    n, latent = arch["qk_nope_head_dim"], arch["kv_lora_rank"]
    theta = float(arch["rope_theta"])
    q = jnp.einsum("sd,dhk->shk", h, blk["wq"])
    q = jnp.concatenate([q[..., :n], rotary(q[..., n:], theta)], axis=-1)
    kva = h @ blk["wkva"]
    c = rmsnorm(kva[:, :latent], blk["ln_kv"]["scale"])
    k_r = rotary(kva[:, None, latent:], theta)           # one key: [s, 1, r]
    kv = jnp.einsum("sc,chk->shk", c, blk["wkvb"])
    k_n, v = kv[..., :n], kv[..., n:]
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r, (s, k_n.shape[1], k_r.shape[-1]))],
        axis=-1)
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def rows(start):
        """Queries ``start .. start + block - 1``."""
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = jnp.einsum("shk,thk->hst", qb, k) / math.sqrt(q.shape[-1])
        i = start + jnp.arange(block)[:, None]
        j = jnp.arange(s)[None, :]
        scores = jnp.where((j <= i)[None], scores, -jnp.inf)
        scores = scores - scores.max(axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        probs = probs / probs.sum(axis=-1, keepdims=True)
        return jnp.einsum("hst,thk->shk", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, s, block))
    return jnp.einsum("shk,hkd->sd", out.reshape(v.shape), blk["wo"])


def gated_mlp(u, weights):
    return (jax.nn.silu(u @ weights["gate"]) * (u @ weights["up"])
            ) @ weights["down"]


def router_weights(u, router, bias, k: int, scale: float, imposed=None):
    """[T, experts] weights: ``scale * s / (sum of s over the chosen +
    1e-20)`` on the ``k`` experts with the largest ``s + bias``, ``s =
    sigmoid(u W_r)``, zero elsewhere; which they are, as a mask; and 0.0.

    ``imposed`` (a mask like the one returned; a token's row all False
    leaves that token to the router) makes the weights this router's own
    sigmoids normalised over the imposed sets instead. That is how a
    comparison tells a tie from a fault (reference/smallthinker.py
    ``router_weights``); the third result is then the largest amount,
    over the tokens, by which an imposed pick's *biased* score lies
    under this router's own k-th largest biased score, in units of the
    spread (standard deviation) of that token's biased scores."""
    s = jax.nn.sigmoid(u @ router)
    biased = s + bias
    kth = jnp.sort(biased, axis=-1)[:, -k][:, None]
    own = biased >= kth
    chosen = own if imposed is None else jnp.where(
        imposed.any(axis=-1, keepdims=True), imposed, own)
    picked = jnp.where(chosen, s, 0.0)
    short = jnp.where(chosen, kth - biased, 0.0).max(axis=-1)
    return (scale * picked / (picked.sum(axis=-1, keepdims=True) + 1e-20),
            own, (short / biased.std(axis=-1)).max())


def experts(u, params, weights, first_held: int):
    """Every held expert on every token, weighted; one expert after the
    other."""
    held = params["gate"].shape[0]

    def add(y, each):
        gate, up, down, w = each
        hidden = jax.nn.silu(u @ gate) * (u @ up)
        return y + w[:, None] * (hidden @ down), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(u), (
        params["gate"], params["up"], params["down"],
        weights[:, first_held:first_held + held].T))
    return y


def layer(x, blk, imposed, arch: dict):
    """One block; an expert layer also returns (the router's own mask,
    the imposed picks' shortfall), a dense one None."""
    y = x + attention(rmsnorm(x, blk["ln1"]["scale"]), blk, arch)
    u = rmsnorm(y, blk["ln2"]["scale"])
    if "experts" not in blk:
        return y + gated_mlp(u, blk["mlp"]), None
    weights, chosen, short = router_weights(
        u, blk["router"], blk["router_bias"], arch["num_experts_per_tok"],
        float(arch["routed_scaling_factor"]), imposed)
    out = (y + experts(u, blk["experts"], weights, arch["first_held"])
           + gated_mlp(u, blk["shared"]))
    return out, (chosen, short)


def loss(params, tokens, arch: dict, imposed=None):
    """Mean next-token cross-entropy of one sequence ``tokens`` [s + 1]
    over the rows held; and per *expert* layer the router's own
    chosen-expert mask [s, experts] and, where ``imposed`` gives each
    expert layer a mask to use instead, by how much those picks fall
    short (`router_weights`). ``arch``: ``qk_nope_head_dim``,
    ``kv_lora_rank``, ``rope_theta``, ``num_experts_per_tok``,
    ``routed_scaling_factor``, ``first_held``."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    x = params["embed"][tokens[:-1]]
    routing = []
    for blk in params["blocks"]:
        step = jax.checkpoint(functools.partial(layer, arch=arch))
        sparse = "experts" in blk
        x, routed = step(
            x, blk, imposed[len(routing)] if sparse and imposed else None)
        if sparse:
            routing.append(routed)
    logits = rmsnorm(x, params["ln_f"]["scale"]) @ params["head"]
    logits = logits - logits.max(axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.exp(logits).sum(axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return -picked.mean(), routing
