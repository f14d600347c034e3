"""Plain float32 reference of a GPT-2-style decoder's training loss.

Written from the GPT-2 description (Radford et al. 2019; learned
position embedding, pre-norm blocks of causal multi-head attention and a
4x GELU feed-forward, final norm, classifier tied to the token
embedding), with the departures of horovod_tpu's ``models/transformer.py``
block that the configuration file lists: RMSNorm with a scale and no
bias for LayerNorm, no linear biases, tanh-approximated GELU.

Straightforward ``jax.numpy`` in float32: no rematerialisation, no
chunking, no kernels, dense softmax cross-entropy over the whole
vocabulary. On a TPU a float32 matmul runs as bfloat16 passes unless
told otherwise, so callers run this under
``jax.default_matmul_precision("highest")``.

The parameter tree is the program's (``models/transformer.py init``):
``embed [V, d]``, ``pos [n_positions, d]``, ``ln_f.scale [d]`` and per
block ``ln1.scale``, ``ln2.scale``, ``wq/wk/wv [d, h, k]``,
``wo [h, k, d]``, ``w1 [d, f]``, ``w2 [f, d]``.
"""

import math

import jax.numpy as jnp

NORM_EPS = 1e-6


def rmsnorm(x, scale):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + NORM_EPS)) * scale


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(x, blk):
    s = x.shape[1]
    q = jnp.einsum("bsd,dhk->bhsk", x, blk["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", x, blk["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, blk["wv"])
    scores = jnp.einsum("bhsk,bhtk->bhst", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    scores = scores - scores.max(axis=-1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhst,bhtk->bhsk", probs, v)
    return jnp.einsum("bhsk,hkd->bsd", out, blk["wo"])


def hidden(params, tokens):
    """Final-norm hidden states [b, s, d] for token ids [b, s]."""
    x = params["embed"][tokens] + params["pos"][: tokens.shape[1]][None]
    for blk in params["blocks"]:
        x = x + attention(rmsnorm(x, blk["ln1"]["scale"]), blk)
        h = rmsnorm(x, blk["ln2"]["scale"])
        x = x + gelu_tanh(h @ blk["w1"]) @ blk["w2"]
    return rmsnorm(x, params["ln_f"]["scale"])


def loss(params, tokens):
    """Mean next-token cross-entropy: positions ``tokens[:, :-1]``
    predict ``tokens[:, 1:]``."""
    params = _as_f32(params)
    logits = hidden(params, tokens[:, :-1]) @ params["embed"].T
    logits = logits - logits.max(axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.exp(logits).sum(axis=-1, keepdims=True))
    targets = tokens[:, 1:]
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -picked.mean()


def _as_f32(tree):
    import jax

    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)
