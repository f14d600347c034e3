"""Plain float32 reference of an Ouro (looped) decoder's training loss.

Written from the model's ``config.json`` (ByteDance/Ouro-2.6B,
``model_type`` ``ouro``) and the first-stage objective of "Scaling
Latent Reasoning via Looped Language Models" as the configuration file's
``assumed`` lists. With ``R = total_ut_steps`` passes over ``N`` blocks
whose weights every pass shares, and every norm ``RMS(x; g) = x /
sqrt(mean x^2 + eps) * g``:

    block l:   h = RMS(x; g1);  q, k, v = h W_q, h W_k, h W_v   (no biases)
               rotary turn of q and k over the whole head (rotate-half
               pairing, theta = rope_theta)
               a = softmax_causal(q k^T / sqrt(head)) v W_o
               x' = x + RMS(a; g2)                       (sandwich norm)
               m = (silu(RMS(x'; g3) W_gate) * RMS(x'; g3) W_up) W_down
               x'' = x' + RMS(m; g4)                     (sandwich norm)
    pass t:    u = s^(t-1);  for l = 1..N: u = block_l(u);  s^t = RMS(u; g_f)
               (s^0 the tokens' embeddings; the *normed* state starts the
               next pass)
    exit t:    z = s^t W_head;  L^t_i = -log softmax(z_i)[target_i]
               lam^t_i = sigmoid(s^t_i . w_g + b_g)
    leaving:   p^t_i = lam^t_i prod_{j<t}(1 - lam^j_i)  for t < R
               p^R_i = prod_{j<R}(1 - lam^j_i)
    loss:      mean_i [ sum_t p^t_i L^t_i - beta H(p_i) ],
               H(p) = -sum_t p^t log p^t

Straightforward ``jax.numpy`` in float32, nothing of the program: **the
passes are a Python loop** (the program's recurrence is a ``lax.scan``
with the weights closed over; here a pass is the same Python function
called again on the same arrays, and a shared weight's gradient is the
sum autodiff makes of its uses), no kernels, the exit distribution by
the products above, dense logits over the whole vocabulary, one exit at
a time. Attention goes by blocks of queries (``lax.map``) so that one
block's scores ([heads, block, keys]) fit a chip at 4096 positions.
**Within a pass the layers are a ``lax.scan`` over the stacked blocks**
(the program's are a Python loop): written out 24 times, the
applications compile for the v5e to 926 MB of code in 97 s, and to an
entry of the persistent compile cache of 195 MB, which alone fills the
chip machine's 192 MiB and evicts the cell's every other program
(PERF.md, PR 35). Each pass, each application of a layer, each block of
queries in it and each exit is a plain ``jax.checkpoint`` (no policy), so
that one sequence at the published widths fits beside the program. On a
TPU callers run this under ``jax.default_matmul_precision("highest")``.

The parameter tree is the program's (``models/transformer.py init``):
``embed [rows, d]``, ``head [d, rows]``, ``ln_f.scale``, ``gate.w [d]``,
``gate.b []`` and per block ``ln1.scale`` (g1), ``ln1_post.scale`` (g2),
``ln2.scale`` (g3), ``ln2_post.scale`` (g4), ``wq/wk/wv [d, heads,
head]``, ``wo [heads, head, d]``, ``mlp.gate/up [d, f]``, ``mlp.down [f,
d]``.
"""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def rmsnorm(x, scale, eps: float):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * scale


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


def attention(h, blk, theta: float):
    """[s, d] -> [s, d], one sequence: block by block of QUERY_BLOCK
    queries against all the keys (the mask does the rest)."""
    s = h.shape[0]
    q = rotary(jnp.einsum("sd,dhk->shk", h, blk["wq"]), theta)
    k = rotary(jnp.einsum("sd,dhk->shk", h, blk["wk"]), theta)
    v = jnp.einsum("sd,dhk->shk", h, blk["wv"])
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


def block(x, blk, theta: float, eps: float):
    """One application of one block, both sub-layers with their sandwich
    norms."""
    a = attention(rmsnorm(x, blk["ln1"]["scale"], eps), blk, theta)
    x = x + rmsnorm(a, blk["ln1_post"]["scale"], eps)
    m = gated_mlp(rmsnorm(x, blk["ln2"]["scale"], eps), blk["mlp"])
    return x + rmsnorm(m, blk["ln2_post"]["scale"], eps)


def exit_of(s, head, gate, targets):
    """One exit on a pass's normed state [s, d]: each token's loss over
    the whole vocabulary and its gate ``lam``."""
    logits = s @ head
    logits = logits - logits.max(axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.exp(logits).sum(axis=-1, keepdims=True))
    losses = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return losses, jax.nn.sigmoid(s @ gate["w"] + gate["b"])


def leaving(gates):
    """The exit distribution [R, tokens] from the gates of the passes (a
    list of R arrays [tokens]; the last pass's gate is not read)."""
    left, p = jnp.ones_like(gates[0]), []
    for lam in gates[:-1]:
        p.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(p + [left])


def loss(params, tokens, arch: dict):
    """The training loss of one sequence ``tokens`` [s + 1]; and (each
    exit's mean loss [R], the mean exit distribution [R]). ``arch``:
    ``total_ut_steps``, ``rope_theta``, ``rms_norm_eps``,
    ``exit_beta``."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    theta, eps = float(arch["rope_theta"]), float(arch["rms_norm_eps"])
    state = params["embed"][tokens[:-1]]
    losses, gates = [], []
    stack = jax.tree.map(lambda *each: jnp.stack(each), *params["blocks"])

    @jax.checkpoint
    def one(x, blk):
        return block(x, blk, theta, eps), None

    @jax.checkpoint
    def a_pass(state, stack, ln_f, head, gate):
        """The stack, the final norm, the exit: the normed state and the
        exit's token losses and gates."""
        state, _ = jax.lax.scan(one, state, stack)
        state = rmsnorm(state, ln_f["scale"], eps)
        return (state, *jax.checkpoint(exit_of)(state, head, gate,
                                                tokens[1:]))

    for _ in range(arch["total_ut_steps"]):  # the same weights in every pass
        state, each, lam = a_pass(state, stack, params["ln_f"],
                                  params["head"], params["gate"])
        losses.append(each)
        gates.append(lam)
    losses, p = jnp.stack(losses), leaving(gates)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    total = jnp.mean(jnp.sum(p * losses, axis=0)
                     - arch["exit_beta"] * entropy)
    return total, (losses.mean(axis=1), p.mean(axis=1))


def loss_and_grads(params, tokens, arch: dict, leaves):
    """`loss`'s three results and the loss's gradients in the leaves at
    the dotted paths ``leaves`` (``chipbench.cell.pick``'s)."""
    from chipbench.cell import pick

    (total, exits), grads = jax.value_and_grad(loss, has_aux=True)(
        params, tokens, arch)
    return total, exits, [pick(grads, p) for p in leaves]
