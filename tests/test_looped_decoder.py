"""The looped decoder of ``models/transformer.py`` (``n_loops`` > 1: one
stack of blocks with sandwich norms run several times over its own
output as one ``lax.scan``, the final norm inside the recurrence, an
exit after every pass, the loss an expectation over the exits less an
entropy term) against the plain reference of ``chipbench/reference/
ouro.py``, at a toy size on the CPU; the sharing of the weights; and the
older decoders left as they were."""

import hashlib
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from chipbench.reference import ouro as reference
from horovod_tpu.models import transformer as T
from horovod_tpu.parallel import data_parallel_step, dp
from horovod_tpu.utils import scopes

ARCH = {"total_ut_steps": 4, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
        "exit_beta": 0.05}


def toy(**kw):
    """Two blocks of four heads of 8, run four times."""
    return T.TransformerConfig(**{**dict(
        vocab_size=64, d_model=32, n_heads=4, d_head=8, n_layers=2, d_ff=48,
        max_seq=16, dtype=jnp.float32, positions="layout", rope_layout=(1,),
        rope_theta=1e6, tie_embeddings=False, mlp="gated", n_loops=4,
        sandwich_norms=True, exit_beta=0.05), **kw})


def seeded_params(cfg, key=0):
    """Seeded weights with the gate and every norm's scale away from
    their seeded values, or nothing hangs on where a norm sits and what
    the gate reads (seeded, the scales are 1 and the gate's bias 0)."""
    params = T.init(jax.random.PRNGKey(key), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(key + 100), 64))

    def scale(leaf):
        return leaf * (1 + 0.3 * jax.random.normal(next(keys), leaf.shape))

    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: scale(leaf)
        if "scale" in jax.tree_util.keystr(path) else leaf, params)
    params["gate"] = {"w": 20 * params["gate"]["w"], "b": jnp.float32(0.3)}
    return params


def close(got, want, rtol=1e-4):
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=rtol,
            atol=rtol * float(jnp.abs(w).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_decoder_matches_the_reference(remat):
    """(a) Loss, every exit's mean loss, the exit distribution and every
    gradient (the shared blocks', the final norm's inside the
    recurrence, the gate's, the head's) against the reference."""
    cfg = toy(remat=remat)
    params = seeded_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 17), 0, 64)
    (loss, exits), grads = jax.jit(jax.value_and_grad(
        lambda p: T.lm_loss(p, tokens, cfg, use_constraints=False,
                            return_exits=True), has_aux=True))(params)
    (want, want_exits), want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens[0], ARCH), has_aux=True))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    close(exits, want_exits, 1e-5)
    assert float(exits[1].sum()) == pytest.approx(1.0, abs=1e-6)
    # the gate is away from a half: the exits are not weighted alike
    assert float(exits[1].max()) > 2 * float(exits[1].min())
    assert set(grads) == {"embed", "head", "ln_f", "gate", "blocks"}
    close(grads, want_grads)
    assert float(jnp.abs(grads["gate"]["w"]).max()) > 0
    # `apply` gives the last pass's logits, whose loss is the last exit's
    logits = T.apply(params, tokens[:, :-1], cfg, use_constraints=False)
    last = -jnp.take_along_axis(jax.nn.log_softmax(logits), tokens[:, 1:, None],
                                axis=-1).mean()
    assert float(last) == pytest.approx(float(want_exits[0][-1]), rel=1e-5)


def test_a_shared_leafs_gradient_is_the_sum_over_its_uses():
    """(b) The sharing: in a hand-unrolled model every pass has its own
    copy of every block, of the final norm, the head and the gate; the
    gradient the program gives a shared leaf is the sum of the gradients
    of its four copies."""
    cfg = toy()
    params = seeded_params(cfg, key=3)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (17,), 0, 64)
    shared = {w: params[w] for w in ("blocks", "ln_f", "head", "gate")}

    def unrolled(copies):
        """``copies[t]``: pass ``t``'s own weights."""
        state = params["embed"][tokens[:-1]]
        losses, gates = [], []
        for own in copies:
            for blk in own["blocks"]:
                state = reference.block(state, blk, 1e6, 1e-6)
            state = reference.rmsnorm(state, own["ln_f"]["scale"], 1e-6)
            each, lam = reference.exit_of(state, own["head"], own["gate"],
                                          tokens[1:])
            losses.append(each)
            gates.append(lam)
        p = reference.leaving(gates)
        return jnp.mean(jnp.sum(p * jnp.stack(losses), axis=0)
                        + 0.05 * jnp.sum(p * jnp.log(p), axis=0))

    uses = jax.jit(jax.grad(unrolled))([shared] * 4)
    got = jax.jit(jax.grad(lambda p: T.lm_loss(
        p, tokens[None], cfg, use_constraints=False)))(params)
    close({w: got[w] for w in shared},
          jax.tree.map(lambda *each: sum(each), *uses))
    # and no use is idle: every pass's copy of a block has a gradient of
    # its own, the last pass's gate none (the last exit takes what is left)
    per_pass = [float(jnp.abs(u["blocks"][0]["wq"]).max()) for u in uses]
    assert min(per_pass) > 0 and len(set(per_pass)) == 4
    assert float(jnp.abs(uses[-1]["gate"]["w"]).max()) == 0.0
    assert float(jnp.abs(uses[0]["gate"]["w"]).max()) > 0


#: the three older decoders at toy sizes: sha256 (16 hex digits) of the
#: seeded weights' bytes and of the traced loss-and-gradient program's
#: text, both taken on the parent of PR 35 (commit c01d7b2), the two
#: expert decoders' programs anew in PR 37 (its expert layer). A change to
#: `init`'s keys or to what an older configuration traces shows here; a
#: PR that means to change either takes the pins anew.
OLDER = {
    "dense": (dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                   max_seq=16, remat=True),
              "ad99436c66a6983d", "fe4b1df6ec5684f7"),
    "sparse": (dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
                    d_head=8, n_layers=4, d_ff=0, max_seq=16, remat=True,
                    positions="layout", rope_layout=(0, 1, 1, 1), window=8,
                    window_layout=(0, 1, 1, 1), n_experts=8,
                    experts_per_token=2, d_expert=16, experts_held=(2, 4),
                    tie_embeddings=False),
               "e39f63738102cbe0", "0be0a84cb0a173c3"),
    "latent": (dict(vocab_size=64, d_model=32, n_heads=4, d_head=8,
                    n_layers=3, d_ff=48, max_seq=16, remat=True,
                    positions="layout", tie_embeddings=False, kv_latent=16,
                    d_rope=4, mlp="gated", n_dense_layers=1, n_experts=8,
                    experts_per_token=2, d_expert=16, experts_held=(0, 4),
                    n_shared_experts=1, router_scoring="sigmoid",
                    router_input="normed", routed_scale=2.5,
                    expert_activation="silu"),
               "4a94bc3337cf63cb", "e969273b6e53ed27"),
}


def digest(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


@pytest.mark.parametrize("name", list(OLDER))
def test_an_older_decoder_is_what_it_was(name):
    """(c) A fixed seed gives an older decoder the weights it gave, and
    its loss and gradients are the program they were, instruction for
    instruction (so bit for bit), with the new settings at their
    defaults or spelled out (``n_loops=1``, no sandwich norms)."""
    settings, weights, program = OLDER[name]
    tokens = jnp.zeros((2, 17), jnp.int32)
    for cfg in (T.TransformerConfig(**settings),
                T.TransformerConfig(**settings, n_loops=1,
                                    sandwich_norms=False, exit_beta=0.05)):
        params = T.init(jax.random.PRNGKey(7), cfg)
        assert "gate" not in params and "ln1_post" not in params["blocks"][0]
        assert digest(b"".join(np.asarray(leaf).tobytes()
                               for leaf in jax.tree.leaves(params))) == weights
        traced = str(jax.make_jaxpr(jax.value_and_grad(
            lambda p: T.lm_loss(p, tokens, cfg, use_constraints=False)))(
                params))
        assert digest(re.sub(r"0x[0-9a-f]+", "0x", traced).encode()) \
            == program


def test_the_looped_settings_and_what_they_refuse():
    """The new leaves and their specs; the gate comes from the one key no
    older leaf drew, so the leaves a looped decoder shares with a plain
    one of the same settings are the plain one's; and what no cell needs
    is refused, as the latent heads refuse a window."""
    cfg = toy()
    params = T.init(jax.random.PRNGKey(7), cfg)
    assert set(params["blocks"][0]) == {"ln1", "ln1_post", "ln2", "ln2_post",
                                        "wq", "wk", "wv", "wo", "mlp"}
    assert params["gate"]["w"].shape == (32,) and params["gate"]["b"] == 0
    assert jax.tree.structure(T.param_specs(cfg)) == jax.tree.structure(
        jax.tree.map(lambda _: P(), params))
    plain = T.init(jax.random.PRNGKey(7), toy(n_loops=1,
                                              sandwich_norms=False))
    for w in ("embed", "head"):
        assert (params[w] == plain[w]).all()
    assert (params["blocks"][1]["mlp"]["up"]
            == plain["blocks"][1]["mlp"]["up"]).all()
    tokens = jnp.zeros((1, 9), jnp.int32)
    for settings, call, word in (
            (dict(n_experts=4, experts_per_token=2, d_expert=8), None,
             "experts"),
            (dict(kv_latent=8, d_rope=4), None, "latent"),
            (dict(xent_chunk=32), None, "xent_chunk"),
            ({}, lambda p, c: T.apply(p, tokens, c, use_constraints=False,
                                      attn_fn=lambda q, k, v: q), "attn_fn"),
            ({}, lambda p, c: T.lm_loss(p, tokens, c, use_constraints=False,
                                        return_routing=True), "routing")):
        with pytest.raises(ValueError, match=word):
            bad = toy(**settings)
            (call or (lambda p, c: T.init(jax.random.PRNGKey(0), c)))(
                params, bad)


def test_exit_loss_by_hand():
    """Two tokens, three exits, by the definitions: ``p = (lam_1, (1 -
    lam_1) lam_2, (1 - lam_1)(1 - lam_2))``; the last gate is not read."""
    losses = jnp.array([[1.0, 2.0], [3.0, 1.0], [0.5, 4.0]])
    logits = jnp.array([[0.0, 1.0], [-1.0, 2.0], [9.0, -9.0]])
    lam = jax.nn.sigmoid(logits)
    p = jnp.stack([lam[0], (1 - lam[0]) * lam[1],
                   (1 - lam[0]) * (1 - lam[1])])
    want = jnp.mean(jnp.sum(p * losses, 0) + 0.1 * jnp.sum(p * jnp.log(p), 0))
    loss, (each, share) = T.exit_loss(losses, logits, 0.1)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(each, losses.mean(1), rtol=1e-6)
    np.testing.assert_allclose(share, p.mean(1), rtol=1e-6)
    moved = T.exit_loss(losses, logits.at[2].set(0.0), 0.1)[0]
    assert float(moved) == float(loss)


def test_the_recurrence_on_the_kernels_is_one_scan_that_counts_every_pass(
        monkeypatch):
    """The looped decoder as a chip traces it (the fused kernels, in
    interpret mode here, remat on, through ``data_parallel_step``): loss
    and gradients against the reference; the lowered step holds the
    stack once, inside a loop (one forward kernel entry a block, not one
    a block and pass); and the counters state what a step runs and
    keeps: every pass's calls and residuals."""
    F = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(T, "FUSED_ATTENTION_MIN_SEQ", 256)
    monkeypatch.setattr(F, "BLOCKS", (128,))
    monkeypatch.setattr(T, "_on_tpu", lambda: True)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 64)
    cfg = toy(d_model=64, n_heads=2, d_head=128, n_layers=2, max_seq=256,
              n_loops=3, remat=True)
    params = seeded_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 257), 0, 64)
    step = data_parallel_step(
        lambda p, t: jax.value_and_grad(T.lm_loss)(
            p, t, cfg, use_constraints=False),
        mesh=Mesh(np.array(jax.devices()[:1]), ("hvd",)), batch_argnums=(1,),
        donate_argnums=())
    loss, grads = step(params, tokens)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens[0],
                                 {**ARCH, "total_ut_steps": 3})[0]))(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    close(grads, want_grads, 2e-3)
    text = step.lower(params, tokens).as_text(debug_info=True)
    assert text.count("call @_flash_fwd_lse") == cfg.n_layers
    assert "stablehlo.while" in text
    for part in (scopes.EXIT, scopes.HEAD, scopes.MLP, scopes.ATTENTION):
        assert part in text, part
    counters = dp.step_counters(step)
    assert (counters["loop_steps"], counters["loop_layers"],
            counters["loop_exits"]) == (3, 2, 3)
    assert counters["attention_calls"] == 2 * 3
    assert counters["attention_kernel_calls"] == 6
    assert counters["attention_kept_calls"] == 6
    assert counters["remat_kept_mb"] == pytest.approx(
        6 * T._kept_bytes((1, 256), cfg) / 1e6)
