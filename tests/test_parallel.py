"""TP/PP/SP/MoE strategy tests on the 8-device virtual mesh — the
greenfield strategies SURVEY.md §2.3 requires beyond the reference's DP."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models.transformer import causal_attention
from horovod_tpu.parallel import (
    column_parallel_dense,
    parallel_mlp,
    pipeline_apply,
    pipeline_loss,
    ring_attention,
    row_parallel_dense,
    ulysses_attention,
)


def mesh1d(name, n=8):
    devs = jax.devices()[:n]
    return Mesh(np.array(devs, dtype=object), (name,))


# --- tensor parallel --------------------------------------------------------

def test_tp_column_row_pair_matches_dense():
    rng = np.random.RandomState(0)
    x = rng.randn(4, 16).astype(np.float32)
    w1 = rng.randn(16, 32).astype(np.float32)
    w2 = rng.randn(32, 16).astype(np.float32)
    expect = np.maximum(x @ w1, 0) @ w2

    mesh = mesh1d("tp")

    def f(x, w1_l, w2_l):
        return parallel_mlp(x, w1_l, w2_l, "tp", act=jax.nn.relu)

    out = jax.shard_map(f, mesh=mesh,
                        in_specs=(P(), P(None, "tp"), P("tp", None)),
                        out_specs=P())(x, w1, w2)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4)


# --- sequence parallel ------------------------------------------------------

def _ref_attention(q, k, v):
    return np.asarray(causal_attention(jnp.asarray(q), jnp.asarray(k),
                                       jnp.asarray(v)))


@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ring_attention_matches_full(sp):
    rng = np.random.RandomState(0)
    b, s, h, hd = 2, 32, 4, 8
    q = rng.randn(b, s, h, hd).astype(np.float32)
    k = rng.randn(b, s, h, hd).astype(np.float32)
    v = rng.randn(b, s, h, hd).astype(np.float32)
    expect = _ref_attention(q, k, v)

    mesh = mesh1d("sp", sp)
    out = jax.shard_map(lambda q, k, v: ring_attention(q, k, v, "sp"),
                        mesh=mesh,
                        in_specs=(P(None, "sp"),) * 3,
                        out_specs=P(None, "sp"))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("sp", [2, 4])
def test_ulysses_attention_matches_full(sp):
    rng = np.random.RandomState(1)
    b, s, h, hd = 2, 16, 8, 4
    q = rng.randn(b, s, h, hd).astype(np.float32)
    k = rng.randn(b, s, h, hd).astype(np.float32)
    v = rng.randn(b, s, h, hd).astype(np.float32)
    expect = _ref_attention(q, k, v)

    mesh = mesh1d("sp", sp)
    out = jax.shard_map(lambda q, k, v: ulysses_attention(q, k, v, "sp"),
                        mesh=mesh,
                        in_specs=(P(None, "sp"),) * 3,
                        out_specs=P(None, "sp"))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-3, atol=2e-3)


def test_ring_attention_grad_finite():
    mesh = mesh1d("sp", 4)
    rng = np.random.RandomState(2)
    q = rng.randn(1, 16, 2, 4).astype(np.float32)

    def loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, "sp") ** 2)

    def f(q):
        g = jax.grad(loss)(q, q, q)
        return jax.lax.pmean(jnp.sum(g * g), "sp")

    out = jax.shard_map(f, mesh=mesh, in_specs=P(None, "sp"), out_specs=P(),
                        check_vma=False)(q)
    assert np.isfinite(float(out))


# --- pipeline parallel ------------------------------------------------------

def test_pipeline_matches_sequential():
    """4 stages, each y = relu(x @ W_i); pipeline output == sequential."""
    n_stages, n_micro, mb, d = 4, 6, 3, 8
    rng = np.random.RandomState(0)
    ws = rng.randn(n_stages, d, d).astype(np.float32) * 0.5
    xs = rng.randn(n_micro, mb, d).astype(np.float32)

    expect = xs.copy()
    for i in range(n_stages):
        expect = np.maximum(expect @ ws[i], 0)

    mesh = mesh1d("pp", n_stages)

    def stage(w, x):
        return jax.nn.relu(x @ w)

    def f(ws, xs):
        out = pipeline_apply(stage, ws[0], xs, axis_name="pp")
        # outputs live on the last stage; bring to all via psum
        return jax.lax.psum(out, "pp")

    out = jax.shard_map(f, mesh=mesh, in_specs=(P("pp"), P()), out_specs=P(),
                        check_vma=False)(ws, xs)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4, atol=1e-5)


def test_pipeline_backward_trains():
    """Gradient flows through the ppermute schedule (functional PP claim)."""
    n_stages, n_micro, mb, d = 4, 4, 2, 4
    rng = np.random.RandomState(1)
    ws = rng.randn(n_stages, d, d).astype(np.float32) * 0.3
    xs = rng.randn(n_micro, mb, d).astype(np.float32)
    tgt = rng.randn(n_micro, mb, d).astype(np.float32)

    mesh = mesh1d("pp", n_stages)

    def stage(w, x):
        return jnp.tanh(x @ w)

    def loss_fn(outputs, targets):
        return jnp.mean((outputs - targets) ** 2)

    def f(ws, xs, tgt):
        def L(w):
            return pipeline_loss(stage, loss_fn, w, xs, tgt, axis_name="pp")

        l0 = L(ws[0])
        g = jax.grad(L)(ws[0])
        w1 = ws[0] - 1.0 * g
        return l0, L(w1)

    l0, l1 = jax.shard_map(f, mesh=mesh, in_specs=(P("pp"), P(), P()),
                           out_specs=(P(), P()), check_vma=False)(ws, xs, tgt)
    assert float(l1) < float(l0), (float(l0), float(l1))


# --- expert parallel --------------------------------------------------------

def _brute_force_experts(x, gate_w, params, k):
    """Every token's k best experts, weights normalised over the k, in
    numpy, one token at a time."""
    logits = x @ gate_w
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        best = np.argsort(logits[t])[-k:]
        w = np.exp(logits[t][best] - logits[t][best].max())
        for e, we in zip(best, w / w.sum()):
            h = np.maximum(x[t] @ params["gate"][e], 0) * (x[t] @ params["up"][e])
            y[t] += we * (h @ params["down"][e])
    return y


def _expert_inputs(tokens, d, f, n_exp, seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(tokens, d).astype(np.float32)
    gate_w = rng.randn(d, n_exp).astype(np.float32)
    params = {"gate": 0.3 * rng.randn(n_exp, d, f).astype(np.float32),
              "up": 0.3 * rng.randn(n_exp, d, f).astype(np.float32),
              "down": 0.3 * rng.randn(n_exp, f, d).astype(np.float32)}
    return x, gate_w, params


@pytest.mark.parametrize("ep,k", [(4, 1), (8, 2)], ids=["top1-ep4", "top2-ep8"])
def test_expert_layer_routes_and_combines(ep, k):
    """route + expert_layer over the 'ep' axis, 8 experts spread over the
    chips: every token reaches its k best experts wherever they live and
    comes back with their outputs weighted (weights normalised over the
    k chosen, so top-1 is the expert's output itself), whatever the
    load: no capacity, no dropped token."""
    from horovod_tpu.parallel.moe import expert_layer, route

    x, gate_w, params = _expert_inputs(ep * 8, 16, 8, 8, seed=ep)

    def f(x, gate_w, params):
        chosen, weights = route(x @ gate_w, k)
        return expert_layer(x, chosen, weights, params, axis_name="ep")

    y = jax.jit(jax.shard_map(
        f, mesh=mesh1d("ep", ep), in_specs=(P("ep"), P(), P("ep")),
        out_specs=P("ep"), check_vma=False))(x, gate_w, params)
    np.testing.assert_allclose(
        np.asarray(y), _brute_force_experts(x, gate_w, params, k),
        rtol=1e-4, atol=1e-4)


def test_hierarchical_mesh_nested_psum_equals_flat():
    """create_hierarchical_mesh numerics (VERDICT weak #7): psum over the
    nested (dcn, ici) axes equals a flat psum over one axis — the
    RS-ICI → AR-DCN → AG-ICI decomposition is value-identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel.mesh import create_hierarchical_mesh, create_mesh

    hier = create_hierarchical_mesh({"dp_ici": 4}, {"dp_dcn": 2})
    assert hier.axis_names == ("dp_dcn", "dp_ici")
    flat = create_mesh({"dp": 8})
    x = jnp.asarray(np.random.RandomState(0).randn(8, 16), jnp.float32)

    def nested(xs):
        return jax.lax.psum(jax.lax.psum(xs, "dp_ici"), "dp_dcn")

    def flat_sum(xs):
        return jax.lax.psum(xs, "dp")

    out_h = jax.jit(jax.shard_map(nested, mesh=hier,
                                  in_specs=P(("dp_dcn", "dp_ici")),
                                  out_specs=P(), check_vma=False))(x)
    out_f = jax.jit(jax.shard_map(flat_sum, mesh=flat, in_specs=P("dp"),
                                  out_specs=P(), check_vma=False))(x)
    # nested vs flat differ only in summation order
    np.testing.assert_allclose(np.asarray(out_h), np.asarray(out_f),
                               rtol=1e-5, atol=1e-6)


def test_fsdp_specs_shard_large_replicate_small():
    from horovod_tpu.parallel import fsdp_specs

    params = {"w": jnp.zeros((256, 128)), "scale": jnp.zeros((128,)),
              "odd": jnp.zeros((130, 3))}
    specs = fsdp_specs(params, axis="dp", min_shard_elems=1024, axis_size=8)
    assert specs["w"] == P("dp", None)          # largest dim 256 % 8 == 0
    assert specs["scale"] == P()                # small -> replicated
    assert specs["odd"] == P()                  # no dim divisible by 8
    # without axis_size constraint the largest dim is taken as-is
    specs2 = fsdp_specs(params, axis="dp", min_shard_elems=64)
    assert specs2["scale"] == P("dp")
    assert specs2["odd"] == P("dp", None)


def test_fsdp_matches_replicated_dp():
    """ZeRO-3 sharding is a memory layout, not a math change: the FSDP
    train step's trajectory equals single-device training on the global
    batch, and params/opt-state actually live sharded."""
    import optax
    from horovod_tpu.parallel import create_mesh, fsdp_train_step

    n = len(jax.devices())
    mesh = create_mesh({"dp": n})
    rng = np.random.RandomState(0)
    params = {"w1": jnp.asarray(rng.randn(32, 64), jnp.float32),
              "b1": jnp.asarray(rng.randn(64), jnp.float32),
              "w2": jnp.asarray(rng.randn(64, 8), jnp.float32)}
    x = jnp.asarray(rng.randn(n * 4, 32), jnp.float32)
    y = jnp.asarray(rng.randn(n * 4, 8), jnp.float32)

    def loss_fn(p, batch):
        xb, yb = batch
        h = jnp.tanh(xb @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - yb) ** 2)

    opt = optax.adam(1e-2)

    # reference: plain single-program training on the full batch
    ref_p, ref_s = params, opt.init(params)
    for _ in range(3):
        g = jax.grad(loss_fn)(ref_p, (x, y))
        u, ref_s = opt.update(g, ref_s, ref_p)
        ref_p = optax.apply_updates(ref_p, u)

    make = fsdp_train_step(loss_fn, opt, mesh, axis="dp",
                           min_shard_elems=64,
                           batch_spec=(P("dp", None), P("dp", None)))
    fp, fs, step = make(params, opt.init(params))
    # the big leaves are genuinely sharded across devices
    assert fp["w1"].sharding.spec == P(None, "dp")  # largest dim = 64
    m_state = fs[0].mu["w1"]
    assert m_state.sharding.spec == P(None, "dp")
    for _ in range(3):
        fp, fs, loss = step(fp, fs, (x, y))
    for k in params:
        np.testing.assert_allclose(np.asarray(fp[k]), np.asarray(ref_p[k]),
                                   rtol=2e-5, atol=2e-6)


def test_fsdp_transformer_step_runs_sharded():
    """FSDP composes with the transformer LM: one jitted step over an
    8-way mesh with every big leaf 1/8 per chip."""
    import optax
    from horovod_tpu.models import transformer as T
    from horovod_tpu.parallel import create_mesh, fsdp_train_step

    n = len(jax.devices())
    mesh = create_mesh({"dp": n})
    cfg = T.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                              n_layers=2, d_ff=64, max_seq=16,
                              dtype=jnp.float32, dp_axis=None, tp_axis=None,
                              sp_axis=None)
    params = T.init(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (n * 2, 16)))

    def loss_fn(p, batch):
        return T.lm_loss(p, batch, cfg, use_constraints=False)

    opt = optax.adam(1e-3)
    make = fsdp_train_step(loss_fn, opt, mesh, axis="dp",
                           min_shard_elems=256, batch_spec=P("dp", None))
    fp, fs, step = make(params, opt.init(params))
    assert fp["embed"].sharding.spec == P("dp", None)
    losses = []
    for _ in range(3):
        fp, fs, loss = step(fp, fs, toks)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("sp", [2, 4, 8])
def test_striped_ring_attention_matches_full(sp):
    """Striped layout (chip i holds tokens i, i+n, ...) with per-round
    inclusive/strict causal masks reproduces dense causal attention
    exactly — while every chip does equal work every round."""
    from horovod_tpu.parallel import (stripe_tokens, striped_ring_attention,
                                      unstripe_tokens)

    rng = np.random.RandomState(3)
    b, s, h, hd = 2, 32, 4, 8
    q = rng.randn(b, s, h, hd).astype(np.float32)
    k = rng.randn(b, s, h, hd).astype(np.float32)
    v = rng.randn(b, s, h, hd).astype(np.float32)
    expect = _ref_attention(q, k, v)

    mesh = mesh1d("sp", sp)
    qs, ks, vs = (stripe_tokens(jnp.asarray(x), sp) for x in (q, k, v))
    out = jax.shard_map(
        lambda q, k, v: striped_ring_attention(q, k, v, "sp"),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"))(qs, ks, vs)
    out = unstripe_tokens(out, sp)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-3, atol=2e-3)


def test_striped_ring_attention_grad_matches_dense():
    """Autodiff through the striped ring (scan + ppermute + switch) agrees
    with the dense-causal oracle's gradients. Differentiated from OUTSIDE
    the shard_map (vma-typed boundary), the natural jit-training path."""
    from horovod_tpu.parallel import (stripe_tokens, striped_ring_attention,
                                      unstripe_tokens)

    sp = 4
    rng = np.random.RandomState(4)
    b, s, h, hd = 1, 16, 2, 4
    q = rng.randn(b, s, h, hd).astype(np.float32)
    co = rng.randn(b, s, h, hd).astype(np.float32)  # fixed cotangent

    def dense_loss(qg):
        return jnp.sum(causal_attention(qg, qg, qg) * jnp.asarray(co))

    expect_grad = np.asarray(jax.grad(dense_loss)(jnp.asarray(q)))

    mesh = mesh1d("sp", sp)
    ring = jax.shard_map(
        lambda q, k, v: striped_ring_attention(q, k, v, "sp"),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"))
    cos = stripe_tokens(jnp.asarray(co), sp)

    def ring_loss(qs):
        return jnp.sum(ring(qs, qs, qs) * cos)

    g = jax.grad(ring_loss)(stripe_tokens(jnp.asarray(q), sp))
    got = np.asarray(unstripe_tokens(g, sp))
    np.testing.assert_allclose(got, expect_grad, rtol=3e-3, atol=3e-3)


def test_pipeline_remat_stage_grads_identical():
    """remat_stage=True changes only memory: gradients through the
    pipelined schedule are identical to the non-remat run."""
    from horovod_tpu.parallel.pp import pipeline_loss

    pp = 4
    mesh = mesh1d("pp", pp)
    d, n_micro, mb = 8, 6, 4
    rng = np.random.RandomState(5)
    # deep stage: several matmuls so remat has intermediates to drop
    params = {
        "w1": jnp.asarray(rng.randn(pp, d, d) * 0.3, jnp.float32),
        "w2": jnp.asarray(rng.randn(pp, d, d) * 0.3, jnp.float32),
        "w3": jnp.asarray(rng.randn(pp, d, d) * 0.3, jnp.float32),
    }
    x = jnp.asarray(rng.randn(n_micro, mb, d), jnp.float32)
    tgt = jnp.asarray(rng.randn(n_micro, mb, d), jnp.float32)

    def stage(p, h):
        h = jnp.tanh(h @ p["w1"][0])
        h = jnp.tanh(h @ p["w2"][0])
        return jnp.tanh(h @ p["w3"][0])

    def make_grad(remat):
        def loss(p, x, tgt):
            return pipeline_loss(
                stage, lambda o, t: jnp.mean((o - t) ** 2), p, x, tgt,
                n_micro=n_micro, remat_stage=remat)

        return jax.shard_map(jax.grad(loss), mesh=mesh,
                             in_specs=(P("pp"), P(), P()),
                             out_specs=P("pp"), check_vma=False)

    g0 = make_grad(False)(params, x, tgt)
    g1 = make_grad(True)(params, x, tgt)
    for k in params:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g0[k]),
                                   rtol=1e-6, atol=1e-7)
