"""DistributedOptimizer / distributed_grad semantics (reference:
tensorflow DistributedGradientTape + torch _DistributedOptimizer tests,
gradient aggregation with backward_passes_per_step)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.common.context import DEFAULT_AXIS
from horovod_tpu.opt import (
    DistributedOptimizer,
    distributed_grad,
    distributed_value_and_grad,
    fused_tree_allreduce,
)

N = 8


def smap(fn, in_specs, out_specs):
    # check_vma=False: Horovod semantics — gradients stay local, the
    # optimizer layer performs the explicit allreduce (see opt/ docstring).
    return jax.shard_map(fn, mesh=hvd.global_process_set().mesh,
                         in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def test_distributed_grad_averages():
    # loss_i(w) = 0.5 * c_i * w^2  => dL_i/dw = c_i * w ; avg = mean(c) * w
    c = np.arange(1.0, N + 1, dtype=np.float32)
    w = 3.0

    def loss(w, ci):
        return 0.5 * ci[0] * w * w

    g = smap(lambda ci: distributed_grad(loss)(w, ci),
             in_specs=P(DEFAULT_AXIS), out_specs=P())(c)
    np.testing.assert_allclose(np.asarray(g), c.mean() * w, rtol=1e-6)


@pytest.mark.parametrize("fuse", [True, False])
def test_distributed_optimizer_sgd_step(fuse):
    c = np.arange(1.0, N + 1, dtype=np.float32)
    params = {"w": jnp.array([2.0, -1.0]), "b": jnp.array(0.5)}
    opt = DistributedOptimizer(optax.sgd(0.1), fuse_buckets=fuse)

    def step(ci):
        def loss(p):
            return ci[0] * (jnp.sum(p["w"] ** 2) + p["b"] ** 2)

        grads = jax.grad(loss)(params)
        state = opt.init(params)
        updates, _ = opt.update(grads, state, params)
        return optax.apply_updates(params, updates)

    new = smap(step, in_specs=P(DEFAULT_AXIS), out_specs=P())(c)
    cm = c.mean()
    np.testing.assert_allclose(np.asarray(new["w"]),
                               np.array([2.0, -1.0]) - 0.1 * 2 * cm * np.array([2.0, -1.0]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(new["b"]), 0.5 - 0.1 * 2 * cm * 0.5,
                               rtol=1e-5)


def test_backward_passes_per_step_accumulates():
    # 2 micro-steps accumulate then one reduced update fires
    opt = DistributedOptimizer(optax.sgd(1.0), backward_passes_per_step=2)
    params = jnp.array([1.0])

    def run(ci):
        state = opt.init(params)
        g1 = jnp.array([ci[0]])
        u1, state = opt.update(g1, state, params)
        g2 = jnp.array([ci[0] * 3.0])
        u2, state = opt.update(g2, state, params)
        return u1, u2

    c = np.arange(1.0, N + 1, dtype=np.float32)
    u1, u2 = smap(run, in_specs=P(DEFAULT_AXIS), out_specs=(P(), P()))(c)
    np.testing.assert_allclose(np.asarray(u1), 0.0)  # first micro-step: no update
    # second: -lr * mean_i( (c_i + 3 c_i)/2 ) = -2 * mean(c)
    np.testing.assert_allclose(np.asarray(u2), -2.0 * c.mean(), rtol=1e-5)


def test_value_and_grad_pmeans_loss():
    c = np.arange(1.0, N + 1, dtype=np.float32)

    def loss(w, ci):
        return ci[0] * w

    (val, g) = smap(lambda ci: distributed_value_and_grad(loss)(2.0, ci),
                    in_specs=P(DEFAULT_AXIS), out_specs=(P(), P()))(c)
    np.testing.assert_allclose(np.asarray(val), 2.0 * c.mean(), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g), c.mean(), rtol=1e-6)


def test_fused_tree_allreduce_matches_per_leaf():
    tree = {"a": np.random.RandomState(0).randn(3, 4).astype(np.float32),
            "b": np.random.RandomState(1).randn(7).astype(np.float32),
            "c": np.random.RandomState(2).randn(2).astype(np.float64)}
    trees = jax.tree.map(lambda x: np.stack([x * (i + 1) for i in range(N)]), tree)

    def f(a, b, c):
        return fused_tree_allreduce({"a": a[0], "b": b[0], "c": c[0]},
                                    op=hvd.Sum)

    out = smap(f, in_specs=(P(DEFAULT_AXIS),) * 3,
               out_specs=P())(trees["a"], trees["b"], trees["c"])
    scale = sum(range(1, N + 1))
    for k in tree:
        np.testing.assert_allclose(np.asarray(out[k]), tree[k] * scale, rtol=1e-5)


def test_quantized_tree_allreduce_is_its_two_wires_side_by_side():
    """The quantized tree exchange is, bit for bit, `fused_tree_allreduce`
    of its guardrail leaves and `C.quantized_allreduce` of each dtype's
    packed eligible leaves; its residuals are laid out as
    `quant_residual_init` lays them out."""
    from horovod_tpu.ops import collectives as C
    from horovod_tpu.opt import quant_residual_init, quantized_tree_allreduce

    spec = hvd.Compression.int8.quant_spec
    rng = np.random.RandomState(7)
    shapes = {  # eligible: the four kernels of 4096 elements and more
        "a": {"kernel": ((80, 64), np.float32), "bias": ((64,), np.float32)},
        "b": {"kernel": ((70, 60), np.float32)},
        "c": {"kernel": ((96, 48), np.float16)},
        "d": {"kernel": ((4100,), np.float16)},
        "norm": {"scale": ((32,), np.float16)},
        "small": ((10,), np.float32),
        "count": ((5,), np.int32),
    }
    is_leaf = lambda x: isinstance(x, tuple)  # noqa: E731
    tree = jax.tree.map(
        lambda sd: (rng.randn(N, *sd[0]) * 3).astype(sd[1]), shapes,
        is_leaf=is_leaf)
    plain = ("a", "bias"), ("norm", "scale"), ("small",), ("count",)
    eligible = {"float32": [("a", "kernel"), ("b", "kernel")],
                "float16": [("c", "kernel"), ("d", "kernel")]}

    def get(t, path):
        for k in path:
            t = t[k]
        return t

    def both(t):
        t = jax.tree.map(lambda x: x[0], t)
        red, res = quantized_tree_allreduce(t, spec, op=hvd.Sum,
                                            prescale_factor=0.5)
        want = dict(zip(plain, fused_tree_allreduce(
            [get(t, path) for path in plain], op=hvd.Sum,
            prescale_factor=0.5)))
        want_res = {}
        for dt, paths in eligible.items():
            flat, want_res[dt] = C.quantized_allreduce(
                jnp.concatenate([jnp.ravel(get(t, path)) for path in paths]),
                DEFAULT_AXIS, spec, op=hvd.Sum, prescale_factor=0.5)
            off = 0
            for path in paths:
                n = get(t, path).size
                want[path] = flat[off:off + n].reshape(get(t, path).shape)
                off += n
        got = {path: get(red, path) for path in want}
        # residuals are this member's own: leave them on the axis
        return got, want, jax.tree.map(lambda r: r[None], (res, want_res))

    spec_of = jax.tree.map(lambda _: P(DEFAULT_AXIS), tree)
    got, want, (res, want_res) = jax.jit(smap(
        both, in_specs=(spec_of,),
        out_specs=(P(), P(), P(DEFAULT_AXIS))))(tree)
    assert sorted(got) == sorted(plain + tuple(sum(eligible.values(), [])))
    for path in want:
        assert got[path].dtype == want[path].dtype, path
        np.testing.assert_array_equal(np.asarray(got[path]),
                                      np.asarray(want[path]), str(path))
    init = quant_residual_init(jax.tree.map(lambda x: x[0], tree), spec)
    assert sorted(res) == sorted(init) == ["float16", "float32"]
    for dt in init:
        assert res[dt].shape == (N,) + init[dt].shape
        assert res[dt].dtype == init[dt].dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(res[dt]),
                                      np.asarray(want_res[dt]))
    assert float(jnp.abs(res["float32"]).max()) > 0  # the wire does round


def test_broadcast_parameters():
    params = {"w": jnp.arange(4.0), "b": jnp.array(1.5)}
    out = hvd.broadcast_parameters(params, root_rank=0)
    np.testing.assert_allclose(np.asarray(out["w"]), np.arange(4.0))


def test_sharded_update_of_every_leaf_matches_replicated():
    """ZeRO-1 weight-update sharding (arXiv:2004.13336) with every leaf
    sharded (``min_shard_elems=0``): RS -> shard-local Adam -> AG
    produces EXACTLY the replicated Adam trajectory for elementwise
    optimizers, with optimizer state num_shards x smaller."""
    hvd.init()
    mesh = hvd.global_process_set().mesh
    n = hvd.size()
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(13, 5), jnp.float32),  # 65 % 8 != 0
              "b": jnp.asarray(rng.randn(5), jnp.float32)}
    X = jnp.asarray(rng.randn(8 * n, 13), jnp.float32)
    Y = jnp.asarray(rng.randn(8 * n, 5), jnp.float32)

    def local_grads(p, xb, yb):
        def loss(p):
            return jnp.mean((xb @ p["w"] + p["b"] - yb) ** 2)
        g = jax.grad(loss)(p)
        return g

    base = optax.adam(1e-2)

    # replicated reference: allreduced grads + full-state adam
    ref_p = params
    ref_state = base.init(params)

    def ref_step(p, s, x, y):
        g = local_grads(p, x, y)
        g = jax.tree.map(lambda t: jax.lax.pmean(t, DEFAULT_AXIS), g)
        u, s = base.update(g, s, p)
        return optax.apply_updates(p, u), s

    ref = jax.jit(jax.shard_map(
        ref_step, mesh=mesh,
        in_specs=(P(), P(), P(DEFAULT_AXIS), P(DEFAULT_AXIS)),
        out_specs=(P(), P()), check_vma=False))

    # sharded-update path
    z1 = hvd.DistributedOptimizer(base, sharded_update=True, num_shards=n,
                                  min_shard_elems=0)
    z_p = params
    z_state = z1.init(params)
    # ZeRO-1 memory win: state is ONE fused leaf per dtype at shard size
    m_leaves = jax.tree.leaves(z_state[0].mu)
    assert len(m_leaves) == 1  # one f32 fused buffer for b(5)+w(65)=70
    assert m_leaves[0].shape == (-(-70 // n),), m_leaves[0].shape

    def z_step(p, s, x, y):
        g = local_grads(p, x, y)  # LOCAL grads: z1 reduce-scatters itself
        u, s = z1.update(g, s, p)
        return optax.apply_updates(p, u), s

    zf = jax.jit(jax.shard_map(
        z_step, mesh=mesh,
        in_specs=(P(), P(), P(DEFAULT_AXIS), P(DEFAULT_AXIS)),
        out_specs=(P(), P()), check_vma=False))

    for _ in range(5):
        ref_p, ref_state = ref(ref_p, ref_state, X, Y)
        z_p, z_state = zf(z_p, z_state, X, Y)
    np.testing.assert_allclose(np.asarray(z_p["w"]), np.asarray(ref_p["w"]),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(z_p["b"]), np.asarray(ref_p["b"]),
                               rtol=2e-5, atol=2e-6)


def test_sharded_update_of_every_leaf_mixed_precision():
    """bf16 grads under fp32 params: grads cast up to the param dtype
    before the sharded update (master-weight semantics) — must trace and
    step without dtype-key mismatches."""
    hvd.init()
    mesh = hvd.global_process_set().mesh
    n = hvd.size()
    params = {"w": jnp.ones((9,), jnp.float32)}
    opt = hvd.DistributedOptimizer(optax.sgd(0.1), sharded_update=True,
                                   num_shards=n, min_shard_elems=0)
    state = opt.init(params)

    def step(p, s):
        g = {"w": jnp.ones((9,), jnp.bfloat16)}  # local bf16 grads
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s

    f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(P(), P()),
                              out_specs=(P(), P()), check_vma=False))
    p2, _ = f(params, state)
    assert p2["w"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(p2["w"]), 0.9, rtol=1e-6)


# -- an axis of one member: the exchange is the identity --------------------

def _toy_loss(p, x):
    return jnp.mean(jnp.tanh(x @ p["w"] + p["b"]) ** 2) + 0.1 * p["s"] ** 2


def _after(opt, grads_of, batches):
    """The toy parameters after a step of ``opt`` on ``grads_of(params,
    x)`` for each of ``batches``, through `data_parallel_step` on a
    one-device mesh."""
    from jax.sharding import Mesh

    from horovod_tpu.parallel import data_parallel_step

    def step(params, opt_state, x):
        updates, opt_state = opt.update(grads_of(params, x), opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    step = data_parallel_step(
        step, mesh=Mesh(np.array(jax.devices()[:1]), (DEFAULT_AXIS,)),
        donate_argnums=())
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {"w": jax.random.normal(k[0], (4, 3)),
              "b": jax.random.normal(k[1], (3,)),
              "s": jax.random.normal(k[2], ())}
    state = opt.init(params)
    for x in batches:
        params, state = step(params, state, x)
    return params


def _batches(micro):
    return jax.random.normal(jax.random.PRNGKey(1), (2 * micro, 2, 4))


@functools.lru_cache(maxsize=None)
def _plain_optax_after(factor, micro):
    """Two updates of plain optax on ``factor`` times the mean gradient
    of ``micro`` batches each."""
    def grads_of(params, x):
        grads = [jax.grad(_toy_loss)(params, x[i]) for i in range(micro)]
        return jax.tree.map(lambda *g: sum(g) * (factor / micro), *grads)

    return _after(optax.adamw(1e-2), grads_of,
                  _batches(micro).reshape(2, micro, 2, 4))


def _wrapped(**kw):
    """Local gradients into the wrapped optimizer."""
    return (hvd.DistributedOptimizer(optax.adamw(1e-2), **kw),
            jax.grad(_toy_loss))


_INT8 = hvd.Compression.int8.with_options(error_feedback=False)
#: id -> (the optimizer and the gradient function under test, the factor
#: plain optax's gradients take, micro-batches per update)
ONE_MEMBER = {
    "average": (lambda: _wrapped(op=hvd.Average), 1.0, 1),
    "sum": (lambda: _wrapped(op=hvd.Sum), 1.0, 1),
    "min": (lambda: _wrapped(op=hvd.Min), 1.0, 1),
    "adasum": (lambda: _wrapped(op=hvd.Adasum), 1.0, 1),
    "scaled": (lambda: _wrapped(prescale_factor=0.5, postscale_factor=4.0),
               2.0, 1),
    "two_passes": (lambda: _wrapped(backward_passes_per_step=2), 1.0, 2),
    "unfused": (lambda: _wrapped(fuse_buckets=False), 1.0, 1),
    "unfused_scaled": (lambda: _wrapped(fuse_buckets=False, op=hvd.Sum,
                                        prescale_factor=0.5,
                                        postscale_factor=4.0), 2.0, 1),
    "fp16": (lambda: _wrapped(compression=hvd.Compression.fp16), 1.0, 1),
    "int8_stateless": (lambda: _wrapped(compression=_INT8), 1.0, 1),
    "distributed_grad": (
        lambda: (optax.adamw(1e-2),
                 distributed_grad(_toy_loss, compression=hvd.Compression.fp16)),
        1.0, 1),
    "distributed_value_and_grad": (
        lambda: (optax.adamw(1e-2),
                 lambda p, x: distributed_value_and_grad(_toy_loss)(p, x)[1]),
        1.0, 1),
}


@pytest.mark.parametrize("case", ONE_MEMBER)
def test_one_member_axis_hands_the_gradients_straight_to_the_update(case):
    """On a one-device mesh the wrapper's step is plain optax's on the
    (scaled) local gradients, bit for bit: nothing is rounded for a wire
    (fp16, int8) and no factor is lost (the scales, the mean over the
    micro-batches)."""
    make, factor, micro = ONE_MEMBER[case]
    got = _after(*make(), _batches(micro))
    want = _plain_optax_after(factor, micro)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


def test_eager_update_never_asks_for_an_axis(monkeypatch):
    """Concrete gradients take the negotiated path, which has no axis:
    the one-member rule must not look one up outside a trace."""
    def no_axis(name):
        raise AssertionError(f"axis_size({name!r}) asked outside a trace")

    monkeypatch.setattr(jax.lax, "axis_size", no_axis)
    opt = DistributedOptimizer(optax.sgd(0.1))
    params = {"w": jnp.array([2.0, -1.0]), "b": jnp.array(0.5)}
    updates, _ = opt.update(params, opt.init(params), params)
    np.testing.assert_allclose(np.asarray(updates["w"]), [-0.2, 0.1],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(updates["b"]), -0.05, rtol=1e-6)
