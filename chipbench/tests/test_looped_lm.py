"""The ``looped_lm`` family's yardsticks: the operation count of
flops_looped.py pinned at the cell's sizes, the four readers on a
hand-made trace, and the check's tolerances against the faults they are
written to catch (toy sizes, CPU)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops_looped, run, step_split
from chipbench.cell import pick, rel_l2

TOY = run.os.path.join(run.HERE, "tests", "toy")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "ouro-2.6b.dp1"


def test_operation_counts_at_the_cell():
    _, config, workload = run.load_cell(BENCH, CELL)
    sizes, s = config["sizes"], workload["sequence"]
    assert (sizes["n_layer"], sizes["total_ut_steps"], s,
            workload["per_chip_batch"]) == (6, 4, 4096, 1)
    d = 2048
    # one application of one block, by hand: the four projections, the
    # gated MLP's three matmuls, what the causal mask leaves
    assert 8 * d * d == 33_554_432
    assert 6 * d * 5632 == 69_206_016
    assert 4 * d * (s + 1) // 2 == 16_781_312
    assert flops_looped.block_fwd_flops_per_token(sizes, s) == 119_541_760
    # one exit: the classifier over every row, and the gate
    assert flops_looped.exit_fwd_flops_per_token(sizes) \
        == 2 * d * 49152 + 2 * d == 201_330_688
    # as run: 24 applications and four exits a token
    fwd = flops_looped.fwd_flops_per_token(sizes, s)
    assert fwd == 24 * 119_541_760 + 4 * 201_330_688 == 3_674_324_992
    assert 3 * fwd == 11_022_974_976
    assert 3 * fwd * s == pytest.approx(45.15e12, rel=1e-3)   # a step
    # ISSUE 35's first size, and the whole model: every application counts
    assert flops_looped.fwd_flops_per_token({**sizes, "n_layer": 8}, s) \
        == 4_630_659_072
    whole = flops_looped.fwd_flops_per_token({**sizes, "n_layer": 48}, s)
    assert 4 * 201_330_688 / whole == pytest.approx(0.034, abs=5e-4)
    assert 4 * 201_330_688 / fwd == pytest.approx(0.219, abs=5e-4)
    # the parameters the configuration's ``deployment`` reckons
    a_layer = 4 * d * d + 3 * d * 5632 + 4 * d
    assert a_layer == 51_388_416
    assert 6 * a_layer + 2 * 49152 * d + d + d + 1 == 509_661_185


FWD = ("%hvd_flash_fwd.7 = (bf16[1,4096,2048]{2,1,0}, f32[16,1,4096]{2,1,0}) "
       "custom-call(bf16[1,4096,2048]{2,1,0} %q), "
       "custom_call_target=\"tpu_custom_call\"")
PRE = "jit(hvd_data_parallel_step)/shard_map/hvd.step/"
BODY = "transpose(jvp(hvd.step))/while/body/closed_call/checkpoint/"
TABLE = {
    "fusion.1": PRE + "jvp(hvd.step)/jvp(while)/body/closed_call/checkpoint/"
                      "hvd.model/exit/dot_general",
    "fusion.2": PRE + BODY + "hvd.model/head/dot_general",
    "fusion.3": PRE + BODY + "rematted_computation/hvd.model/mlp/dot_general",
    "fusion.4": PRE + "transpose(jvp(hvd.model/exit))/mul",
    "fusion.5": PRE + "jvp(hvd.step)/jvp(while)/body/hvd.model/head/mul",
    "hvd_flash_fwd.7": PRE + "jvp(hvd.step)/jvp(while)/body/closed_call/"
                             "checkpoint/hvd.model/attention/pallas_call",
    "while.8": "hvd.spans_its_body",
}
SECONDS = {"fusion.1": 0.002, "fusion.2": 0.030, "fusion.3": 0.040,
           "fusion.4": 0.004, "fusion.5": 0.010}


def made_up(forward_events=48):
    """Two traced steps on one device: 24 applications a step."""
    instructions = {f"%{name} = f32[8]{{0}} fusion(%x)":
                    {"count": 8, "seconds": s} for name, s in SECONDS.items()}
    instructions[FWD] = {"count": forward_events, "seconds": 0.050}
    instructions["%while.8 = (f32[8]{0}) while(%t)"] = {
        "count": 4, "seconds": 0.130}     # spans its body: left out
    return {"devices": [{"steps": 2, "instructions": instructions}]}


def test_the_four_readers_on_a_made_up_trace(monkeypatch):
    from horovod_tpu.utils import scopes

    class Program:
        scope_table = staticmethod(lambda: TABLE)
        step_counters = staticmethod(lambda: {})

    step_split.table.cache_clear()
    monkeypatch.setattr(step_split, "program", lambda: (scopes, Program))

    def read(name, trace):
        return run.load_module("layer_metrics", name).read(trace, {}, {})

    try:
        trace = made_up()
        assert read("exit_ms", trace) == pytest.approx(3.0)    # ms a step
        assert read("head_ms", trace) == pytest.approx(20.0)
        assert read("mlp_ms", trace) == pytest.approx(20.0)
        assert read("block_applications", trace) == 24.0
        # a second forward kernel a block, were the residuals not kept
        assert read("block_applications", made_up(96)) == 48.0
    finally:
        step_split.table.cache_clear()
    # without the kernel (the einsum path, another program): nothing
    plain = {"devices": [{"steps": 2, "instructions": {
        "%fusion.1 = f32[8]{0} fusion(%x)": {"count": 2, "seconds": 1.0}}}]}
    assert read("block_applications", plain) is None
    # without a device in the trace, or without the program's table: nothing
    monkeypatch.setattr(step_split, "program", lambda: None)
    for name in ("exit_ms", "head_ms", "mlp_ms", "block_applications"):
        assert read(name, {"devices": []}) is None
    step_split.table.cache_clear()
    assert read("exit_ms", made_up()) is None


@pytest.fixture(scope="module")
def toy():
    """The family at the toy sizes, float32 reference gradients."""
    family = run.load_module("families", "looped_lm")
    _, config, _ = run.load_cell(BENCH, CELL, TOY)
    # the cell's compute type (the toy override rehearses in float32: 32
    # predictions do not average bfloat16's roundings out of an exit's loss)
    cfg = dataclasses.replace(family.make_cfg(config), dtype=jnp.bfloat16)
    opt, _ = family.build_optimizer(config["optimizer"])
    params = jax.jit(lambda k: family.init_state(cfg, opt, k)[0])(
        jax.random.PRNGKey(5))
    # the norms' scales and the gate away from their seeded values, or
    # nothing hangs on where a norm sits and what the exits weigh
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf * (1 + 0.3 * jax.random.normal(
            next(keys), leaf.shape))
        if "scale" in jax.tree_util.keystr(path) else leaf, params)
    params["gate"] = {"w": 5 * params["gate"]["w"], "b": jnp.float32(0.3)}
    tokens = jax.random.randint(jax.random.PRNGKey(7), (65,), 0,
                                cfg.vocab_size)
    return family, cfg, family.arch_of(config), params, tokens


def errors(family, cfg, arch, params, tokens):
    (loss, exits), grads = jax.jit(jax.value_and_grad(
        lambda p: family.T.lm_loss(p, tokens[None], cfg,
                                   use_constraints=False, return_exits=True),
        has_aux=True))(params)
    want, want_exits, want_grads = jax.jit(
        lambda p: family.reference.loss_and_grads(
            p, tokens, arch, family.CHECK_LEAVES))(params)
    if exits[0].shape != want_exits[0].shape:  # not even as many exits
        return (float("inf"),) * 4
    errs = {p: float(rel_l2(pick(grads, p), w))
            for p, w in zip(family.CHECK_LEAVES, want_grads)}
    losses = max(abs(float(loss) - float(want)) / float(want),
                 float(jnp.max(jnp.abs(exits[0] - want_exits[0])
                               / want_exits[0])))
    gate = [(pick(grads, p).ravel(), w.ravel())
            for p, w in zip(family.CHECK_LEAVES, want_grads) if "gate" in p]
    return (losses, float(jnp.max(jnp.abs(exits[1] - want_exits[1]))),
            max(e for p, e in errs.items() if "gate" not in p),
            float(rel_l2(*(jnp.concatenate(each) for each in zip(*gate)))))


def fails(family, losses, shares, grads, gate):
    return (losses > family.LOSS_RTOL or shares > family.EXIT_SHARE_ATOL
            or grads > family.GRAD_RTOL or gate > family.GATE_GRAD_RTOL)


def test_the_tolerances_pass_the_program(toy):
    family, *rest = toy
    assert not fails(family, *errors(family, *rest)), errors(family, *rest)


FAULTS = ("8-bit float", "a pass left out", "sandwich norms left out",
          "entropy term dropped", "entropy of the wrong sign",
          "exits weighted alike", "the last exit's share from its own gate",
          "rotary on half the columns", "the gate's bias left out")


@pytest.mark.parametrize("fault", FAULTS)
def test_the_tolerances_catch_the_fault(toy, fault, monkeypatch):
    """At the toy sizes each fault, made in the program, misses at least
    one stated tolerance."""
    family, cfg, arch, params, tokens = toy
    T = family.T
    if fault == "8-bit float":  # the nearest precision below bfloat16
        cfg = dataclasses.replace(cfg, dtype=jnp.float8_e4m3fn)
    elif fault == "a pass left out":
        cfg = dataclasses.replace(cfg, n_loops=cfg.n_loops - 1)
    elif fault == "sandwich norms left out":
        monkeypatch.setattr(T, "_normed_if", lambda blk, name, out: out)
    elif fault == "entropy term dropped":
        cfg = dataclasses.replace(cfg, exit_beta=0.0)
    elif fault == "entropy of the wrong sign":
        cfg = dataclasses.replace(cfg, exit_beta=-cfg.exit_beta)
    elif fault == "exits weighted alike":
        sound = T.exit_loss
        monkeypatch.setattr(T, "exit_loss", lambda losses, logits, beta: sound(
            losses, jnp.stack([jnp.log(1.0 / (len(logits) - t - 1 + 1e-9))
                               * jnp.ones_like(logits[0])
                               for t in range(len(logits))]), beta))
    elif fault == "the last exit's share from its own gate":
        def own(losses, logits, beta):
            lam = jax.nn.sigmoid(logits)
            left = jnp.cumprod(jnp.concatenate(
                [jnp.ones_like(lam[:1]), 1 - lam[:-1]]), axis=0)
            p = lam * left
            per_token = jnp.sum(p * losses, 0) + beta * jnp.sum(
                p * jnp.log(p), 0)
            tokens_of = tuple(range(1, losses.ndim))
            return jnp.mean(per_token), (losses.mean(tokens_of),
                                         p.mean(tokens_of))

        monkeypatch.setattr(T, "exit_loss", own)
    elif fault == "rotary on half the columns":
        tables = T._rope_tables
        monkeypatch.setattr(T, "_rope_tables", lambda pos, hd, theta: tuple(
            jnp.where(jnp.arange(hd) % (hd // 2) >= hd // 4, t,
                      1.0 if i == 0 else 0.0)
            for i, t in enumerate(tables(pos, hd, theta))))
    elif fault == "the gate's bias left out":
        # the program handed a gate without its bias, the reference with
        _, (_, shares) = jax.jit(lambda p: T.lm_loss(
            p, tokens[None], cfg, use_constraints=False,
            return_exits=True))(
                {**params, "gate": {**params["gate"], "b": jnp.float32(0)}})
        _, (_, ref_shares) = jax.jit(lambda p: family.reference.loss(
            p, tokens, arch))(params)
        assert float(jnp.max(jnp.abs(shares - ref_shares))) \
            > family.EXIT_SHARE_ATOL
        return
    bad = errors(family, cfg, arch, params, tokens)
    assert fails(family, *bad), (fault, bad)


def test_the_toy_cell_checks_and_sees_a_leaf_left_alone():
    """The toy cell's own check passes; a step that leaves the head as it
    was seeded is seen by the whole-tree reading, though the head is not
    among UPDATE_LEAVES."""
    from jax.sharding import Mesh

    family = run.load_module("families", "looped_lm")
    _, config, workload = run.load_cell(BENCH, CELL, TOY)
    mesh = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    cell = family.build(config, workload, chips=1, seed=7, mesh=mesh)
    seeded = jax.tree.map(jnp.copy, cell.state)  # the step donates its own
    step = cell.step
    sound = cell.check(cell)
    assert sound["ok"], sound
    assert sound["leaves"] == len(jax.tree.leaves(seeded))
    assert sound["leaf_step_over_lr"][0] > 2 * family.EVERY_LEAF_STEP_MIN
    assert len(sound["exit_losses"]) == len(sound["exit_shares"]) == 4
    assert sum(sound["exit_shares"]) == pytest.approx(1.0, abs=1e-5)

    def head_left_alone(*args):
        new, opt_state, loss = step(*args)
        return {**new, "head": seeded["head"]}, opt_state, loss

    cell.step = head_left_alone
    seen = cell.check(cell)
    assert not seen["ok"] and seen["stillest_leaf"] == "head"
    assert seen["leaf_step_over_lr"][0] == 0.0
