"""The ``moe_lm`` family's yardsticks: the operation counts of
flops_moe.py at the cell's sizes, the tiles the fused kernels compute,
the trace readers on hand-made instructions, and the check's tolerances
against the faults they are written to catch (toy sizes, CPU)."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops_moe, moe_reads, run
from chipbench.cell import pick, rel_l2

TOY = run.os.path.join(run.HERE, "tests", "toy")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "smallthinker-21b-a3b.dp1"


def test_operation_counts_at_the_cell_and_at_the_first_cut():
    _, config, workload = run.load_cell(BENCH, CELL)
    sizes, s = config["sizes"], workload["sequence"]
    assert (sizes["experts_held"], sizes["embedding_rows"], s) == (
        8, 18992, 8192)
    # as run: 492.6 M forward, 1.478 G training operations a token
    assert flops_moe.fwd_flops_per_token(sizes, s) == 492_570_112
    assert 3 * flops_moe.fwd_flops_per_token(sizes, s) == 1_477_710_336
    # ISSUE 28's first cut (16 experts, 37,984 rows), which did not fit:
    # 625.2 M forward, 1.876 G training
    first = {**sizes, "experts_held": 16, "embedding_rows": 37984}
    assert flops_moe.fwd_flops_per_token(first, s) == 625_198_592
    assert 3 * flops_moe.fwd_flops_per_token(first, s) == 1_875_595_776
    # by hand, one global and one windowed layer's attention
    assert flops_moe.mean_visible_keys(8192) == 4096.5
    assert flops_moe.mean_visible_keys(8192, 4096) == 3072.25
    assert flops_moe.mean_visible_keys(2048, 4096) == 1024.5


def test_tiles_computed():
    assert flops_moe.flash_tiles(8192, 1024, 1024) == 36
    assert flops_moe.flash_tiles(8192, 1024, 1024, 4096) == 30
    assert flops_moe.flash_tiles(2048, 1024, 1024, 4096) == 3
    # a window that ends inside a tile still computes that tile
    assert flops_moe.flash_tiles(512, 128, 128, 200) == 4 + 3 + 2 + 1 - 1
    assert flops_moe.flash_kernel_flops(
        "hvd_flash_bwd_dkv", 2, 28, 8192, 128, 1024, 1024, 4096) == (
        4 * 2 * 1024 * 1024 * 128 * 30 * 2 * 28)


def test_readers_find_the_kernels_and_nothing_else():
    fwd = ("%hvd_flash_fwd_w4096.3 = (bf16[2,8192,3584]{2,1,0}, "
           "f32[56,1,8192]{2,1,0}, f32[56,1,8192]{2,1,0}) custom-call("
           "bf16[2,8192,3584]{2,1,0} %a, bf16[2,8192,512]{2,1,0} %b, "
           "bf16[2,8192,512]{2,1,0} %c), custom_call_target=\"tpu_custom_call\"")
    grouped = ("%ragged-dot-none.7 = bf16[98304,768]{1,0} custom-call("
               "s32[1]{0} %g, s32[9]{0} %h, bf16[98304,2560]{1,0} %x, "
               "bf16[8,2560,768]{2,1,0} %w), custom_call_target=\"x\"")
    device = {"instructions": {
        fwd: {"count": 12, "seconds": 0.06},
        grouped: {"count": 4, "seconds": 0.004},
        "%ragged-dot-metadata.1 = (s32[9]) custom-call(s32[8] %gs)":
            {"count": 4, "seconds": 1e-5},
        "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8] %p), kind=kLoop":
            {"count": 1, "seconds": 1.0}}, "steps": 2}
    (k,) = moe_reads.flash_kernels(device)
    assert (k["kernel"], k["window"], k["count"]) == ("hvd_flash_fwd", 4096,
                                                      12)
    assert k["flops"] == flops_moe.flash_kernel_flops(
        "hvd_flash_fwd", 2, 28, 8192, 128, 1024, 1024, 4096)
    (m,) = moe_reads.grouped_matmuls(device)
    assert (m["buffer_rows"], m["contract"], m["out"], m["groups"],
            m["itemsize"]) == (98304, 2560, 768, 8, 2)
    counters = {"moe_buffer_rows": 98304, "experts_per_token": 6,
                "experts_held": 8, "experts_total": 64}
    assert moe_reads.expected_rows(counters) == 12288
    assert moe_reads.expected_rows({}) is None
    seconds, bound = flops_moe.grouped_matmul_seconds(
        12288, 2560, 768, 8, 2, 197e12, 819e9)
    assert bound == "compute" and seconds == pytest.approx(
        2 * 12288 * 2560 * 768 / 197e12)
    # a dense cell's trace: nothing to read
    plain = {"instructions": {"%fusion.2 = f32[4]{0} fusion(f32[4] %p)":
                              {"count": 1, "seconds": 1.0}}, "steps": 1}
    assert moe_reads.flash_kernels(plain) == []
    assert moe_reads.grouped_matmuls(plain) == []
    for name in ("window_attention_ms", "attention_kernel_roofline_pct",
                 "moe_matmul_roofline_pct"):
        read = run.load_module("layer_metrics", name).read
        assert read({"devices": []}, {}, {"peak_flops_per_s": 1.0}) is None
        if name != "moe_matmul_roofline_pct":
            assert read({"devices": [plain]}, {},
                        {"peak_flops_per_s": 1.0}) is None


@pytest.fixture(scope="module")
def toy():
    """The family at the toy sizes, float32 reference gradients."""
    family = run.load_module("families", "moe_lm")
    _, config, _ = run.load_cell(BENCH, CELL, TOY)
    cfg = family.make_cfg(config)
    params = jax.jit(lambda k: family.T.init(k, cfg))(jax.random.PRNGKey(5))
    # routers and attention away from their 0.02 spread, or nothing hangs
    # on which keys a query sees and which experts a token takes
    params["blocks"] = [{**b, "router": 10 * b["router"],
                         "wq": 4 * b["wq"], "wk": 4 * b["wk"]}
                        for b in params["blocks"]]
    tokens = jax.random.randint(jax.random.PRNGKey(6), (33,), 0,
                                cfg.vocab_size)
    return family, cfg, family.arch_of(config), params, tokens


def errors(family, cfg, arch, params, tokens, reference=None):
    reference = reference or family.reference
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: family.T.lm_loss(p, tokens[None], cfg,
                                   use_constraints=False,
                                   return_routing=True), has_aux=True))(params)
    (want, _), want_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens, arch), has_aux=True))(params)
    errs = {p: float(rel_l2(pick(grads, p), pick(want_grads, p)))
            for p in family.CHECK_LEAVES}
    routers = max(e for p, e in errs.items() if "router" in p)
    gates = max(e for p, e in errs.items() if "gate" in p)
    others = max(e for p, e in errs.items()
                 if "router" not in p and "gate" not in p)
    return (abs(float(loss) - float(want)) / float(want), max(
        others, gates * family.GRAD_RTOL / family.GATE_GRAD_RTOL), routers)


def test_the_tolerances_pass_the_program_and_catch_the_faults(toy,
                                                               monkeypatch):
    """At the toy sizes: the program as it is passes every tolerance;
    with a window one 'tile' too long, with the key/value heads
    mis-grouped, with the weights normalised over the held experts
    instead of the chosen, and computed in an 8-bit float (the nearest
    precision below the bfloat16 the configuration states), at least
    one tolerance fails, by a wide margin."""
    family, cfg, arch, params, tokens = toy
    loss, grads, routers = errors(family, cfg, arch, params, tokens)
    assert loss <= family.LOSS_RTOL and grads <= family.GRAD_RTOL
    assert routers <= family.ROUTER_GRAD_RTOL

    def fails(loss, grads, routers):
        return (loss > family.LOSS_RTOL or grads > family.GRAD_RTOL
                or routers > family.ROUTER_GRAD_RTOL)

    # a window off by a tile (the toy's window is 8: 12 is half a tile on)
    wide = {**arch, "sliding_window_size": 12}
    assert fails(*errors(family, cfg, wide, params, tokens))
    assert errors(family, cfg, wide, params, tokens)[1] > 3 * family.GRAD_RTOL
    # key/value heads mis-grouped: the program's two swapped
    swapped = {**params, "blocks": [
        {**b, "wk": b["wk"][:, ::-1], "wv": b["wv"][:, ::-1]}
        for b in params["blocks"]]}
    (_, _), g = jax.jit(jax.value_and_grad(
        lambda p: family.T.lm_loss(p, tokens[None], cfg,
                                   use_constraints=False,
                                   return_routing=True), has_aux=True))(swapped)
    (_, _), want = jax.jit(jax.value_and_grad(
        lambda p: family.reference.loss(p, tokens, arch),
        has_aux=True))(params)
    assert float(rel_l2(pick(g, "blocks.1.wq"),
                        pick(want, "blocks.1.wq"))) > 3 * family.GRAD_RTOL
    # weights normalised over the held experts instead of the chosen
    first, held = cfg.held
    plain_weights = family.reference.router_weights

    def over_the_held(x, router, k, imposed=None):
        w, chosen, short = plain_weights(x, router, k, imposed)
        part = w[:, first:first + held].sum(-1, keepdims=True)
        return w / jnp.where(part == 0, 1.0, part), chosen, short

    monkeypatch.setattr(family.reference, "router_weights", over_the_held)
    bad = errors(family, cfg, arch, params, tokens)
    monkeypatch.undo()
    assert fails(*bad) and bad[1] > 3 * family.GRAD_RTOL
    # an 8-bit float for bfloat16
    import dataclasses

    low = dataclasses.replace(cfg, dtype=jnp.float8_e4m3fn)
    assert fails(*errors(family, low, arch, params, tokens))


def test_the_step_half_sees_a_leaf_left_alone_and_a_gate_update_lost():
    """The toy cell's own check passes; a step that leaves the head as it
    was seeded is seen by the whole-tree reading, though the head is not
    among UPDATE_LEAVES; a step that loses the gate matrices' update
    reads 1.0 against EXPERT_UPDATE_RTOL."""
    import numpy as np
    from jax.sharding import Mesh

    family = run.load_module("families", "moe_lm")
    _, config, workload = run.load_cell(BENCH, CELL, TOY)
    mesh = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    cell = family.build(config, workload, chips=1, seed=7, mesh=mesh)
    seeded = jax.tree.map(jnp.copy, cell.state)  # the step donates its own
    step = cell.step
    sound = cell.check(cell)
    assert sound["ok"], sound
    assert sound["leaves"] == len(jax.tree.leaves(seeded))
    assert sound["leaf_step_over_lr"][0] > 2 * family.EVERY_LEAF_STEP_MIN

    def head_left_alone(*args):
        new, opt_state, loss = step(*args)
        return {**new, "head": seeded["head"]}, opt_state, loss

    cell.step = head_left_alone
    seen = cell.check(cell)
    assert not seen["ok"] and seen["stillest_leaf"] == "head"
    assert seen["leaf_step_over_lr"][0] == 0.0

    def gates_left_alone(*args):
        new, opt_state, loss = step(*args)
        blocks = [{**b, "experts": {**b["experts"],
                                    "gate": old["experts"]["gate"]}}
                  for b, old in zip(new["blocks"], seeded["blocks"])]
        return {**new, "blocks": blocks}, opt_state, loss

    cell.step = gates_left_alone
    lost = cell.check(cell)
    gate = lost["update_rel_l2_err"]["blocks.0.experts.gate.3"]
    assert not lost["ok"] and gate > 10 * family.EXPERT_UPDATE_RTOL
