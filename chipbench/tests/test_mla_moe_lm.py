"""The ``mla_moe_lm`` family's yardsticks: the operation counts of
flops_mla.py at the cell's sizes, the tiles the latent kernels compute,
the trace reader on hand-made instructions, and the check's tolerances
against the faults they are written to catch (toy sizes, CPU)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops_mla, mla_reads, run
from chipbench.cell import pick, rel_l2

TOY = run.os.path.join(run.HERE, "tests", "toy")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
CELL = "kanana-2-30b-a3b.dp1"


def test_operation_counts_at_the_cell():
    _, config, workload = run.load_cell(BENCH, CELL)
    sizes, s = config["sizes"], workload["sequence"]
    assert (sizes["n_layer"], sizes["experts_held"], sizes["embedding_rows"],
            s) == (5, 16, 16032, 8192)
    # as run: 930.0 M forward, 2.790 G training operations a token
    assert flops_mla.fwd_flops_per_token(sizes, s) == 930_007_040
    assert 3 * flops_mla.fwd_flops_per_token(sizes, s) == 2_790_021_120
    # by hand, the parts of ISSUE 33's table
    d = 2048
    assert 2 * (d * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d) \
        == 52_690_944
    assert 2 * 32 * (192 + 128) * (s + 1) // 2 == 83_896_320
    assert 6 * d * 6144 == 75_497_472
    assert 2 * d * 128 + 6 * d * 1536 + 6 * 16 / 128 * 6 * d * 768 \
        == 26_476_544
    assert 2 * d * 16032 == 65_667_072
    # the whole model's layer, all 128 experts held: 6 experts a token
    whole = {**sizes, "experts_held": 128}
    assert (flops_mla.fwd_flops_per_token(whole, s)
            - flops_mla.fwd_flops_per_token(sizes, s)) == 4 * (
                6 - 0.75) * 6 * d * 768
    # the parameters the configuration's ``deployment`` reckons
    attention = d * 32 * 192 + d * 576 + 512 + 512 * 32 * 256 + 32 * 128 * d
    assert attention == 26_345_984
    dense = attention + 4096 + 3 * d * 6144
    outside = attention + 4096 + d * 128 + 128 + 3 * d * 1536
    assert (dense, outside) == (64_098_816, 36_049_536)
    assert dense + 4 * (outside + 16 * 3 * d * 768) + 2 * 16032 * d + d \
        == 575_955_968


def test_tiles_computed_at_the_two_widths():
    # 36 tiles a head at 8192 positions and 1024-blocks; the score 192
    # wide, the value 128: forward 1 + 1, dQ 2 + 1, dK/dV 2 + 2 matmuls
    tile = 2 * 1024 * 1024 * 36 * 2 * 32
    args = (2, 32, 8192, 192, 128, 1024, 1024)
    assert flops_mla.mla_kernel_flops("hvd_mla_fwd", *args) == tile * 320
    assert flops_mla.mla_kernel_flops("hvd_mla_bwd_dq", *args) == tile * 512
    assert flops_mla.mla_kernel_flops("hvd_mla_bwd_dkv", *args) == tile * 640


def test_reader_finds_the_latent_kernels_and_nothing_else():
    dkv = ("%hvd_mla_bwd_dkv.12 = (bf16[2,8192,4096]{2,1,0}, "
           "bf16[2,32,8192,64]{3,2,1,0}, bf16[2,8192,4096]{2,1,0}) "
           "custom-call(bf16[2,8192,4096]{2,1,0} %q, "
           "bf16[2,32,8192,64]{3,2,1,0} %qr, bf16[2,8192,4096]{2,1,0} %k, "
           "bf16[2,8192,64]{2,1,0} %kr, bf16[2,8192,4096]{2,1,0} %v, "
           "bf16[2,8192,4096]{2,1,0} %do, f32[64,1,8192]{2,1,0} %lse, "
           "f32[64,1,8192]{2,1,0} %delta), "
           "custom_call_target=\"tpu_custom_call\"")
    flash = ("%hvd_flash_fwd.3 = (bf16[2,8192,3584]{2,1,0}, "
             "f32[56,1,8192]{2,1,0}) custom-call(bf16[2,8192,3584]{2,1,0} "
             "%a), custom_call_target=\"tpu_custom_call\"")
    device = {"instructions": {
        dkv: {"count": 30, "seconds": 0.9},
        flash: {"count": 6, "seconds": 0.1},
        "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8] %p), kind=kLoop":
            {"count": 1, "seconds": 1.0}}, "steps": 6}
    (k,) = mla_reads.mla_kernels(device)
    assert (k["kernel"], k["count"]) == ("hvd_mla_bwd_dkv", 30)
    assert k["flops"] == flops_mla.mla_kernel_flops(
        "hvd_mla_bwd_dkv", 2, 32, 8192, 192, 128, 1024, 1024)
    read = run.load_module("layer_metrics", "mla_attention_roofline_pct").read
    share = read({"devices": [device]}, {}, {"peak_flops_per_s": 197e12})
    assert share == pytest.approx(k["flops"] * 30 / 0.9 / 197e12 * 100)
    # the accepted reader of ``hvd_flash_*`` never sees these
    from chipbench import moe_reads

    assert [f["kernel"] for f in moe_reads.flash_kernels(device)] == [
        "hvd_flash_fwd"]
    # another cell's trace, or no device: nothing to read
    plain = {"instructions": {flash: {"count": 1, "seconds": 1.0}},
             "steps": 1}
    assert mla_reads.mla_kernels(plain) == []
    assert read({"devices": [plain]}, {}, {"peak_flops_per_s": 1.0}) is None
    for name in ("mla_attention_roofline_pct", "latent_ms",
                 "shared_expert_ms", "attention_latent_pct"):
        assert run.load_module("layer_metrics", name).read(
            {"devices": []}, {}, {"peak_flops_per_s": 1.0}) is None


@pytest.fixture(scope="module")
def toy():
    """The family at the toy sizes, float32 reference gradients."""
    family = run.load_module("families", "mla_moe_lm")
    _, config, _ = run.load_cell(BENCH, CELL, TOY)
    cfg = family.make_cfg(config)
    opt, _ = family.build_optimizer(config["optimizer"])
    params = jax.jit(lambda k: family.init_state(
        cfg, opt, config["model"], k)[0])(jax.random.PRNGKey(5))
    # routers, attention, the bias and the second norm's scale away from
    # their seeded values, or nothing hangs on which keys a query sees,
    # which experts a token takes, whether the weights come from s or
    # from s + b, and whether the router reads x, y or u (seeded, the
    # three are nearly one array: x has a spread of 1 and the first
    # attention adds little to it)
    params["blocks"] = [
        {**b, "wq": 4 * b["wq"], "wkva": 4 * b["wkva"], "wo": 4 * b["wo"],
         **({"router": 10 * b["router"],
             "router_bias": 20 * b["router_bias"],
             "ln2": {"scale": 1.5 * b["ln2"]["scale"]}}
            if "router" in b else {})}
        for b in params["blocks"]]
    tokens = jax.random.randint(jax.random.PRNGKey(6), (33,), 0,
                                cfg.vocab_size)
    return family, cfg, family.arch_of(config), params, tokens


def errors(family, cfg, arch, params, tokens, given=lambda p: p):
    """``given``: what the program makes of the parameters before it
    runs (a fault in the weights it is handed)."""
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: family.T.lm_loss(given(p), tokens[None], cfg,
                                   use_constraints=False,
                                   return_routing=True), has_aux=True))(params)
    (want, _), want_grads = jax.jit(jax.value_and_grad(
        lambda p: family.reference.loss(p, tokens, arch), has_aux=True))(
            params)
    errs = {p: float(rel_l2(pick(grads, p), pick(want_grads, p)))
            for p in family.CHECK_LEAVES}
    routers = max(e for p, e in errs.items() if "router" in p)
    others = max(e for p, e in errs.items() if "router" not in p)
    return abs(float(loss) - float(want)) / float(want), others, routers


def fails(family, loss, grads, routers):
    return (loss > family.LOSS_RTOL or grads > family.GRAD_RTOL
            or routers > family.ROUTER_GRAD_RTOL)


def test_the_tolerances_pass_the_program(toy):
    family, *rest = toy
    loss, grads, routers = errors(family, *rest)
    assert loss <= family.LOSS_RTOL and grads <= family.GRAD_RTOL
    assert routers <= family.ROUTER_GRAD_RTOL


FAULTS = ("8-bit float", "rotary missing on the shared key",
          "rotary on the wrong columns", "scale 1/sqrt(128)",
          "norm_kv left out", "weights from s + b", "2.448 left out",
          "shared experts left out", "shared experts weighted",
          "softmax for sigmoid", "router reads the block's input")


@pytest.mark.parametrize("fault", FAULTS)
def test_the_tolerances_catch_the_fault(toy, fault, monkeypatch):
    """At the toy sizes each fault ISSUE 33 lists, made in the program
    (or, for the columns, in the weights it is given), misses at least
    one stated tolerance."""
    family, cfg, arch, params, tokens = toy
    T = family.T
    hd, r, latent = cfg.head_dim, cfg.d_rope, cfg.kv_latent
    shared = cfg.n_shared_experts * cfg.d_expert
    if fault == "8-bit float":  # the nearest precision below bfloat16
        cfg = dataclasses.replace(cfg, dtype=jnp.float8_e4m3fn)
    elif fault == "rotary missing on the shared key":
        rope = T._rope
        monkeypatch.setattr(T, "_rope", lambda x, tables, heads_first=False: (
            x if x.shape[2] == 1 and not heads_first
            else rope(x, tables, heads_first)))
    elif fault == "rotary on the wrong columns":
        # the program handed W_q and W_kva with their columns rolled by
        # r: it turns columns that hold no positions and leaves rotary
        # ones unturned
        bad = errors(family, cfg, arch, params, tokens, lambda p: {
            **p, "blocks": [{**b, "wq": jnp.roll(b["wq"], r, axis=-1),
                             "wkva": jnp.roll(b["wkva"], r, axis=-1)}
                            for b in p["blocks"]]})
        assert fails(family, *bad), (fault, bad)
        return
    elif fault == "scale 1/sqrt(128)":
        attend = T.causal_attention
        monkeypatch.setattr(T, "causal_attention", lambda q, k, v, *a: attend(
            q * math.sqrt((hd + r) / hd), k, v, *a))
    elif fault == "norm_kv left out":
        norm = T._rmsnorm
        monkeypatch.setattr(T, "_rmsnorm", lambda x, scale: (
            x if scale.shape[-1] == latent else norm(x, scale)))
    elif fault == "weights from s + b":
        from horovod_tpu.parallel import moe

        route = moe.route

        def biased(logits, k, *, scoring, bias, scale, name):
            chosen, _ = route(logits, k, scoring=scoring, bias=bias,
                              name=name)
            top = jnp.take_along_axis(jax.nn.sigmoid(logits) + bias, chosen,
                                      axis=-1)
            return chosen, scale * top / top.sum(-1, keepdims=True)

        monkeypatch.setattr(moe, "route", biased)
    elif fault == "2.448 left out":
        cfg = dataclasses.replace(cfg, routed_scale=1.0)
    elif fault.startswith("shared experts"):
        factor = 0.0 if fault.endswith("left out") else 0.5
        mlp = T._gated_mlp
        monkeypatch.setattr(T, "_gated_mlp", lambda h, w, dt: mlp(h, w, dt) * (
            factor if w["gate"].shape[-1] == shared else 1.0))
    elif fault == "softmax for sigmoid":
        cfg = dataclasses.replace(cfg, router_scoring="softmax")
    elif fault == "router reads the block's input":
        cfg = dataclasses.replace(cfg, router_input="block")
    bad = errors(family, cfg, arch, params, tokens)
    assert fails(family, *bad), (fault, bad)


def test_the_step_half_sees_a_leaf_left_alone_and_a_bias_that_moved():
    """The toy cell's own check passes, and the bias stays where it was
    seeded; a step that leaves the head as it was seeded is seen by the
    whole-tree reading, though the head is not among UPDATE_LEAVES; a
    step that decays the bias like any other leaf is seen too."""
    import numpy as np
    from jax.sharding import Mesh

    family = run.load_module("families", "mla_moe_lm")
    _, config, workload = run.load_cell(BENCH, CELL, TOY)
    mesh = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    cell = family.build(config, workload, chips=1, seed=7, mesh=mesh)
    seeded = jax.tree.map(jnp.copy, cell.state)  # the step donates its own
    assert float(jnp.abs(seeded["blocks"][1]["router_bias"]).max()) > 0
    step = cell.step
    sound = cell.check(cell)
    assert sound["ok"], sound
    assert sound["leaves"] == len(jax.tree.leaves(seeded))
    assert sound["leaf_step_over_lr"][0] > 2 * family.EVERY_LEAF_STEP_MIN
    assert sound["router_bias_step_over_lr"] == 0.0

    def head_left_alone(*args):
        new, opt_state, loss = step(*args)
        return {**new, "head": seeded["head"]}, opt_state, loss

    cell.step = head_left_alone
    seen = cell.check(cell)
    assert not seen["ok"] and seen["stillest_leaf"] == "head"
    assert seen["leaf_step_over_lr"][0] == 0.0

    def bias_decayed(*args):
        new, opt_state, loss = step(*args)
        blocks = [{**b, "router_bias": 0.999 * b["router_bias"]}
                  if "router_bias" in b else b for b in new["blocks"]]
        return {**new, "blocks": blocks}, opt_state, loss

    cell.step = bias_decayed
    moved = cell.check(cell)
    assert not moved["ok"] and moved["router_bias_step_over_lr"] > 0
