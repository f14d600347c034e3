"""Ahead-of-time compiles for a described TPU v5e 2x2: the chip's own
compiler, installed here, judges the programs of the main path at their
real widths without a chip attached (on-chip-measurement guide, section
2.3). Interpret mode passes kernels the TPU refuses — the (1, bq) blocks
of the flash kernel's m/l outputs went unnoticed that way — so these
cases guard every later PR at no chip time. Nothing runs: a compile that
passes says nothing about results or times.

Skipped where the topology cannot be described. The persistent cache is
off around the compiles: an entry written for a described chip cannot be
read back without one, and the next compile would warn.
"""

import collections
import importlib
import os
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs in /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

F = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")

B, D, BLOCK = 128, 128, 512  # the 1.2B LM: batch 8 x 16 heads, width 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(autouse=True)
def as_on_tpu(monkeypatch):
    """The code under test asks ``jax.default_backend()`` and would take
    its CPU branch (interpret mode, the XLA stats path) here; answer for
    the described chip. Persistent cache off, as the module says."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def qkv(topo, s):
    one = SingleDeviceSharding(topo.devices[0])
    return (jax.ShapeDtypeStruct((B, s, D), jnp.bfloat16, sharding=one),) * 3


@pytest.mark.parametrize("s", [1024, 2048])
@pytest.mark.parametrize("causal,offset",
                         [(True, 0), (False, 0), (True, 1)],
                         ids=["causal", "noncausal", "offset1"])
def test_flash_fwd_compiles(topo, s, causal, offset):
    text = F._flash_fwd.lower(*qkv(topo, s), causal=causal, block_q=BLOCK,
                              block_k=BLOCK,
                              causal_offset=offset).compile().as_text()
    assert "tpu_custom_call" in text
    assert "hvd_flash_fwd" in text  # the kernel's stable name in a trace


@pytest.mark.parametrize("b,s", [(4, 2048), (16, 512)],
                         ids=["64x2048x128", "256x512x128"])
def test_flash_bwd_kernels_compile(topo, b, s):
    """The decoder's backward at the LM cells' shapes, sixteen heads of
    128 side by side as its projections write them, at the blocks
    `block_sizes` gives: both kernels, under their names."""
    one = SingleDeviceSharding(topo.devices[0])
    heads = 16
    x = jax.ShapeDtypeStruct((b, s, heads * D), jnp.bfloat16, sharding=one)
    lse = jax.ShapeDtypeStruct((b * heads, 1, s), jnp.float32, sharding=one)
    bq, bk = F.block_sizes(s, D)
    text = F._flash_bwd.lower(x, x, x, x, x, lse, causal=True, block_q=bq,
                              block_k=bk, heads=heads).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "hvd_flash_bwd_dq" in text and "hvd_flash_bwd_dkv" in text


@pytest.mark.parametrize("window", [None, 4096], ids=["global", "window"])
def test_grouped_head_kernels_compile_at_the_sparse_decoder_shapes(
        topo, window):
    """The sparse-expert cell's attention: 28 query heads over 4
    key/value heads of 128 at 8192 positions, global and with a window
    of 4096, at the blocks `block_sizes` gives. The TPU compiler takes
    all three kernels; the windowed ones carry the window in their
    names, which is how a trace's reader tells them apart."""
    one = SingleDeviceSharding(topo.devices[0])
    heads, kv, s = 28, 4, 8192
    q = jax.ShapeDtypeStruct((1, s, heads * D), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((1, s, kv * D), jnp.bfloat16, sharding=one)
    lse = jax.ShapeDtypeStruct((heads, 1, s), jnp.float32, sharding=one)
    bq, bk = F.block_sizes(s, D)
    static = dict(causal=True, block_q=bq, block_k=bk, heads=heads,
                  window=window, kv_heads=kv)
    suffix = "" if window is None else f"_w{window}"
    fwd = F._flash_fwd_lse.lower(q, k, k, **static).compile().as_text()
    assert "tpu_custom_call" in fwd and f"hvd_flash_fwd{suffix}" in fwd
    bwd = F._flash_bwd.lower(q, k, k, q, q, lse, **static).compile()
    text = bwd.as_text()
    assert f"hvd_flash_bwd_dq{suffix}" in text
    assert f"hvd_flash_bwd_dkv{suffix}" in text
    assert [o.shape for o in bwd.out_info] == [q.shape, k.shape, k.shape]


def test_latent_kernels_compile_at_the_mla_decoder_shapes(topo):
    """The latent-attention cell's heads: 32 of 128 no-rope + 64 rotary
    columns (a query/key 192 wide, no lane multiple) and a value of 128
    at 8192 positions, the rotary key one [1, 8192, 64] array for all
    heads, at the blocks `block_sizes` gives. The TPU compiler takes all
    three kernels, under names of their own that no reader of
    ``hvd_flash_*`` matches; the shared key's gradient comes back in its
    own shape."""
    import re

    one = SingleDeviceSharding(topo.devices[0])
    heads, r, s = 32, 64, 8192

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    x, q_rope, k_rope = (shape(1, s, heads * D), shape(1, heads, s, r),
                         shape(1, s, r))
    lse = shape(heads, 1, s, dtype=jnp.float32)
    bq, bk = F.block_sizes(s, D)
    static = dict(block_q=bq, block_k=bk, heads=heads)
    fwd = F._latent_fwd_lse.lower(x, q_rope, x, k_rope, x,
                                  **static).compile().as_text()
    assert "tpu_custom_call" in fwd and "hvd_mla_fwd" in fwd
    bwd = F._latent_bwd.lower(x, q_rope, x, k_rope, x, x, x, lse,
                              **static).compile()
    text = bwd.as_text()
    assert "hvd_mla_bwd_dq" in text and "hvd_mla_bwd_dkv" in text
    assert [o.shape for o in bwd.out_info] == [
        x.shape, q_rope.shape, x.shape, k_rope.shape, x.shape]
    flash = re.compile(r"^hvd_flash_(fwd|bwd_dq|bwd_dkv)(_w\d+)?$")
    assert not any(flash.match(n) for n in (
        "hvd_mla_fwd", "hvd_mla_bwd_dq", "hvd_mla_bwd_dkv"))


@pytest.mark.parametrize("direction", ["forward", "gradient"])
def test_chunked_expert_layer_compiles_at_the_sparse_cell_sizes(
        topo, direction):
    """The sparse-expert cell's layer: 16,384 tokens of 2560, experts 0-7
    of 64 held, 6 a token, so six chunks of 16,384 sorted rows. The TPU's
    compiler keeps one ``while`` a direction for the chunks after the
    first (and no ``conditional``: nothing unrolled), adds no ``scatter``
    (both directions of every row movement are gathers) and leaves no
    hidden activation at the bound's size, [98304, 1536], and since PR 37
    no rows of the pairs, [98304, 2560], in either direction."""
    import re

    from horovod_tpu.parallel import moe

    one = SingleDeviceSharding(topo.devices[0])
    t, d, f, held, k = 16384, 2560, 768, 8, 6

    def array(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = {"gate": array((held, d, f), jnp.float32),
              "up": array((held, d, f), jnp.float32),
              "down": array((held, f, d), jnp.float32)}

    def layer(u, w, params, chosen):  # the router's own gradient aside
        return moe.expert_layer(u, chosen, w, params, (0, held))

    fn = layer if direction == "forward" else jax.grad(
        lambda *a: jnp.sum(layer(*a).astype(jnp.float32) ** 2), (0, 1, 2))
    text = jax.jit(fn).lower(
        array((t, d), jnp.bfloat16), array((t, k), jnp.float32), params,
        array((t, k), jnp.int32)).compile().as_text()
    assert moe.chunk_rows(t, t * k) == t
    directions = 1 if direction == "forward" else 2
    assert len(re.findall(r"\swhile\(", text)) == directions
    assert not re.search(r"\sconditional\(", text)
    assert not re.search(r"\sscatter\(", text)
    assert "ragged-dot" in text and f"[{t},{2 * f}]" in text
    assert f"[{t * k},{2 * f}]" not in text and f"[{t * k},{f}]" not in text
    assert f"[{t * k},{d}]" not in text


def test_dense_kernels_trace_as_before_the_window_and_the_groups(topo):
    """``window=None, kv_heads=heads`` is the dense decoder's call: its
    jaxpr (grid, index maps and kernel bodies) is, letter for letter,
    that of the call without the two arguments, under the kernels' plain
    names. (The lowered modules cannot be compared so: a kernel's MLIR
    carries the caller's line numbers.)"""
    one = SingleDeviceSharding(topo.devices[0])
    heads, s = 16, 2048
    x = jax.ShapeDtypeStruct((4, s, heads * D), jnp.bfloat16, sharding=one)
    lse = jax.ShapeDtypeStruct((4 * heads, 1, s), jnp.float32, sharding=one)
    bq, bk = F.block_sizes(s, D)
    static = dict(causal=True, block_q=bq, block_k=bk, heads=heads)
    for entry, args in ((F._flash_fwd_lse, (x, x, x)),
                        (F._flash_bwd, (x, x, x, x, x, lse))):
        plain = str(entry.trace(*args, **static).jaxpr)
        assert plain == str(entry.trace(*args, **static, window=None,
                                        kv_heads=heads).jaxpr)
        assert "hvd_flash" in plain and "_w" not in "".join(
            line for line in plain.splitlines() if "name=hvd_flash" in line)


@pytest.mark.parametrize("offset", [0, 1])
def test_attention_stats_vjp_compiles(topo, offset):
    """Kernel forward + the blockwise scan_stats backward, with all three
    outputs' cotangents live (ring combination makes m and l outputs)."""
    def loss(q, k, v):
        o, m, l = F.attention_stats(q, k, v, True, BLOCK, BLOCK, offset)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(m * l)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *qkv(topo, 2048)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # blockwise: one [B, sq, block_k] float32 score block and its kin,
    # never the [B, sq, sk] matrix (2 GiB in float32 alone at s=2048)
    assert compiled.memory_analysis().temp_size_in_bytes < 6 << 30


def test_ring_attention_round_compiles_on_four_chips(topo):
    """``ring_attention`` as the chip sees it: inside a vma-checked
    shard_map over a 4-way sp axis, the kernel picked by ``_auto_flash``
    (nothing forces ``use_flash``), K/V rotating by collective-permute."""
    from horovod_tpu.parallel import ring_attention

    mesh = Mesh(np.array(topo.devices), ("sp",))
    x = jax.ShapeDtypeStruct((1, 8192, 8, D), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P(None, "sp")))
    ring = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp"), mesh=mesh,
        in_specs=P(None, "sp"), out_specs=P(None, "sp")))
    text = ring.lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text and "collective-permute" in text


def test_auto_flash_follows_the_tiling_rule():
    from horovod_tpu.parallel.sp import _auto_flash

    assert _auto_flash(2048, 512, 512, None)       # blocks of 512 tile
    assert _auto_flash(200, 512, 512, None)        # one whole-sequence block
    assert not _auto_flash(2048 + 64, 512, 512, None)  # 512 does not divide
    assert not _auto_flash(256, 64, 64, None)      # 64 is no lane multiple
    assert _auto_flash(256, 64, 64, True)          # the caller's word wins


def test_fused_chunk_plan_compiles_for_64mib_over_four_processes(topo):
    """The negotiated eager path's steady-state program: a 64 MiB fused
    chunk summed over four processes with one chip each (the layout
    ``hvdrun -np 4`` makes), unpacked into its two tensors."""
    from horovod_tpu.common.context import LOCAL_AXIS, PROC_AXIS
    from horovod_tpu.ops import collectives as C

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), (PROC_AXIS, LOCAL_AXIS))
    ps = types.SimpleNamespace(name="aot", cross_size=4, mesh_2d=mesh)
    n = (32 << 20) // 4  # two float32 tensors of 32 MiB
    plan = C._build_fused_plan(ps, 4, C.ReduceOp.SUM, 1.0, 1.0, (n, n),
                               ((n,), (2, n // 2)), False, False)
    g = jax.ShapeDtypeStruct((4, 2 * n), jnp.float32,
                             sharding=NamedSharding(mesh, P(PROC_AXIS)))
    compiled = plan.run.lower(g).compile()
    assert "all-reduce" in compiled.as_text()
    assert [o.shape for o in compiled.out_info] == [(n,), (2, n // 2)]


@pytest.mark.parametrize("seq", [128, 512], ids=["einsum", "kernels"])
def test_toy_lm_step_carries_every_phase_on_four_chips(topo, seq):
    """A remat'd decoder step through ``DistributedOptimizer`` +
    ``data_parallel_step`` as the chip's compiler leaves it: the scopes
    of utils/scopes.py survive into the entry computation's fusions (the
    instructions a trace's ``XLA Ops`` events are named by), and an
    instruction's name identifies it within the module. At 512
    positions the decoder's rule takes the fused kernels: their calls
    carry the attention scope, forward and backward, and the ``recompute``
    phase (norms, output projection, MLP) holds none of them."""
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as T
    from horovod_tpu.parallel import data_parallel_step, dp
    from horovod_tpu.utils import scopes

    mesh = Mesh(np.array(topo.devices), ("hvd",))
    cfg = T.TransformerConfig(vocab_size=512, d_model=256, n_heads=2,
                              n_layers=2, d_ff=512, max_seq=seq, remat=True)
    opt = hvd.DistributedOptimizer(optax.adamw(1e-3))

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(T.lm_loss)(
            params, tokens, cfg, use_constraints=False)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, "hvd"))

    def placed(tree, spec):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)

    params = jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), cfg))
    step = data_parallel_step(step, mesh=mesh)
    text = step.lower(
        placed(params, P()), placed(jax.eval_shape(opt.init, params), P()),
        placed(jax.ShapeDtypeStruct((8, seq + 1), jnp.int32), P("hvd"))
    ).compile().as_text()
    # what the step remembered of its arguments lowers to the same module
    assert dp.scope_table(step) == scopes.instruction_scopes(text)

    instruction_lines = [l for l in text.splitlines() if " = " in l
                         and l.startswith(" ")]
    assert len(instruction_lines) == len(dp.scope_table(step))
    entry = scopes.instruction_scopes(text[text.index("\nENTRY "):])
    phases = {scopes.phase_of(dp.scope_table(step)[name]) for name in entry
              if "fusion" in name}
    assert phases >= {"forward", "backward", "recompute", "optimizer",
                      "grad_exchange"}

    kernels = {(name.split(".")[0], scopes.phase_of(op), scopes.part_of(op))
               for name, op in entry.items() if name.startswith("hvd_flash")}
    counters = dp.step_counters(step)
    assert counters["attention_kernel_calls"] == (
        counters["attention_calls"] if seq >= T.FUSED_ATTENTION_MIN_SEQ
        else 0)
    # what the backward kernels read is kept (the checkpoint's policy),
    # so no forward kernel stands in the recomputation
    assert kernels == ({
        ("hvd_flash_fwd", "forward", scopes.ATTENTION),
        ("hvd_flash_bwd_dq", "backward", scopes.ATTENTION),
        ("hvd_flash_bwd_dkv", "backward", scopes.ATTENTION),
    } if seq >= T.FUSED_ATTENTION_MIN_SEQ else set())
    assert counters["attention_kept_calls"] == counters[
        "attention_kernel_calls"]


def test_toy_lm_step_builds_no_gradient_exchange_on_one_chip(topo):
    """The same step for one described chip: an exchange over one member
    is the identity, so the chip's compiler is handed no flat buffer and
    the module names no ``hvd.grad_exchange`` work; the other phases
    are all there and the counters read zero, not nothing."""
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as T
    from horovod_tpu.parallel import data_parallel_step, dp
    from horovod_tpu.utils import scopes

    mesh = Mesh(np.array(topo.devices[:1]), ("hvd",))
    cfg = T.TransformerConfig(vocab_size=512, d_model=256, n_heads=2,
                              n_layers=2, d_ff=512, max_seq=512, remat=True)
    opt = hvd.DistributedOptimizer(optax.adamw(1e-3))

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(T.lm_loss)(
            params, tokens, cfg, use_constraints=False)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, "hvd"))

    def placed(tree, spec):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)

    params = jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), cfg))
    step = data_parallel_step(step, mesh=mesh)
    text = step.lower(
        placed(params, P()), placed(jax.eval_shape(opt.init, params), P()),
        placed(jax.ShapeDtypeStruct((2, 513), jnp.int32), P("hvd"))
    ).compile().as_text()
    assert scopes.GRAD_EXCHANGE not in text
    phases = {scopes.phase_of(op)
              for op in scopes.instruction_scopes(text).values()}
    assert phases >= {"forward", "backward", "recompute", "optimizer"}
    assert "grad_exchange" not in phases
    counters = dp.step_counters(step)
    assert (counters["collectives"], counters["collective_bytes"],
            counters["packed_bytes"], counters["axis_size"]) == (0, 0, 0, 1)
    assert counters["attention_kernel_calls"] == counters["attention_calls"]


def test_looped_lm_step_keeps_its_kernels_inside_the_loops(topo, monkeypatch):
    """A looped decoder's step for one described chip, as the chip traces
    it (the decoder's rule answering for a TPU): the recurrence compiles
    to ``while``s whose bodies hold the three fused kernels once a block
    (two blocks, three passes: two forward kernels in the module, none
    in the recomputation, since what the backward kernels read is kept
    across the scan), the loops' own instructions are filed as spanning
    their bodies, and the exits' part is there in every phase."""
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as T
    from horovod_tpu.parallel import data_parallel_step, dp
    from horovod_tpu.utils import scopes

    monkeypatch.setattr(T, "_on_tpu", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    mesh = Mesh(np.array(topo.devices[:1]), ("hvd",))
    cfg = T.TransformerConfig(
        vocab_size=512, d_model=256, n_heads=2, n_layers=2, d_ff=512,
        max_seq=512, remat=True, positions="layout", rope_layout=(1,),
        rope_theta=1e6, tie_embeddings=False, mlp="gated", n_loops=3,
        sandwich_norms=True, exit_beta=0.05)
    opt = hvd.DistributedOptimizer(optax.adamw(1e-3))

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(T.lm_loss)(
            params, tokens, cfg, use_constraints=False)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, "hvd"))

    def placed(tree, spec):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)

    params = jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), cfg))
    step = data_parallel_step(step, mesh=mesh)
    text = step.lower(
        placed(params, P()), placed(jax.eval_shape(opt.init, params), P()),
        placed(jax.ShapeDtypeStruct((1, 513), jnp.int32), P("hvd"))
    ).compile().as_text()
    table = scopes.instruction_scopes(text)
    assert sum(op == scopes.SPANS_ITS_BODY for op in table.values()) >= 2
    kernels = collections.Counter(
        (name.split(".")[0], scopes.phase_of(op), scopes.part_of(op),
         "while/body" in op)
        for name, op in table.items() if name.startswith("hvd_flash"))
    assert kernels == {
        ("hvd_flash_fwd", "forward", scopes.ATTENTION, True): 2,
        ("hvd_flash_bwd_dq", "backward", scopes.ATTENTION, True): 2,
        ("hvd_flash_bwd_dkv", "backward", scopes.ATTENTION, True): 2}
    exits = {scopes.phase_of(op) for op in table.values()
             if scopes.part_of(op) == scopes.EXIT}
    assert exits >= {"forward", "backward"}
    counters = dp.step_counters(step)
    assert (counters["loop_steps"], counters["attention_calls"],
            counters["attention_kernel_calls"],
            counters["attention_kept_calls"]) == (3, 6, 6, 6)
    assert counters["remat_kept_mb"] == pytest.approx(
        6 * T._kept_bytes((1, 512), cfg) / 1e6)


def test_checkpointed_decoder_traces_and_lowers_each_kernel_once(
        topo, monkeypatch):
    """Eight ``jax.checkpoint``-ed blocks under ``value_and_grad``
    through ``apply``, as the LM cell's reference check runs the loss (a
    bare ``jit`` on one device): each kernel's body is traced once, and
    the module carries exactly three distinct ``tpu_custom_call`` bodies
    under the three kernel names. Every block and both directions call
    the jitted entries of ops/pallas/flash_attention.py; the blocks'
    checkpoint keeps what the backward kernels read, so the forward pass
    writes ``lse`` and the recomputation holds no kernel. Compiled,
    there are three kernel instructions a layer, under the attention
    scope."""
    import collections
    import functools
    import re

    from horovod_tpu.models import transformer as T
    from horovod_tpu.utils import scopes

    traced = collections.Counter()
    for name in ("_flash_fwd_kernel", "_flash_bwd_dq_kernel",
                 "_flash_bwd_dkv_kernel"):
        def counting(*args, _body=getattr(F, name), _name=name, **kwargs):
            traced[_name] += 1
            return _body(*args, **kwargs)

        monkeypatch.setattr(F, name, counting)
    monkeypatch.setattr(jax, "device_count", lambda: 1)  # as on the chip

    cfg = T.TransformerConfig(vocab_size=512, d_model=256, n_heads=2,
                              n_layers=8, d_ff=512, max_seq=512, remat=True)
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        jax.eval_shape(functools.partial(T.init, cfg=cfg),
                       jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 513), jnp.int32, sharding=one)
    lowered = jax.jit(lambda p, t: jax.value_and_grad(T.lm_loss)(
        p, t, cfg, use_constraints=False)).lower(params, tokens)
    assert traced == {"_flash_fwd_kernel": 1, "_flash_bwd_dq_kernel": 1,
                      "_flash_bwd_dkv_kernel": 1}

    calls = re.findall(r"stablehlo\.custom_call @tpu_custom_call\(.*",
                       lowered.as_text())
    bodies = {re.search(r'backend_config = "?(.*?)"?, ', c + ", ").group(1)
              for c in calls}
    names = collections.Counter(
        re.search(r'kernel_name = "(\w+)"', c).group(1) for c in calls)
    assert len(bodies) == 3, (len(bodies), names)
    assert set(names) == {"hvd_flash_fwd", "hvd_flash_bwd_dq",
                          "hvd_flash_bwd_dkv"}
    assert names["hvd_flash_bwd_dq"] == names["hvd_flash_bwd_dkv"] == 1

    kernels = {name: op for name, op in scopes.instruction_scopes(
        lowered.compile().as_text()).items() if name.startswith("hvd_flash")}
    assert len(kernels) == 3 * cfg.n_layers   # forward, dq, dkv
    assert all(scopes.part_of(op) == scopes.ATTENTION
               for op in kernels.values()), kernels


def test_fsdp_step_keeps_the_einsum_path_on_four_chips(topo, monkeypatch):
    """``fsdp_train_step`` is a bare GSPMD ``jit`` over sharded
    parameters and batch, and its callers pass ``use_constraints=False``:
    at widths the fused kernels would take (512 positions, heads of 128)
    the decoder must leave the attention to XLA there — a Mosaic call is
    not partitioned, and lowering one in that jit raises."""
    import optax

    from horovod_tpu.models import transformer as T
    from horovod_tpu.parallel import fsdp_train_step

    mesh = Mesh(np.array(topo.devices), ("dp",))
    cfg = T.TransformerConfig(vocab_size=512, d_model=256, n_heads=2,
                              n_layers=2, d_ff=512, max_seq=512, remat=True,
                              dp_axis=None, tp_axis=None, sp_axis=None)
    assert T.fused_attention_blocks(512, cfg.head_dim, False) is None
    opt = optax.adamw(1e-3)
    # no chip to put the state on: describe it where the factory places it
    monkeypatch.setattr(jax, "device_put", lambda tree, shardings: jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings))
    params = jax.eval_shape(lambda key: T.init(key, cfg), jax.random.PRNGKey(0))
    make = fsdp_train_step(
        lambda p, batch: T.lm_loss(p, batch, cfg, use_constraints=False),
        opt, mesh, axis="dp", min_shard_elems=256, batch_spec=P("dp", None))
    params, opt_state, step = make(params, jax.eval_shape(opt.init, params))
    assert params["embed"].sharding.spec == P("dp", None)
    tokens = jax.ShapeDtypeStruct((8, 513), jnp.int32,
                                  sharding=NamedSharding(mesh, P("dp", None)))
    text = step.lower(params, opt_state, tokens).compile().as_text()
    assert "hvd_flash" not in text and "all-gather" in text
