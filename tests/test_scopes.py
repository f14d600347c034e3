"""utils/scopes.py and what ``parallel/dp.py`` remembers of a step it
built: the scope vocabulary against the literal ``op_name`` forms the
compiler writes, and two toy steps (a remat'd decoder, a small ResNet)
through ``DistributedOptimizer`` + ``data_parallel_step`` on the CPU."""

import collections

import jax
import jax.numpy as jnp
import optax
import pytest
from jax import monitoring

import horovod_tpu as hvd
from horovod_tpu.models import transformer as T
from horovod_tpu.models.resnet import ResNet
from horovod_tpu.parallel import data_parallel_step, dp
from horovod_tpu.utils import scopes

PRE = "jit(hvd_data_parallel_step)/shard_map/hvd.step/"


@pytest.mark.parametrize("op_name,phase,part", [
    (PRE + "jvp(hvd.model/mlp)/dot_general", "forward", "hvd.model/mlp"),
    (PRE + "transpose(jvp(hvd.model/head))/mul", "backward",
     "hvd.model/head"),
    (PRE + "transpose(jvp(hvd.step))/jvp()/checkpoint/hvd.model/attention/"
     "dot_general", "backward", "hvd.model/attention"),
    (PRE + "transpose(jvp(hvd.step))/jvp()/checkpoint/rematted_computation/"
     "hvd.model/attention/dot_general", "recompute", "hvd.model/attention"),
    (PRE + "jvp(hvd.model/latent)/dot_general", "forward",
     "hvd.model/latent"),
    (PRE + "transpose(jvp(hvd.step))/jvp()/checkpoint/rematted_computation/"
     "hvd.model/shared_expert/dot_general", "recompute",
     "hvd.model/shared_expert"),
    # a looped decoder's model lives in a scan's body, forward and backward
    (PRE + "jvp(hvd.step)/jvp(while)/body/closed_call/checkpoint/"
     "hvd.model/exit/dot_general", "forward", "hvd.model/exit"),
    (PRE + "transpose(jvp(hvd.step))/while/body/closed_call/checkpoint/"
     "rematted_computation/hvd.model/head/dot_general", "recompute",
     "hvd.model/head"),
    (PRE + "transpose(jvp(hvd.model/exit))/mul", "backward",
     "hvd.model/exit"),
    (PRE + "jvp(hvd.model/indexer)/closed_call/while/body/closed_call/"
     "jvp(bchs,bch->bcs)/dot_general", "forward", "hvd.model/indexer"),
    # a nested name is filed under its parent: the new parts sit beside it
    (PRE + "jvp(hvd.model/attention)/hvd.model/latent/dot_general",
     "forward", "hvd.model/attention"),
    (PRE + "hvd.optimizer/sub", "optimizer", None),
    (PRE + "hvd.grad_exchange/pack/concatenate", "grad_exchange",
     "hvd.grad_exchange/pack"),
    (PRE + "hvd.grad_exchange/reduce/psum", "grad_exchange",
     "hvd.grad_exchange/reduce"),
    (PRE + "hvd.grad_exchange/unpack/slice", "grad_exchange",
     "hvd.grad_exchange/unpack"),
    # XLA joins merged instructions' names: the precedence is over all
    (PRE + "transpose(jvp(hvd.model/mlp))/mul;" + PRE
     + "hvd.grad_exchange/pack/concatenate", "grad_exchange",
     "hvd.grad_exchange/pack"),
    (PRE + "transpose(jvp(hvd.model/mlp))/mul;" + PRE
     + "transpose(jvp(hvd.model/mlp))/broadcast_in_dim", "backward",
     "hvd.model/mlp"),
    ("jit(hvd_data_parallel_step)/shard_map/hvd.step/psum", "other", None),
    ("args[0]['embed']", "other", None),
    ("", "other", None),
    (None, "other", None),
])
def test_phase_and_part_of_literal_op_names(op_name, phase, part):
    assert scopes.phase_of(op_name) == phase
    assert scopes.part_of(op_name) == part
    assert phase in scopes.PHASES


def named(op):
    return 'metadata={op_name="' + PRE + op + '" stack_frame_id=7}'


def test_instruction_scopes_and_seconds_by_phase():
    text = "\n".join([
        "HloModule jit_hvd_data_parallel_step, entry_computation_layout={}",
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        "  %slice.1 = f32[4]{0} slice(%p), "
        + named("hvd.grad_exchange/unpack/slice"),
        "  %mul.3 = f32[4]{0} multiply(%p, %slice.1), "
        + named("hvd.optimizer/mul"),
        "  %sub.4 = f32[4]{0} subtract(%p, %mul.3), "
        + named("hvd.optimizer/sub"),
        "  ROOT %add.5 = f32[4]{0} add(%p, %sub.4), " + named("add"),
        "}",
        "%fused_computation.2 (q: f32[4]) -> f32[4] {",
        "  %q = f32[4]{0} parameter(0)",
        "  ROOT %dynamic-update-slice.6 = f32[4]{0} "
        "dynamic-update-slice(%q, %q), backend_config={}",
        "}",
        "ENTRY %main.9 (a: f32[4]) -> f32[4] {",
        '  %a = f32[4]{0} parameter(0), metadata={op_name="args[0]"}',
        # its root is the user's add: named by what is inside, the phase
        # most of the names have (not by `phase_of`'s precedence)
        "  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.1, " + named("add"),
        # no name on it, none inside: stays unnamed
        "  %update_fusion.4 = f32[4]{0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.2, backend_config={}",
        "  %copy.5 = f32[4]{0} copy(%update_fusion.4)",
        # the compiler's own re-tiling of a result: filed with what made
        # it, through a chain of them; an asynchronous copy is not
        "  %copy.10 = f32[4]{0:T(8)} copy(%fusion.1), backend_config={}",
        "  %copy.11 = f32[4]{0} copy(%copy.10)",
        "  %copy-start.12 = (f32[4]{0}, f32[4]{0}, u32[]) "
        "copy-start(%fusion.1)",
        # a root with a phase keeps it, whatever is fused in
        "  %fusion.7 = f32[4]{0} fusion(%a), kind=kOutput, "
        "calls=%fused_computation.1, "
        + named("transpose(jvp(hvd.model/mlp))/dot_general"),
        "  ROOT %dot.2 = f32[4]{0} dot(%fusion.1, %a), "
        + named("jvp(hvd.model/mlp)/dot_general") + ', source_file="x.py"',
        "}"])
    table = scopes.instruction_scopes(text)
    assert {k: v for k, v in table.items() if "." in k or k == "a"} == {
        "slice.1": PRE + "hvd.grad_exchange/unpack/slice",
        "mul.3": PRE + "hvd.optimizer/mul", "sub.4": PRE + "hvd.optimizer/sub",
        "add.5": PRE + "add", "dynamic-update-slice.6": "", "a": "args[0]",
        "fusion.1": PRE + "hvd.optimizer/mul;" + PRE + "hvd.optimizer/sub",
        "update_fusion.4": "", "copy.5": "",
        "copy.10": PRE + "hvd.optimizer/mul;" + PRE + "hvd.optimizer/sub",
        "copy.11": PRE + "hvd.optimizer/mul;" + PRE + "hvd.optimizer/sub",
        "copy-start.12": "",
        "fusion.7": PRE + "transpose(jvp(hvd.model/mlp))/dot_general",
        "dot.2": PRE + "jvp(hvd.model/mlp)/dot_general"}
    instructions = {
        "%fusion.1 = f32[4]{0} fusion(%a), kind=kLoop":
            {"count": 2, "seconds": 0.5},
        "%dot.2 = f32[4]{0} dot(%fusion.1, %a)": {"count": 2, "seconds": 1.0},
        "%copy.8 = f32[4]{0} copy(%a)": {"count": 2, "seconds": 0.5},
    }
    by_phase, found = scopes.seconds_by_phase(instructions, table)
    assert by_phase == {"optimizer": 0.5, "forward": 1.0, "other": 0.5}
    assert found == 0.75  # %copy.8 is not in the table
    by_part, found = scopes.seconds_by_part(instructions, table)
    assert by_part == {"hvd.model/mlp": 1.0} and found == 0.75


def test_grouped_matmul_is_filed_with_the_rows_it_multiplies():
    """The TPU compiler's ``ragged-dot*`` custom calls (what it makes of
    ``jax.lax.ragged_dot``) carry their own name as ``op_name`` and none
    of the program's scopes: `instruction_scopes` files each with the
    first of its operands that has a phase; the metadata kernel beside
    them, which has no such operand, stays as it is."""
    moe = PRE + "transpose(jvp(" + scopes.MOE + "))/gather"
    text = "\n".join([
        "ENTRY %main (a: s32[8]) -> bf16[64,8] {",
        '  %a = s32[8]{0} parameter(0), metadata={op_name="sizes"}',
        "  %ragged-dot-metadata.1 = (s32[9]{0}, s32[1]{0}) custom-call(%a), "
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-metadata"}',
        "  %get-tuple-element.3 = s32[9]{0} "
        "get-tuple-element(%ragged-dot-metadata.1), index=0",
        '  %fusion.4 = bf16[64,8]{1,0} fusion(%a), kind=kCustom, '
        'calls=%g, metadata={op_name="' + moe + '"}',
        "  ROOT %ragged-dot-none.2 = bf16[64,8]{1,0} custom-call("
        "%get-tuple-element.3, /*index=1*/%fusion.4, %a), "
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
        "}"])
    table = scopes.instruction_scopes(text)
    assert table["ragged-dot-none.2"] == moe
    assert scopes.phase_of(table["ragged-dot-none.2"]) == "backward"
    assert scopes.part_of(table["ragged-dot-none.2"]) == scopes.MOE
    assert table["ragged-dot-metadata.1"] == "ragged-dot-metadata"
    # the rows may reach it through the compiler's asynchronous copy, which
    # has no metadata: the matmul is filed with what was copied, the copy
    # itself stays unfiled
    moved = text.replace("/*index=1*/%fusion.4", "/*index=1*/%copy-done.6") \
        .replace("  ROOT %ragged", "\n".join([
            "  %copy-start.5 = (bf16[64,8]{1,0:S(1)}, bf16[64,8]{1,0}, u32[]) "
            "copy-start(%fusion.4)",
            "  %copy-done.6 = bf16[64,8]{1,0:S(1)} copy-done(%copy-start.5)",
            "  ROOT %ragged"]))
    table = scopes.instruction_scopes(moved)
    assert table["ragged-dot-none.2"] == moe
    assert table["copy-done.6"] == table["copy-start.5"] == ""


@pytest.mark.parametrize("opcode", ["while", "conditional"])
def test_a_loop_spans_its_body_and_is_counted_once(opcode):
    """A ``while``'s (or a ``conditional``'s) own event in a profile
    spans the events of its body (of the branch that ran): the sums
    leave it out, so the phases still add up to the step, and the body's
    instructions are filed by their own names, a backward rule's under
    the scope the rule names again."""
    fwd = PRE + "jvp(" + scopes.MOE + ")/while/body/gather"
    bwd = PRE + "transpose(jvp(hvd.model))/" + scopes.MOE + "/while/body/mul"
    calls = ("branch_computations={%skip, %chunk}" if opcode == "conditional"
             else "condition=%skip, body=%chunk")
    text = "\n".join([
        "%chunk (p: (bf16[64,8])) -> (bf16[64,8]) {",
        "  %p = (bf16[64,8]{1,0}) parameter(0)",
        "  %fusion.4 = bf16[64,8]{1,0} fusion(%p), kind=kCustom, calls=%g, "
        + named(fwd[len(PRE):]),
        "  %fusion.5 = bf16[64,8]{1,0} fusion(%fusion.4), kind=kLoop, "
        "calls=%h, " + named(bwd[len(PRE):]),
        "  ROOT %tuple.6 = (bf16[64,8]{1,0}) tuple(%fusion.5)",
        "}",
        "ENTRY %main (a: bf16[64,8]) -> (bf16[64,8]) {",
        "  %a = bf16[64,8]{1,0} parameter(0)",
        "  %t = (bf16[64,8]{1,0}) tuple(%a)",
        f"  ROOT %cond.7.clone = (bf16[64,8]{{1,0}}) {opcode}(%t), {calls}, "
        + named("jvp(" + scopes.MOE + ")/while"),
        "}"])
    table = scopes.instruction_scopes(text)
    assert table["cond.7.clone"] == scopes.SPANS_ITS_BODY
    assert (table["fusion.4"], table["fusion.5"]) == (fwd, bwd)
    instructions = {
        "%cond.7.clone = (bf16[64,8]{1,0}) " + opcode + "(%t)":
            {"count": 2, "seconds": 3.1},     # spans the two below
        "%fusion.4 = bf16[64,8]{1,0} fusion(%p), kind=kCustom":
            {"count": 2, "seconds": 2.0},
        "%fusion.5 = bf16[64,8]{1,0} fusion(%fusion.4), kind=kLoop":
            {"count": 2, "seconds": 1.0},
    }
    by_phase, found = scopes.seconds_by_phase(instructions, table)
    assert by_phase == {"forward": 2.0, "backward": 1.0} and found == 1.0
    by_part, found = scopes.seconds_by_part(instructions, table)
    assert by_part == {scopes.MOE: 3.0} and found == 1.0


def test_note_moe_notes_the_bound_and_its_chunks():
    record = scopes.StepRecord()
    with scopes.recording(record):
        scopes.note_moe(8, 64, 6, 98304, 16384)
        scopes.note_moe(8, 64, 6, 98304, 16384)
    scopes.note_moe(8, 64, 6, 98304, 16384)   # outside a traced step
    assert record.counters == {
        "moe_layers": 2, "experts_held": 8, "experts_total": 64,
        "experts_per_token": 6, "moe_buffer_rows": 98304,
        "moe_chunk_rows": 16384, "moe_chunks": 6}
    record = scopes.StepRecord()
    with scopes.recording(record):   # the last chunk padded past the bound
        scopes.note_moe(8, 64, 6, 98304, 15360)
    assert (record.counters["moe_chunk_rows"],
            record.counters["moe_chunks"]) == (15360, 7)


def test_note_loop_counts_what_its_body_notes_once_a_pass():
    """A scan's body is traced once whatever its trip count: inside
    `note_loop` an attention call and a kept block count ``steps``
    times; outside it, and after it, once; and the loop's own counters
    are what it was given. The multiplier works with no step being
    traced too (it is the counters that are a no-op there)."""
    record = scopes.StepRecord()
    with scopes.recording(record):
        scopes.note_attention(kernel=True, kept=True)
        scopes.note_kept(1_000_000)
        with scopes.note_loop(4, 2, 4):
            for _ in range(2):            # the body's two blocks, traced once
                scopes.note_attention(kernel=True, kept=True)
                scopes.note_kept(1_000_000)
            scopes.note_xent(16)          # the body's exit
        scopes.note_attention(kernel=False)
        scopes.note_xent(16)
    with scopes.note_loop(3, 1, 1):       # outside a traced step
        scopes.note_attention(kernel=True)
        scopes.note_xent(16)
    assert record.counters == {
        "attention_calls": 1 + 2 * 4 + 1, "attention_kernel_calls": 9,
        "attention_kept_calls": 9, "remat_kept_mb": pytest.approx(9.0),
        "xent_rows": 16 * 4 + 16,
        "loop_steps": 4, "loop_layers": 2, "loop_exits": 4}


def test_a_looped_step_is_filed_from_inside_its_loops():
    """A looped decoder's compiled step keeps its whole model inside two
    ``while`` bodies (the scan forward, its transpose backward): the
    loops' own events are left out (`SPANS_ITS_BODY`), the bodies'
    instructions carry every phase and every part of the model, the
    exits' among them, and the counters count every pass's blocks."""
    cfg = T.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=48,
        max_seq=16, remat=True, positions="layout", rope_layout=(1,),
        tie_embeddings=False, mlp="gated", n_loops=3, sandwich_norms=True,
        exit_beta=0.05)
    opt = hvd.DistributedOptimizer(optax.adamw(1e-3))

    def per_chip(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(T.lm_loss)(
            params, tokens, cfg, use_constraints=False)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, "hvd"))

    from jax.sharding import Mesh

    step = data_parallel_step(per_chip, mesh=Mesh(jax.devices()[:1], ("hvd",)))
    params = T.init(jax.random.PRNGKey(0), cfg)
    step.lower(params, opt.init(params), jnp.zeros((2, 17), jnp.int32))
    counters = dp.step_counters(step)
    assert (counters["loop_steps"], counters["loop_layers"],
            counters["loop_exits"]) == (3, 2, 3)
    assert counters["attention_calls"] == 2 * 3   # layers x passes
    assert counters["xent_rows"] == 2 * 16 * 3    # tokens x exits
    # the einsum path names nothing to keep: numbers all the same
    assert (counters["attention_kernel_calls"],
            counters["attention_kept_calls"],
            counters["remat_kept_mb"]) == (0, 0, 0.0)
    table = dp.scope_table(step)
    loops = [n for n, op in table.items() if op == scopes.SPANS_ITS_BODY]
    assert len(loops) >= 2
    in_a_body = {op for op in table.values() if "while/body" in op}
    phases = collections.Counter(scopes.phase_of(op) for op in in_a_body)
    assert {"forward", "backward", "recompute"} <= set(phases)
    parts = {scopes.part_of(op) for op in in_a_body}
    assert {scopes.ATTENTION, scopes.MLP, scopes.HEAD, scopes.EXIT} <= parts
    # the exit distribution and the expected loss come after the loop
    assert any(scopes.part_of(op) == scopes.EXIT and "while/body" not in op
               for op in table.values())


@pytest.fixture(scope="module")
def compiles():
    """Every trace and compile request JAX makes from here on, through
    ``jax.monitoring`` (a listener cannot be taken off again: one for
    the module)."""
    heard = []

    def listen(event, duration, **kw):
        if event.endswith(("jaxpr_trace_duration",
                           "backend_compile_duration")):
            heard.append(event)

    monitoring.register_event_duration_secs_listener(listen)
    return heard


def _lm_step(mesh):
    cfg = T.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                              n_layers=2, d_ff=64, max_seq=16, remat=True)
    opt = hvd.DistributedOptimizer(optax.adamw(1e-3))

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(T.lm_loss)(
            params, tokens, cfg, use_constraints=False)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, "hvd"))

    params = T.init(jax.random.PRNGKey(0), cfg)
    return (data_parallel_step(step, mesh=mesh), params,
            (params, opt.init(params), jnp.zeros((8, 17), jnp.int32)),
            {"forward", "backward", "recompute", "optimizer",
             "grad_exchange"})


def _resnet_step(mesh):
    model = ResNet(stage_sizes=[1, 1], num_filters=8, num_classes=10)
    opt = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))

    def step(state, opt_state, images, labels):
        params, stats = state

        def loss_fn(p):
            logits, upd = model.apply({"params": p, "batch_stats": stats},
                                      images, train=True,
                                      mutable=["batch_stats"])
            onehot = jax.nn.one_hot(labels, 10)
            return (-jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot,
                                      -1)), upd["batch_stats"])

        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return ((optax.apply_updates(params, updates), stats), opt_state,
                jax.lax.pmean(loss, "hvd"))

    images = jnp.zeros((8, 32, 32, 3), jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(0), images[:2], train=True)
    params = variables["params"]
    return (data_parallel_step(step, mesh=mesh, batch_argnums=(2, 3)),
            params,
            ((params, variables["batch_stats"]), opt.init(params), images,
             jnp.zeros((8,), jnp.int32)),
            {"forward", "backward", "optimizer", "grad_exchange"})


@pytest.mark.parametrize("build", [_lm_step, _resnet_step],
                         ids=["dense_lm", "resnet"])
def test_a_traced_step_describes_itself(build, compiles):
    mesh = hvd.global_process_set().mesh
    step, params, args, phases = build(mesh)
    assert dp.scope_table(step) is None and dp.step_counters(step) is None

    step.lower(*args)
    table = dp.scope_table(step)
    assert dp.scope_table() is table  # the step traced last is this one

    # every phase the model has is there; the phases partition the table
    count = collections.Counter(scopes.phase_of(v) for v in table.values())
    assert phases <= {p for p, n in count.items() if n}
    assert ("recompute" in count) == ("recompute" in phases)
    assert sum(count.values()) == len(table) and set(count) <= set(
        scopes.PHASES)
    parts = {scopes.part_of(v) for v in table.values()}
    assert {scopes.PACK, scopes.REDUCE, scopes.UNPACK} <= parts
    if build is _lm_step:
        assert {scopes.EMBED, scopes.ATTENTION, scopes.MLP,
                scopes.HEAD} <= parts

    # one fused float32 buffer, one collective, by hand from the tree
    nbytes = sum(p.size * 4 for p in jax.tree.leaves(params))
    counters = dp.step_counters(step)
    # the decoder notes where its attention went: off the TPU, never to
    # the fused kernels (jax.checkpoint traces a block once or several times, so
    # only the share of the two means anything)
    attention = {k: counters.pop(k) for k in list(counters)
                 if k.startswith(("attention", "remat"))}
    if build is _lm_step:
        assert attention["attention_calls"] >= 1
        assert attention["attention_kernel_calls"] == 0
        # no kernel, so nothing named for the blocks' checkpoint to keep
        assert attention["attention_kept_calls"] == 0
        assert attention["remat_kept_mb"] == 0.0
        # the loss takes a chip's tokens: 8 x 16 over the mesh
        assert counters.pop("xent_rows") == 8 * 16 // mesh.devices.size
    else:
        assert attention == {}
        assert "xent_rows" not in counters
    assert counters == {
        "collectives": 1, "collective_bytes": nbytes,
        "packed_bytes": nbytes, "axis_size": mesh.devices.size}

    before = len(compiles)
    assert dp.scope_table(step) is table
    assert len(compiles) == before  # the second call compiles nothing
    assert before > 0               # and the listener does hear compiles


def test_unfused_exchange_counts_a_collective_per_leaf():
    mesh = hvd.global_process_set().mesh
    opt = hvd.DistributedOptimizer(optax.sgd(0.1), fuse_buckets=False)
    params = {"a": jnp.ones((3, 5)), "b": jnp.ones((7,))}

    def step(params, opt_state, x):
        grads = jax.grad(lambda p: jnp.sum(p["a"]) * jnp.sum(x)
                         + jnp.sum(p["b"]))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, jnp.sum(x)

    step = data_parallel_step(step, mesh=mesh)
    step.lower(params, opt.init(params), jnp.ones((8, 2)))
    assert dp.step_counters(step) == {
        "collectives": 2, "collective_bytes": (15 + 7) * 4,
        "packed_bytes": 0, "axis_size": mesh.devices.size}
    phases = {scopes.phase_of(v) for v in dp.scope_table(step).values()}
    assert {"grad_exchange", "optimizer"} <= phases


def _exchange_reading(wrapper):
    """What one of ``opt/``'s exchange builders puts under
    ``hvd.grad_exchange`` on a four-member axis, for a tree with two
    dtypes, leaves the quantized wire's guardrails keep off it and a
    scalar: the counters, and the instructions of the compiled module
    by part (``"<stem of the name>:<how many>"``, sorted)."""
    from jax.sharding import Mesh

    kw = {"fused": {},
          "int8": {"compression": hvd.Compression.int8.with_options(
              error_feedback=False)},
          "int8_ef": {"compression": hvd.Compression.int8},
          "zero1": {"sharded_update": True, "num_shards": 4,
                    "min_shard_elems": 100}}[wrapper]
    opt = hvd.DistributedOptimizer(optax.sgd(0.1), **kw)
    params = {"dense": {"kernel": jnp.ones((96, 64)), "bias": jnp.ones(64)},
              "wide": {"kernel": jnp.ones((71, 59))},  # 10,333 with dense's
              "proj": {"kernel": jnp.ones((80, 64), jnp.bfloat16)},
              "norm": {"scale": jnp.ones(64, jnp.bfloat16)},
              "step": jnp.ones(())}

    def step(params, opt_state, x):
        grads = jax.grad(lambda p: jnp.sum(x) * sum(
            jnp.sum(l.astype(jnp.float32) ** 2)
            for l in jax.tree.leaves(p)))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, jnp.sum(x)

    step = data_parallel_step(step, mesh=Mesh(jax.devices()[:4], ("hvd",)))
    step.lower(params, opt.init(params), jnp.ones((8, 2)))
    count = collections.defaultdict(collections.Counter)
    for name, op_name in dp.scope_table(step).items():
        part = scopes.part_of(op_name)
        if part and part.startswith(scopes.GRAD_EXCHANGE):
            count[part.rsplit("/", 1)[1]][name.rstrip("0123456789.")] += 1
    return dp.step_counters(step), {
        part: " ".join(f"{k}:{n}" for k, n in sorted(names.items()))
        for part, names in count.items()}


@pytest.mark.parametrize("wrapper", ["fused", "int8", "int8_ef", "zero1"])
def test_every_exchange_builder_keeps_its_scopes_and_counters(wrapper):
    """``collectives`` and ``collective_bytes`` are PR 30's tree's (the
    parent of the PR that gave the builders one packer); so are the
    instructions, and ``packed_bytes`` but for ZeRO-1's (see the pins)."""
    counters, instructions = _exchange_reading(wrapper)
    want_counters, want_instructions = _EXCHANGE_PINS[wrapper]
    assert counters == dict(zip(
        ("collectives", "collective_bytes", "packed_bytes", "axis_size"),
        want_counters))
    assert instructions == want_instructions


#: (collectives, collective_bytes, packed_bytes, axis_size), instructions.
#: ZeRO-1's ``packed_bytes`` read 51,584 at the parent, which counted its
#: one-leaf bfloat16 group as packed; `_pack`'s rule (more than one leaf)
#: is the plain fused path's, the one the benchmark's cells read.
_EXCHANGE_PINS = {
    "fused": ((2, 51960, 51960, 4), {
        "pack": "bitcast_concatenate_fusion:2 concatenate:2",
        "reduce": "add:1 add_convert_fusion:2 all-reduce:1 "
                  "bitcast_add_fusion:3 broadcast:6 convert:2 "
                  "get-tuple-element:2 multiply:6 multiply_add_fusion:1 "
                  "psum:2",
        "unpack": "slice:6"}),
    "int8": ((3, 51960, 51960, 4), {
        "pack": "bitcast_concatenate_fusion:2 concatenate:3",
        "reduce": "abs:2 add:1 add_convert_fusion:2 all-reduce:1 all-to-all:2 "
                  "all_gather:2 bitcast:13 bitcast_abs_fusion:1 "
                  "bitcast_add_fusion:3 bitcast_slice_fusion:3 broadcast:11 "
                  "broadcast_divide_fusion:1 broadcast_in_dim:7 clamp:5 "
                  "concatenate:2 concatenate_pad_fusion:1 convert:4 "
                  "convert_bitcast_fusion:2 convert_convert_fusion:4 "
                  "convert_element_type:20 div:4 get-tuple-element:2 gt:12 max:5 "
                  "min:5 mul:6 multiply:13 multiply_abs_fusion:1 "
                  "multiply_add_fusion:1 multiply_reduce_fusion:1 pad:1 psum:2 "
                  "reduce:1 reduce_max:14 reduce_sum:3 round:5 select_n:7 slice:8 "
                  "slice_bitcast_fusion:1 wrapped_reduce:2",
        "unpack": "slice:6"}),
    "int8_ef": ((3, 51960, 51960, 4), {
        "pack": "bitcast_concatenate_fusion:2 concatenate:3",
        "reduce": "abs:2 add:2 add_convert_fusion:2 add_pad_fusion:1 all-reduce:1 "
                  "all-to-all:2 all_gather:2 bitcast:16 bitcast_abs_fusion:1 "
                  "bitcast_add_fusion:3 bitcast_slice_fusion:4 broadcast:12 "
                  "broadcast_divide_fusion:1 broadcast_in_dim:8 clamp:6 "
                  "concatenate:2 convert:4 convert_bitcast_fusion:2 "
                  "convert_convert_fusion:4 convert_element_type:24 div:5 "
                  "get-tuple-element:2 gt:14 max:6 min:6 mul:7 multiply:14 "
                  "multiply_abs_fusion:1 multiply_add_fusion:1 "
                  "multiply_reduce_fusion:1 pad:1 psum:2 reduce:1 reduce_max:14 "
                  "reduce_sum:3 round:6 select_n:8 slice:9 slice_bitcast_fusion:1 "
                  "sub:1 wrapped_reduce:2",
        "unpack": "slice:6"}),
    "zero1": ((7, 64868, 41344, 4), {
        "pack": "bitcast:1 concatenate:1 concatenate_pad_fusion:1 "
                "convert_bitcast_fusion:1 pad:1",
        "reduce": "add:2 add_convert_fusion:1 all-reduce:1 all_gather:2 "
                  "broadcast:1 convert:2 get-tuple-element:3 multiply:1 "
                  "psum:2 reduce_scatter:4",
        "unpack": "bitcast:2 bitcast_add_fusion:2 slice:2"}),
}


@pytest.mark.parametrize("build", ["dense_lm", "fused", "unfused"])
def test_one_member_axis_builds_no_exchange(build):
    """On a one-device mesh the wrapper builds nothing around the
    gradients: no scope, no flat buffer, no collective; the counters say
    so with numbers (a reader of a missing key would read None)."""
    from jax.sharding import Mesh

    mesh = Mesh(jax.devices()[:1], ("hvd",))
    if build == "dense_lm":
        step, params, args, phases = _lm_step(mesh)
    else:
        opt = hvd.DistributedOptimizer(optax.sgd(0.1),
                                       fuse_buckets=build == "fused")
        params = {"a": jnp.ones((3, 5)), "b": jnp.ones((7,))}

        def step(params, opt_state, x):
            grads = jax.grad(lambda p: jnp.sum(p["a"]) * jnp.sum(x)
                             + jnp.sum(p["b"]))(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, jnp.sum(x)

        step = data_parallel_step(step, mesh=mesh)
        args = (params, opt.init(params), jnp.ones((8, 2)))
        phases = {"optimizer"}
    text = step.lower(*args).as_text(debug_info=True)
    counters = dp.step_counters(step)
    # the decoder's loss: every token of the batch (8 x 16) on the one chip
    assert counters.pop("xent_rows", None) == (
        8 * 16 if build == "dense_lm" else None)
    counters = {k: v for k, v in counters.items()
                if not k.startswith(("attention", "remat"))}
    assert counters == {"collectives": 0, "collective_bytes": 0,
                        "packed_bytes": 0, "axis_size": 1}
    table = dp.scope_table(step)
    assert {scopes.phase_of(v) for v in table.values()} >= (
        phases - {"grad_exchange"})
    assert not any(scopes.GRAD_EXCHANGE in v for v in table.values())
    assert scopes.GRAD_EXCHANGE not in text
    if build != "dense_lm":  # the decoder's own step averages its loss
        for op in ("concatenate", "all_reduce", "all-reduce", "psum"):
            assert op not in text, op


@pytest.mark.parametrize("routed,want", [
    ([True, True, True], {"attention_calls": 3, "attention_kernel_calls": 3}),
    ([False, False], {"attention_calls": 2, "attention_kernel_calls": 0}),
    ([True, False], {"attention_calls": 2, "attention_kernel_calls": 1}),
    ([], None),
], ids=["kernels", "einsum", "mixed", "no-decoder"])
def test_attention_counters_note_where_each_call_went(routed, want):
    """`note_attention` counts into the step being traced, the calls and
    those of them the fused kernels took (``attention_kernel_pct`` is
    their share), with zeros for what no checkpoint kept, and is a no-op
    with no step being traced."""
    want = {} if want is None else dict(
        want, attention_kept_calls=0, remat_kept_mb=0.0)
    record = scopes.StepRecord()
    with scopes.recording(record):
        for kernel in routed:
            scopes.note_attention(kernel=kernel)
    scopes.note_attention(kernel=True)   # outside a traced step
    assert record.counters == want


def test_latent_and_layer_counters_are_there_only_where_noted():
    """A latent call counts as an attention call too; the layer counters
    count a layer each; a decoder that notes neither keeps the counters
    it had (no ``attention_latent_calls``, ``dense_layers`` or
    ``shared_experts`` key at all)."""
    record = scopes.StepRecord()
    with scopes.recording(record):
        scopes.note_attention(kernel=True, kept=True, latent=True)
        scopes.note_attention(kernel=False, latent=True)
        scopes.note_layer("dense_layers")
        scopes.note_layer("shared_experts")
        scopes.note_layer("shared_experts")
    scopes.note_layer("dense_layers")    # outside a traced step
    assert record.counters == {
        "attention_calls": 2, "attention_kernel_calls": 1,
        "attention_kept_calls": 1, "remat_kept_mb": 0.0,
        "attention_latent_calls": 2, "dense_layers": 1, "shared_experts": 2}
    assert set(scopes.KEPT_BY_REMAT) < set(scopes.KEPT_BY_REMAT_LATENT)


def test_the_sparse_counter_is_there_only_where_noted():
    """A call over each query's selection counts as an attention call
    too, and its indexer's kernels as an ``indexer_kernel_calls`` (a
    number on either path); a decoder that notes none keeps the counters
    it had; the kept selection's names are none of the kernels' or the
    router's."""
    record = scopes.StepRecord()
    with scopes.recording(record):
        scopes.note_attention(kernel=True, kept=True, sparse=True,
                              indexer_kernel=True)
        scopes.note_attention(kernel=False, sparse=True)
    assert record.counters == {
        "attention_calls": 2, "attention_kernel_calls": 1,
        "attention_kept_calls": 1, "remat_kept_mb": 0.0,
        "attention_sparse_calls": 2, "indexer_kernel_calls": 1}
    record = scopes.StepRecord()
    with scopes.recording(record):
        scopes.note_attention(kernel=True)
    assert "attention_sparse_calls" not in record.counters
    assert "indexer_kernel_calls" not in record.counters
    assert not set(scopes.KEPT_SELECTION) & {
        *scopes.KEPT_BY_REMAT_LATENT, scopes.KEPT_CHOICE,
        scopes.KEPT_ROUTING, *scopes.KEPT_XENT}


@pytest.mark.parametrize("path,remat,kept", [
    ("kernels", True, True), ("einsum", True, False),
    ("kernels", False, False)], ids=["kept", "einsum", "no-remat"])
def test_kept_counters_say_what_the_blocks_checkpoint_keeps(
        path, remat, kept, monkeypatch):
    """A checkpointed block keeps what the fused backward kernels read,
    and the step says so: every attention call noted as kept, and
    ``remat_kept_mb`` the bytes of `flash_attention`'s five residuals
    over the blocks (here against the arrays its forward rule really
    hands on). On the einsum path nothing carries a name and without
    ``remat`` there is no checkpoint: 0 and 0.0, numbers both."""
    import importlib

    from jax.sharding import Mesh

    F = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(T, "_on_tpu", lambda: path == "kernels")
    monkeypatch.setattr(T, "FUSED_ATTENTION_MIN_SEQ", 256)
    monkeypatch.setattr(F, "BLOCKS", (128,))
    cfg = T.TransformerConfig(vocab_size=64, d_model=256, n_heads=2,
                              n_kv_heads=1, n_layers=3, d_ff=64, max_seq=256,
                              remat=remat)
    step = data_parallel_step(
        lambda p, t: (p, jax.grad(T.lm_loss)(p, t, cfg,
                                             use_constraints=False)),
        mesh=Mesh(jax.devices()[:1], ("hvd",)), batch_argnums=(1,),
        donate_argnums=())
    step.lower(jax.eval_shape(lambda: T.init(jax.random.PRNGKey(0), cfg)),
               jax.ShapeDtypeStruct((2, 257), jnp.int32))
    counters = dp.step_counters(step)
    assert counters["attention_kernel_calls"] == (
        counters["attention_calls"] if path == "kernels" else 0)
    if not kept:
        assert (counters["attention_kept_calls"],
                counters["remat_kept_mb"]) == (0, 0.0)
        assert isinstance(counters["remat_kept_mb"], float)
        return
    assert counters["attention_kept_calls"] == counters["attention_calls"] > 0
    q, k = (jax.ShapeDtypeStruct((2, 256, heads * 128), cfg.dtype)
            for heads in (2, 1))
    residuals = jax.eval_shape(
        lambda q, k, v: F._fwd(q, k, v, True, 128, 128, 2, None, 1)[1],
        q, k, k)
    a_block = sum(r.size * r.dtype.itemsize for r in residuals)
    assert a_block == 2 * 256 * (2 * (256 + 128) * 2 + 4 * 2)
    assert counters["remat_kept_mb"] == pytest.approx(3 * a_block / 1e6)


# -- set-up, seen from inside the program ----------------------------------

class _Clock:
    """``time`` for `scopes`, its ``perf_counter`` reading the given
    ticks in turn."""

    def __init__(self, *ticks):
        self.ticks = iter(ticks)

    def perf_counter(self):
        return next(self.ticks)


def _spans(name):
    return [s for s in scopes.setup_record()["spans"] if s["name"] == name]


def test_spans_nest_by_thread_and_keep_their_self_time(monkeypatch):
    monkeypatch.setattr(scopes, "time",
                        _Clock(10.0, 11.0, 13.0, 14.0, 14.5, 15.0, 16.0,
                               20.0))
    with scopes.span("test.outer", what="set-up"):
        with scopes.span("test.a"):
            pass
        with scopes.span("test.b"):
            with scopes.span("test.c"):
                pass
    (outer,), (a,), (b,), (c,) = map(
        _spans, ("test.outer", "test.a", "test.b", "test.c"))
    assert outer["parent"] is None and outer["attrs"] == {"what": "set-up"}
    assert (a["parent"], b["parent"], c["parent"]) == (
        outer["id"], outer["id"], b["id"])
    assert (outer["start"], outer["end"]) == (10.0, 20.0)
    assert [s["self_s"] for s in (outer, a, b, c)] == [6.0, 2.0, 1.5, 0.5]


def test_a_first_call_leaves_its_compile_spans_and_a_second_none():
    def only_here(x):
        return x * 3.0 - 1.0

    f, x = jax.jit(only_here), jnp.arange(5.0)
    x.block_until_ready()
    before = scopes.setup_record()["counters"]
    with scopes.span("test.first_call"):
        f(x).block_until_ready()
    with scopes.span("test.second_call"):
        f(x).block_until_ready()
    after = scopes.setup_record()["counters"]
    (first,), (second,) = _spans("test.first_call"), _spans("test.second_call")
    spans = scopes.setup_record()["spans"]
    # the jitted operations it calls are traced inside its trace: no
    # spans of their own
    children = [s for s in spans if s["parent"] == first["id"]]
    assert sorted(s["name"] for s in children) == [
        scopes.COMPILE_LOWER, scopes.COMPILE_TRACE, scopes.COMPILE_XLA]
    assert all("only_here" in s["attrs"]["fun_name"] for s in children)
    assert all(first["start"] <= s["start"] <= s["end"] <= first["end"]
               for s in children)
    assert after["compile_requests"] - before["compile_requests"] == 1
    assert not [s for s in spans if s["parent"] == second["id"]]
    assert second["self_s"] == second["end"] - second["start"]


def test_the_persistent_cache_verdicts_are_counted(persistent_cache):
    def cached_here(x):
        return jnp.sin(x) * 2.0

    f, x = jax.jit(cached_here), jnp.arange(3.0)
    x.block_until_ready()
    before = scopes.setup_record()["counters"]
    f(x).block_until_ready()
    written = scopes.setup_record()["counters"]
    jax.clear_caches()
    f(x).block_until_ready()
    after = scopes.setup_record()["counters"]
    assert (written["cache_misses"] - before["cache_misses"],
            written["cache_hits"] - before["cache_hits"]) == (1, 0)
    assert (after["cache_hits"] - written["cache_hits"],
            after["cache_misses"] - written["cache_misses"]) == (1, 0)
    compiles = [s for s in _spans(scopes.COMPILE_XLA)
                if "cached_here" in s["attrs"]["fun_name"]][-2:]
    assert [(s["attrs"]["cache_misses"], s["attrs"]["cache_hits"])
            for s in compiles] == [(1, 0), (0, 1)]


def test_the_split_of_set_up_closes_on_a_toy_record():
    def span(name, start, end, ident, parent=None):
        return {"id": ident, "name": name, "start": start, "end": end,
                "parent": parent, "attrs": {}, "self_s": 0.0}

    record = {"counters": {}, "spans": [
        span(scopes.SETUP_IMPORT, 1.0, 4.0, 0),
        span(scopes.COMPILE_TRACE, 5.1, 5.2, 2, parent=1),
        span(scopes.SETUP_INIT, 5.0, 5.5, 1),
        span(scopes.COMPILE_TRACE, 6.2, 6.5, 3),  # traced inside the next
        span(scopes.COMPILE_TRACE, 6.0, 7.0, 4),
        span(scopes.COMPILE_LOWER, 7.0, 7.5, 5),
        span(scopes.COMPILE_XLA, 7.5, 9.0, 6),
        span(scopes.COMPILE_XLA, 12.0, 13.0, 7),  # after the window opened
    ]}
    split = scopes.setup_split(record, until=11.0)
    assert split["total"] == 10.0
    assert abs(sum(v for k, v in split.items() if k != "total")
               - split["total"]) < 1e-3
    want = {scopes.SETUP_IMPORT: 3.0, scopes.SETUP_INIT: 0.4,
            scopes.COMPILE_TRACE: 1.1, scopes.COMPILE_LOWER: 0.5,
            scopes.COMPILE_XLA: 1.5, scopes.UNATTRIBUTED: 3.5}
    assert {k: round(v, 9) for k, v in split.items()
            if k != "total"} == want
    assert scopes.setup_split({"spans": [], "counters": {}}, 1.0) == {}
