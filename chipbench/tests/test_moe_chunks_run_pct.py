"""``moe_chunks_run_pct``'s reader: the forward pass's grouped matmuls
that a made-up trace ran over those a made-up module could run, and None
wherever the program has nothing to read (a parent that notes no
``moe_chunks`` among them)."""

import pytest

from chipbench import run, step_split
from horovod_tpu.utils import scopes

PRE = "jit(hvd_data_parallel_step)/hvd.step/"
FORWARD = PRE + "jvp(hvd.model/moe)/gather"
RECOMPUTE = PRE + "checkpoint/rematted_computation/hvd.model/moe/gather"
BACKWARD = PRE + "transpose(jvp(hvd.model))/hvd.model/moe/gather"


def read(trace):
    return run.load_module("layer_metrics", "moe_chunks_run_pct").read(
        trace, {}, {})


def module(layers: int) -> dict:
    """The table of a step with two forward, two recomputed and four
    backward grouped matmuls a layer for chunk 0, as many again in the
    bodies of the loops over the further chunks, and other instructions."""
    table, n = {"fusion.1": FORWARD, "ragged-dot-metadata.1": ""}, 0
    for _ in range(layers):
        for op_name in [FORWARD] * 2 + [RECOMPUTE] * 2 + [BACKWARD] * 4:
            for where in ("/", "/while/body/"):
                n += 1
                table[f"ragged-dot-none.{n}"] = op_name.replace(
                    "/gather", where + "gather")
    return table


def trace_of(table: dict, live: int, steps: int = 3) -> dict:
    """A device on which, every step, the instructions outside the loops
    ran once and those in their bodies once for each further live chunk."""
    return {"devices": [{"steps": steps, "instructions": {
        f"%{name} = bf16[64,8]{{1,0}} custom-call(%a, %b)":
            {"count": steps * times, "seconds": 0.1 * steps * times}
        for name, op_name in table.items()
        for times in [live - 1 if "while/body" in op_name else 1]
        if times > 0}}]}


@pytest.fixture
def program(monkeypatch):
    """Stand in for the program's ``dp.step_counters()`` and
    ``dp.scope_table()``."""
    class Program:
        noted, table = None, None

        @classmethod
        def step_counters(cls):
            return cls.noted

    monkeypatch.setattr(step_split, "program", lambda: (scopes, Program))
    monkeypatch.setattr(step_split, "table", lambda: Program.table)
    return Program


@pytest.mark.parametrize("live,share", [(1, 100 / 6), (2, 100 / 3),
                                        (6, 100.0)])
def test_share_of_the_chunks_that_ran(program, live, share):
    """Four layers of six chunks: chunk 0 and the ``live - 1`` further
    chunks of every layer ran."""
    program.noted = {"moe_chunks": 6, "moe_chunk_rows": 16384}
    program.table = module(layers=4)
    assert read(trace_of(program.table, live)) == pytest.approx(share)
    assert read({"devices": []}) is None  # a CPU rehearsal has no device


def test_a_layer_of_one_chunk_has_no_loop(program):
    program.noted = {"moe_chunks": 1, "moe_chunk_rows": 64}
    program.table = {k: v for k, v in module(layers=2).items()
                     if "while/body" not in v}
    assert read(trace_of(program.table, 1)) == 100.0


@pytest.mark.parametrize("noted", [
    # a program from before the counter (the parent): not 0, not an error
    {"moe_layers": 4, "moe_buffer_rows": 98304},
    {"collectives": 1}, {}, None])
def test_none_without_the_counter(program, noted):
    program.noted = noted
    program.table = module(layers=4)
    assert read(trace_of(program.table, 6)) is None


def test_none_without_the_programs_table(program, monkeypatch):
    program.noted = {"moe_chunks": 6}
    assert read(trace_of(module(1), 6)) is None
    monkeypatch.setattr(step_split, "program", lambda: None)
    assert read(trace_of(module(1), 6)) is None
