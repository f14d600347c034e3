"""``moe_gathered_rows``' reader: the rows that a made-up trace's row
gathers under ``hvd.model/moe`` read a step, a loop's body's events
counted as often as they ran, and nothing of a gather elsewhere, of an
instruction that is no gather, of a grouped matmul filed under the
gather of its rows (as `instruction_scopes` files it) or of a gather of
single elements."""

import pytest

from chipbench import run, step_split

PRE = "jit(hvd_data_parallel_step)/hvd.step/"
TABLE = {
    # chunk 0's gather of the tokens' rows, forward and backward
    "fusion.1": PRE + "jvp(hvd.model/moe)/gather",
    "fusion.2": PRE + "transpose(jvp(hvd.model))/hvd.model/moe/gather",
    # the further chunks' gather, in a loop's body
    "fusion.3": PRE + "jvp(hvd.model/moe)/while/body/gather",
    # not counted: no gather, another part, single elements
    "fusion.4": PRE + "jvp(hvd.model/moe)/ragged_dot_general",
    "fusion.5": PRE + "jvp(hvd.model/attention)/gather",
    "fusion.6": PRE + "jvp(hvd.model/moe)/gather",
    "ragged-dot-none.8": PRE + "jvp(hvd.model/moe)/gather",
    "fusion.9": PRE + "jvp(hvd.model/moe)/gather",
    "while.7": "hvd.spans_its_body",
}
SHAPES = {"fusion.6": "s32[16384]{0}", "fusion.4": "bf16[16384,768]{1,0}",
          "ragged-dot-none.8": "bf16[16384,1536]{1,0}",
          "fusion.9": "f32[16384,1]{0,1}"}


def trace_of(times: dict, steps: int = 3) -> dict:
    return {"devices": [{"steps": steps, "instructions": {
        f"%{name} = {SHAPES.get(name, 'bf16[16384,2560]{1,0:T(8,128)(2,1)}')}"
        f" fusion(%a, %b), kind=kCustom": {"count": steps * n,
                                           "seconds": 0.001 * steps * n}
        for name, n in times.items() if n}}]}


@pytest.fixture
def table(monkeypatch):
    monkeypatch.setattr(step_split, "table", lambda: TABLE)


def read(trace):
    return run.load_module("layer_metrics", "moe_gathered_rows").read(
        trace, {}, {})


@pytest.mark.parametrize("further", [0, 1, 5])
def test_rows_a_step_with_the_loops_trips(table, further):
    every = {name: 1 for name in TABLE}
    assert read(trace_of({**every, "fusion.3": further})) == \
        16384 * (2 + further)


def test_none_where_nothing_is_gathered(table, monkeypatch):
    assert read(trace_of({"fusion.4": 1, "fusion.5": 1})) is None
    assert read({"devices": []}) is None     # a CPU rehearsal
    monkeypatch.setattr(step_split, "table", lambda: None)
    assert read(trace_of({"fusion.1": 1})) is None
