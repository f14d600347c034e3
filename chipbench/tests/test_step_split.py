"""The step-split readers on a hand-made ``instructions`` dictionary with
a hand-made table: exact numbers, a partition, and None wherever the
program has nothing to read."""

import pytest

from chipbench import run, step_split

PRE = "jit(hvd_data_parallel_step)/shard_map/hvd.step/"
TABLE = {
    "fusion.1": PRE + "jvp(hvd.model/attention)/dot_general",
    "fusion.2": PRE + "transpose(jvp(hvd.model/mlp))/dot_general",
    "fusion.3": PRE + "transpose(jvp(hvd.step))/jvp()/checkpoint/"
                      "rematted_computation/hvd.model/attention/exp",
    "fusion.4": PRE + "hvd.optimizer/mul",
    "concatenate.5": PRE + "hvd.grad_exchange/pack/concatenate",
    "all-reduce.6": PRE + "hvd.grad_exchange/reduce/psum",
    "slice.7": PRE + "hvd.grad_exchange/unpack/slice",
    "copy.8": "",
}
#: seconds in a steady window of 2 steps, on each of two devices
SECONDS = {"fusion.1": 0.020, "fusion.2": 0.040, "fusion.3": 0.010,
           "fusion.4": 0.004, "concatenate.5": 0.002, "all-reduce.6": 0.006,
           "slice.7": 0.001, "copy.8": 0.003}
#: milliseconds per step
EXPECTED = {"forward_ms": 10.0, "backward_ms": 20.0, "recompute_ms": 5.0,
            "optimizer_ms": 2.0, "grad_exchange_ms": 4.5, "grad_pack_ms": 1.5,
            "attention_ms": 15.0, "unscoped_pct": 0.003 / 0.086 * 100,
            "grad_exchange_calls": 1, "grad_exchange_mb": 2.5}
COUNTERS = {"collectives": 1, "collective_bytes": 2_500_000,
            "packed_bytes": 2_500_000, "axis_size": 4}


def trace(extra=None):
    instructions = {f"%{name} = f32[8]{{0}} something(%x)":
                    {"count": 2, "seconds": s}
                    for name, s in {**SECONDS, **(extra or {})}.items()}
    return {"devices": [{"steps": 2, "instructions": instructions}] * 2}


class Program:
    """Stands in for ``horovod_tpu.parallel.dp``."""

    def __init__(self, table=TABLE, counters=COUNTERS):
        self.scope_table = lambda: table
        self.step_counters = lambda: counters


@pytest.fixture
def program(monkeypatch):
    def use(dp):
        from horovod_tpu.utils import scopes

        step_split.table.cache_clear()
        monkeypatch.setattr(step_split, "program",
                            lambda: dp and (scopes, dp))
    yield use
    step_split.table.cache_clear()


def read(name, trace_):
    return run.load_module("layer_metrics", name).read(trace_, {}, {})


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_exact_number(program, name):
    program(Program())
    assert read(name, trace()) == pytest.approx(EXPECTED[name], rel=1e-12)


def test_the_phases_partition_the_instructions_seconds(program):
    program(Program())
    phases = ("forward_ms", "backward_ms", "recompute_ms", "optimizer_ms",
              "grad_exchange_ms")
    total_ms = sum(SECONDS.values()) / 2 * 1e3
    other_ms = read("unscoped_pct", trace()) / 100 * total_ms
    assert sum(read(p, trace()) for p in phases) + other_ms \
        == pytest.approx(total_ms, rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECTED))
@pytest.mark.parametrize("why", ["no program API", "no step traced",
                                 "module does not match", "no device"])
def test_reader_returns_none_where_there_is_nothing_to_read(
        program, capsys, name, why):
    traced = trace()
    if why == "no program API":       # the parent commit
        program(None)
    elif why == "no step traced":
        program(Program(table=None, counters=None))
    elif why == "module does not match":
        program(Program())
        traced = trace({"fusion.99": 0.002})  # 2.3% not in the table
    else:
        program(Program())
        traced = {"devices": []}
    counter = name in ("grad_exchange_calls", "grad_exchange_mb")
    if counter and why == "module does not match":
        assert read(name, traced) == EXPECTED[name]  # counters need no table
    else:
        assert read(name, traced) is None
    said = capsys.readouterr().out
    assert ("no step split" in said) == (
        name == "unscoped_pct" and why == "module does not match")


def test_a_table_that_raises_is_said_once_and_reads_none(program, capsys):
    def boom():
        raise RuntimeError("compile failed")

    dp = Program()
    dp.scope_table = boom
    program(dp)
    assert read("forward_ms", trace()) is None
    assert read("backward_ms", trace()) is None
    assert capsys.readouterr().out.count("dp.scope_table() raised") == 1
