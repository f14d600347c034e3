"""``attention_kept_pct``'s reader: exact shares on hand-made counters,
None wherever the program has nothing to read (a parent that notes no
``attention_kept_calls`` among them), and the toy LM cell's own step as
the CPU traces it (no kernel off the TPU, so nothing is kept)."""

import os

import pytest

from chipbench import run, step_split

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
ONE_DEVICE = {"devices": [{"steps": 2, "instructions": {}}]}


def read(trace):
    return run.load_module("layer_metrics", "attention_kept_pct").read(
        trace, {}, {})


@pytest.fixture
def counters(monkeypatch):
    """Stand in for the program's ``dp.step_counters()``."""
    class Program:
        noted = None

        @classmethod
        def step_counters(cls):
            return cls.noted

    monkeypatch.setattr(step_split, "program", lambda: (None, Program))
    return Program


@pytest.mark.parametrize("noted,share", [
    ({"attention_calls": 2, "attention_kept_calls": 2}, 100.0),
    ({"attention_calls": 4, "attention_kept_calls": 1}, 25.0),
    ({"attention_calls": 2, "attention_kept_calls": 0}, 0.0),
    # a program from before the counter (the parent): not 0, not an error
    ({"attention_calls": 2, "attention_kernel_calls": 2}, None),
    ({"collectives": 1}, None),    # a step with no decoder in it (ResNet)
    ({}, None), (None, None),      # nothing noted; no step traced
])
def test_share_of_the_noted_calls(counters, noted, share):
    counters.noted = noted
    assert read(ONE_DEVICE) == share
    assert read({"devices": []}) is None  # a CPU rehearsal has no device


def test_none_without_the_programs_counters(monkeypatch):
    monkeypatch.setattr(step_split, "program", lambda: None)
    assert read(ONE_DEVICE) is None


@pytest.mark.parametrize("cell", ["cerebras-gpt-1.3b.dp1", "resnet50.dp1"])
def test_on_the_toy_step(cell):
    """The cell's toy step, traced here: the LM's attention is noted and
    none of it is kept where no kernel runs; ResNet notes none."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    _, config, workload = run.load_cell(bench, cell, TOY)
    family = run.load_module("families", config["family"])
    mesh = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    step, shapes = family.abstract_step(config, workload, chips=1, mesh=mesh)
    step.lower(*shapes)
    assert read(ONE_DEVICE) == (0.0 if config["family"] == "dense_lm"
                                else None)
