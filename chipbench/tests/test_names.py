"""Every name in BENCHMARK.json resolves to its files and keeps to the
contract's characters; no width of a configuration differs from its
source."""

import json
import os
import re

import pytest

from chipbench import flops, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return run.load_json(run.ROOT, "BENCHMARK.json")


def exists(*parts):
    return os.path.isfile(os.path.join(run.HERE, *parts))


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    size = os.path.getsize(os.path.join(run.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_configs_resolve(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        config = run.load_json(run.ROOT, c["file"])
        assert config["name"] == c["name"]
        assert config["reduced"] == c["reduced"]
        assert all(NAME.match(k) for k in c["reduced"])
        assert exists("families", config["family"] + ".py")
        assert exists("tests", "toy", "configs", c["name"] + ".json")
        for key in ("source", "published", "sizes", "assumed", "departures",
                    "deployment", "optimizer", "compiler_memory"):
            assert key in config, (c["name"], key)
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        for text in (c["why"], c["source"]):
            assert 1 <= len(text) <= 200 and "\n" not in text


def test_no_width_differs_from_the_source(bench):
    """Only the keys in ``reduced`` may differ, and none of them is a
    width."""
    widths = re.compile(r"(_dim|_rank|n_embd|n_inner|n_head|hidden|"
                        r"intermediate|num_filters|vocab)")
    for c in bench["configs"]:
        config = run.load_json(run.ROOT, c["file"])
        for key, value in config["sizes"].items():
            if key in config["reduced"]:
                assert not widths.search(key), key
                assert value != config["published"][key]
            else:
                assert value == config["published"][key], (c["name"], key)


def test_cells_resolve(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert exists("workloads", w["name"] + ".json")
        assert exists("tests", "toy", "workloads", w["name"] + ".json")
        run.load_cell(bench, w["name"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(names) // 4)


def test_metrics_resolve(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    seen = set()
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert exists("layer_metrics", m["name"] + ".py")
        assert hasattr(run.load_module("layer_metrics", m["name"]), "read")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in seen
        seen.add(m["name"])
        assert UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:  # setup_s, another end-to-end metric, a layer metric
        assert len(run.metrics_for(bench, "end_to_end", cell)) >= 2
        assert run.metrics_for(bench, "per_layer", cell)


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for folder, _, files in os.walk(run.HERE):
        if "__pycache__" in folder:
            continue
        for f in files:
            path = os.path.relpath(os.path.join(folder, f), run.ROOT)
            assert ok.match(path), path


def test_flop_counts():
    # bench.py's RESNET50_FWD_FLOP_PER_IMG, counted from the sizes
    assert flops.resnet_fwd_flops_per_image([3, 4, 6, 3], 64, 224, 1000) \
        == pytest.approx(2 * 4.09e9, rel=2e-4)
    # benchmarks/bench_transformer.py fwd_flops_per_token at the cell's sizes
    assert flops.lm_fwd_flops_per_token(8, 2048, 8192, 50257, 2048) \
        == 8 * (8 * 2048 ** 2 + 4 * 2048 * 8192 + 4 * 2048 * 2048) \
        + 2 * 2048 * 50257


def test_peaks_name_their_source_and_refuse_an_unknown_kind():
    from chipbench import device

    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(KeyError):
            device.peaks(kind)
    with open(os.path.join(run.HERE, "peaks.json")) as f:
        assert "Google Cloud" in json.load(f)["_source"]
