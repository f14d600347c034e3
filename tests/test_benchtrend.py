"""tools/benchtrend (the banked-trajectory renderer) and the bench.py
artifact provenance stamps (git SHA + active knob snapshot) — together
they make a banked ``BENCH_r{n}.json`` attributable (which code, which
knobs) and its trajectory visible.
"""

import json
import os
import re
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from tools.benchtrend import (  # noqa: E402
    build_rows, load_rounds, render_markdown)


def _bank(tmp_path, n, value, metric="resnet50_images_per_sec_per_chip",
          fallback=False, parsed=True, mfu=None):
    doc = {"n": n, "parsed": None}
    if parsed:
        doc["parsed"] = {"metric": metric, "value": value,
                         "unit": "images/sec/chip",
                         "extras": {"fallback_cpu": fallback}}
        if mfu is not None:
            doc["parsed"]["mfu"] = mfu
    (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(doc))


def test_load_rounds_sorts_and_keeps_holes(tmp_path):
    _bank(tmp_path, 2, 110.0)
    _bank(tmp_path, 1, 100.0)
    _bank(tmp_path, 3, 0, parsed=False)  # wedged round: parsed null
    (tmp_path / "BENCH_r04.json").write_text("{torn")  # unreadable: skip
    rounds = load_rounds(str(tmp_path / "BENCH_r*.json"))
    assert [r["n"] for r in rounds] == [1, 2, 3]
    assert rounds[2]["parsed"] is None  # the hole is kept as information


def test_build_rows_arrows_and_regression_judgement(tmp_path):
    _bank(tmp_path, 1, 100.0)
    _bank(tmp_path, 2, 120.0)            # higher-better: improvement
    _bank(tmp_path, 3, 120.1)            # < 0.5%: flat
    _bank(tmp_path, 4, 90.0, fallback=True)  # drop: regression, flagged
    rows = build_rows(load_rounds(str(tmp_path / "BENCH_r*.json")))
    assert [r["arrow"] for r in rows] == ["", "↑", "→", "↓"]
    assert rows[1]["delta_pct"] == pytest.approx(20.0)
    assert not rows[1]["regression"] and not rows[2]["regression"]
    assert rows[3]["regression"] and rows[3]["fallback_cpu"]


def test_build_rows_lower_is_better_metrics(tmp_path):
    for n, v in ((1, 50.0), (2, 40.0), (3, 60.0)):
        _bank(tmp_path, n, v, metric="dispatch_ms")
    rows = build_rows(load_rounds(str(tmp_path / "BENCH_r*.json")))
    # _ms suffix: down is improvement, up is regression
    assert rows[1]["arrow"] == "↓" and not rows[1]["regression"]
    assert rows[2]["arrow"] == "↑" and rows[2]["regression"]


def test_render_markdown_flags_cpu_fallback_rounds(tmp_path):
    _bank(tmp_path, 1, 100.0, mfu=0.41)
    _bank(tmp_path, 2, 90.0, fallback=True)
    _bank(tmp_path, 3, 0, parsed=False)
    md = render_markdown(build_rows(load_rounds(
        str(tmp_path / "BENCH_r*.json"))))
    lines = md.splitlines()
    assert lines[0].startswith("| round |")
    assert any("0.4100" in ln for ln in lines)  # mfu rendered
    assert any("CPU-fallback" in ln for ln in lines)
    assert any("no parsed result" in ln for ln in lines)
    assert md.rstrip().endswith("must not anchor chip comparisons.")
    assert "rounds 2 ran on the forced-CPU fallback" in md


def test_cli_markdown_json_and_exit_codes(tmp_path):
    _bank(tmp_path, 1, 100.0)
    _bank(tmp_path, 2, 105.0)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.benchtrend", "BENCH_r*.json"],
        cwd=tmp_path, env={**os.environ, "PYTHONPATH": _REPO},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.startswith("| round |")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.benchtrend", "BENCH_r*.json",
         "--json"],
        cwd=tmp_path, env={**os.environ, "PYTHONPATH": _REPO},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0
    rows = json.loads(proc.stdout)
    assert [r["n"] for r in rows] == [1, 2] and rows[1]["arrow"] == "↑"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.benchtrend", "NOPE_*.json"],
        cwd=tmp_path, env={**os.environ, "PYTHONPATH": _REPO},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "nothing matched" in proc.stderr


def test_cli_renders_banked_trajectory_with_fallback_caveat(tmp_path):
    """Tier-1 smoke on a banked trajectory in the driver's wrapper shape
    (a chip round, a null-parse round, then forced-CPU rounds): the
    CPU-fallback rounds must carry the caveat."""
    _bank(tmp_path, 1, 2241.08, mfu=0.14)
    _bank(tmp_path, 2, 0, parsed=False)
    _bank(tmp_path, 4, 0.65, fallback=True)
    _bank(tmp_path, 5, 0.62, fallback=True)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.benchtrend", "BENCH_r*.json"],
        cwd=tmp_path, env={**os.environ, "PYTHONPATH": _REPO},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "CPU-fallback" in proc.stdout
    assert "rounds 4, 5 ran on the forced-CPU fallback" in proc.stdout


# --- bench.py provenance stamps ----------------------------------------------

def _load_bench_module():
    import importlib.util as ilu

    spec = ilu.spec_from_file_location(
        "_bench_stamp_test", os.path.join(_REPO, "bench.py"))
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_stamps_git_sha_and_knobs():
    """Satellite: every bench artifact must record which code and which
    active knob values produced it — a banked baseline without them is
    unattributable once the branch moves."""
    mod = _load_bench_module()
    sha = mod._git_sha()
    assert sha and re.fullmatch(r"[0-9a-f]{40}", sha)
    knobs = mod._knob_snapshot()
    assert isinstance(knobs, dict) and "fusion_threshold_bytes" in knobs
    assert "anatomy_enabled" in knobs  # new knobs ride along
    json.dumps(knobs)  # flat + JSON-able: lands in extras verbatim
