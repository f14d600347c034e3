"""Every cell end to end at a toy size on the CPU, through run.py's
rehearsal override: the same code path as a chip run up to the result
line, which a rehearsal never prints."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import run

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")
BENCH = run.load_json(run.ROOT, "BENCHMARK.json")
MARK = "REHEARSAL (never a result): "


def run_cell(name, chips, trace, *extra, seconds="1.5"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    return subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload", name,
         "--seed", str(2 ** 31 + 17), "--seconds", seconds, "--trace",
         str(trace), *extra],
        env=env, cwd=run.ROOT, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_rehearses(cell, trace):
    done = run_cell(cell["name"], cell["chips"], trace, "--rehearse", TOY)
    assert done.returncode == run.EXIT_REHEARSAL, done.stderr[-2000:]
    assert not [ln for ln in done.stdout.splitlines() if ln.startswith("{")]
    line = next(ln for ln in done.stderr.splitlines() if ln.startswith(MARK))
    result = json.loads(line[len(MARK):])
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == cell["chips"]
    if trace:  # no TPU plane in a CPU trace: only the host-clock readers
        assert set(result["metrics"]) == {"compile_s", "dispatch_ms"}
    else:
        assert set(result["metrics"]) == {
            m["name"] for m in run.metrics_for(BENCH, "end_to_end",
                                               cell["name"])}
    assert ", 0 traces or compiles" in done.stdout


def test_without_a_tpu_nothing_runs_and_no_line_is_printed():
    done = run_cell("resnet50.dp1", 1, 0)
    assert done.returncode == run.EXIT_NO_CHIP
    assert done.stdout == ""
    assert "nothing was run" in done.stderr


def test_unknown_cell_is_an_error():
    done = run_cell("no-such-cell", 1, 0)
    assert done.returncode not in (0, run.EXIT_REHEARSAL)
    assert done.stdout == ""


def test_warm_up_compiles_the_step_once():
    """Lowering, compiling and then calling the jitted step makes one
    compile request: the call finds the executable ``.compile()`` made."""
    import jax
    import jax.numpy as jnp

    from chipbench import loop
    from chipbench.cell import Cell

    requests = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: requests.append(kw.get("fun_name"))
        if event.endswith("backend_compile_duration") else None)

    def toy_step(state, opt_state, x):
        return state + x.sum(), opt_state + 1, (state * x).mean()

    cell = Cell(step=jax.jit(toy_step, donate_argnums=(0, 1)),
                state=jnp.zeros(()), opt_state=jnp.zeros(()),
                batch=(jnp.ones((8,)),), items_per_step=8,
                train_flops_per_item=1.0, check=lambda c: {"ok": True})
    jax.block_until_ready((cell.state, cell.opt_state, cell.batch))
    requests.clear()
    counter = loop.CompileCounter()
    warm = loop.warm_up(cell)
    assert [r for r in requests if "toy_step" in str(r)] == ["jit(toy_step)"]
    assert warm["program_memory"]["peak_bytes"] > 0
    assert warm["compile_s"] > 0
    before = counter.count
    host = loop.measure(cell, 0.2)
    assert counter.count == before  # nothing traces or compiles in a window
    assert len(host["completed_at"]) == len(host["losses"]) > 2
    samples = loop.step_times_ms(host["opened_at"], host["completed_at"])
    assert len(samples) == len(host["completed_at"])  # every step, each once
    assert sum(samples) == pytest.approx(
        (host["completed_at"][-1] - host["opened_at"]) * 1e3)
    assert loop.percentile([1, 2, 3, 4, 5], 95) == pytest.approx(4.8)
