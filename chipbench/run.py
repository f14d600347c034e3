"""chipbench: one cell, one run, one process.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data the harness finds by the names in
``BENCHMARK.json`` (README.md): ``configs/<config>.json``,
``workloads/<cell>.json``, ``families/<family>.py`` and, for a traced
run, ``layer_metrics/<metric>.py``. The run requires a TPU with the
cell's chips (no CPU fallback), builds weights and data on the device
from ``--seed``, warms up the cell's one shape, checks the program
against its plain reference, measures the user's loop for ``--seconds``
and prints one JSON object as its last line of standard output.

Exit codes: 0 a result line was printed; 1 the run failed; 2 no TPU, or
fewer chips than the cell asks for; 3 a ``--rehearse`` run finished (a
rehearsal never prints a result line).
"""

import time

STARTED_AT = time.perf_counter()  # process start, as near as Python can say

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXIT_NO_CHIP, EXIT_REHEARSAL = 2, 3


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py``, found by its name in the data."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(bench: dict, name: str, rehearse_dir=None):
    """The cell's entry in BENCHMARK.json, its configuration and its
    workload parameters. A rehearsal lays its directory's toy sizes over
    them, key by key."""
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{[w['name'] for w in bench['workloads']]}")
    config = load_json(HERE, "configs", entry["config"] + ".json")
    workload = load_json(HERE, "workloads", name + ".json")
    if rehearse_dir:
        for target, kind, key in ((config, "configs", entry["config"]),
                                  (workload, "workloads", name)):
            for k, v in load_json(rehearse_dir, kind, key + ".json").items():
                if isinstance(v, dict):
                    target[k] = {**target.get(k, {}), **v}
                else:
                    target[k] = v
    return entry, config, workload


def metrics_for(bench: dict, group: str, cell: str) -> list:
    """The metrics of ``group`` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


class Tracer:
    """Profiles a few seconds in the middle of the window (a third of the
    way in, for at least ``steps`` steps and ``min_s`` seconds, at most a
    third of the window)."""

    def __init__(self, trace_dir: str, seconds: float, step_s: float,
                 steps: int = 8, min_s: float = 2.0):
        import jax.profiler

        self.profiler = jax.profiler
        self.dir = trace_dir
        self.start_at = seconds / 3.0
        self.stop_at = self.start_at + min(seconds / 3.0,
                                           max(min_s, steps * step_s))
        self.state = "waiting"
        self.options = self.profiler.ProfileOptions()
        self.options.python_tracer_level = 0  # the loop's own spans suffice

    def on_step(self, elapsed_s: float) -> None:
        if self.state == "waiting" and elapsed_s >= self.start_at:
            self.profiler.start_trace(self.dir,
                                      profiler_options=self.options)
            self.state = "tracing"
        elif self.state == "tracing" and elapsed_s >= self.stop_at:
            self.close()

    def close(self) -> None:
        if self.state == "tracing":
            self.profiler.stop_trace()
            self.state = "done"


def end_to_end(host: dict, cell, chips: int, peak_flops: float) -> dict:
    """The end-to-end metrics from the loop's host-clock samples: all the
    steps and all the time between the window's opening and its last
    completion."""
    from chipbench import loop

    steps = len(host["completed_at"])
    window_s = host["completed_at"][-1] - host["opened_at"]
    throughput = steps * cell.items_per_step / window_s / chips
    samples = loop.step_times_ms(host["opened_at"], host["completed_at"])
    say(f"window {window_s:.3f} s, {steps} steps; step time of each: median "
        f"{loop.percentile(samples, 50):.3f} ms, longest "
        f"{max(samples):.3f} ms")
    return {
        "setup_s": host["opened_at"] - STARTED_AT,
        "throughput": throughput,
        "mfu_pct": throughput * cell.train_flops_per_item / peak_flops * 100,
        "step_p95_ms": loop.percentile(samples, 95),
    }


def per_layer(metrics: list, reduced: dict, host: dict, cell, chips: int,
              peak_flops: float) -> dict:
    """Each per-layer metric from its own reader; one that finds nothing
    to read returns None and is left out."""
    about = {"chips": chips, "peak_flops_per_s": peak_flops,
             "train_flops_per_step_per_chip":
                 cell.train_flops_per_item * cell.items_per_step / chips}
    values = {m["name"]: load_module("layer_metrics", m["name"]).read(
        reduced, host, about) for m in metrics}
    return {name: v for name, v in values.items() if v is not None}


def run_cell(args, bench: dict, entry: dict, config: dict, workload: dict,
             dev: dict, peak_flops: float, mesh) -> dict:
    """Build, warm up, check, measure; returns the result line's object."""
    import jax

    from chipbench import device, loop, trace_reduce

    chips = entry["chips"]
    compiles = loop.CompileCounter()
    family = load_module("families", config["family"])
    cell = family.build(config, workload, chips=chips, seed=args.seed,
                        mesh=mesh)
    jax.block_until_ready((cell.state, cell.opt_state, cell.batch))
    say(f"state and batch on the mesh after {since_start():.1f} s")

    warm = loop.warm_up(cell)
    say(f"warm-up: {warm['compile_s']:.1f} s to the first ready step, then "
        f"{warm['warm_step_s'] * 1e3:.1f} ms a step | compiler's bytes for "
        f"the step program: {warm['program_memory']}")
    program_bytes = warm["program_memory"]["peak_bytes"]
    check = cell.check(cell)
    say(f"reference check done after {since_start():.1f} s: {check}")

    tracer = None
    if args.trace:
        tracer = Tracer(args.trace_dir or tempfile.mkdtemp(prefix="chipbench-"),
                        args.seconds, warm["warm_step_s"])
    compiles_before = compiles.count
    try:
        host = loop.measure(cell, args.seconds,
                            tracer.on_step if tracer else None)
    finally:
        if tracer:
            tracer.close()
    compiles_in_window = compiles.count - compiles_before
    failed = sum(not math.isfinite(x) for x in host["losses"])
    say(f"in the window: {failed} losses that are not finite, "
        f"{compiles_in_window} traces or compiles")

    peak_bytes, peak_from = device.memory_peak_bytes(mesh.devices.flat,
                                                     program_bytes)
    say(f"memory: runtime counters {device.memory_counters(mesh.devices.flat)}"
        f", step program by the compiler {program_bytes}; memory_peak_bytes "
        f"is the {peak_from}")
    result = {
        "correct": bool(check["ok"] and failed == 0
                        and compiles_in_window == 0),
        "attempted": len(host["completed_at"]),
        "failed": failed,
        # count: the chips of the cell's mesh, which do the work
        "device": {**dev, "count": mesh.devices.size,
                   "memory_peak_bytes": peak_bytes},
    }
    if tracer:
        group = "per_layer"
        reduced = trace_reduce.reduce_file(
            trace_reduce.find_xplane(tracer.dir))
        if not args.trace_dir:
            shutil.rmtree(tracer.dir, ignore_errors=True)
        host["compile_s"] = warm["compile_s"]
        values = per_layer(metrics_for(bench, group, entry["name"]), reduced,
                           host, cell, chips, peak_flops)
        traced = reduced["devices"]
        if traced:  # a CPU rehearsal's trace has no TPU plane
            for key in ("busy_s", "window_s"):
                result["device"][key] = (sum(d[key] for d in traced)
                                         / len(traced))
            result["breakdown"] = trace_reduce.breakdown(traced[0])
            say(f"trace: step program {traced[0]['step_module']}, "
                f"{traced[0]['steps']} steps in the steady window")
    else:
        group = "end_to_end"
        values = end_to_end(host, cell, chips, peak_flops)
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in metrics_for(bench, group, entry["name"])
        if m["name"] in values}
    return result


def since_start() -> float:
    return time.perf_counter() - STARTED_AT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's files here (default: a "
                         "temporary directory, removed after the reduction)")
    ap.add_argument("--rehearse", default=None, metavar="DIR",
                    help="rehearsal on whatever JAX finds, at the toy sizes "
                         "under DIR (chipbench/tests/toy); prints no result "
                         "line and exits 3")
    args = ap.parse_args(argv)

    # as a script, sys.path[0] is this directory: the checkout's root
    # takes its place, so that chipbench's modules are found only as
    # chipbench.<name> and shadow nothing
    sys.path[:] = [ROOT] + [p for p in sys.path
                            if os.path.abspath(p or ".") not in (HERE, ROOT)]
    bench = load_json(ROOT, "BENCHMARK.json")
    entry, config, workload = load_cell(bench, args.workload, args.rehearse)
    chips = entry["chips"]

    # the program's one rule places the compile cache:
    # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache
    from horovod_tpu.utils import compile_cache

    reason = compile_cache.enable_compilation_cache()
    if reason is not None:
        raise SystemExit(f"no persistent compile cache: {reason}")

    import jax

    # every program of a run is in the cache after the first run, also
    # those that compile in under the helper's one second: set-up is then
    # the same work every time (PERF.md, PR 21's finding on the cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from chipbench import device

    if args.rehearse:
        dev = device.describe()
        if dev["count"] < chips:
            raise SystemExit(f"rehearsal needs {chips} device(s), JAX "
                             f"found {dev['count']}")
        peak_flops = float("nan")
    else:
        try:
            dev = device.require_tpu(chips)
        except device.NoChip as e:
            sys.stderr.write(f"chipbench: {e}; nothing was run\n")
            return EXIT_NO_CHIP
        peak_flops = device.peaks(dev["kind"])["bf16_flops_per_s"]
    say(f"cell {entry['name']} = {entry['config']} x {entry['traffic']} on "
        f"{chips} chip(s) | device {dev} | compile cache "
        f"{compile_cache.active_cache_dir()} | backend up after "
        f"{since_start():.1f} s")

    import horovod_tpu as hvd

    hvd.init(ranks=list(range(chips)))
    try:
        result = run_cell(args, bench, entry, config, workload, dev,
                          peak_flops, hvd.global_process_set().mesh)
    finally:
        hvd.shutdown()
    if args.rehearse:
        sys.stderr.write("REHEARSAL (never a result): "
                         + json.dumps(result) + "\n")
        return EXIT_REHEARSAL
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
