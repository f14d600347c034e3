"""Measure a cell's run-to-run spread the way the bounds are set from it.

    python3 chipbench/sets.py --workload <cell> [--sets 2] [--runs 6] [--traced 1]

For each set, ``--runs`` runs of the cell, one process each and one
after the other, with the same seeds in every set; for each end-to-end
metric the spread of each set (the distance between the first and third
quartile of ``statistics.quantiles(values, n=4)`` as a share of the
median) and the wider of them. A bound is about five times the widest
spread over the cells and never under 1%. ``--traced`` runs follow with
``--trace 1``. Every result line, with what the run said before it, is
appended to ``chiprun_out/sets/<cell>.jsonl``.

This parent never touches JAX: a chip belongs to one process at a time.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: large, as the driver's are: more than 32 signed bits hold
FIRST_SEED = 2 ** 31 + 1009


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def run_once(cell: str, seed: int, seconds: int, trace: int, log) -> dict:
    t0 = time.perf_counter()
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    if done.returncode != 0:
        sys.stderr.write(done.stdout[-3000:] + done.stderr[-3000:])
        raise SystemExit(f"{cell} seed {seed}: exit code {done.returncode}")
    *said, last = done.stdout.splitlines()
    record = {"cell": cell, "seed": seed, "trace": trace, "wall_s": wall,
              **json.loads(last), "said": said}
    log.write(json.dumps(record) + "\n")
    log.flush()
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=0)
    ap.add_argument("--seconds", type=int, default=None,
                    help="default: BENCHMARK.json's run_seconds")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "sets")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, args.workload + ".jsonl"), "a") as log:
        sets = []
        for s in range(args.sets):
            runs = []
            for i in range(args.runs):
                r = run_once(args.workload, FIRST_SEED + i, seconds, 0, log)
                runs.append(r)
                print(f"set {s} run {i} seed {r['seed']} correct "
                      f"{r['correct']} wall {r['wall_s']:.1f} s: " + ", ".join(
                          f"{k} {v['value']:.6g}"
                          for k, v in r["metrics"].items()), flush=True)
            sets.append(runs)
        for name in sets[0][0]["metrics"]:
            per_set = [[r["metrics"][name]["value"] for r in runs]
                       for runs in sets]
            line = {"metric": name,
                    "medians": [statistics.median(v) for v in per_set],
                    "spreads": [spread(v) for v in per_set]}
            # set-up of each set's first run is recorded apart: in a
            # fresh checkout it compiles
            if name == "setup_s":
                line["spreads_without_first_run"] = [spread(v[1:])
                                                     for v in per_set]
            line["widest_spread"] = max(line["spreads"])
            print(json.dumps(line), flush=True)
        for i in range(args.traced):
            r = run_once(args.workload, FIRST_SEED + i, seconds, 1, log)
            print("traced: " + json.dumps(
                {k: r[k] for k in ("correct", "metrics", "device",
                                   "breakdown") if k in r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
