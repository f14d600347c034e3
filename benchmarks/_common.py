"""Shared helpers for the benchmark/measurement scripts."""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


_LAST_PROGRESS = [time.time()]


def make_recorder(path):
    """JSONL appender: one flushed line per event, ts-stamped, echoed to
    stdout so partial progress survives interruptions. Each record also
    feeds the stall watchdog's progress clock."""
    def record(**kw):
        kw["ts"] = time.time()
        with open(path, "a") as f:
            f.write(json.dumps(kw) + "\n")
        print(json.dumps(kw), flush=True)
        _LAST_PROGRESS[0] = time.time()
    return record


def start_stall_watchdog(timeout_s: float = 600.0):
    """Hard-exit the phase if no record() lands for ``timeout_s``.

    A phase that wedges mid-run (a device call that never returns) would
    otherwise burn its caller's whole time limit — this converts that
    into a bounded ``timeout_s`` loss. ``timeout_s`` must cover one
    compile of the phase's largest program plus one measured config.
    Exit code 42 marks a watchdog abort.
    """
    import threading

    _LAST_PROGRESS[0] = time.time()

    def watch():
        while True:
            idle = time.time() - _LAST_PROGRESS[0]
            if idle > timeout_s:
                print(f"STALL-WATCHDOG: no progress for {idle:.0f}s, "
                      "aborting phase", flush=True)
                os._exit(42)
            time.sleep(min(10.0, timeout_s / 3.0))

    threading.Thread(target=watch, daemon=True).start()


def enable_compilation_cache():
    """The one shared cache (horovod_tpu/utils/compile_cache.py): what a
    benchmark script compiles, bench.py and chip_smoke.py find again."""
    from horovod_tpu.utils.compile_cache import enable_compilation_cache as en

    en()


def write_tuned_if_better(cfg: dict):
    """Write benchmarks/bench_tuned.json only if ``cfg['img_s']`` beats
    the existing file's — concurrent/sequential campaigns must never
    clobber a faster config. tmp + os.replace so a SIGTERM/watchdog kill
    mid-write can't truncate the file a later read depends on. Returns
    ``(written, prev_img_s)`` so callers can log the margin."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bench_tuned.json")
    prev = -1.0
    try:
        with open(path) as f:
            prev = float(json.load(f).get("img_s", -1.0))
    except Exception:
        pass
    if float(cfg.get("img_s", 0.0)) > prev:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(cfg, f)
        os.replace(tmp, path)
        return True, prev
    return False, prev


# A/A runs of the same config differ by a few percent on a shared CI
# host; the off-vs-baseline check allows noise_ratio + this margin.
AA_NOISE_MARGIN = 0.02


def aa_overhead_main(measure_fn, feature: str, reps: int = 5,
                     noise_margin: float = AA_NOISE_MARGIN) -> int:
    """Shared A/A overhead harness for the zero-cost feature benches
    (trace_overhead.py / flightrec_overhead.py / perfledger_overhead.py
    all gate the same contract: feature-off must be indistinguishable
    from a featureless baseline).

    ``measure_fn(on, cycles=..., warmup=...)`` measures one config and
    returns a dict with ``dispatch_ms_median``. The harness:

    - discards one full run first (the process's first pass pays jax
      compile-cache population, which would otherwise read as "overhead"
      on whichever config happens to go first);
    - runs the configs INTERLEAVED across best-of-``reps`` reps
      (baseline, off, on; baseline, off, on; ...) rather than as
      sequential blocks: allocator/CPU-frequency warm-up drifts
      monotonically over a fresh process's first seconds, and a block
      layout aliases that drift into a fake A-vs-A difference;
    - judges on the best-of-``reps`` run per config: scheduler
      interference is one-sided — a preemption or GC pause only ever
      *adds* time — so the minimum across interleaved reps converges on
      each config's deterministic floor, where per-rep medians on a
      busy single-core host keep a ±5% jitter that no 2% gate can sit
      inside. Two configs running identical code share one floor.

    Prints one JSON line keyed ``{feature}_off`` / ``{feature}_on`` and
    returns the process exit code (1 when feature-off escapes the noise
    bound — the zero-cost contract is broken).
    """
    measure_fn(False, cycles=10, warmup=2)  # discarded warm-up run
    runs = {"baseline": [], "off": [], "on": []}
    for _ in range(reps):
        runs["baseline"].append(measure_fn(False))
        runs["off"].append(measure_fn(False))
        runs["on"].append(measure_fn(True))

    baseline, off, on = (
        min(runs[k], key=lambda r: r["dispatch_ms_median"])
        for k in ("baseline", "off", "on"))
    noise = abs(off["dispatch_ms_median"] / baseline["dispatch_ms_median"]
                - 1.0)
    on_over = on["dispatch_ms_median"] / baseline["dispatch_ms_median"]
    ok = noise <= noise_margin
    print(json.dumps({
        "baseline": baseline,
        f"{feature}_off": off,
        f"{feature}_on": on,
        "off_vs_baseline_noise": round(noise, 4),
        "off_within_noise_bound": ok,
        "noise_bound": noise_margin,
        "on_over_baseline": round(on_over, 3),
    }))
    if not ok:
        print(f"FAIL: {feature}-off differs from baseline by "
              f"{noise:.1%} > {noise_margin:.0%}", file=sys.stderr)
        return 1
    return 0


def require_tpu():
    """Refuse to let a measurement phase run (and mark itself done) on a
    CPU fallback backend. Override with HVD_ALLOW_CPU_PHASE=1 for local
    testing of the scripts themselves."""
    import jax

    if os.environ.get("HVD_ALLOW_CPU_PHASE") == "1":
        return
    d = jax.devices()[0]
    ident = (d.platform + " " + d.device_kind).lower()
    if "tpu" not in ident:
        raise SystemExit(f"phase requires a TPU device, got {ident!r} "
                         "(set HVD_ALLOW_CPU_PHASE=1 to override)")
