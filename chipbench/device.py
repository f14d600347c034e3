"""The device a run is on: what JAX reports, its published peaks, its
memory."""

import json
import os

import jax

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def describe() -> dict:
    """Platform, kind and count as JAX reports them."""
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def require_tpu(chips: int) -> dict:
    """``describe()``, or NoChip: a benchmark run never falls back to
    the CPU."""
    try:
        dev = describe()
    except RuntimeError as e:  # JAX was told to use a TPU and found none
        raise NoChip(f"JAX could not start its backend: {e}") from e
    if dev["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev['platform']!r}, "
                     f"kind {dev['kind']!r})")
    if dev["count"] < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX found "
                     f"{dev['count']}")
    return dev


def peaks(kind: str) -> dict:
    """The published peaks of a device kind (chipbench/peaks.json). A
    kind that is not in the table is an error: an assumed peak would
    make every utilization a guess."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table or kind.startswith("_"):
        raise KeyError(f"no peaks known for device_kind {kind!r}; add it "
                       "to chipbench/peaks.json with its source")
    return table[kind]


def memory_counters(devices) -> list:
    """What the runtime says of each device's memory, for the log."""
    keys = ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")
    every = [d.memory_stats() or {} for d in devices]
    return [{k: stats.get(k) for k in keys} for stats in every]


def program_memory(compiled) -> dict:
    """The compiler's count of a compiled program's bytes on one device
    (``memory_analysis()``), and ``peak_bytes``: what it holds at its
    peak, which is arguments, outputs that are not donated arguments,
    temporaries and code."""
    mem = compiled.memory_analysis()
    out = {"argument_bytes": mem.argument_size_in_bytes,
           "output_bytes": mem.output_size_in_bytes,
           "alias_bytes": mem.alias_size_in_bytes,
           "temp_bytes": mem.temp_size_in_bytes,
           "code_bytes": mem.generated_code_size_in_bytes}
    out["peak_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                         - out["alias_bytes"] + out["temp_bytes"]
                         + out["code_bytes"])
    return out


def memory_peak_bytes(devices, program_bytes: int) -> tuple:
    """Peak bytes on the fullest of ``devices``, and which of two
    readings it is: ``"runtime counter"`` or ``"compiler's count"``.

    The larger of the runtime's ``peak_bytes_in_use`` and the compiler's
    count for the step program (arguments, outputs that are not donated
    arguments, temporaries, code): on this runtime the counter follows
    the buffers the program's caller holds and leaves out the
    temporaries a running program takes (PERF.md: PR 21 read 322 MiB
    against 4.3 GB counted, PR 23 1.05 GB against 9.42 GB), so alone it
    under-reports. While that is so the number is a compile-time one
    under a measured field's name (PERF.md, Open questions).
    """
    reported = 0
    for d in devices:
        stats = d.memory_stats() or {}
        reported = max(reported, int(stats.get("peak_bytes_in_use", 0)))
    if reported >= program_bytes:
        return reported, "runtime counter"
    return int(program_bytes), "compiler's count"
