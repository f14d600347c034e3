"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read. Reads with ``jax.profiler.ProfileData`` only.

What a TPU trace holds (looked at by hand, PERF.md Findings): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per executed program, named ``jit_<fn>(<hash>)``), ``XLA Ops`` (one event
per executed HLO instruction, named by its HLO text ``%name = shape
opcode(operands), attributes``) and ``Async XLA Ops`` (one event per
asynchronous pair, named by its ``-start`` instruction and lasting from
start to done); and one plane ``/host:CPU`` with a line per thread, on
which ``jax.profiler.TraceAnnotation`` spans appear under their names.
Times are nanoseconds on one clock for all planes.

The reduction keeps to a *steady window* on each device: the step
program is the module that took most time, and the window runs from the
start of its third event to the end of its last but one, so the stall
that starting and stopping the profiler puts into the loop is left out.
Everything below is taken inside that window.
"""

import functools
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
LINE_MODULES, LINE_OPS, LINE_ASYNC = "XLA Modules", "XLA Ops", "Async XLA Ops"

#: HLO opcodes that move data between chips; ``-start``/``-done`` halves
#: of the asynchronous forms match by prefix
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")

#: spans of the benchmark's own loop (chipbench/loop.py); an idle gap is
#: charged to the one that covers most of it
OWN_SPAN_PREFIX = "chipbench."

_OPCODE = re.compile(r"\b([a-z][a-z0-9_\-]*)\(")
_INSTRUCTION = re.compile(r"^%?([^\s=]+)")
_FUSION_KIND = re.compile(r"\bkind=(k[A-Za-z]+)")


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]


# an instruction's text repeats with every step: parse each once
@functools.lru_cache(maxsize=None)
def opcode_of(hlo_text: str) -> str:
    """The opcode of an ``XLA Ops`` event's name. Layouts and tiles in
    the shape are upper case (``T(8,128)``, ``S(1)``), so the first
    lower-case word followed by ``(`` after the ``=`` is the opcode; a
    bare instruction name gives its stem."""
    _, eq, rest = hlo_text.partition(" = ")
    if eq:
        m = _OPCODE.search(rest)
        if m:
            return m.group(1)
    return instruction_stem(hlo_text)


def instruction_stem(hlo_text: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``: the instruction's name
    without its number, which is how XLA says what a fusion is made of
    (``convolution_add_fusion``, ``transpose_copy_fusion``...)."""
    name = _INSTRUCTION.match(hlo_text).group(1)
    return re.sub(r"[.\d]+$", "", name) or name


def is_collective(opcode: str) -> bool:
    return opcode.startswith(COLLECTIVES)


@functools.lru_cache(maxsize=None)
def op_class(hlo_text: str) -> str:
    """What ``breakdown.device_ops`` groups by: the opcode, for a fusion
    its kind (on a TPU ``kOutput`` is a convolution or a dot with its
    fused epilogue, ``kLoop`` elementwise work, ``kInput`` a reduction),
    and the stem of the instruction's name where that says more."""
    opcode = opcode_of(hlo_text)
    kind = _FUSION_KIND.search(hlo_text)
    if opcode == "fusion" and kind:
        opcode = f"fusion.{kind.group(1)}"
    stem = instruction_stem(hlo_text)
    return opcode if stem.replace("_", "-") in opcode else f"{opcode}:{stem}"


# -- interval arithmetic on lists of (start, end) -------------------------

def union(intervals) -> list:
    """Disjoint sorted intervals covering the same points."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def length(disjoint) -> float:
    return sum(e - s for s, e in disjoint)


def overlap(a, b) -> float:
    """Length of the intersection of two disjoint sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(disjoint, start: float, end: float) -> list:
    """The parts of [start, end] that ``disjoint`` leaves uncovered."""
    out, at = [], start
    for s, e in disjoint:
        if s > at:
            out.append((at, min(s, end)))
        at = max(at, e)
    if end > at:
        out.append((at, end))
    return [(s, e) for s, e in out if e > s]


# -- the reduction ---------------------------------------------------------

def _events(plane, line_name: str) -> list:
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, float(e.start_ns),
                     float(e.start_ns) + float(e.duration_ns))
                    for e in line.events]
    return []


def _own_host_spans(profile) -> list:
    spans = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans += [(e.name, float(e.start_ns),
                       float(e.start_ns) + float(e.duration_ns))
                      for e in line.events
                      if e.name.startswith(OWN_SPAN_PREFIX)]
    return spans


def breakdown(device: dict, n: int = 10) -> dict:
    """The result line's ``breakdown`` of one reduced device: the ``n``
    op classes that took most device time and the ``n`` longest idle
    causes, as ``[name, seconds]`` pairs. Only here is anything cut: the
    per-layer readers get every class and every instruction."""
    def top(seconds_by_name: dict) -> list:
        ranked = sorted(seconds_by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[name, seconds] for name, seconds in ranked]

    return {"device_ops": top(device["seconds_by_class"]),
            "idle_gaps": top(device["idle_seconds_by_cause"])}


def reduce_device(plane, host_spans: list):
    """One device plane -> its numbers in the steady window, or None when
    no program ran on it."""
    modules = _events(plane, LINE_MODULES)
    if not modules:
        return None
    total = {}
    for name, s, e in modules:
        total[name] = total.get(name, 0.0) + (e - s)
    step_module = max(total, key=total.get)
    steps = sorted((s, e) for name, s, e in modules if name == step_module)
    steady = steps[2:-1] if len(steps) >= 6 else steps
    w0, w1 = steady[0][0], steady[-1][1]

    def inside(events):
        return [(n, s, e) for n, s, e in events if s >= w0 and e <= w1]

    ops = inside(_events(plane, LINE_OPS))
    async_ops = inside(_events(plane, LINE_ASYNC))
    busy = union([(s, e) for _, s, e in ops + async_ops])

    # collectives: the synchronous instructions themselves, and each
    # asynchronous pair from its start to its done; exposed is the part
    # during which no other instruction runs on this device
    collective, other = [], []
    for name, s, e in ops:
        opcode = opcode_of(name)
        if not is_collective(opcode):
            other.append((s, e))
        elif not opcode.endswith(("-start", "-done")):
            collective.append((s, e))
    collective += [(s, e) for name, s, e in async_ops
                   if is_collective(opcode_of(name))]
    collective, other = union(collective), union(other)

    # every instruction by its HLO text (shapes and all, so that a reader
    # can count a kernel's operations and bytes) and summed by class
    by_class, instructions = {}, {}
    for name, s, e in ops:
        cls = op_class(name)
        by_class[cls] = by_class.get(cls, 0.0) + (e - s) * 1e-9
        seen = instructions.setdefault(name, {"count": 0, "seconds": 0.0})
        seen["count"] += 1
        seen["seconds"] += (e - s) * 1e-9

    # idle gaps, by where the device was (inside the step program or
    # between two programs) and, between programs, by what the host's
    # loop was doing
    in_program = union([(s, e) for _, s, e in modules])
    by_gap = {}
    for s, e in gaps(busy, w0, w1):
        within = overlap([(s, e)], in_program)
        if within:
            by_gap["inside a program"] = (
                by_gap.get("inside a program", 0.0) + within * 1e-9)
        if e - s > within:
            cover = {}
            for name, hs, he in host_spans:
                o = min(e, he) - max(s, hs)
                if o > 0:
                    cover[name] = cover.get(name, 0.0) + o
            label = "between programs, host in " + (
                max(cover, key=cover.get) if cover else "no span of the loop")
            by_gap[label] = (by_gap.get(label, 0.0)
                             + (e - s - within) * 1e-9)

    return {
        "plane": plane.name,
        "step_module": step_module,
        "steps": len(steady),
        "step_ms": [(e - s) * 1e-6 for s, e in steady],
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": length(busy) * 1e-9,
        "collective_s": length(collective) * 1e-9,
        "exposed_collective_s":
            (length(collective) - overlap(collective, other)) * 1e-9,
        "seconds_by_class": by_class,
        "instructions": instructions,
        "idle_seconds_by_cause": by_gap,
    }


def reduce_profile(profile) -> dict:
    """``jax.profiler.ProfileData`` -> ``{"devices": [...]}``, one entry
    per TPU plane on which a program ran, in the order of their
    numbers."""
    host_spans = _own_host_spans(profile)
    planes = sorted((int(DEVICE_PLANE.match(p.name).group(1)), p)
                    for p in profile.planes if DEVICE_PLANE.match(p.name))
    devices = [reduce_device(p, host_spans) for _, p in planes]
    return {"devices": [d for d in devices if d is not None]}


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))
