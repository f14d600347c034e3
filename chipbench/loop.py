"""The user's training loop, timed: one dispatch per optimizer step, one
step kept in flight, nothing but the clock between the steps."""

import time
from typing import Callable, Optional

import jax
from jax import monitoring

from chipbench import device

#: host spans around the two things the loop does; trace_reduce.py
#: attributes the device's idle gaps to them
SPAN_DISPATCH = "chipbench.dispatch"
SPAN_WAIT = "chipbench.wait"

#: JAX records one of these for every trace and every compile request,
#: persistent-cache hit or not (jax/_src/dispatch.py)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileCounter:
    """Counts traces and compile requests through ``jax.monitoring``."""

    def __init__(self):
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event in _COMPILE_EVENTS:
            self.count += 1


def warm_up(cell, steps: int = 2) -> dict:
    """Compile the cell's one shape and run ``steps`` steps to a ready
    result. Returns ``compile_s``, the seconds to the first ready result
    (tracing, lowering, the compile or its load from the cache, and the
    first step); the compiler's count of the step program's bytes on one
    device; and the seconds of the last warm step."""
    t0 = time.perf_counter()
    # lowering first and calling after compiles once: the call finds the
    # executable that .compile() made (chipbench/tests checks it, and a
    # second compile would show in compile_s)
    memory = device.program_memory(
        cell.step.lower(cell.state, cell.opt_state, *cell.batch).compile())
    compile_s = step_s = None
    for _ in range(steps):
        t = time.perf_counter()
        cell.state, cell.opt_state, loss = cell.step(
            cell.state, cell.opt_state, *cell.batch)
        loss.block_until_ready()
        step_s = time.perf_counter() - t
        if compile_s is None:
            compile_s = time.perf_counter() - t0
    return {"compile_s": compile_s, "warm_step_s": step_s,
            "program_memory": memory}


def measure(cell, seconds: float, on_step: Optional[Callable] = None) -> dict:
    """Run the loop for ``seconds`` and return its host-clock samples.

    One step is kept in flight: after dispatching step i+1 the loop waits
    for step i's loss, so the device never waits for the host's wait and
    a step's time is the difference between consecutive completions. The
    window opens at the completion of the step dispatched before it and
    closes at the first completion ``seconds`` or more later; every step
    completed in between counts, and the rate is taken over exactly that
    time. ``on_step(elapsed_s)`` is called once per step (the traced run
    starts and stops the profiler from it).
    """
    annotate = jax.profiler.TraceAnnotation
    dispatch_s, completed_at, losses = [], [], []
    cell.state, cell.opt_state, in_flight = cell.step(
        cell.state, cell.opt_state, *cell.batch)
    opened_at = None
    while True:
        with annotate(SPAN_DISPATCH):
            t0 = time.perf_counter()
            cell.state, cell.opt_state, loss = cell.step(
                cell.state, cell.opt_state, *cell.batch)
            t1 = time.perf_counter()
        with annotate(SPAN_WAIT):
            in_flight.block_until_ready()
            now = time.perf_counter()
        if opened_at is None:
            opened_at = now  # the step before the window has completed
        else:
            losses.append(in_flight)
            completed_at.append(now)
            dispatch_s.append(t1 - t0)
            if now - opened_at >= seconds:
                break
            if on_step is not None:
                on_step(now - opened_at)
        in_flight = loss
    loss.block_until_ready()  # the step in flight at the close: not counted
    return {"opened_at": opened_at, "completed_at": completed_at,
            "dispatch_s": dispatch_s,
            "losses": [float(x) for x in jax.device_get(losses)]}


def step_times_ms(opened_at: float, completed_at: list) -> list:
    """Milliseconds of every step in the window: the differences between
    consecutive completions, the first from the window's opening."""
    marks = [opened_at] + completed_at
    return [(b - a) * 1e3 for a, b in zip(marks, marks[1:])]


def percentile(values: list, q: float) -> float:
    """The q-th percentile by linear interpolation between order
    statistics (numpy's default)."""
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)
