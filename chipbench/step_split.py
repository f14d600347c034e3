"""What the step-split readers (``layer_metrics/forward_ms.py`` and its
kin) share: the join of a traced run's instructions to the scopes the
program names its work by.

The program brings the vocabulary and the table
(``horovod_tpu/utils/scopes.py``: ``seconds_by_phase``,
``seconds_by_part``; ``horovod_tpu/parallel/dp.py``: ``scope_table``,
``step_counters``); a checkout without them (a parent commit older than
the scopes) has nothing to read, and every reader returns None there.
"""

import functools
import time

#: under this share of a device's ``XLA Ops`` seconds found in the
#: table, the compiled module is not the one that was profiled, and no
#: reader gives a number
MIN_FOUND = 0.99


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def program():
    """``(scopes, dp)`` of the checkout, or None where it has none."""
    try:
        from horovod_tpu.parallel import dp
        from horovod_tpu.utils import scopes
    except ImportError:
        return None
    return (scopes, dp) if hasattr(dp, "scope_table") else None


@functools.lru_cache(maxsize=1)  # a failure is not tried again per reader
def table():
    """``dp.scope_table()`` of the step traced last, or None. Its first
    call compiles (a load from the persistent cache after the run)."""
    found = program()
    if found is None:
        return None
    t0 = time.perf_counter()
    try:
        scope_table = found[1].scope_table()
    except Exception as e:  # a reader never fails the run it reads
        say(f"no step split: dp.scope_table() raised {e!r}")
        return None
    if scope_table is not None:
        say(f"dp.scope_table(): {len(scope_table)} instructions after "
            f"{time.perf_counter() - t0:.1f} s")
    return scope_table


def split(trace, by: str = "phase"):
    """Per device of the traced run: ``({key: ms per step}, all ms per
    step, share found)``, keyed by phase or by part; None without the
    program's scopes, without a traced step or a device."""
    if not table() or not trace["devices"]:
        return None
    seconds_by = getattr(program()[0], "seconds_by_" + by)
    out = []
    for d in trace["devices"]:
        seconds, share = seconds_by(d["instructions"], table())
        total = sum(seen["seconds"] for seen in d["instructions"].values())
        out.append(({k: s / d["steps"] * 1e3 for k, s in seconds.items()},
                    total / d["steps"] * 1e3, share))
    return out


def ms(trace, keys, by: str = "phase"):
    """Milliseconds per step under ``keys``, mean over the cell's
    devices; None where `split` is, or where the table does not match
    the trace (``unscoped_pct`` says so)."""
    devices = split(trace, by)
    if devices is None or min(share for _, _, share in devices) < MIN_FOUND:
        return None
    return sum(sum(each.get(k, 0.0) for k in keys)
               for each, _, _ in devices) / len(devices)


def counter(trace, name: str):
    """A counter the gradient exchange noted while the step was traced
    (per step and per chip); None without the program's counters, and
    without a device in the trace (a CPU rehearsal reports no program
    metric)."""
    found = program()
    counters = found[1].step_counters() if found else None
    return counters.get(name) if counters and trace["devices"] else None
