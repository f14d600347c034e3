"""unscoped_pct: share of all ``XLA Ops`` seconds in phase ``other``: the
instructions that carry none of the program's scopes or JAX's markers, and
those the step's compiled module does not name at all; mean over the cell's
devices. Says the whole split on a line before the result, and why no split
is given where the module does not match the trace. Program span."""

from chipbench import step_split


def read(trace, host, cell):
    devices = step_split.split(trace)
    if devices is None:
        return None
    found = min(share for _, _, share in devices)
    if found < step_split.MIN_FOUND:
        step_split.say(
            f"no step split: only {found:.1%} of the XLA Ops seconds name an "
            "instruction of dp.scope_table()'s module")
        return None
    first, total, _ = devices[0]
    step_split.say(
        "step split, ms per step on the first device: "
        + ", ".join(f"{k} {v:.3f}" for k, v in sorted(first.items()))
        + f" | all XLA Ops {total:.3f} | {found:.2%} of them found in the "
        "step's compiled module")
    return sum(each.get("other", 0.0) / total
               for each, total, _ in devices) / len(devices) * 100.0
