"""busy_mfu_pct: the operations one chip's share of a training step
needs (chipbench/flops.py; recomputation not counted), over the step
program's median device time, as a share of the chip's bf16 peak: what
the compiled step reaches while it runs, leaving out whatever idles the
device between steps. Device trace."""

import statistics


def read(trace, host, cell):
    devices = trace["devices"]
    if not devices:
        return None
    step_s = statistics.median(statistics.median(d["step_ms"])
                               for d in devices) * 1e-3
    return (cell["train_flops_per_step_per_chip"] / step_s
            / cell["peak_flops_per_s"] * 100.0)
