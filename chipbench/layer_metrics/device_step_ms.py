"""device_step_ms: median duration of the step program's events on the
``XLA Modules`` line, median over the cell's devices. Device trace."""

import statistics


def read(trace, host, cell):
    devices = trace["devices"]
    if not devices:
        return None
    return statistics.median(statistics.median(d["step_ms"])
                             for d in devices)
