"""dispatch_ms: median milliseconds the host spends in the call that
enqueues one step (``opt/`` and ``parallel/dp.py`` as called from
Python), over the steps of the window. Host clock."""

import statistics


def read(trace, host, cell):
    samples = host.get("dispatch_s")
    return statistics.median(samples) * 1e3 if samples else None
