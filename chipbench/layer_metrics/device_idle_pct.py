"""device_idle_pct: share of the steady traced window in which no
instruction ran on the device (1 - union of op intervals / window), mean
over the cell's devices. Device trace."""


def read(trace, host, cell):
    devices = trace["devices"]
    if not devices:
        return None
    return sum(1.0 - d["busy_s"] / d["window_s"] for d in devices) \
        / len(devices) * 100.0
