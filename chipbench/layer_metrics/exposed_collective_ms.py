"""exposed_collective_ms: the part of ``collective_ms`` during which no
other instruction runs on that device; mean over the cell's devices.
Device trace."""


def read(trace, host, cell):
    devices = trace["devices"]
    if not devices:
        return None
    return sum(d["exposed_collective_s"] / d["steps"] for d in devices) \
        / len(devices) * 1e3
