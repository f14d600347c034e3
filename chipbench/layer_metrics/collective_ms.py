"""collective_ms: per step, the device time of all-reduce, all-gather,
reduce-scatter, collective-permute and all-to-all instructions, an
asynchronous pair counted from its start to its done; mean over the
cell's devices. Device trace."""


def read(trace, host, cell):
    devices = trace["devices"]
    if not devices:
        return None
    return sum(d["collective_s"] / d["steps"] for d in devices) \
        / len(devices) * 1e3
