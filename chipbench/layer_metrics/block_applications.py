"""block_applications: the fused attention forward kernel's events
(``hvd_flash_fwd*``) a traced step and device: one for every application
of a block. A looped decoder of ``n`` blocks and ``r`` passes reads ``n *
r`` when every pass runs and the residuals a checkpointed block keeps
spare the backward pass a second forward kernel, twice that if they do
not. Read off the device, not off the program's counters: a loop's body
is traced once, and how often it ran is the trace's to say. None where
no such kernel ran (the einsum path, a parent without it). Device
trace."""

import re

#: ``hvd_flash_fwd`` with a window's suffix or without, and XLA's number
_FORWARD = re.compile(r"^%?hvd_flash_fwd(?:_w\d+)?(?:\.\d+)?$")


def read(trace, host, cell):
    per_device = []
    for d in trace["devices"]:
        events = sum(seen["count"] for text, seen in d["instructions"].items()
                     if _FORWARD.match(text.split(" ", 1)[0]))
        if events and d["steps"]:
            per_device.append(events / d["steps"])
    return sum(per_device) / len(per_device) if per_device else None
