"""window_attention_ms: per step, the device time of the fused attention
kernels that carry a window (``hvd_flash_*_w<window>``), forward as often
as it runs and both backward kernels; mean over the cell's devices. Device
trace."""

from chipbench import moe_reads


def read(trace, host, cell):
    per_device = []
    for d in trace["devices"]:
        windowed = [k for k in moe_reads.flash_kernels(d) if k["window"]]
        if windowed:
            per_device.append(sum(k["seconds"] for k in windowed)
                              / d["steps"] * 1e3)
    return sum(per_device) / len(per_device) if per_device else None
