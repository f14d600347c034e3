"""attention_kernel_roofline_pct: the operations of the fused attention
kernels' matmuls over the score tiles they compute (tiles the mask cuts
count whole, tiles it empties not at all; the forward as often as it
runs: chipbench/flops_moe.py), over the kernels' device time, as a share
of the chip's bf16 peak. The kernels are compute-bound: a tile's
operands stay in VMEM. Says each kernel's share on a line before the
result. Device trace."""

from chipbench import moe_reads


def read(trace, host, cell):
    by_kernel = {}
    for d in trace["devices"]:
        for k in moe_reads.flash_kernels(d):
            if k["flops"] is None:
                return None
            name = k["kernel"] + (f"_w{k['window']}" if k["window"] else "")
            f, s = by_kernel.get(name, (0.0, 0.0))
            by_kernel[name] = (f + k["flops"] * k["count"], s + k["seconds"])
    flops = sum(f for f, _ in by_kernel.values())
    seconds = sum(s for _, s in by_kernel.values())
    if not seconds:
        return None
    peak = cell["peak_flops_per_s"]
    moe_reads.say("attention kernels, share of peak by tiles computed: "
                  + ", ".join(f"{n} {f / s / peak:.1%}"
                              for n, (f, s) in sorted(by_kernel.items())))
    return flops / seconds / peak * 100.0
