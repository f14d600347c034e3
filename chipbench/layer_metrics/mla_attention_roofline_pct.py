"""mla_attention_roofline_pct: the operations of the latent attention
kernels' matmuls over the score tiles they compute, at the published
widths (a head's score 192 wide, its value 128, whatever the kernel
pads to; tiles the diagonal cuts count whole, tiles it empties not at
all; the forward as often as it runs: chipbench/flops_mla.py), over the
kernels' device time, as a share of the chip's bf16 peak. The kernels
are compute-bound: a tile's operands stay in VMEM. Says each kernel's
share on a line before the result. None on a program without the
kernels. Device trace."""

from chipbench import mla_reads, moe_reads


def read(trace, host, cell):
    by_kernel = {}
    for d in trace["devices"]:
        for k in mla_reads.mla_kernels(d):
            if k["flops"] is None:
                return None
            f, s = by_kernel.get(k["kernel"], (0.0, 0.0))
            by_kernel[k["kernel"]] = (f + k["flops"] * k["count"],
                                      s + k["seconds"])
    flops = sum(f for f, _ in by_kernel.values())
    seconds = sum(s for _, s in by_kernel.values())
    if not seconds:
        return None
    peak = cell["peak_flops_per_s"]
    moe_reads.say("latent attention kernels, share of peak by tiles "
                  "computed: " + ", ".join(
                      f"{n} {f / s / peak:.1%}"
                      for n, (f, s) in sorted(by_kernel.items())))
    return flops / seconds / peak * 100.0
