"""moe_matmul_roofline_pct: the least time the chip could take for the
expert layer's grouped matmuls over the rows uniform routing sends to
the experts held (per matmul the larger of operations over the bf16 peak
and bytes over the HBM peak: chipbench/flops_moe.py), over their device
time. Reckoned on the expected rows, not on the buffers' bound: a kernel
whose work followed the bound would read a quarter of this. Says which
bound decides on a line before the result. Device trace."""

from chipbench import device, flops_moe, moe_reads, step_split


def read(trace, host, cell):
    found = step_split.program()
    counters = found[1].step_counters() if found else None
    rows = moe_reads.expected_rows(counters or {})
    if rows is None or not trace["devices"]:
        return None
    import jax

    peak_bytes = device.peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    least = seconds = 0.0
    bounds = set()
    for d in trace["devices"]:
        for m in moe_reads.grouped_matmuls(d):
            t, bound = flops_moe.grouped_matmul_seconds(
                rows, m["contract"], m["out"], m["groups"], m["itemsize"],
                cell["peak_flops_per_s"], peak_bytes)
            least += t * m["count"]
            seconds += m["seconds"]
            bounds.add(bound)
    if not seconds:
        return None
    moe_reads.say(f"grouped matmuls: {rows:.0f} expected rows of a bound of "
                  f"{counters['moe_buffer_rows']}, bound by "
                  f"{' and '.join(sorted(bounds))}")
    return least / seconds * 100.0
