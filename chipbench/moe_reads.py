"""What the ``moe_lm`` family's device-trace readers share: finding the
fused attention kernels and the expert layer's grouped matmuls among a
traced run's instructions, and reading their shapes from the HLO text
the trace names them by. On a program without them (a parent commit, a
dense cell) nothing is found and every reader returns None.
"""

import re

from chipbench import flops_moe

#: ``hvd_flash_fwd``, ``hvd_flash_bwd_dq``, ``hvd_flash_bwd_dkv``, with
#: ``_w<window>`` where the kernel has one (ops/pallas/flash_attention.py)
_FLASH = re.compile(r"^%?(hvd_flash_(?:fwd|bwd_dq|bwd_dkv))(?:_w(\d+))?"
                    r"(?:\.\d+)?$")
#: XLA's grouped matmul, as the TPU compiler names ``jax.lax.ragged_dot``
_GROUPED = re.compile(r"^%?ragged-dot(?!-metadata)[\w\-]*(?:\.\d+)?$")
_SHAPE = re.compile(r"\b(bf16|f16|f32)\[([\d,]+)\]")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def _name(hlo_text: str) -> str:
    return hlo_text.split(" ", 1)[0]


def _shapes(hlo_text: str) -> list:
    """Every floating-point array shape in an instruction's text, result
    first: ``[(dtype, (dims...)), ...]``."""
    return [(t, tuple(int(n) for n in dims.split(",")))
            for t, dims in _SHAPE.findall(hlo_text)]


def flash_kernels(device: dict) -> list:
    """The fused attention kernels that ran on a traced device:
    ``{"kernel", "window", "count", "seconds", "flops"}`` each, ``flops``
    by the tiles one call computes (flops_moe.flash_kernel_flops) at the
    blocks the program's own ``block_sizes`` gives; None for ``flops``
    where the shapes do not say."""
    found = []
    for text, seen in device["instructions"].items():
        m = _FLASH.match(_name(text))
        if m is None:
            continue
        kernel, window = m.group(1), m.group(2) and int(m.group(2))
        found.append({"kernel": kernel, "window": window, **seen,
                      "flops": _flash_flops(kernel, window, text)})
    return found


def _flash_flops(kernel: str, window, text: str):
    try:
        from horovod_tpu.ops.pallas.flash_attention import block_sizes
    except ImportError:
        return None
    shapes = [dims for _, dims in _shapes(text)]
    wide = [d for d in shapes if len(d) == 3 and d[1] > 1]  # [B, s, h*hd]
    rows = [d for d in shapes if len(d) == 3 and d[1] == 1]  # [B*h, 1, s]
    if not wide or not rows:
        return None
    batch, s, width = max(wide, key=lambda d: d[2])
    heads = rows[0][0] // batch
    blocks = block_sizes(s, width // heads)
    if blocks is None:
        return None
    return flops_moe.flash_kernel_flops(kernel, batch, heads, s,
                                        width // heads, *blocks, window)


def grouped_matmuls(device: dict) -> list:
    """The expert layer's grouped matmuls that ran on a traced device:
    ``{"buffer_rows", "contract", "out", "groups", "itemsize", "count",
    "seconds"}`` each. One of an instruction's three arrays has a
    leading group dimension, [groups, a, b]; the other two are [rows, a]
    and [rows, b], whichever of the three is the result."""
    found = []
    for text, seen in device["instructions"].items():
        if _GROUPED.match(_name(text)) is None:
            continue
        # the result's buffer may come in as an operand too: distinct shapes
        shapes = list(dict.fromkeys(_shapes(text)))
        weights = [(t, d) for t, d in shapes if len(d) == 3]
        flat = [d for _, d in shapes if len(d) == 2]
        if len(flat) == 1:  # as many columns in as out
            flat = flat * 2
        if len(weights) != 1 or len(flat) != 2 or flat[0][0] != flat[1][0]:
            say(f"grouped matmul with shapes not understood: {text[:400]}")
            continue
        dtype, (groups, a, b) = weights[0]
        found.append({"buffer_rows": flat[0][0], "contract": a, "out": b,
                      "groups": groups, "itemsize": _ITEMSIZE[dtype], **seen})
    return found


def expected_rows(counters: dict):
    """Rows a sparse-expert layer routes to the experts it holds under
    uniform routing, from the counters the program noted while its step
    was traced; None without them."""
    try:
        tokens = counters["moe_buffer_rows"] / min(
            counters["experts_per_token"], counters["experts_held"])
        return (tokens * counters["experts_per_token"]
                * counters["experts_held"] / counters["experts_total"])
    except (KeyError, TypeError, ZeroDivisionError):
        return None
