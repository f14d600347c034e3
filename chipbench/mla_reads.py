"""What the ``mla_moe_lm`` family's device-trace reader needs: finding
the latent attention kernels (``hvd_mla_fwd``, ``hvd_mla_bwd_dq``,
``hvd_mla_bwd_dkv``: ops/pallas/flash_attention.py ``latent_attention``)
among a traced run's instructions, and reading their shapes from the HLO
text the trace names them by. On a program without them (a parent
commit, another cell) nothing is found.
"""

import re

from chipbench import flops_mla
from chipbench.moe_reads import _name, _shapes

_MLA = re.compile(r"^%?(hvd_mla_(?:fwd|bwd_dq|bwd_dkv))(?:\.\d+)?$")


def mla_kernels(device: dict) -> list:
    """The latent attention kernels that ran on a traced device:
    ``{"kernel", "count", "seconds", "flops"}`` each, ``flops`` by the
    tiles one call computes (flops_mla.mla_kernel_flops) at the blocks
    the program's own ``block_sizes`` gives and at the widths the shapes
    say (q, k, v, o [B, s, heads*d]; q_rope [B, heads, s, r]: the score
    is ``d + r`` wide, the value ``d``); None for ``flops`` where the
    shapes do not say."""
    found = []
    for text, seen in device["instructions"].items():
        m = _MLA.match(_name(text))
        if m:
            found.append({"kernel": m.group(1), **seen,
                          "flops": _mla_flops(m.group(1), text)})
    return found


def _mla_flops(kernel: str, text: str):
    try:
        from horovod_tpu.ops.pallas.flash_attention import block_sizes
    except ImportError:
        return None
    shapes = [dims for _, dims in _shapes(text)]
    wide = [d for d in shapes if len(d) == 3 and d[1] > 1 and d[2] >= 128]
    rope = [d for d in shapes if len(d) == 4]            # [B, heads, s, r]
    if not wide or not rope:
        return None
    batch, s, width = max(wide, key=lambda d: d[2])
    _, heads, _, r = rope[0]
    d = width // heads
    blocks = block_sizes(s, d)
    if blocks is None:
        return None
    return flops_mla.mla_kernel_flops(kernel, batch, heads, s, d + r, d,
                                      *blocks)
