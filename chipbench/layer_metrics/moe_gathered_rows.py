"""moe_gathered_rows: the rows the expert layers' row gathers read in a
traced step, all phases: every instruction of the step's compiled module
(``dp.scope_table()``) whose ``op_name`` lies under ``hvd.model/moe``
and names a ``gather`` (JAX's ``x.at[idx].get()``, ``x[idx]``) with a
two-dimensional result of rows wider than one element, counted at its
leading dimension each time it ran; not a grouped matmul
(``ragged-dot*``), which the table files under the gather of the rows it
multiplies. A loop's body's instructions run as often as the loop goes
round, and the trace has an event for each, so a chunk that ran is
counted and one that did not is not. Per step and device, mean over the
devices. Whether the layer's row movement follows the rows routed or a
bound on them (horovod_tpu/parallel/moe.py): a gather of ``tokens x k``
rows reads six times a chunk's. None where no such gather ran or the
program has no table. Device trace."""

import re

from chipbench import step_split

_MOE = "hvd.model/moe"
#: the primitive's name at the end of an op_name (fusions of several
#: instructions join their names with ``;``)
_GATHER = re.compile(r"(?:^|/)gather(?:;|$)")
#: XLA's grouped matmul, as the TPU compiler names ``jax.lax.ragged_dot``
_GROUPED = re.compile(r"^ragged-dot")
#: the result's shape in an instruction's text: ``= bf16[16384,2560]{...``
_SHAPE = re.compile(r"=\s*\(?[a-z][a-z0-9]*\[([0-9,]*)\]")


def rows_of(text: str):
    """The leading dimension of a two-dimensional result whose rows are
    wider than one element, else None."""
    shape = _SHAPE.search(text)
    dims = [int(d) for d in shape.group(1).split(",") if d] if shape else []
    return dims[0] if len(dims) == 2 and dims[1] > 1 else None


def read(trace, host, cell):
    table = step_split.table()
    if not table:
        return None
    per_device = []
    for d in trace["devices"]:
        rows = 0
        for text, seen in d["instructions"].items():
            name = text.split(" ", 1)[0].lstrip("%")
            op_name = table.get(name) or ""
            if (_MOE in op_name and _GATHER.search(op_name)
                    and not _GROUPED.match(name)):
                rows += (rows_of(text) or 0) * seen["count"]
        if rows and d["steps"]:
            per_device.append(rows / d["steps"])
    return sum(per_device) / len(per_device) if per_device else None
