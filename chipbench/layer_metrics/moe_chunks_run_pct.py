"""moe_chunks_run_pct: of the chunks the expert layers' sorted side is
walked in (counter ``moe_chunks`` a layer, noted in
horovod_tpu/models/transformer.py while the step is traced; each chunk
is as many sorted rows as the layer has tokens, and one runs only if a
routed row reaches it: horovod_tpu/parallel/moe.py), the share that ran
in the traced steps. How many run is the data's, so it is read off the
device: the forward pass's grouped matmuls (``ragged-dot*``) that the
traced steps ran, over those they would have run had every chunk of
every layer run. That is reckoned from the forward pass's
grouped-matmul instructions of the step's compiled module
(``dp.scope_table()``): one outside a loop is one chunk's of one layer,
once a step; one in a loop's body (``while/body`` in its name) is every
further chunk's, ``moe_chunks - 1`` times a step. The forward pass
alone because there every chunk's instructions are its own: the
compiler may share a recomputation between the recomputed forward pass
and the backward pass of a chunk that always runs. None on a program
that notes no ``moe_chunks`` (one whose layer is not chunked). Device
trace and program counter."""

import re

from chipbench import step_split

#: XLA's grouped matmul, as the TPU compiler names ``jax.lax.ragged_dot``
_GROUPED = re.compile(r"^ragged-dot(?!-metadata)")
#: in the name of an instruction of a ``lax.fori_loop``'s body
_IN_A_LOOP = "while/body"


def read(trace, host, cell):
    chunks = step_split.counter(trace, "moe_chunks")
    if not chunks:
        return None
    table, phase_of = step_split.table(), step_split.program()[0].phase_of
    if not table:
        return None
    #: instruction -> the most it runs in a step
    forward = {name: chunks - 1 if _IN_A_LOOP in op_name else 1
               for name, op_name in table.items()
               if _GROUPED.match(name) and phase_of(op_name) == "forward"}
    ran = could = 0
    for d in trace["devices"]:
        could += sum(forward.values()) * d["steps"]
        ran += sum(seen["count"] for text, seen in d["instructions"].items()
                   if text.split(" ", 1)[0].lstrip("%") in forward)
    return 100.0 * ran / could if could else None
