"""The names the compiled step gives its work, and how to read them back.

A ``jax.named_scope`` costs nothing when a step runs: it is metadata
(``op_name``) on the instructions of the compiled module. A TPU trace's
``XLA Ops`` events are named by HLO text without that metadata, but the
text's first token is the instruction's name, and the compiled module's
text maps that name to its ``op_name``: `instruction_scopes` builds the
map (``parallel.dp.scope_table()`` does it for a step it compiled),
`seconds_by_phase`/`seconds_by_part` join it to any profile's op events.

Scopes the program sets (the only place their names are written):

- ``hvd.step``                  the per-chip body of ``data_parallel_step``
- ``hvd.grad_exchange/pack``    gradients raveled into one flat buffer
- ``hvd.grad_exchange/reduce``  the collective(s) over the data axis
- ``hvd.grad_exchange/unpack``  slices of the buffer back into leaves
                                (none of the three where the axis has one
                                member: ``opt/`` builds no exchange there
                                and the counters below read 0)
- ``hvd.optimizer``             the inner optax update
- ``hvd.model/embed|attention|mlp|head``  ``models/transformer.py``
- ``hvd.model/router``          a sparse-expert block's router: scores,
                                the top k, their weights
- ``hvd.model/moe``             its expert layer (``parallel/moe.py``): sort,
                                gathers, grouped matmuls, the weighted sum,
                                and the all-to-all where there is one
- ``hvd.model/latent``          a latent-attention block's down-projection to
                                the key/value latent and the shared rotary key,
                                the latent's norm, that key's rotary turn and
                                the up-projection to every head's keys and
                                values (beside ``hvd.model/attention``, which
                                keeps the queries, the kernels and the output
                                projection: a nested name would be filed
                                under its parent)
- ``hvd.model/shared_expert``   the gated MLP every token takes beside its
                                routed experts
- ``hvd.model/exit``            a looped decoder's exits: each pass's gate on
                                its normed state, then the exit distribution,
                                its entropy and the expected loss (the final
                                norm, classifier and cross-entropy of every
                                exit stay under ``hvd.model/head``)

Forward, backward and recompute need no scope: JAX marks them itself
(``jvp(``, ``transpose(``, ``rematted_computation``).

Names the program gives arrays (``jax.ad_checkpoint.checkpoint_name``;
the identity outside a ``jax.checkpoint``): `KEPT_BY_REMAT`, what the
fused attention's backward kernels read (``hvd.attention/q|k|v|o|lse``,
named in ``ops/pallas/flash_attention.py``'s forward rule), and
`KEPT_BY_REMAT_LATENT`, those and a latent head's rotary parts
(``hvd.attention/q_rope``, ``/k_rope``: `latent_attention`'s residuals);
`KEPT_CHOICE`, the choice of an expert layer's router, and `KEPT_ROUTING`,
what the expert layer sorted out of it.
A decoder block under ``remat`` keeps these and recomputes the rest.

Counters (`StepRecord.counters`, noted once while a step is traced, so
per step and per chip): ``collectives`` the exchange issued,
``collective_bytes`` handed to them, ``packed_bytes`` copied into flat
buffers, ``axis_size``; ``attention_calls`` the layers whose attention is
the decoder's default one (a looped decoder's once a pass) and
``attention_kernel_calls`` of them routed to the fused kernels,
``attention_window_calls`` of them with a window,
``attention_latent_calls`` of them over latent heads, ``attention_kept_calls``
of them in a checkpointed block that keeps the kernels' residuals, and
``remat_kept_mb``, those residuals' and a kept choice's bytes / 1e6 over the blocks
(counts all: each layer is noted once, outside its checkpoint); of a sparse-expert
decoder ``moe_layers``, ``experts_held`` of ``experts_total`` in each,
``experts_per_token`` chosen, ``moe_buffer_rows``, the bound its expert layer's buffers are sized for
(per layer: tokens times the most experts one token can have here),
``moe_chunks`` and ``moe_chunk_rows``, the chunks that bound is walked in
and the rows of each (how many of them ran is the data's: a trace's),
``dense_layers`` its leading layers without experts and ``shared_experts``
its expert layers with shared experts (a count of layers each); of a
looped decoder ``loop_steps`` the passes over its stack, ``loop_layers``
the blocks of one pass and ``loop_exits`` the exits a step takes a loss
from. The recurrence is a ``lax.scan`` whose body is traced once, so
nothing counts there by being traced: what is noted inside `note_loop`
counts once a pass (``attention_calls`` = blocks x passes, and
``remat_kept_mb`` what all the passes keep).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import re
from typing import Callable, Optional

import jax

STEP = "hvd.step"
GRAD_EXCHANGE = "hvd.grad_exchange"
PACK = GRAD_EXCHANGE + "/pack"
REDUCE = GRAD_EXCHANGE + "/reduce"
UNPACK = GRAD_EXCHANGE + "/unpack"
OPTIMIZER = "hvd.optimizer"
MODEL = "hvd.model"
EMBED = MODEL + "/embed"
ATTENTION = MODEL + "/attention"
MLP = MODEL + "/mlp"
HEAD = MODEL + "/head"
ROUTER = MODEL + "/router"
MOE = MODEL + "/moe"
LATENT = MODEL + "/latent"
SHARED_EXPERT = MODEL + "/shared_expert"
EXIT = MODEL + "/exit"

#: `flash_attention`'s residuals, by the names its forward rule gives them
KEPT_BY_REMAT = tuple("hvd.attention/" + a
                      for a in ("q", "k", "v", "o", "lse"))
#: `latent_attention`'s, in its residuals' order: a latent head's rotary
#: parts beside the five
KEPT_BY_REMAT_LATENT = tuple("hvd.attention/" + a for a in (
    "q", "q_rope", "k", "k_rope", "v", "o", "lse"))

#: the experts an expert layer's router chose (``models/transformer.py
#: _route``, either scoring, either input), [tokens, k] int32 a layer:
#: kept by a checkpointed decoder block, whose backward pass has to route
#: as the forward pass did (the expert layer keeps `KEPT_ROUTING`)
KEPT_CHOICE = "hvd.router/chosen"
#: what an expert layer reads off its routing (``parallel/moe.py
#: _indices``: its sorts' results, int32 and float32 vectors of the
#: pairs' and the bound's size): kept by a checkpointed decoder block, so
#: that its backward pass does not sort again
KEPT_ROUTING = "hvd.moe/routing"

#: in `phase_of`'s order of precedence
PHASES = ("grad_exchange", "optimizer", "recompute", "backward", "forward",
          "other")
_PHASE_MARKS = ((GRAD_EXCHANGE, "grad_exchange"), (OPTIMIZER, "optimizer"),
                ("rematted_computation", "recompute"),
                ("transpose(", "backward"), ("jvp(", "forward"))
_PARTS = (re.compile(re.escape(GRAD_EXCHANGE) + r"/\w+"),
          re.compile(re.escape(MODEL) + r"/\w+"))
_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_COMPUTATION = re.compile(r"(?:ENTRY\s+)?%?([^\s(]+) \(.*\{\s*$")
_CALLS = re.compile(r"\bcalls=%?([^\s,}]+)")
_COPY_OF = re.compile(r"\scopy\(%?([^\s,)]+)\)")
_ASYNC_COPY_OF = re.compile(r"\scopy-(?:start|done)\(%?([^\s,)]+)\)")
#: XLA's grouped matmul, as the TPU compiler rewrites ``ragged_dot``
_GROUPED_MATMUL = re.compile(r"ragged-dot(?!-metadata)")
_OPERANDS = re.compile(r"\s[a-z\-]+\(([^)]*)\)")
#: an instruction's opcode: the first lower-case word before a ``(``
#: (layouts and tiles in the shape are upper case)
_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
#: what `instruction_scopes` files a ``conditional`` or a ``while`` under:
#: its event in a profile spans its branch's or its body's events, which
#: are counted by themselves
SPANS_ITS_BODY = "hvd.spans_its_body"


def phase_of(op_name: Optional[str]) -> str:
    """The phase of a training step an instruction belongs to. XLA joins
    the names of instructions it merges with ``;``: the precedence holds
    over all of them."""
    for mark, phase in _PHASE_MARKS:
        if op_name and mark in op_name:
            return phase
    return "other"


def part_of(op_name: Optional[str]) -> Optional[str]:
    """``hvd.grad_exchange/<child>`` or ``hvd.model/<part>``, whichever
    the name carries (the exchange first), else None."""
    for part in _PARTS:
        m = part.search(op_name or "")
        if m:
            return m.group(0)
    return None


def instruction_scopes(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` over every computation of a
    compiled module's text (``compiled.as_text()``); an instruction
    without metadata maps to ``""``.

    A fusion's own ``op_name`` is its root's, which is right where the
    root is the convolution or matmul that takes the time. Where it
    reads ``other`` (on the v5e: AdamW's update fused under
    ``optax.apply_updates``' unscoped add; PERF.md, PR 25) the fusion
    takes the names inside the computation it calls instead, those of
    the phase most of them have. A ``copy`` without metadata is the
    compiler re-tiling a result for its user (on the v5e: the attention
    weights' gradients ahead of the flat buffer; PERF.md, PR 26): it is
    filed with the instruction that made the result. The TPU compiler's
    grouped matmul (``ragged-dot*``, what it makes of
    ``jax.lax.ragged_dot``) carries its own name for metadata and none
    of the program's: it is filed with the first of its operands that
    has a phase (the rows it multiplies; PERF.md, PR 28), seen through
    the compiler's asynchronous copies (``copy-start``/``copy-done``,
    which carry no metadata and stay unfiled themselves; PERF.md, PR
    34). A ``conditional`` or a ``while`` is filed under
    `SPANS_ITS_BODY`, which `seconds_by_phase` and `seconds_by_part`
    leave out: the instructions of the computations it calls are in the
    table under their own names."""
    table, inside, fusions, copies, computation = {}, {}, {}, {}, None
    grouped, moved = {}, {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            header = _COMPUTATION.match(line)
            if header:
                computation = header.group(1)
            continue
        opcode = _OPCODE.search(line, m.end())
        if opcode and opcode.group(1) in ("conditional", "while"):
            table[m.group(1)] = SPANS_ITS_BODY
            continue
        op = _OP_NAME.search(line, m.end())
        table[m.group(1)] = op.group(1) if op else ""
        if op:
            inside.setdefault(computation, []).append(op.group(1))
        called = _CALLS.search(line, m.end())
        if called and phase_of(table[m.group(1)]) == "other":
            fusions[m.group(1)] = called.group(1)
        copied = None if op else _COPY_OF.search(line, m.end())
        moving = None if op else _ASYNC_COPY_OF.search(line, m.end())
        if copied:
            copies[m.group(1)] = copied.group(1)
        elif moving:
            moved[m.group(1)] = moving.group(1)
        elif (_GROUPED_MATMUL.match(m.group(1))
              and phase_of(table[m.group(1)]) == "other"):
            operands = _OPERANDS.search(line, m.end())
            grouped[m.group(1)] = re.findall(
                r"%([^\s,)]+)", operands.group(1)) if operands else []
    for name, called in fusions.items():
        names = inside.get(called, ())
        votes = collections.Counter(phase_of(n) for n in names)
        if votes:
            winner = max(PHASES, key=lambda p: votes[p])  # ties: precedence
            table[name] = ";".join(dict.fromkeys(
                n for n in names if phase_of(n) == winner))
    for name, source in copies.items():  # in program order: chains resolve
        table[name] = table.get(source, "")

    def made(operand):  # what an asynchronous copy moved, else itself
        while operand in moved:
            operand = moved[operand]
        return operand

    for name, operands in grouped.items():
        table[name] = next((table[o] for o in map(made, operands)
                            if phase_of(table.get(o)) != "other"),
                           table[name])
    return table


def _seconds_by(key_of: Callable, instructions: dict, table: dict):
    seconds, found, total = {}, 0.0, 0.0
    for hlo_text, seen in instructions.items():
        name = hlo_text.split(" ", 1)[0].lstrip("%")
        if table.get(name) == SPANS_ITS_BODY:
            continue
        total += seen["seconds"]
        if name in table:
            found += seen["seconds"]
        key = key_of(table.get(name))
        if key is not None:
            seconds[key] = seconds.get(key, 0.0) + seen["seconds"]
    return seconds, (found / total if total else 0.0)


def seconds_by_phase(instructions: dict, table: dict):
    """``({phase: seconds}, found)`` for a profile's op events as
    ``{hlo text: {"count", "seconds"}}`` (chipbench's ``instructions``;
    the instruction's name is the text's first token). The phases
    partition the seconds, but for a ``conditional``'s or a ``while``'s
    own event, which is left out (`SPANS_ITS_BODY`): an instruction the
    table lacks is ``other``.
    ``found`` is the share of the seconds whose instruction the table
    has; where it is low the module is not the one that was profiled."""
    return _seconds_by(phase_of, instructions, table)


def seconds_by_part(instructions: dict, table: dict):
    """As `seconds_by_phase`, by `part_of`, over all phases; seconds
    outside every part are left out."""
    return _seconds_by(part_of, instructions, table)


# -- what a traced step notes about itself ---------------------------------

@dataclasses.dataclass
class StepRecord:
    """One ``data_parallel_step``'s abstract signature (what
    ``scope_table`` lowers), its counters and its cached table."""

    signature: Optional[tuple] = None
    counters: dict = dataclasses.field(default_factory=dict)
    table: Optional[dict] = None


_tracing: contextvars.ContextVar = contextvars.ContextVar(
    "hvd_step_record", default=None)
#: how often what is being traced runs in a step: a loop's trip count
#: while its body is traced (`note_loop`), else 1
_times: contextvars.ContextVar = contextvars.ContextVar(
    "hvd_times_a_step", default=1)


@contextlib.contextmanager
def recording(record: StepRecord):
    """While a step is traced: `note_exchange` notes into ``record``."""
    token = _tracing.set(record)
    try:
        yield record
    finally:
        _tracing.reset(token)


def note_exchange(buffers, axis_name: str, packed: bool = False) -> None:
    """Called where a gradient exchange is built, with the arrays handed
    to collectives over ``axis_name`` (one collective each). A no-op
    outside a traced ``data_parallel_step``."""
    record = _tracing.get()
    if record is None:
        return
    nbytes = sum(b.size * b.dtype.itemsize for b in buffers)
    c = record.counters
    c["collectives"] = c.get("collectives", 0) + len(buffers)
    c["collective_bytes"] = c.get("collective_bytes", 0) + nbytes
    c["packed_bytes"] = c.get("packed_bytes", 0) + (nbytes if packed else 0)
    c["axis_size"] = int(jax.lax.axis_size(axis_name))


def note_attention(kernel: bool, window: bool = False,
                   kept: bool = False, latent: bool = False) -> None:
    """Called once per layer whose attention is the default one, where
    ``models/transformer.py`` lays its blocks out (outside
    ``jax.checkpoint``, which traces a block of one kind once however
    many layers share it: the counters count layers, and inside
    `note_loop` every pass's), with where the layer's call goes: to the
    fused kernels or to `causal_attention`, with a window or without;
    ``kept``: in a checkpointed block whose policy keeps the kernels'
    residuals (`KEPT_BY_REMAT`). A no-op outside a traced
    ``data_parallel_step``."""
    record = _tracing.get()
    if record is None:
        return
    c, times = record.counters, _times.get()
    c["attention_calls"] = c.get("attention_calls", 0) + times
    c["attention_kernel_calls"] = (c.get("attention_kernel_calls", 0)
                                   + times * int(kernel))
    c["attention_kept_calls"] = (c.get("attention_kept_calls", 0)
                                 + times * int(kept))
    c.setdefault("remat_kept_mb", 0.0)
    if window:  # a decoder without windows keeps the counters it had
        c["attention_window_calls"] = (c.get("attention_window_calls", 0)
                                       + times)
    if latent:  # and one without latent heads
        c["attention_latent_calls"] = (c.get("attention_latent_calls", 0)
                                       + times)


def note_kept(nbytes: int) -> None:
    """Called once per checkpointed block that keeps `KEPT_BY_REMAT` or
    `KEPT_CHOICE`, where ``models/transformer.py`` lays its blocks out
    (outside ``jax.checkpoint``, so ``remat_kept_mb`` is a sum over the
    blocks), with the bytes of the arrays kept. A no-op outside a traced
    ``data_parallel_step``."""
    record = _tracing.get()
    if record is not None:
        c = record.counters
        c["remat_kept_mb"] = (c.get("remat_kept_mb", 0.0)
                              + _times.get() * nbytes / 1e6)


@contextlib.contextmanager
def note_loop(steps: int, layers: int, exits: int):
    """Around the one ``lax.scan`` of a looped decoder, where
    ``models/transformer.py`` lays the recurrence out: ``steps`` passes
    over ``layers`` blocks, ``exits`` of them ending in a loss. The
    scan's body is traced once whatever its trip count, so what
    `note_attention` and `note_kept` note while it is traced counts
    ``steps`` times: the counters state what a step runs and keeps. The
    counters are a no-op outside a traced ``data_parallel_step``."""
    record = _tracing.get()
    if record is not None:
        record.counters.update(loop_steps=steps, loop_layers=layers,
                               loop_exits=exits)
    token = _times.set(_times.get() * steps)
    try:
        yield
    finally:
        _times.reset(token)


def note_layer(counter: str) -> None:
    """Called once per layer of a kind a decoder counts (``dense_layers``:
    a leading dense layer of a sparse-expert decoder; ``shared_experts``:
    an expert layer with shared experts), where ``models/transformer.py``
    lays its blocks out. A no-op outside a traced ``data_parallel_step``."""
    record = _tracing.get()
    if record is not None:
        record.counters[counter] = record.counters.get(counter, 0) + 1


def note_moe(held: int, total: int, per_token: int,
             buffer_rows: int, chunk_rows: int) -> None:
    """Called once per sparse-expert layer where ``models/transformer.py``
    lays its blocks out (outside ``jax.checkpoint``, so ``moe_layers`` is
    a count), with the layer's bound and the rows of one of the chunks
    its sorted side is walked in. A no-op outside a traced
    ``data_parallel_step``."""
    record = _tracing.get()
    if record is None:
        return
    c = record.counters
    c["moe_layers"] = c.get("moe_layers", 0) + 1
    c["experts_held"], c["experts_total"] = held, total
    c["experts_per_token"] = per_token
    c["moe_buffer_rows"] = buffer_rows
    c["moe_chunk_rows"] = chunk_rows
    c["moe_chunks"] = buffer_rows // chunk_rows
