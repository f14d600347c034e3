"""ZeRO-1 sharded weight update: reduce-scatter → sharded step → allgather.

"Automatic Cross-Replica Sharding of Weight Update" (arXiv:2004.13336)
as a first-class Horovod-contract subsystem. The replicated-update
contract (allreduce every gradient, then every rank repeats the same
optimizer step) moves 2·(N-1)/N·B update-path bytes per rank and holds
N copies of the optimizer state; this module splits the allreduce around
the update instead:

1. **reduce-scatter** the fused gradient buffer — each rank receives
   only its contiguous 1/N shard of the reduced gradient, (N-1)/N·B on
   the wire: half the replicated update path's gradient traffic;
2. **sharded optimizer step** on the owned shard only — optimizer state
   (Adam m/v, momentum) is allocated 1/N per rank, the ZeRO-1 ledger;
3. **allgather** the updated *parameter* shards back to full params.

Total step bytes are unchanged (RS + AG ≡ ring allreduce); what changes
is where they sit: the gradient/update path halves and the other half
moves to the parameter side, where it can overlap the next forward and
ride the (often narrower) param dtype. See docs/sharded_optimizer.md.

Layout (:func:`plan_shard_layout`) is deterministic: leaves are grouped
by param dtype in pytree-flatten order, each group flattened into one
buffer, zero-padded to a world-divisible extent, and cut into contiguous
per-rank shards. Leaves below the replicate threshold
(``HOROVOD_SHARDED_MIN_ELEMS``, shared with parallel/fsdp.py through
``parallel/sharding_policy.py``) stay on the classic allreduce path —
scattering a norm scale costs more latency than it saves. The layout
digest is folded into every compiled-plan signature (ops/collectives.py
``sharded_*_plan``), so a rebuild — elastic resize, threshold change —
misses onto fresh programs and stale ones fall to
``invalidate_fused_plans()``.

Two execution flavors share the planner and the compiled plans:

- :func:`ShardedDistributedOptimizer` — optax GradientTransformation
  for *traced* per-chip contexts (shard_map/pjit), ``psum_scatter`` /
  ``all_gather`` over the named axis;
- :class:`ShardedUpdateEngine` — the *eager* per-process engine behind
  the framework shims and benches, running the cached
  pack → reduce-scatter → update → allgather → unpack plan chain. A
  single process can drive N virtual ranks in lockstep through
  :func:`simulated_step` (tests, CPU microbench).

Exact for elementwise optimizers (SGD/momentum/Adam/AdamW/...); not for
optimizers that couple elements across a leaf (LARS, Adafactor): see
:func:`ShardedDistributedOptimizer`.
"""

from __future__ import annotations

import dataclasses
import hashlib
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import tree_util as jtu

from ..common import env as env_schema
from ..common.context import DEFAULT_AXIS
from ..ops import collectives as C
from ..ops.collectives import ReduceOp
from ..parallel.sharding_policy import DEFAULT_MIN_SHARD_ELEMS, should_shard
from ..utils import flightrec
from ..utils import memledger as memledger_mod
# the package imports this module at its foot, below these definitions
from . import _pack, _reduce, _unpack, _update

_SUPPORTED_OPS = (ReduceOp.AVERAGE, ReduceOp.SUM)


def _resolve_min_shard_elems(min_shard_elems: Optional[int]) -> int:
    if min_shard_elems is not None:
        return int(min_shard_elems)
    return env_schema.get_int(env_schema.HOROVOD_SHARDED_MIN_ELEMS,
                              DEFAULT_MIN_SHARD_ELEMS)


def sharded_update_enabled() -> bool:
    """The ``HOROVOD_SHARDED_UPDATE`` knob (shims consult this when the
    caller passes ``sharded_update=None``)."""
    enabled = env_schema.get_bool(env_schema.HOROVOD_SHARDED_UPDATE)
    if enabled:
        # mutual exclusion with the quantized wire (docs/
        # sharded_optimizer.md): the reduce-scatter shard is never
        # materialized as a full tensor to compress, and quantizing the
        # shard would desynchronize the replicated allgather result.
        # Composing the two (quantized reduce-scatter à la ZeRO++) is
        # future work — fail loudly instead of silently ignoring a knob.
        mode = env_schema.get_str(env_schema.HOROVOD_COMPRESSION) \
            .strip().lower()
        if mode not in ("", "none", "0", "off"):
            raise ValueError(
                f"{env_schema.HOROVOD_SHARDED_UPDATE} and "
                f"{env_schema.HOROVOD_COMPRESSION}={mode!r} are mutually "
                "exclusive: the sharded update path cannot run the "
                "quantized wire (see docs/sharded_optimizer.md)")
    return enabled


# ===========================================================================
# Layout planner
# ===========================================================================


@dataclasses.dataclass(frozen=True)
class ShardGroup:
    """One per-dtype fused buffer and its per-rank cut."""

    dtype: str
    indices: Tuple[int, ...]            # leaf positions, flatten order
    sizes: Tuple[int, ...]              # elements per leaf
    shapes: Tuple[Tuple[int, ...], ...]
    total: int                          # sum(sizes)
    shard_elems: int                    # ceil(total / world)


@dataclasses.dataclass(frozen=True)
class ShardLayout:
    """Deterministic shard layout for one (pytree, world, threshold).

    Every rank computes an identical layout from identical inputs — no
    negotiation — which elastic relies on after a resize. ``digest``
    goes into every compiled-plan key."""

    world_size: int
    generation: int
    min_shard_elems: int
    num_leaves: int
    groups: Tuple[ShardGroup, ...]
    replicated: Tuple[int, ...]         # leaf positions on the classic path
    replicated_elems: int
    replicated_bytes: int               # per full replica, for accounting
    digest: str

    @property
    def shard_elems(self) -> int:
        """This layout's per-rank owned elements (across groups)."""
        return sum(g.shard_elems for g in self.groups)

    @property
    def shard_fraction(self) -> float:
        sharded = sum(g.total for g in self.groups)
        total = sharded + self.replicated_elems
        return (sharded / total) if total else 0.0

    def group_padded(self, group: ShardGroup) -> int:
        return group.shard_elems * self.world_size


def plan_shard_layout(tree, world_size: int, *,
                      min_shard_elems: Optional[int] = None,
                      generation: Optional[int] = None) -> ShardLayout:
    """Plan the deterministic ZeRO-1 layout for ``tree``.

    Groups shardable leaves by param dtype in flatten order, computes the
    padded per-rank cut, and fingerprints the whole decision. Leaves
    below the threshold (or scalars) land in ``replicated``.
    """
    world_size = max(int(world_size), 1)
    mse = _resolve_min_shard_elems(min_shard_elems)
    if generation is None:
        generation = env_schema.get_int(env_schema.HOROVOD_ELASTIC_GEN, 0)
    leaves = jax.tree.leaves(tree)
    by_dtype: Dict[str, List[int]] = {}
    replicated: List[int] = []
    rep_elems = 0
    rep_bytes = 0
    for i, leaf in enumerate(leaves):
        shape = tuple(int(d) for d in jnp.shape(leaf))
        if should_shard(shape, min_shard_elems=mse):
            by_dtype.setdefault(str(leaf.dtype), []).append(i)
        else:
            replicated.append(i)
            n = int(np.prod(shape, dtype=np.int64)) if shape else 1
            rep_elems += n
            rep_bytes += n * np.dtype(str(leaf.dtype)).itemsize
    groups = []
    for dt in sorted(by_dtype):
        idxs = tuple(by_dtype[dt])
        sizes = tuple(int(leaves[i].size) for i in idxs)
        shapes = tuple(tuple(int(d) for d in jnp.shape(leaves[i]))
                       for i in idxs)
        total = sum(sizes)
        groups.append(ShardGroup(dtype=dt, indices=idxs, sizes=sizes,
                                 shapes=shapes, total=total,
                                 shard_elems=-(-total // world_size)))
    payload = repr((world_size, generation, mse,
                    tuple((g.dtype, g.indices, g.sizes, g.shapes)
                          for g in groups), tuple(replicated)))
    return ShardLayout(
        world_size=world_size, generation=int(generation),
        min_shard_elems=mse, num_leaves=len(leaves),
        groups=tuple(groups), replicated=tuple(replicated),
        replicated_elems=rep_elems, replicated_bytes=rep_bytes,
        digest=hashlib.sha1(payload.encode()).hexdigest())


def _rep_key(i: int) -> str:
    return f"{i:05d}"


def _combined(layout: ShardLayout, leaves, shard: dict) -> dict:
    """The structure the inner optimizer sees: the replicated leaves
    (``leaves[i]``) verbatim plus one flat shard per dtype group."""
    return {"rep": {_rep_key(i): leaves[i] for i in layout.replicated},
            "shard": shard}


# ===========================================================================
# Traced flavor: optax GradientTransformation over a named mesh axis
# ===========================================================================


def ShardedDistributedOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    num_shards: Optional[int] = None,
    axis_name: str = DEFAULT_AXIS,
    op: ReduceOp = ReduceOp.AVERAGE,
    min_shard_elems: Optional[int] = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
) -> optax.GradientTransformation:
    """ZeRO-1 drop-in for ``DistributedGradientTransformation`` (traced).

    ``hvd.DistributedOptimizer(inner, sharded_update=True)`` builds it.

    Inside a shard_map/pjit region with ``axis_name`` bound: sub-threshold
    leaves take the classic allreduce; everything else is fused per dtype
    of the parameter (bf16 gradients under float32 parameters are cast up:
    master-weight semantics), ``psum_scatter``'d, stepped on the owned
    shard (inner optimizer state 1/N per chip), and the update shards
    ``all_gather``'d back: one reduce-scatter and one all-gather per dtype
    per step. ``min_shard_elems=0`` shards every leaf but the scalars.
    ``num_shards`` may be omitted — the axis size is static at trace time
    — except for an ``init`` outside a traced region.

    Exact for elementwise optimizers (SGD/momentum/Adam/AdamW/...): the
    sharded update equals the replicated update slice for slice. Not for
    optimizers whose update couples elements across a leaf or reads the
    tree structure (per-layer norms like LARS, Adafactor row factors,
    ``optax.masked``/``multi_transform``): the inner optimizer sees ONE
    flat leaf per dtype, so use the replicated wrapper for those.
    """
    if op not in _SUPPORTED_OPS:
        raise ValueError(
            f"sharded update supports AVERAGE/SUM, got {op!r}")
    mse = _resolve_min_shard_elems(min_shard_elems)
    pre = float(prescale_factor)
    post = float(postscale_factor)

    def _world() -> int:
        if num_shards is not None:
            return int(num_shards)
        try:
            return jax.lax.axis_size(axis_name)
        except NameError as e:
            raise ValueError(
                "ShardedDistributedOptimizer: pass num_shards= when "
                f"calling init() outside a traced '{axis_name}' region"
            ) from e

    def init_fn(params):
        layout = plan_shard_layout(params, _world(), min_shard_elems=mse,
                                   generation=0)
        # init only needs shapes, and must work outside any trace, where
        # the rank is unknown: zeros stand for the shard
        return optimizer.init(_combined(
            layout, jax.tree.leaves(params),
            {g.dtype: jnp.zeros((g.shard_elems,), g.dtype)
             for g in layout.groups}))

    def update_fn(grads, state, params=None):
        world = jax.lax.axis_size(axis_name)
        if num_shards is not None and num_shards != world:
            raise ValueError(
                f"ShardedDistributedOptimizer(num_shards={num_shards}) used "
                f"under a {world}-wide '{axis_name}' axis")
        idx = jax.lax.axis_index(axis_name)
        leaves, treedef = jax.tree.flatten(grads)
        p_leaves = jax.tree.leaves(params) if params is not None else None
        # layout from the PARAM dtypes when params are given (master-weight
        # semantics: bf16 grads under fp32 params cast up before the
        # sharded step)
        layout = plan_shard_layout(params if params is not None else grads,
                                   world, min_shard_elems=mse, generation=0)

        g_rep = dict(zip(layout.replicated, _reduce(
            [leaves[i] for i in layout.replicated], axis_name,
            lambda g: C.allreduce(g, op=op, axis_name=axis_name,
                                  prescale_factor=pre,
                                  postscale_factor=post))))
        g_shard, p_shard = {}, {}
        for g in layout.groups:
            packing = dict(dtype=g.dtype, padded=layout.group_padded(g))
            fused = _pack(leaves, g.indices, **packing)
            if pre != 1.0:
                fused = fused * pre
            scattered, = _reduce(
                [fused], axis_name,
                lambda b: jax.lax.psum_scatter(b, axis_name, tiled=True),
                packed=len(g.indices) > 1)
            if op == ReduceOp.AVERAGE:
                scattered = scattered / world
            if post != 1.0:
                scattered = scattered * post
            g_shard[g.dtype] = scattered
            if p_leaves is not None:
                p_shard[g.dtype] = jax.lax.dynamic_slice(
                    _pack(p_leaves, g.indices, **packing),
                    (idx * g.shard_elems,), (g.shard_elems,))
        u, new_state = _update(
            optimizer, _combined(layout, g_rep, g_shard), state,
            None if p_leaves is None else _combined(layout, p_leaves, p_shard))

        out = list(leaves)
        for i in layout.replicated:
            out[i] = u["rep"][_rep_key(i)]
        for g in layout.groups:
            full, = _reduce(
                [u["shard"][g.dtype]], axis_name,
                lambda b: jax.lax.all_gather(b, axis_name, tiled=True))
            # cast back to the param leaf's dtype when given: casting
            # updates to a bf16 GRAD dtype under fp32 params would drift
            # from the replicated trajectory
            _unpack(full, leaves, g.indices, out,
                    like=leaves if p_leaves is None else p_leaves)
        return jax.tree.unflatten(treedef, out), new_state

    return optax.GradientTransformation(init_fn, update_fn)


# ===========================================================================
# Eager flavor: the per-process engine behind the shims and benches
# ===========================================================================

# live engines, for elastic's reshard notification (weak: an engine dies
# with its optimizer wrapper, the registry must not pin it)
_ENGINES: "weakref.WeakSet" = weakref.WeakSet()


def notify_reshard() -> None:
    """Elastic hook: a generation change invalidates every engine's
    layout; the next step replans (new digest → fresh compiled plans)
    and re-notes the ``reshard`` flightrec event."""
    for eng in list(_ENGINES):
        eng.invalidate_layout()


class ShardedUpdateEngine:
    """Eager ZeRO-1 update engine over the fused-plan cache.

    Real mode (``process_set=``): each process contributes its local
    gradients; the pack → reduce-scatter → sharded step → allgather →
    unpack chain replays as cached compiled programs
    (ops/collectives.py ``sharded_*_plan``). Simulated mode
    (``world_size=``/``rank=``, no process set): N engines in one
    process driven in lockstep by :func:`simulated_step` — the same
    plans, keyed ``ps=None`` — for tests and the CPU microbench.

    Optimizer state is allocated for this rank's shard only; params stay
    full (they are re-gathered every step).
    """

    def __init__(self, optimizer: optax.GradientTransformation, *,
                 process_set=None, world_size: Optional[int] = None,
                 rank: Optional[int] = None,
                 min_shard_elems: Optional[int] = None,
                 op: ReduceOp = ReduceOp.AVERAGE,
                 prescale_factor: float = 1.0,
                 postscale_factor: float = 1.0):
        if op not in _SUPPORTED_OPS:
            raise ValueError(
                f"sharded update supports AVERAGE/SUM, got {op!r}")
        self._opt = optimizer
        self._ps = process_set
        if process_set is not None:
            self._world = int(process_set.cross_size)
            self._rank = int(process_set.cross_rank)
        else:
            if world_size is None or rank is None:
                raise ValueError(
                    "simulated engine needs world_size= and rank=")
            self._world = int(world_size)
            self._rank = int(rank)
        self._mse = _resolve_min_shard_elems(min_shard_elems)
        self._op = op
        self._pre = float(prescale_factor)
        self._post = float(postscale_factor)
        self._layout: Optional[ShardLayout] = None
        from ..utils import metrics as metrics_mod

        reg = metrics_mod.get_registry()
        wire = "hvd_sharded_update_wire_bytes_total"
        wire_help = ("sharded-update wire bytes by phase (ring accounting: "
                     "(N-1)/N of the buffer per RS or AG pass)")
        self._m_rs = reg.counter(wire, wire_help, phase="reduce_scatter")
        self._m_ag = reg.counter(wire, wire_help, phase="allgather")
        self._m_rep = reg.counter(wire, wire_help, phase="allreduce")
        self._m_shard = reg.gauge(
            "hvd_sharded_update_shard_elems",
            "per-rank owned elements under the current shard layout")
        self._m_frac = reg.gauge(
            "hvd_sharded_update_shard_fraction",
            "fraction of elements on the sharded path (rest replicate)")
        _ENGINES.add(self)

    # -- layout -------------------------------------------------------------

    @property
    def layout(self) -> Optional[ShardLayout]:
        return self._layout

    def invalidate_layout(self) -> None:
        # safe without the plan funnel: the layout digest is a literal
        # component of every sharded plan signature (module docstring),
        # so a rebuilt layout misses onto fresh compiled programs — a
        # stale plan can never alias the new digest's key
        self._layout = None  # hvdlint: disable=invalidation-funnel (digest keys plans)

    def ensure_layout(self, params) -> ShardLayout:
        gen = env_schema.get_int(env_schema.HOROVOD_ELASTIC_GEN, 0)
        if self._layout is not None and self._layout.generation == gen:
            return self._layout
        layout = plan_shard_layout(params, self._world,
                                   min_shard_elems=self._mse, generation=gen)
        # same digest-keyed proof as invalidate_layout above
        self._layout = layout  # hvdlint: disable=invalidation-funnel (digest keys plans)
        self._m_shard.set(layout.shard_elems)
        self._m_frac.set(round(layout.shard_fraction, 6))
        flightrec.note("reshard", generation=layout.generation,
                       world=layout.world_size, rank=self._rank,
                       digest=layout.digest[:12],
                       groups=len(layout.groups),
                       replicated_leaves=len(layout.replicated),
                       shard_elems=layout.shard_elems)
        memledger_mod.sample_event("sharded_layout_rebuild")
        return layout

    # -- state --------------------------------------------------------------

    def init(self, params):
        """Inner optimizer state over this rank's shard (1/N) plus the
        replicated leaves — the combined structure the sharded step
        updates in one ``inner.update`` call."""
        layout = self.ensure_layout(params)
        leaves = jax.tree.leaves(params)
        state = self._opt.init(_combined(
            layout, leaves, self._param_shards(layout, leaves)))
        # the sharded-state bytes are the whole point of ZeRO-1: the
        # ledger's component attribution turns "should be 1/N" into a
        # measured number (tests/test_sharded_update.py asserts it)
        memledger_mod.note_sharded_state(state)
        return state

    # -- phase methods (shared by step() and simulated_step()) --------------

    def _pack(self, layout: ShardLayout, leaves, group: ShardGroup):
        plan = C.sharded_pack_plan(self._ps, layout.world_size, group.sizes,
                                   group.shapes, group.dtype,
                                   group.shard_elems, layout.digest)
        return plan(*[leaves[i] for i in group.indices])

    def _reduce_scatter_plan(self, layout: ShardLayout, group: ShardGroup):
        return C.sharded_reduce_scatter_plan(
            self._ps, layout.world_size, self._rank, self._op,
            group.shard_elems, group.dtype, layout.digest, self._pre,
            self._post)

    def _param_shards(self, layout: ShardLayout, p_leaves) -> dict:
        shards = {}
        for g in layout.groups:
            flat = self._pack(layout, p_leaves, g)
            lo = self._rank * g.shard_elems
            shards[g.dtype] = C._cached_slice(flat, lo, lo + g.shard_elems)
        return shards

    def _local_update(self, layout: ShardLayout, params, red_shards: dict,
                      red_rep: dict, state):
        """The sharded optimizer step: inner update over the combined
        (replicated leaves + owned shards) structure, updates applied.
        Returns (new param shards per dtype, new replicated leaves by
        index, new inner state)."""
        leaves = jax.tree.leaves(params)
        p_shard = self._param_shards(layout, leaves)
        u, new_state = self._opt.update(
            _combined(layout, red_rep, red_shards), state,
            _combined(layout, leaves, p_shard))
        new_rep = {i: optax.apply_updates(leaves[i], u["rep"][_rep_key(i)])
                   for i in layout.replicated}
        new_shards = {dt: optax.apply_updates(p_shard[dt], u["shard"][dt])
                      for dt in p_shard}
        return new_shards, new_rep, new_state

    def _unfuse(self, layout: ShardLayout, params, gathered: dict,
                new_rep: dict):
        """Updated param tree from the gathered shard stacks
        (``gathered[dtype]`` is S[world, shard_elems]) plus the locally
        updated replicated leaves."""
        leaves, treedef = jax.tree.flatten(params)
        out = list(leaves)
        for g in layout.groups:
            plan = C.sharded_allgather_plan(
                self._ps, layout.world_size, g.sizes, g.shapes, g.dtype,
                g.shard_elems, layout.digest)
            for i, part in zip(g.indices, plan(gathered[g.dtype])):
                out[i] = part
        for i, v in new_rep.items():
            out[i] = v
        return jax.tree.unflatten(treedef, out)

    def _account_step(self, layout: ShardLayout) -> None:
        """Analytic ring-accounting wire bytes for one step (the eager
        transport is a compiled XLA program, not a socket — bytes are
        derived, the same convention as hvd_allreduce byte counters)."""
        w = layout.world_size
        scale = (w - 1) / w if w > 1 else 0.0
        for g in layout.groups:
            b = layout.group_padded(g) * np.dtype(g.dtype).itemsize
            self._m_rs.inc(int(b * scale))
            self._m_ag.inc(int(b * scale))
        # replicated leaves ride a full allreduce: RS + AG phases
        self._m_rep.inc(int(2 * scale * layout.replicated_bytes))

    # -- real (process-backed) step -----------------------------------------

    def step(self, params, grads, state):
        """One eager sharded update across the process set. Returns
        ``(new_params, new_state)`` — params come back full (gathered)."""
        if self._ps is None:
            raise ValueError(
                "simulated engines step through simulated_step()")
        layout = self.ensure_layout(params)
        g_leaves = jax.tree.leaves(grads)
        red_rep = {i: C.allreduce(g_leaves[i], op=self._op,
                                  process_set=self._ps,
                                  prescale_factor=self._pre,
                                  postscale_factor=self._post)
                   for i in layout.replicated}
        red_shards = {}
        for g in layout.groups:
            flat = self._pack(layout, g_leaves, g)
            red_shards[g.dtype] = self._reduce_scatter_plan(layout, g)(
                C._global_row_array(self._ps, flat))
        new_shards, new_rep, new_state = self._local_update(
            layout, params, red_shards, red_rep, state)
        gathered = {dt: C._global_row_array(self._ps, sh)
                    for dt, sh in new_shards.items()}
        new_params = self._unfuse(layout, params, gathered, new_rep)
        self._account_step(layout)
        return new_params, new_state

    # -- elastic ------------------------------------------------------------

    def full_state(self, state, *, gather=None):
        """Materialize the unsharded inner state (elastic commit payload:
        every rank can restore from it under any future layout). Shard
        leaves are allgathered (``gather(position, leaf)``) and trimmed
        to their group's true extent; replicated leaves and scalars pass
        through."""
        layout = self._layout
        if layout is None:
            raise ValueError("no layout yet — run init()/step() first")
        if gather is None:
            if self._ps is None:
                raise ValueError(
                    "simulated engines use simulated_full_state()")
            gather = lambda pos, leaf: C.allgather(  # noqa: E731
                leaf, process_set=self._ps)
        flat, treedef = jtu.tree_flatten_with_path(state)
        out = []
        for pos, (path, leaf) in enumerate(flat):
            g = _shard_group_for(layout, path, leaf)
            out.append(leaf if g is None else gather(pos, leaf)[:g.total])
        return jtu.tree_unflatten(treedef, out)

    def load_full_state(self, full, params):
        """Re-materialize this rank's shard of ``full`` (a
        :meth:`full_state` payload, possibly from a previous world size)
        under the current layout."""
        layout = self.ensure_layout(params)
        flat, treedef = jtu.tree_flatten_with_path(full)
        out = []
        for path, leaf in flat:
            g = _shard_group_for(layout, path, leaf, full_extent=True)
            if g is not None:
                arr = _pack([jnp.asarray(leaf)], [0],
                            padded=layout.group_padded(g))
                lo = self._rank * g.shard_elems
                out.append(arr[lo:lo + g.shard_elems])
            else:
                out.append(leaf)
        return jtu.tree_unflatten(treedef, out)


def _shard_group_for(layout: ShardLayout, path, leaf, *,
                     full_extent: bool = False) -> Optional[ShardGroup]:
    """The dtype group a state leaf belongs to, or None for replicated
    leaves/scalars. Shard leaves are recognized by their tree path — the
    combined structure keys them under ``["shard"][dtype]`` — plus the
    expected extent (shard_elems, or the trimmed group total for
    full-state payloads)."""
    keys = [k.key for k in path if isinstance(k, jtu.DictKey)]
    if "shard" not in keys[:-1]:
        return None
    dt = keys[keys.index("shard") + 1]
    for g in layout.groups:
        if g.dtype == dt:
            want = g.total if full_extent else g.shard_elems
            fits = jnp.ndim(leaf) == 1 and jnp.shape(leaf)[0] == want
            return g if fits else None
    return None


# ===========================================================================
# Simulated lockstep world (tests, CPU microbench)
# ===========================================================================


def make_simulated_engines(optimizer, world: int, **kw) -> List[ShardedUpdateEngine]:
    """N virtual-rank engines sharing one process (and one plan cache)."""
    return [ShardedUpdateEngine(optimizer, world_size=world, rank=r, **kw)
            for r in range(world)]


def _sim_reduce(stack, op: ReduceOp, pre: float, post: float):
    """Replicated-leaf reduction for the simulated world, as a cached
    compiled program (same reduce body the RS plans use, so the sharded
    and replicated paths agree bitwise)."""
    key = ("sharded_sim_reduce", tuple(stack.shape), str(stack.dtype),
           int(op), float(pre), float(post))

    return C._cached(key, lambda: jax.jit(
        C._allreduce_body(None, op, pre, post, False)))(stack)


def simulated_step(engines: Sequence[ShardedUpdateEngine], params,
                   grads_per_rank: Sequence, states: Sequence):
    """Drive N simulated engines through one lockstep sharded update.

    ``params`` is the replicated tree (identical on every rank by
    contract); ``grads_per_rank[r]`` is rank r's local gradient tree.
    Returns ``(new_params, new_states)`` — new_params identical for all
    ranks by construction (same reduced inputs, same programs).
    """
    world = len(engines)
    layouts = [e.ensure_layout(params) for e in engines]
    layout = layouts[0]
    g_leaves = [jax.tree.leaves(g) for g in grads_per_rank]
    red_rep = {}
    for i in layout.replicated:
        stack = jnp.stack([g_leaves[r][i] for r in range(world)])
        red_rep[i] = _sim_reduce(stack, engines[0]._op, engines[0]._pre,
                                 engines[0]._post)
    fused = [{g.dtype: e._pack(lay, leaves, g) for g in lay.groups}
             for e, lay, leaves in zip(engines, layouts, g_leaves)]
    red_shards_per_rank: List[dict] = [{} for _ in range(world)]
    for g in layout.groups:
        G = jnp.stack([fused[r][g.dtype] for r in range(world)])
        for r, e in enumerate(engines):
            red_shards_per_rank[r][g.dtype] = e._reduce_scatter_plan(
                layouts[r], g)(G)
    locals_ = [e._local_update(lay, params, red_shards_per_rank[r], red_rep,
                               states[r])
               for r, (e, lay) in enumerate(zip(engines, layouts))]
    gathered = {g.dtype: jnp.stack([locals_[r][0][g.dtype]
                                    for r in range(world)])
                for g in layout.groups}
    new_params = engines[0]._unfuse(layout, params, gathered, locals_[0][1])
    for e, lay in zip(engines, layouts):
        e._account_step(lay)
    return new_params, [st for _, _, st in locals_]


def simulated_full_state(engines: Sequence[ShardedUpdateEngine],
                         states: Sequence):
    """:meth:`ShardedUpdateEngine.full_state` for a simulated world —
    shard leaves concatenated across the in-process engines."""
    flats = [jax.tree.leaves(s) for s in states]
    return engines[0].full_state(
        states[0], gather=lambda pos, _: jnp.concatenate(
            [flat[pos] for flat in flats]))
