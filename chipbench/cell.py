"""What a family hands the harness: one cell, built and placed."""

import dataclasses
from typing import Any, Callable


@dataclasses.dataclass
class Cell:
    """One configuration under one traffic mix, ready to step.

    ``step(state, opt_state, *batch)`` is the program's compiled step
    (``parallel.data_parallel_step``) and returns ``(state, opt_state,
    loss)``; it donates ``state`` and ``opt_state``. Both are already
    placed on the mesh (PERF.md, PR 21's findings: otherwise the step
    compiles twice) and ``batch`` is resident and sharded over it.
    """

    step: Callable
    state: Any
    opt_state: Any
    batch: tuple
    #: items (images, tokens) one step completes, over all chips
    items_per_step: int
    #: forward + backward operations one item needs (chipbench/flops.py)
    train_flops_per_item: float
    #: ``check(cell)`` compares the program with the family's plain
    #: references outside the timed window and returns a dict with a
    #: boolean ``ok`` and the numbers it compared; it may start again
    #: from the seed and leave ``state`` and ``opt_state`` a step on
    check: Callable[["Cell"], dict]


def seed_key(seed: int):
    """PRNG key for any whole-number ``--seed`` (the driver's pass 2**31)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def build_optimizer(spec: dict):
    """The configuration file's optimizer, by optax name, under
    ``hvd.DistributedOptimizer`` (the program under test) and plain (the
    reference's)."""
    import optax

    import horovod_tpu as hvd

    plain = getattr(optax, spec["name"])(**spec["args"])
    return hvd.DistributedOptimizer(plain), plain


def dtype_of(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def placed(mesh, shapes, spec) -> tuple:
    """``shapes`` (a sequence of pytrees of ShapeDtypeStruct) with the
    sharding ``spec`` over ``mesh`` on every leaf."""
    import jax
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, spec)
    return tuple(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree) for tree in shapes)


def replica_on(device, tree):
    """``device``'s own copy of a tree that is replicated over a mesh
    (no transfer: the shard that already lives there)."""
    import jax

    return jax.tree.map(
        lambda x: next(s.data for s in x.addressable_shards
                       if s.device == device), tree)


def pick(tree, path: str):
    """The leaf at a dotted path; an integer step indexes a list."""
    for part in path.split("."):
        tree = tree[int(part)] if part.lstrip("-").isdigit() else tree[part]
    return tree


def rel_l2(got, want):
    """L2 size of ``got - want`` as a share of ``want``'s, in float32."""
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(got - want))
                    / jnp.sum(jnp.square(want)))
