from .mesh import create_mesh, create_hierarchical_mesh, parse_mesh_spec  # noqa: F401
from .dp import data_parallel_step, shard_batch  # noqa: F401
from .tp import (column_parallel_dense, row_parallel_dense, parallel_mlp,  # noqa: F401
                 parallel_attention_output, shard_leading)
from .sp import (  # noqa: F401
    ring_attention,
    stripe_tokens,
    striped_ring_attention,
    ulysses_attention,
    unstripe_tokens,
)
from .pp import pipeline_apply, pipeline_loss  # noqa: F401
from .fsdp import fsdp_specs, opt_state_specs, fsdp_train_step  # noqa: F401

#: the sparse-expert layer resolves on first access (PEP 562): a job
#: without experts does not import it (tests/test_lazy_imports.py)
_LAZY = ("moe", "route", "expert_layer")


def __getattr__(name: str):
    if name not in _LAZY:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    module = importlib.import_module(f"{__name__}.moe")
    return module if name == "moe" else getattr(module, name)


def __dir__():
    return sorted({*globals(), *_LAZY})
