"""What a job pays at its start for what it does not use: the decoder
imports no flax, nothing imports JAX's Pallas until a kernel is taken
(about a second, PERF.md PR 27), a decoder without experts does not
import the expert layer, and the model zoo's names still resolve in
every spelling."""

import json
import os
import subprocess
import sys

import pytest

ZOO = ["InceptionV3", "MLP", "MnistConvNet", "ResNet", "ResNet101",
       "ResNet152", "ResNet50", "VGG", "VGG16", "VGG19", "ViT", "ViT_B16",
       "ViT_L16", "ViT_S16"]
SUBMODULES = ["inception", "mlp", "resnet", "transformer", "vgg", "vit"]


def test_the_decoder_and_the_step_import_neither_pallas_nor_flax():
    """In a process of its own: sys.modules after the imports an LM job
    makes, then the zoo's spellings, which do import flax, then the
    kernels' module as a TPU job imports it at its first trace."""
    code = """
import json, sys
import horovod_tpu, horovod_tpu.models.transformer, horovod_tpu.parallel
early = sorted(m for m in ("jax.experimental.pallas", "flax", "rich")
               if m in sys.modules)
import horovod_tpu.models
listed = dir(horovod_tpu.models)
from horovod_tpu.models import ResNet50, transformer
from horovod_tpu.models import transformer as T
import horovod_tpu as hvd
report = [
    early, "flax" in sys.modules, "jax.experimental.pallas" in sys.modules,
    T is transformer is hvd.models.transformer,
    ResNet50 is hvd.models.resnet.ResNet50, listed]
# where a kernel is taken on a TPU: Pallas without its GPU interpreter
import importlib, jax
from jax._src import xla_bridge
started_by_imports = xla_bridge.backends_are_initialized()
jax.devices()   # the backend is up by a job's first trace
jax.default_backend = lambda: "tpu"
F = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")
gpu = F._GPU_INTERPRETER
report += [gpu in sys.modules, "jax.experimental.mosaic.gpu" in sys.modules,
           importlib.import_module(gpu).__name__ == gpu, started_by_imports]
print(json.dumps(report))
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    (early, flax, pallas, same, zoo, listed, gpu_interpreter, gpu_dialects,
     importable_later, started_by_imports) = json.loads(
         out.stdout.splitlines()[-1])
    assert early == []              # the decoder's job pays for none
    assert flax and not pallas      # ResNet50 is flax's; no kernel was taken
    assert same and zoo
    assert set(ZOO + SUBMODULES) <= set(listed)
    # neither the interpreter nor a None in its place is left behind
    assert not gpu_interpreter and not gpu_dialects and importable_later
    assert not started_by_imports   # no import starts JAX's backend


@pytest.mark.parametrize("name", ZOO + SUBMODULES)
def test_every_name_of_the_zoo_resolves(name):
    import importlib

    import horovod_tpu.models as models

    value = getattr(models, name)
    if name in SUBMODULES:
        assert value is importlib.import_module(f"horovod_tpu.models.{name}")
    else:
        assert callable(value) and value.__module__.startswith(
            "horovod_tpu.models.")
    assert name in dir(models)


def test_an_unknown_name_is_an_attribute_error():
    import horovod_tpu.models as models

    with pytest.raises(AttributeError, match="no attribute 'ResNet51'"):
        models.ResNet51
    with pytest.raises(ImportError):
        from horovod_tpu.models import ResNet51  # noqa: F401


def test_a_dense_decoder_never_imports_the_expert_layer():
    """In a process of its own: a dense decoder's loss and gradient are
    traced with ``parallel/moe.py`` nowhere in ``sys.modules`` (the
    dense and ResNet cells of the benchmark pay nothing for it); its
    names still resolve from ``horovod_tpu.parallel``, and a
    sparse-expert decoder's first trace is what imports it."""
    code = """
import json, sys
import jax, jax.numpy as jnp
import horovod_tpu, horovod_tpu.parallel
from horovod_tpu.models import transformer as T
MOE = "horovod_tpu.parallel.moe"
tokens = jnp.zeros((1, 9), jnp.int32)
dense = T.TransformerConfig(vocab_size=32, d_model=16, n_heads=2, n_layers=1,
                            d_ff=32, max_seq=8)
jax.eval_shape(jax.grad(lambda p: T.lm_loss(p, tokens, dense,
                                            use_constraints=False)),
               T.init(jax.random.PRNGKey(0), dense))
after_dense = MOE in sys.modules
sparse = T.TransformerConfig(vocab_size=32, d_model=16, n_heads=2, n_layers=1,
                             d_ff=0, max_seq=8, n_experts=4,
                             experts_per_token=2, d_expert=8)
jax.eval_shape(lambda p: T.lm_loss(p, tokens, sparse, use_constraints=False),
               T.init(jax.random.PRNGKey(0), sparse))
after_sparse = MOE in sys.modules
from horovod_tpu.parallel import expert_layer, route
print(json.dumps([after_dense, after_sparse,
                  expert_layer is sys.modules[MOE].expert_layer,
                  "route" in dir(horovod_tpu.parallel)]))
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.splitlines()[-1]) == [False, True, True,
                                                       True]
