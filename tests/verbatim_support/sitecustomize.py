"""ENVIRONMENT shims for running the REFERENCE examples verbatim.

The north-star contract (SURVEY.md §7 step 3) is that reference user
scripts run **unmodified** against the ``horovod`` alias package. This
sitecustomize (put on PYTHONPATH only by tests/test_verbatim_examples.py)
injects compensation for exactly two properties of this image, neither
of them horovod behavior:

- **zero egress**: keras's MNIST ``load_data`` (reference
  tensorflow2_mnist.py:29) is replaced with a synthetic in-memory
  generator, and a torchvision stand-in package is provided;
- **Keras/TF version skew**: the reference's 2019-era synthetic
  benchmarks use APIs TF itself later changed — ``opt.variables()``
  as a method and the ``experimental_run_tf_function`` compile kwarg
  (removed in TF 2.4). Two patches restore those spellings; the
  scripts fail identically against ORIGINAL Horovod on this TF
  without them.

No horovod/model/step code is touched. It also chain-loads the system
sitecustomize it shadows, if there is one, since Python imports only the
first one found.
"""

import importlib.abc
import importlib.machinery
import importlib.util
import os
import sys

def _patch_optimizer_variables(module):
    """Keras-VERSION compat (not horovod logic): the reference's
    2019-era synthetic benchmarks call ``opt.variables()``
    (tensorflow2_synthetic_benchmark.py:94) — a method on TF≤2.10-era
    optimizers, a plain list property in Keras 3. Make the property's
    value answer both spellings. The same scripts fail identically
    against original Horovod on this TF; this shim is about the image's
    TF version, exactly like the dataset-download shims are about its
    zero egress."""
    base = getattr(module, "BaseOptimizer", None)
    if base is None:
        return
    orig = base.__dict__.get("variables")
    if not isinstance(orig, property):
        return

    class _CallableList(list):
        def __call__(self):
            return list(self)

    base.variables = property(lambda self: _CallableList(orig.fget(self)))


def _patch_compile_legacy_kwarg(module):
    """Keras-VERSION compat: the reference's Keras synthetic benchmark
    passes ``experimental_run_tf_function=False`` to ``model.compile``
    (tensorflow2_keras_synthetic_benchmark.py:84) — a TF-2.0-era kwarg
    that TF itself removed in 2.4; Keras 3 raises TypeError on it.
    Swallow exactly that kwarg, nothing else."""
    trainer = getattr(module, "Trainer", None)
    if trainer is None:
        return
    orig = trainer.compile

    def compile(self, *args, **kwargs):
        kwargs.pop("experimental_run_tf_function", None)
        return orig(self, *args, **kwargs)

    trainer.compile = compile


def _synthetic_mnist_load_data(path="mnist.npz"):
    """Drop-in for keras.datasets.mnist.load_data: deterministic synthetic
    digits, sized by HVD_VERBATIM_MNIST_DIM/N so CI steps stay cheap."""
    import numpy as np

    dim = int(os.environ.get("HVD_VERBATIM_MNIST_DIM", "10"))
    n = int(os.environ.get("HVD_VERBATIM_MNIST_N", "512"))
    rng = np.random.RandomState(0)

    def split(count):
        x = rng.randint(0, 256, size=(count, dim, dim)).astype("uint8")
        y = rng.randint(0, 10, size=(count,)).astype("uint8")
        return x, y

    return split(n), split(max(n // 2, 1))


def _patch(module):
    module.load_data = _synthetic_mnist_load_data


_TARGETS = {
    "keras.datasets.mnist": _patch,
    "keras.src.datasets.mnist": _patch,
    # legacy-keras spellings (TF_USE_LEGACY_KERAS=1 → tf.keras is
    # tf_keras, matching the reference's Keras-2-era API)
    "tf_keras.datasets.mnist": _patch,
    "tf_keras.src.datasets.mnist": _patch,
    "keras.src.optimizers.base_optimizer": _patch_optimizer_variables,
    "keras.src.trainers.trainer": _patch_compile_legacy_kwarg,
}


class _PatchingLoader(importlib.abc.Loader):
    def __init__(self, wrapped, patch):
        self._wrapped = wrapped
        self._patch = patch

    def __getattr__(self, name):
        return getattr(self._wrapped, name)

    def create_module(self, spec):
        return self._wrapped.create_module(spec)

    def exec_module(self, module):
        self._wrapped.exec_module(module)
        self._patch(module)


class _MnistShimFinder(importlib.abc.MetaPathFinder):
    def find_spec(self, fullname, path=None, target=None):
        if fullname not in _TARGETS:
            return None
        sys.meta_path.remove(self)
        try:
            spec = importlib.util.find_spec(fullname)
        finally:
            sys.meta_path.insert(0, self)
        if spec is None or spec.loader is None:
            return None
        spec.loader = _PatchingLoader(spec.loader, _TARGETS[fullname])
        return spec


if not any(isinstance(f, _MnistShimFinder) for f in sys.meta_path):
    sys.meta_path.insert(0, _MnistShimFinder())

# chain-load the sitecustomize this file shadows (first match on the
# remaining path entries that isn't us)
_here = os.path.dirname(os.path.abspath(__file__))
for _p in sys.path:
    _cand = os.path.join(_p or ".", "sitecustomize.py")
    if os.path.abspath(os.path.dirname(_cand)) == _here:
        continue
    if os.path.isfile(_cand):
        _spec = importlib.util.spec_from_file_location("_chained_sitecustomize", _cand)
        _mod = importlib.util.module_from_spec(_spec)
        try:
            _spec.loader.exec_module(_mod)
        except Exception:
            pass
        break
