"""The model zoo. Names resolve on first access (PEP 562), so importing
one model does not import the others' dependencies: the decoder
(``from horovod_tpu.models import transformer``) is plain JAX and pulls in
neither flax nor rich, a third of a second of a job's start (PERF.md,
PR 27). Every spelling works as before: ``from horovod_tpu.models import
ResNet50``, ``hvd.models.resnet``, ``dir(horovod_tpu.models)``."""

import importlib

#: submodule -> the names it gives this package
_NAMES = {
    "inception": ("InceptionV3",),
    "mlp": ("MLP", "MnistConvNet"),
    "resnet": ("ResNet", "ResNet50", "ResNet101", "ResNet152"),
    "transformer": (),
    "vgg": ("VGG", "VGG16", "VGG19"),
    "vit": ("ViT", "ViT_B16", "ViT_L16", "ViT_S16"),
}
_HOME = {name: module for module, names in _NAMES.items() for name in names}


def __getattr__(name: str):
    if name in _NAMES:
        return importlib.import_module(f"{__name__}.{name}")
    if name in _HOME:
        value = getattr(importlib.import_module(f"{__name__}.{_HOME[name]}"),
                        name)
        globals()[name] = value  # next time without this function
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted({*globals(), *_NAMES, *_HOME})
