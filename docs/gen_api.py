"""Generate docs/api.md from module docstrings (run on CPU)."""
import os
import sys
# importable without the editable install (script dir is docs/, not repo)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"
import jax; jax.config.update("jax_platforms", "cpu")
import importlib, inspect

MODULES = [
    ("horovod_tpu", "Core API: init/topology, collectives, async handles"),
    ("horovod_tpu.tensorflow", "TensorFlow API"),
    ("horovod_tpu.keras", "Keras API"),
    ("horovod_tpu.torch", "PyTorch API"),
    ("horovod_tpu.mxnet", "MXNet API"),
    ("horovod_tpu.elastic", "Elastic training"),
    ("horovod_tpu.parallel", "Parallelism strategies"),
    ("horovod_tpu.spark", "Spark integration"),
    ("horovod_tpu.ray", "Ray integration"),
    ("horovod_tpu.runner", "Launcher"),
    ("horovod_tpu.utils.data", "Input pipeline"),
    ("horovod_tpu.utils.checkpoint", "Checkpoints"),
    ("horovod_tpu.utils.timeline", "Timeline/profiling"),
    ("horovod_tpu.models", "Model zoo"),
    ("horovod_tpu.models.transformer", "Decoder (dense, sparse-expert, latent-attention or looped, composed per layer)"),
    ("horovod_tpu.parallel.moe", "Sparse experts: router and expert layer"),
    ("horovod_tpu.ops.pallas.flash_attention", "Pallas kernels"),
]

def firstline(obj):
    d = inspect.getdoc(obj) or ""
    line = d.split("\n", 1)[0].strip()
    return line[:110]

out = ["# API reference (generated index)", "",
       "One line per public symbol; see docstrings for details.",
       "Regenerate with `python docs/gen_api.py`.", ""]
for name, title in MODULES:
    try:
        mod = importlib.import_module(name)
    except Exception as e:
        print(f"WARNING: skipping {name}: {type(e).__name__}: {e}",
              file=sys.stderr)
        continue
    out.append(f"## `{name}` — {title}")
    out.append("")
    skip = {"Optional", "Any", "Callable", "Iterable", "Iterator",
            "Sequence", "annotations", "Tuple"}
    pub = [n for n in sorted(dir(mod))
           if not n.startswith("_") and n not in skip]
    rows = []
    for n in pub:
        o = getattr(mod, n)
        if inspect.ismodule(o):
            continue
        if inspect.isclass(o) or inspect.isfunction(o) or callable(o):
            rows.append(f"- `{n}` — {firstline(o) or 'see docstring'}")
    seen = set()
    for r in rows:
        if r not in seen:
            out.append(r)
            seen.add(r)
    out.append("")
open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "api.md"), "w").write("\n".join(out) + "\n")
print("wrote", len(out), "lines")
