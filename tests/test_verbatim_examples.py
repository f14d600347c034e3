"""Reference examples run VERBATIM against the ``horovod`` alias package.

SURVEY.md §7 step 3 / VERDICT r4 item 3: copy the reference user
scripts byte-for-byte (reference examples/pytorch/pytorch_mnist.py,
examples/tensorflow2/tensorflow2_mnist.py) — no import edits — and run
them green under ``hvdrun -np 2``. The only injection is the
dataset-download shim dir (tests/verbatim_support: synthetic MNIST +
a torchvision stand-in), because this image has zero egress.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUPPORT = os.path.join(REPO, "tests", "verbatim_support")
REFERENCE_EXAMPLES = "/root/reference/examples"

needs_reference = pytest.mark.skipif(
    not os.path.isdir(REFERENCE_EXAMPLES), reason="reference checkout absent"
)


def _run_verbatim(tmp_path, rel_script, *args, timeout=900, env_extra=None):
    src = os.path.join(REFERENCE_EXAMPLES, rel_script)
    script = os.path.join(str(tmp_path), os.path.basename(rel_script))
    shutil.copyfile(src, script)  # byte-for-byte; no edits

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # shim dir first (sitecustomize + torchvision), then the repo for
    # the horovod alias package itself
    env["PYTHONPATH"] = (
        SUPPORT + os.pathsep + REPO + os.pathsep + env.get("PYTHONPATH", "")
    )
    env["HVD_VERBATIM_MNIST_N"] = "512"
    if env_extra:
        env.update(env_extra)
    worker_env = []
    for k in ("JAX_PLATFORMS", "PYTHONPATH", "HVD_VERBATIM_MNIST_N",
              "HVD_VERBATIM_MNIST_DIM", "TF_USE_LEGACY_KERAS"):
        if k in env:
            worker_env += ["--env", f"{k}={env[k]}"]
    # conftest exports XLA_FLAGS=--xla_force_host_platform_device_count=8
    # for in-process tests; verbatim workers must see 1 chip per process
    # so hvd.rank()/size() match the reference's process-rank math
    worker_env += ["--env", "XLA_FLAGS="]
    p = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         *worker_env, sys.executable, script, *args],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=timeout)
    assert p.returncode == 0, (p.stdout[-3000:], p.stderr[-3000:])
    return p.stdout


@needs_reference
def test_alias_package_identity():
    """horovod.X is horovod_tpu.X — one runtime, not a parallel copy."""
    code = (
        "import horovod, horovod.torch, horovod_tpu.torch\n"
        "assert horovod.torch is horovod_tpu.torch\n"
        "import horovod.tensorflow.keras, horovod_tpu.tensorflow.keras\n"
        "assert horovod.tensorflow.keras is horovod_tpu.tensorflow.keras\n"
        "from horovod.runner import run; assert callable(run)\n"
        "from horovod import run as r2; assert r2 is run\n"
        "print('ALIAS-OK')\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    assert "ALIAS-OK" in p.stdout


@needs_reference
def test_reference_pytorch_mnist_verbatim(tmp_path):
    """reference examples/pytorch/pytorch_mnist.py:11 `import
    horovod.torch as hvd` — unmodified, 2 processes, 1 epoch."""
    out = _run_verbatim(tmp_path, "pytorch/pytorch_mnist.py",
                        "--epochs", "1", "--data-dir", str(tmp_path))
    assert "Test set: Average loss" in out


@needs_reference
def test_reference_tensorflow2_mnist_verbatim(tmp_path):
    """reference examples/tensorflow2/tensorflow2_mnist.py:17 `import
    horovod.tensorflow as hvd` — unmodified. The script's step count is
    hardcoded (10000 // size); the dataset shim keeps images small
    (HVD_VERBATIM_MNIST_DIM) so 5000 CPU steps stay cheap."""
    out = _run_verbatim(
        tmp_path, "tensorflow2/tensorflow2_mnist.py", timeout=1500,
        env_extra={"HVD_VERBATIM_MNIST_DIM": "8",
                   "TF_USE_LEGACY_KERAS": "1"})
    assert "Step #" in out
    assert os.path.exists(os.path.join(str(tmp_path), "checkpoints-1.index")) or any(
        n.startswith("checkpoints") for n in os.listdir(str(tmp_path)))


@needs_reference
def test_reference_tensorflow2_keras_mnist_verbatim(tmp_path):
    """reference examples/tensorflow2/tensorflow2_keras_mnist.py:17
    `import horovod.tensorflow.keras as hvd` — unmodified under
    TF_USE_LEGACY_KERAS=1 (the reference era's Keras-2 API:
    `experimental_run_tf_function=False` compile kwarg, h5 checkpoints).
    24 hardcoded epochs x 250 steps; the dataset shim keeps images 8x8."""
    out = _run_verbatim(
        tmp_path, "tensorflow2/tensorflow2_keras_mnist.py", timeout=900,
        env_extra={"HVD_VERBATIM_MNIST_DIM": "8",
                   "TF_USE_LEGACY_KERAS": "1"})
    assert "Epoch 24/24" in out
    # rank 0 wrote per-epoch h5 checkpoints
    assert any(n.startswith("checkpoint-") and n.endswith(".h5")
               for n in os.listdir(str(tmp_path)))


@needs_reference
def test_reference_tf2_synthetic_benchmark_verbatim(tmp_path):
    """reference examples/tensorflow2/tensorflow2_synthetic_benchmark.py
    — the reference's OWN perf-measurement harness (BASELINE.md's
    in-repo harness row) — unmodified, 2 processes. Only injections:
    the sitecustomize Keras-version compat patch (``opt.variables()``
    was a method in the script's TF era, a property now; fails
    identically against original Horovod on this TF) and tiny sizes via
    its own CLI flags."""
    out = _run_verbatim(
        tmp_path, "tensorflow2/tensorflow2_synthetic_benchmark.py",
        "--model", "MobileNetV2", "--batch-size", "4",
        "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "2", timeout=900)
    assert "Total img/sec on 2" in out


@needs_reference
def test_reference_pytorch_synthetic_benchmark_verbatim(tmp_path):
    """reference examples/pytorch/pytorch_synthetic_benchmark.py —
    DistributedOptimizer(named_parameters, compression, op) + both
    broadcasts on a real torch ResNet-50 — unmodified, 2 processes.
    torchvision is uninstallable here (zero egress), so the stand-in
    provides an independent implementation of the architecture
    (canonical 25,557,032 params, tests/verbatim_support/torchvision/
    models.py)."""
    out = _run_verbatim(
        tmp_path, "pytorch/pytorch_synthetic_benchmark.py",
        "--batch-size", "2", "--num-warmup-batches", "1",
        "--num-batches-per-iter", "1", "--num-iters", "2", timeout=900)
    assert "Total img/sec on 2" in out


@needs_reference
def test_reference_tf2_keras_synthetic_benchmark_verbatim(tmp_path):
    """reference examples/tensorflow2/tensorflow2_keras_synthetic_
    benchmark.py — DistributedOptimizer(compression=) + callbacks on
    model.fit — unmodified, 2 processes (sitecustomize swallows the
    TF-2.0-era ``experimental_run_tf_function`` compile kwarg that TF
    itself removed in 2.4)."""
    out = _run_verbatim(
        tmp_path, "tensorflow2/tensorflow2_keras_synthetic_benchmark.py",
        "--model", "MobileNetV2", "--batch-size", "4",
        "--num-batches-per-iter", "1", "--num-iters", "2", timeout=900)
    assert "Total img/sec on 2" in out


@needs_reference
def test_keras2_distributed_optimizer_actually_averages(tmp_path):
    """The Keras-2 (tf_keras) wrap must intercept apply_gradients — a
    wrong-funnel wrap trains without ever averaging, silently. Proof:
    two ranks with rank-dependent data end one step with IDENTICAL
    weights equal to the single-rank average."""
    import subprocess
    import textwrap

    script = os.path.join(str(tmp_path), "w.py")
    with open(script, "w") as f:
        f.write(textwrap.dedent("""
            import os
            os.environ["TF_USE_LEGACY_KERAS"] = "1"
            # 1 chip per process: hvd.rank()/size() are chip-level
            # (documented TPU semantics), and this test's analytic
            # expectation assumes rank in {0, 1}
            import jax
            jax.config.update("jax_platforms", "cpu")
            import numpy as np
            import tensorflow as tf
            import horovod.tensorflow.keras as hvd

            hvd.init()
            r = hvd.rank()
            model = tf.keras.Sequential(
                [tf.keras.layers.Dense(1, use_bias=False,
                                       kernel_initializer="zeros",
                                       input_shape=(2,))])
            opt = hvd.DistributedOptimizer(tf.optimizers.SGD(0.5))
            model.compile(optimizer=opt, loss="mse",
                          experimental_run_tf_function=False)
            # rank-dependent data -> rank-dependent local grads
            x = np.full((4, 2), 1.0 + r, np.float32)
            y = np.full((4, 1), 2.0 * (1.0 + r), np.float32)
            model.fit(x, y, batch_size=4, epochs=1, verbose=0,
                      callbacks=[hvd.callbacks
                                 .BroadcastGlobalVariablesCallback(0)])
            w = model.get_weights()[0].reshape(-1)
            # local grad for rank r (w=0): d/dw mean((x.w - y)^2)
            #   = 2*mean(x*(x.w - y)) = -2*(1+r)*2*(1+r) = -4(1+r)^2
            # averaged grad = (-4 - 16)/2 = -10 -> w = 0.5*10 = 5 each
            assert np.allclose(w, 5.0, atol=1e-4), w
            others = hvd.allgather_object(w.tolist())
            assert all(np.allclose(o, w) for o in others), others
            print("K2-AVG-OK", r)
        """))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         "--env", "JAX_PLATFORMS=cpu", "--env", "TF_USE_LEGACY_KERAS=1",
         "--env", "PYTHONPATH=" + env["PYTHONPATH"],
         "--env", "XLA_FLAGS=",
         sys.executable, script],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, (p.stdout[-3000:], p.stderr[-3000:])
    assert p.stdout.count("K2-AVG-OK") == 2


@needs_reference
def test_reference_pytorch_mnist_verbatim_adasum_fp16(tmp_path):
    """The reference script's own flag surface: --use-adasum exercises
    the delta-Adasum torch optimizer and --fp16-allreduce the wire
    compression, through the unmodified script."""
    out = _run_verbatim(tmp_path, "pytorch/pytorch_mnist.py",
                        "--epochs", "1", "--use-adasum",
                        "--data-dir", str(tmp_path))
    assert "Test set: Average loss" in out
    out = _run_verbatim(tmp_path, "pytorch/pytorch_mnist.py",
                        "--epochs", "1", "--fp16-allreduce",
                        "--data-dir", str(tmp_path))
    assert "Test set: Average loss" in out


@needs_reference
def test_reference_pytorch_mnist_elastic_verbatim(tmp_path):
    """reference examples/elastic/pytorch/pytorch_mnist_elastic.py —
    `@hvd.elastic.run` + `hvd.elastic.TorchState(model, optimizer,
    epoch=1, batch=0)` driving state.model/state.optimizer publicly,
    with per-batch state.commit(); unmodified under a static -np 2
    launch (the elastic wrapper is world-size-agnostic)."""
    out = _run_verbatim(tmp_path, "elastic/pytorch/pytorch_mnist_elastic.py",
                        "--epochs", "1", "--data-dir", str(tmp_path))
    assert "Test set: Average loss" in out


@needs_reference
def test_reference_tensorflow2_mnist_elastic_verbatim(tmp_path):
    """reference examples/elastic/tensorflow2/tensorflow2_mnist_elastic.py
    — `hvd.elastic.TensorFlowKerasState(model, opt, batch=0)` + the
    traced DistributedGradientTape step with per-10-batch commits;
    unmodified (legacy keras: the script uses opt.lr.assign)."""
    out = _run_verbatim(
        tmp_path, "elastic/tensorflow2/tensorflow2_mnist_elastic.py",
        timeout=1200,
        env_extra={"HVD_VERBATIM_MNIST_DIM": "8",
                   "TF_USE_LEGACY_KERAS": "1"})
    assert "Step #" in out
