"""Examples as smoke tests (reference CI runs examples this way —
.buildkite/gen-pipeline.sh:172-212). Each example launches in a
subprocess (multi-process ones through ``hvdrun -np 2``) with the CPU
platform forced for workers."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


def _env():
    e = dict(os.environ)
    # CPU-only smoke: force the cpu platform in workers
    e["JAX_PLATFORMS"] = "cpu"
    e["PYTHONPATH"] = REPO + os.pathsep + e.get("PYTHONPATH", "")
    return e


def _run(argv, timeout=420, env_extra=None):
    env = _env()
    if env_extra:
        env.update(env_extra)
    p = subprocess.run(argv, env=env, cwd=REPO, capture_output=True,
                       text=True, timeout=timeout)
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    return p.stdout


def _hvdrun(np_, script, *args):
    return _run([sys.executable, "-m", "horovod_tpu.runner", "-np",
                 str(np_), "--env", "JAX_PLATFORMS=cpu", sys.executable,
                 os.path.join(EXAMPLES, script), *args])


def test_tensorflow2_mnist_two_proc():
    out = _hvdrun(2, "tensorflow2_mnist.py", "--steps", "6",
                  "--batch", "32")
    assert "step" in out  # training-progress lines from rank 0


def test_pytorch_mnist_two_proc():
    _hvdrun(2, "pytorch_mnist.py", "--epochs", "1", "--batch-size", "64")


def test_jax_mnist_single_proc():
    _run([sys.executable, os.path.join(EXAMPLES, "jax_mnist.py"),
          "--epochs", "1", "--batch-size", "32"])


def test_adasum_example():
    _run([sys.executable, os.path.join(EXAMPLES, "adasum_jax.py"),
          "--steps", "5", "--batch", "32"])


def test_ray_and_spark_examples():
    _run([sys.executable, os.path.join(EXAMPLES, "ray_run.py"),
          "--workers", "2", "--steps", "2"])
    _run([sys.executable, os.path.join(EXAMPLES, "spark_estimator.py")])


def test_hvdrun_timeline_end_to_end(tmp_path):
    """A 2-process hvdrun job with --timeline-filename produces a parseable
    chrome-trace JSON with negotiation + activity phases (reference
    test/parallel/test_timeline.py shape)."""
    import json
    import textwrap

    tl = os.path.join(str(tmp_path), "timeline.json")
    script = os.path.join(str(tmp_path), "worker.py")
    with open(script, "w") as f:
        f.write(textwrap.dedent("""
            import jax
            jax.config.update("jax_platforms", "cpu")
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            for i in range(3):
                hvd.synchronize(hvd.allreduce_async(
                    np.ones(8, np.float32), name=f"tl.t{i}"))
            hvd.shutdown()
        """))
    _run([sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
          "--timeline-filename", tl, sys.executable, script])
    events = json.load(open(tl))
    assert isinstance(events, list) and events
    names = {e.get("name") for e in events}
    assert any("NEGOTIATE" in (n or "") for n in names), names
    phases = {e.get("ph") for e in events}
    assert "B" in phases and "E" in phases


def test_keras_estimator_distributed_under_hvdrun(tmp_path):
    """KerasEstimator.fit inside an hvdrun worker takes the data-parallel
    branch: wrapped optimizer, sharding, rank-0-only checkpoint."""
    import textwrap

    store_dir = os.path.join(str(tmp_path), "store")
    script = os.path.join(str(tmp_path), "worker.py")
    with open(script, "w") as f:
        f.write(textwrap.dedent(f"""
            import jax
            jax.config.update("jax_platforms", "cpu")
            import numpy as np, keras
            from horovod_tpu.spark import KerasEstimator, FilesystemStore
            keras.utils.set_random_seed(0)
            rng = np.random.RandomState(1)
            import pandas as pd
            x = rng.randn(64, 3).astype(np.float32)
            y = (x @ np.ones((3, 1), np.float32))[:, 0]
            df = pd.DataFrame({{"f": list(x), "y": y}})
            model = keras.Sequential([keras.Input((3,)),
                                      keras.layers.Dense(1)])
            est = KerasEstimator(model=model,
                                 optimizer=keras.optimizers.Adam(0.05),
                                 loss="mse", feature_cols=["f"],
                                 label_cols=["y"], batch_size=8, epochs=10,
                                 store=FilesystemStore({store_dir!r}),
                                 run_id="lk", verbose=0)
            est.fit(df)
            assert getattr(model.optimizer.__class__, "_hvd_wrapped", False)
            print("EST-OK")
        """))
    out = _run([sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
                sys.executable, script])
    assert out.count("EST-OK") == 2
    assert os.path.exists(os.path.join(store_dir, "runs", "lk",
                                       "checkpoint"))


def test_synthetic_benchmarks_two_proc():
    """Per-framework synthetic benchmark examples (reference
    examples/*/..._synthetic_benchmark.py) run under hvdrun -np 2 and
    report throughput."""
    out = _hvdrun(2, "pytorch_synthetic_benchmark.py",
                  "--num-iters", "2", "--num-warmup-batches", "1")
    assert "Img/sec per worker" in out
    out = _hvdrun(2, "tensorflow2_synthetic_benchmark.py",
                  "--num-iters", "2", "--num-warmup-batches", "1")
    assert "Total img/sec on 2 worker" in out


def test_tf_collective_gradients_two_proc(tmp_path):
    """TF gradient registrations at a real world size 2 (size-1 tests
    degenerate to identity): allgather grad slices per rank, broadcast
    grad is zero off-root, alltoall grad routes back."""
    import textwrap

    script = os.path.join(str(tmp_path), "worker.py")
    with open(script, "w") as f:
        f.write(textwrap.dedent("""
            import jax
            jax.config.update("jax_platforms", "cpu")
            import numpy as np
            import tensorflow as tf
            import horovod_tpu.tensorflow as hvd
            hvd.init()
            r = hvd.cross_rank()

            # allgather: dy = [[1],[2]] everywhere; rank r keeps row r
            x = tf.Variable([[float(r + 1)]])
            with tf.GradientTape() as tape:
                g = hvd.allgather(x, name="g.ag")
                loss = tf.reduce_sum(g * tf.constant([[1.0], [2.0]]))
            dx = tape.gradient(loss, x)
            np.testing.assert_allclose(dx.numpy(), [[float(r + 1)]])

            # broadcast from root 0: only rank 0 keeps the grad
            y = tf.Variable([2.0])
            with tf.GradientTape() as tape:
                b = hvd.broadcast(y, root_rank=0, name="g.bc")
                loss = tf.reduce_sum(3.0 * b)
            dy = tape.gradient(loss, y)
            expected = [3.0] if r == 0 else [0.0]
            np.testing.assert_allclose(dy.numpy(), expected)

            # alltoall: weighting the received rows by (recipient-specific
            # weights) must route gradients back to the sender's rows
            z = tf.Variable([[10.0 * r + 1.0], [10.0 * r + 2.0]])
            with tf.GradientTape() as tape:
                out, _ = hvd.alltoall(z, splits=[1, 1], name="g.a2a")
                w = tf.constant([[float(r + 1)], [float(r + 1)]])
                loss = tf.reduce_sum(out * w)
            dz = tape.gradient(loss, z)
            # row i of z went to rank i, whose weight is i+1
            np.testing.assert_allclose(dz.numpy(), [[1.0], [2.0]])
            print("GRAD-OK", r)
        """))
    out = _run([sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
                sys.executable, script])
    assert out.count("GRAD-OK") == 2


def test_elastic_and_moe_examples():
    """Remaining examples as smoke: elastic_jax single-process (plain-loop
    degeneration) and the MoE alltoall benchmark on the 8-dev CPU mesh."""
    mesh8 = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    _run([sys.executable, os.path.join(EXAMPLES, "elastic_jax.py"),
          "--epochs", "1", "--batch", "64"], env_extra=mesh8)
    _run([sys.executable, os.path.join(EXAMPLES, "moe_alltoall_benchmark.py"),
          "--tokens-per-chip", "64", "--d-model", "32", "--exchange-mb",
          "1"], env_extra=mesh8)


def test_long_context_ring_attention_example():
    """Long-context SP example: a sequence sharded over the 'sp' mesh
    axis trains through ring attention (SURVEY.md §5.7 greenfield)."""
    out = _run([sys.executable,
                os.path.join(EXAMPLES, "long_context_ring_attention.py"),
                "--seq-len", "512", "--steps", "2", "--d-model", "128"])
    assert "tok/s" in out
    out = _run([sys.executable,
                os.path.join(EXAMPLES, "long_context_ring_attention.py"),
                "--seq-len", "512", "--steps", "2", "--d-model", "128",
                "--striped"])
    assert "striped" in out and "tok/s" in out


def test_scaling_harness_smoke():
    """BASELINE's headline metric (scaling efficiency 1->N chips) has an
    in-repo harness; smoke it on the virtual mesh."""
    import json

    import tempfile

    out_json = os.path.join(tempfile.mkdtemp(), "scaling.json")
    out = _run([sys.executable,
                os.path.join(REPO, "benchmarks", "bench_scaling.py"),
                "--per-chip", "64", "--iters", "2", "--warmup", "1",
                "--output", out_json],
               env_extra={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=8"})
    line = next(ln for ln in out.splitlines()
                if ln.startswith("BENCH-SCALING"))
    data = json.loads(line.split("BENCH-SCALING ")[1])
    assert [r["chips"] for r in data["rows"]] == [1, 2, 4, 8]
    assert data["rows"][0]["efficiency"] == 1.0


def test_bench_transformer_tiny_smoke():
    """The transformer measurement phase must at least run a tiny config
    on CPU — a bare-jit regression here once left the 'hvd' axis unbound
    and would have burned a whole TPU uptime window to find out."""
    code = (
        "import sys; sys.path.insert(0, 'benchmarks'); sys.path.insert(0, '.')\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import horovod_tpu as hvd\n"
        "hvd.init()\n"
        # off the chip there is no peak to divide by (chip_peak_flops
        # raises on a device it does not know): the smoke is about the
        # step running, so it names one
        "import bench; bench.chip_peak_flops = lambda: 197e12\n"
        "from bench_transformer import bench_lm\n"
        "m = bench_lm(d_model=32, n_layers=1, d_ff=64, n_heads=2,\n"
        "             vocab=128, seq=32, batch=8, scan_steps=2,\n"
        "             warmup=1, iters=1, xent_chunk=32)\n"
        "assert m > 0\n"
        "print('BT-SMOKE-OK')\n")
    import tempfile

    with tempfile.NamedTemporaryFile(suffix=".jsonl") as tmp:
        # route the recorder away from the real TPU evidence file
        out = _run([sys.executable, "-c", code],
                   env_extra={"HVD_BENCH_TRANSFORMER_OUT": tmp.name})
    assert "BT-SMOKE-OK" in out


def test_jax_synthetic_benchmark_model_families():
    """The JAX synthetic harness drives every headline model family
    (reference benchmark set, docs/benchmarks.rst:11-13) — BN models,
    the BN-free dropout VGG, and Inception's 299-style stem at a smoke
    resolution."""
    for model, size in (("ResNet50", "64"), ("VGG16", "64"),
                        ("InceptionV3", "128")):
        out = _run([sys.executable,
                    os.path.join(EXAMPLES, "jax_synthetic_benchmark.py"),
                    "--model", model, "--image-size", size,
                    "--batch-size", "2", "--num-iters", "1",
                    "--num-batches-per-iter", "1",
                    "--num-warmup-batches", "1"], timeout=600)
        assert "Img/sec per chip" in out, (model, out[-300:])
