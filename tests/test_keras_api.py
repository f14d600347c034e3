"""horovod_tpu.keras — optimizer wrap on a real model.fit loop, callbacks,
load_model rewrap (reference test/test_keras.py patterns + horovod/_keras
callbacks)."""

import numpy as np
import pytest

keras = pytest.importorskip("keras")

import horovod_tpu.keras as hvd  # noqa: E402


def setup_module():
    hvd.init()


def _toy_model():
    keras.utils.set_random_seed(1)
    return keras.Sequential([keras.layers.Dense(8, activation="relu"),
                             keras.layers.Dense(1)])


def _toy_data(n=64):
    rng = np.random.RandomState(0)
    X = rng.randn(n, 4).astype(np.float32)
    y = X.sum(1, keepdims=True).astype(np.float32)
    return X, y


def test_fit_with_callbacks_runs_and_learns():
    model = _toy_model()
    opt = hvd.DistributedOptimizer(keras.optimizers.SGD(0.05))
    model.compile(optimizer=opt, loss="mse")
    X, y = _toy_data()
    cbs = [
        hvd.callbacks.BroadcastGlobalVariablesCallback(0),
        hvd.callbacks.MetricAverageCallback(),
        hvd.callbacks.LearningRateWarmupCallback(initial_lr=0.05,
                                                 warmup_epochs=2),
    ]
    hist = model.fit(X, y, epochs=4, batch_size=16, verbose=0,
                     callbacks=cbs)
    assert hist.history["loss"][-1] < hist.history["loss"][0]
    # warmup finished at the target LR
    np.testing.assert_allclose(
        float(model.optimizer.learning_rate.numpy()), 0.05, rtol=1e-5)


def test_lr_schedule_callback():
    model = _toy_model()
    opt = hvd.DistributedOptimizer(keras.optimizers.SGD(0.1))
    model.compile(optimizer=opt, loss="mse")
    X, y = _toy_data(32)
    cb = hvd.callbacks.LearningRateScheduleCallback(
        initial_lr=0.1, multiplier=lambda e: 0.1 ** e, start_epoch=1)
    model.fit(X, y, epochs=3, batch_size=16, verbose=0, callbacks=[cb])
    # epoch 2 multiplier: 0.1**2
    np.testing.assert_allclose(float(model.optimizer.learning_rate.numpy()),
                               0.1 * 0.01, rtol=1e-5)


def test_load_model_rewraps_optimizer(tmp_path):
    model = _toy_model()
    opt = hvd.DistributedOptimizer(keras.optimizers.Adam(0.01))
    model.compile(optimizer=opt, loss="mse")
    X, y = _toy_data(32)
    model.fit(X, y, epochs=1, batch_size=16, verbose=0)
    path = str(tmp_path / "m.keras")
    # save with a PLAIN optimizer (the wrapped class is dynamic and not
    # deserializable by name — reference load_model's whole reason to exist)
    plain = keras.Sequential([keras.layers.Dense(8, activation="relu"),
                              keras.layers.Dense(1)])
    plain.compile(optimizer=keras.optimizers.Adam(0.01), loss="mse")
    plain.fit(X, y, epochs=1, batch_size=16, verbose=0)
    plain.save(path)
    loaded = hvd.load_model(path)
    assert getattr(loaded.optimizer.__class__, "_hvd_wrapped", False)
    # still trainable after the rewrap
    loaded.fit(X, y, epochs=1, batch_size=16, verbose=0)


def test_backward_passes_per_step_aggregates():
    """Local gradient aggregation (reference tensorflow/
    gradient_aggregation.py): with backward_passes_per_step=2, the base
    update runs every 2nd call on the (optionally averaged) aggregate and
    skipped calls leave weights untouched while iterations still tick
    (reference gradient_aggregation_eager.py advances iterations on
    non-aggregation steps so iteration-keyed LR schedules keep per-step
    cadence)."""
    import keras
    import numpy as np
    import tensorflow as tf

    w = tf.Variable([1.0, 2.0])
    opt = hvd.DistributedOptimizer(keras.optimizers.SGD(0.1),
                                   backward_passes_per_step=2,
                                   average_aggregated_gradients=True)
    g1 = tf.constant([1.0, 1.0])
    g2 = tf.constant([3.0, 5.0])
    opt.apply([g1], [w])
    np.testing.assert_allclose(w.numpy(), [1.0, 2.0])  # skipped step
    opt.apply([g2], [w])
    # committed: avg aggregate = (g1+g2)/2 = [2,3]; sgd step 0.1
    np.testing.assert_allclose(w.numpy(), [0.8, 1.7], rtol=1e-6)
    # base apply ran once, but iterations tick EVERY step (reference
    # per-step iteration semantics; round-2 advisor finding)
    assert int(opt.iterations.numpy()) == 2


def test_backward_passes_per_step_inside_model_fit():
    """Aggregation must survive model.fit's traced train_step: the counter
    is a tf.Variable and the commit a tf.cond."""
    import keras
    import numpy as np

    keras.utils.set_random_seed(0)
    x = np.random.RandomState(0).randn(64, 4).astype(np.float32)
    y = (x @ np.random.RandomState(1).randn(4, 1).astype(np.float32))
    model = keras.Sequential([keras.Input((4,)), keras.layers.Dense(1)])
    opt = hvd.DistributedOptimizer(keras.optimizers.Adam(0.05),
                                   backward_passes_per_step=2)
    model.compile(optimizer=opt, loss="mse")
    hist = model.fit(x, y, batch_size=16, epochs=6, verbose=0)
    assert hist.history["loss"][-1] < hist.history["loss"][0]
    # 6 epochs x 4 batches = 24 calls → 12 real optimizer steps, but
    # iterations tick per call (reference per-step iteration semantics)
    assert int(opt.iterations.numpy()) == 24


def test_keras_elastic_callbacks_commit_and_track():
    """Keras-API elastic callbacks (reference keras elastic
    CommitStateCallback/UpdateBatchStateCallback): periodic commits and
    batch/epoch tracking from a real model.fit loop."""
    import keras
    import numpy as np

    from horovod_tpu.elastic import ObjectState

    commits = []
    state = ObjectState(epoch=0, batch=0)
    orig_commit = state.commit
    state.commit = lambda: (commits.append(1), orig_commit())[1]

    keras.utils.set_random_seed(0)
    x = np.random.RandomState(0).randn(32, 4).astype(np.float32)
    y = x @ np.ones((4, 1), np.float32)
    model = keras.Sequential([keras.Input((4,)), keras.layers.Dense(1)])
    model.compile(optimizer="sgd", loss="mse")
    # Update BEFORE Commit: commits must persist updated counters
    cbs = [hvd.callbacks.UpdateBatchStateCallback(state),
           hvd.callbacks.CommitStateCallback(state, batches_per_commit=2)]
    model.fit(x, y, batch_size=8, epochs=2, callbacks=cbs, verbose=0)
    # 2 epochs x 4 batches -> 4 periodic commits + 2 epoch-end commits
    assert len(commits) == 6
    # durable snapshot is "next epoch, batch 0": restore must not repeat
    # the completed epoch
    state.batch = 99
    state.restore()
    assert state.epoch == 2 and state.batch == 0


def test_keras_elastic_mid_epoch_batch_resume():
    """VERDICT r2 weak #7: the state.batch-based dataset-side resume,
    demonstrated end to end. A crash mid-epoch restores the committed
    (epoch, batch); the restarted fit skips the processed batches and
    reduces steps_per_epoch, so every (epoch, batch) trains EXACTLY once
    across the interrupted run (reference keras elastic
    UpdateBatchStateCallbackImpl contract)."""
    import keras
    import numpy as np

    from horovod_tpu.common.exceptions import HorovodInternalError

    EPOCHS, STEPS, BATCH = 3, 5, 8
    rng = np.random.RandomState(0)
    x = rng.randn(STEPS * BATCH, 4).astype(np.float32)
    y = x @ np.ones((4, 1), np.float32)

    keras.utils.set_random_seed(0)
    model = keras.Sequential([keras.Input((4,)), keras.layers.Dense(1)])
    model.compile(optimizer="sgd", loss="mse")
    state = hvd.elastic.KerasState(model, epoch=0, batch=0)

    processed = []   # (epoch, true_batch) forward passes, across restarts
    crashed = {"done": False}

    class CrashMidEpoch(keras.callbacks.Callback):
        """Simulated chip failure at epoch 1, true batch 3."""

        def on_batch_end(self, batch, logs=None):
            processed.append((state.epoch, state.batch - 1))
            if (not crashed["done"] and state.epoch == 1
                    and state.batch == 3):
                crashed["done"] = True
                raise HorovodInternalError("simulated failure")

    def epoch_batches(epoch, start_batch):
        """Dataset-side resume: this epoch's batches AFTER start_batch."""
        for b in range(start_batch, STEPS):
            sl = slice(b * BATCH, (b + 1) * BATCH)
            yield x[sl], y[sl]

    @hvd.elastic.run
    def train(st):
        cbs = [hvd.callbacks.UpdateBatchStateCallback(st),
               hvd.callbacks.CommitStateCallback(
                   st, batches_per_commit=1),
               CrashMidEpoch()]
        while st.epoch < EPOCHS:
            start = st.batch
            model.fit(epoch_batches(st.epoch, start),
                      steps_per_epoch=STEPS - start,
                      initial_epoch=st.epoch, epochs=st.epoch + 1,
                      callbacks=cbs, verbose=0)

    train(state)
    assert crashed["done"]
    # exactly-once: every (epoch, batch) pair appears once, in order
    expect = [(e, b) for e in range(EPOCHS) for b in range(STEPS)]
    assert processed == expect, processed[:10]


def test_keras_tensor_functions_and_best_checkpoint(tmp_path):
    """Reference keras surface: hvd.allreduce/allgather/broadcast on
    values, BestModelCheckpoint (save_best_only pinned), and the gated
    TF1 broadcast_global_variables."""
    import keras
    import numpy as np

    out = hvd.allreduce(np.full((4,), 2.0, np.float32), name="k.ar")
    np.testing.assert_allclose(out, 2.0)
    g = hvd.allgather(np.ones((2, 2), np.float32), name="k.ag")
    assert g.shape == (2, 2)
    b = hvd.broadcast(np.arange(3.0), 0, name="k.bc")
    np.testing.assert_allclose(b, np.arange(3.0))
    with pytest.raises(NotImplementedError):
        hvd.broadcast_global_variables(0)

    with pytest.raises(ValueError, match="never assigned"):
        unset = hvd.callbacks.BestModelCheckpoint(monitor="loss")
        unset.on_epoch_end(0, {"loss": 1.0})
    cb = hvd.callbacks.BestModelCheckpoint(
        filepath=str(tmp_path / "best.keras"), monitor="loss")
    assert cb.save_best_only
    x = np.random.RandomState(0).randn(32, 4).astype(np.float32)
    y = x @ np.ones((4, 1), np.float32)
    model = keras.Sequential([keras.Input((4,)), keras.layers.Dense(1)])
    model.compile(optimizer="sgd", loss="mse")
    model.fit(x, y, epochs=2, batch_size=16, verbose=0, callbacks=[cb])
    assert (tmp_path / "best.keras").exists()


def test_optimizer_from_config_roundtrip():
    """Reference test_tensorflow2_keras.py test_from_config: the wrapped
    class reconstructs from its own get_config."""
    opt = hvd.DistributedOptimizer(keras.optimizers.Adam(0.002))
    cfg = opt.get_config()
    clone = opt.__class__.from_config(cfg)
    assert type(clone) is type(opt)
    assert getattr(clone, "_hvd_wrapped", False)
    np.testing.assert_allclose(float(clone.learning_rate.numpy()
                                     if hasattr(clone.learning_rate, "numpy")
                                     else clone.learning_rate), 0.002,
                               rtol=1e-6)
    # the clone still reduces: a fit step runs through apply()
    model = keras.Sequential([keras.layers.Dense(1)])
    model.compile(optimizer=clone, loss="mse")
    X, y = _toy_data(32)
    model.fit(X, y, epochs=1, batch_size=16, verbose=0)


def test_sparse_as_dense_embedding_fit():
    """Reference test_tensorflow2_keras.py test_sparse_as_dense: embedding
    gradients (IndexedSlices under the TF backend) densify for the wire."""
    keras.utils.set_random_seed(2)
    model = keras.Sequential([
        keras.layers.Embedding(16, 4, input_length=3),
        keras.layers.Flatten(),
        keras.layers.Dense(1),
    ])
    opt = hvd.DistributedOptimizer(keras.optimizers.SGD(0.1),
                                   sparse_as_dense=True)
    model.compile(optimizer=opt, loss="mse")
    rng = np.random.RandomState(0)
    X = rng.randint(0, 16, (64, 3))
    y = rng.randn(64, 1).astype(np.float32)
    hist = model.fit(X, y, epochs=2, batch_size=16, verbose=0)
    assert hist.history["loss"][-1] < hist.history["loss"][0]


def test_keras2_bpps_momentum_graph_mode(tmp_path):
    """Keras-2 (tf_keras) aggregated path under a TRACED train step with
    momentum slots: slot variables must be created outside the commit
    tf.cond (review r5 finding). Single process: the reduce is identity,
    the aggregation machinery is what's under test."""
    import os
    import subprocess
    import sys
    import textwrap

    script = tmp_path / "w.py"
    script.write_text(textwrap.dedent("""
        import os
        os.environ["TF_USE_LEGACY_KERAS"] = "1"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import tensorflow as tf
        import horovod.tensorflow.keras as hvd

        hvd.init()
        model = tf.keras.Sequential(
            [tf.keras.layers.Dense(1, use_bias=False,
                                   kernel_initializer="ones",
                                   input_shape=(2,))])
        opt = hvd.DistributedOptimizer(
            tf.optimizers.SGD(0.1, momentum=0.9),
            backward_passes_per_step=2,
            average_aggregated_gradients=True)
        # default compile: run_eagerly=False -> traced train_step
        model.compile(optimizer=opt, loss="mse")
        x = np.ones((8, 2), np.float32)
        y = np.zeros((8, 1), np.float32)
        w0 = model.get_weights()[0].copy()
        model.fit(x, y, batch_size=2, epochs=1, verbose=0)
        w1 = model.get_weights()[0]
        assert not np.allclose(w0, w1), "no update committed"
        # a var not connected to the loss must not break the wire
        print("K2-BPPS-OK")
    """))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=420)
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    assert "K2-BPPS-OK" in p.stdout


def test_callbacks_are_picklable():
    """Module-level callback classes keep pickleable identity after the
    backend-factory refactor (spawn workers ship callbacks by ref)."""
    import pickle

    from horovod_tpu._keras import callbacks as cb

    inst = cb.MetricAverageCallback()
    assert isinstance(pickle.loads(pickle.dumps(inst)),
                      cb.MetricAverageCallback)
