"""Estimator and Spark-layer tests.

The Estimator trains end-to-end at size 1 (reference style: spark estimator
suites run tiny models in local mode, test_spark_keras.py); the Spark layer
is import-gated, so without pyspark the contract is a clear error.
"""

import os

import numpy as np
import pytest

import horovod_tpu as hvd


def _toy_data(n=256, seed=0):
    rng = np.random.RandomState(seed)
    y = rng.randint(0, 4, size=n)
    centers = rng.randn(4, 8).astype(np.float32)
    x = centers[y] + 0.2 * rng.randn(n, 8).astype(np.float32)
    return x, y


class TestEstimator:
    def test_fit_evaluate_predict(self, hvd_world, tmp_path):
        import jax.numpy as jnp
        from horovod_tpu.models import MLP

        def accuracy(outputs, targets):
            return (jnp.argmax(outputs, -1) == jnp.asarray(targets)).mean()

        import optax
        x, y = _toy_data()
        est = hvd.Estimator(MLP(features=(32,), num_classes=4),
                            optimizer=optax.adam(1e-2),
                            metrics={"acc": accuracy},
                            checkpoint_dir=str(tmp_path))
        hist = est.fit(x, y, epochs=20, batch_size=32)
        assert hist.history["loss"][-1] < hist.history["loss"][0]
        assert hist.history["acc"][-1] > 0.8
        ev = est.evaluate(x, y)
        assert ev["acc"] > 0.8 and "loss" in ev
        preds = est.predict(x[:5])
        assert preds.shape == (5, 4)
        # checkpoints were written per epoch
        from horovod_tpu import checkpoint as ckpt
        assert ckpt.latest_step(str(tmp_path)) == 19

    def test_save_load_roundtrip(self, hvd_world, tmp_path):
        from horovod_tpu.models import MLP
        x, y = _toy_data()
        est = hvd.Estimator(MLP(features=(16,), num_classes=4))
        est.fit(x, y, epochs=1, batch_size=64)
        est.save(str(tmp_path), step=0)
        est2 = hvd.Estimator(MLP(features=(16,), num_classes=4))
        est2.load(str(tmp_path))
        np.testing.assert_allclose(
            np.asarray(est2.predict(x[:3])),
            np.asarray(est.predict(x[:3])), atol=1e-6)

    def test_validation_data(self, hvd_world):
        from horovod_tpu.models import MLP
        x, y = _toy_data()
        est = hvd.Estimator(MLP(features=(16,), num_classes=4))
        hist = est.fit(x[:192], y[:192], epochs=2, batch_size=32,
                       validation_data=(x[192:], y[192:]))
        assert "val_loss" in hist.history

    def test_predict_before_fit_raises(self, hvd_world):
        from horovod_tpu.models import MLP
        est = hvd.Estimator(MLP(features=(16,), num_classes=4))
        with pytest.raises(RuntimeError, match="fit"):
            est.predict(np.zeros((1, 8), np.float32))

    def test_predict_varying_sizes_hits_bucket_cache(self, hvd_world):
        """predict routes through the serving batcher's bucketed jit
        cache: distinct input lengths land on a handful of power-of-two
        bucket shapes (no per-length recompiles) and return the exact
        unpadded eager values."""
        from horovod_tpu.models import MLP
        x, y = _toy_data()
        est = hvd.Estimator(MLP(features=(16,), num_classes=4))
        est.fit(x, y, epochs=1, batch_size=64)
        for n in (1, 3, 5, 8, 13, 5, 3, 13):
            preds = np.asarray(est.predict(x[:n]))
            assert preds.shape == (n, 4)
            np.testing.assert_allclose(
                preds, np.asarray(est.model.apply(est.params, x[:n])),
                atol=1e-6)
        assert est._predict_cache.compiled_buckets == {1, 4, 8, 16}


class TestSparkGate:
    def test_missing_pyspark_raises_clear_error(self):
        try:
            import pyspark  # noqa: F401
            pytest.skip("pyspark installed; gate not exercised")
        except ImportError:
            pass
        import horovod_tpu.spark as hs
        with pytest.raises(ImportError, match="requires pyspark"):
            hs.run(lambda: None)
        with pytest.raises(ImportError, match="requires pyspark"):
            hs.run_elastic(lambda: None)

    def test_shard_smaller_than_batch_raises(self, hvd_world):
        from horovod_tpu.models import MLP
        x, y = _toy_data(n=16)
        est = hvd.Estimator(MLP(features=(16,), num_classes=4))
        with pytest.raises(ValueError, match="fewer than"):
            est.fit(x, y, epochs=1, batch_size=64)


# ---------------------------------------------------------------------------
# round 3: real spark.run_elastic — generation loop, liveness sizing,
# durable-state recovery (reference: spark/runner.py:303+)
# ---------------------------------------------------------------------------
class TestSparkElasticLoop:
    """pyspark-free tests of the elastic generation loop via the
    dependency-injection points (the loop is scheduler-agnostic)."""

    def test_retries_and_env_stability(self):
        from horovod_tpu.spark import run_elastic
        attempts = []

        def submit(n, env):
            attempts.append((n, env["HVD_TPU_ELASTIC_JOB_ID"],
                             env["HVD_TPU_ELASTIC_STATE_DIR"]))
            if len(attempts) < 3:
                raise RuntimeError("barrier task died")
            return [f"rank{i}" for i in range(n)]

        out = run_elastic(None, num_proc=2, min_np=1, reset_limit=3,
                          _submit_attempt=submit,
                          _available_parallelism=lambda: 2)
        assert out == ["rank0", "rank1"]
        assert len(attempts) == 3
        # job id + state dir identical across generations => retried
        # workers find the previous generation's commits
        assert len({a[1] for a in attempts}) == 1
        assert len({a[2] for a in attempts}) == 1

    def test_shrinks_to_liveness(self):
        from horovod_tpu.spark import run_elastic
        sizes = []
        live = {"n": 4}

        def submit(n, env):
            sizes.append(n)
            if len(sizes) == 1:
                live["n"] = 2          # an executor died with the stage
                raise RuntimeError("executor lost")
            return list(range(n))

        out = run_elastic(None, num_proc=4, min_np=2, max_np=4,
                          reset_limit=2, _submit_attempt=submit,
                          _available_parallelism=lambda: live["n"])
        assert sizes == [4, 2]
        assert out == [0, 1]

    def test_reset_limit_exceeded(self):
        from horovod_tpu.spark import run_elastic

        def submit(n, env):
            raise RuntimeError("always fails")

        with pytest.raises(RuntimeError, match="after 2 generations"):
            run_elastic(None, num_proc=1, reset_limit=1,
                        _submit_attempt=submit,
                        _available_parallelism=lambda: 1)

    def test_min_np_enforced(self):
        from horovod_tpu.spark import run_elastic
        with pytest.raises(RuntimeError, match="at least 3"):
            run_elastic(None, min_np=3, reset_limit=0,
                        _submit_attempt=lambda n, e: [],
                        _available_parallelism=lambda: 1)


@pytest.mark.integration
def test_spark_elastic_kill_and_recover(tmp_path):
    """End-to-end recovery through the run_elastic loop with REAL worker
    processes standing in for barrier tasks: rank 1 dies mid-generation,
    the next generation restores the committed epoch and finishes.
    (With pyspark installed the same scenario runs under a local
    SparkSession — test_spark_elastic_real below.)"""
    import socket
    import subprocess
    import sys as _sys

    from horovod_tpu.spark import run_elastic

    worker = os.path.join(os.path.dirname(__file__),
                          "spark_elastic_train_worker.py")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(worker)))
    sim_dir = str(tmp_path)

    def submit(n, attempt_env):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        procs = []
        for pid in range(n):
            env = dict(os.environ)
            env.pop("XLA_FLAGS", None)
            env.update(attempt_env)
            env.update({
                "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
                "JAX_PLATFORMS": "cpu",
                "HVD_TPU_COORDINATOR_ADDR": f"127.0.0.1:{port}",
                "HVD_TPU_SIZE": str(n),
                "HVD_TPU_RANK": str(pid),
                "HVD_TPU_HOSTNAME": "localhost",
                "HVD_TPU_LOCAL_RANK": str(pid),
                "HVD_TPU_HEARTBEAT_TIMEOUT_SECONDS": "10",
                "SPARK_SIM_DIR": sim_dir,
                "SPARK_SIM_EPOCHS": "4",
                "SPARK_SIM_KILL_RANK": "1",
                "SPARK_SIM_KILL_EPOCH": "1",
            })
            procs.append(subprocess.Popen(
                [_sys.executable, worker], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
        outs = [p.communicate(timeout=240)[0].decode(errors="replace")
                for p in procs]
        if any(p.returncode != 0 for p in procs):
            raise RuntimeError(
                "barrier task failed: "
                + " | ".join(o[-400:] for o in outs))
        return list(range(n))

    out = run_elastic(None, num_proc=2, min_np=1, reset_limit=2,
                      state_dir=sim_dir, _submit_attempt=submit,
                      _available_parallelism=lambda: 2)
    assert out == [0, 1]
    with open(os.path.join(sim_dir, "events.log")) as f:
        events = [l.strip() for l in f if l.strip()]
    assert any(e.startswith("killed rank=1 epoch=1") for e in events), events
    # generation 2 restored the committed epoch (>= 1), not scratch
    restored = [e for e in events if e.startswith("restored ")]
    assert restored and all("epoch=0" not in e.split("rank=")[0]
                            for e in restored), events
    assert any("epoch=1" in e for e in restored), events
    done = [e for e in events if e.startswith("done ")]
    assert len(done) == 2 and all("epochs=4" in e for e in done), events


def test_spark_elastic_real_kill_and_recover(tmp_path):
    """The same scenario on an actual local SparkSession (skips without
    pyspark — reference: test_elastic_spark_*.py)."""
    pytest.importorskip("pyspark")
    import horovod_tpu.spark as hvd_spark

    sim_dir = str(tmp_path)

    def train():
        import os as _os
        import numpy as _np
        import horovod_tpu as _hvd
        from horovod_tpu.elastic.run import maybe_load_persisted_state
        state = _hvd.elastic.ObjectState(epoch=0)
        maybe_load_persisted_state(state)
        state.sync()
        while state.epoch < 3:
            _hvd.allreduce(_np.ones(2, _np.float32), op=_hvd.Sum,
                           name="g")
            marker = _os.path.join(_os.environ["SPARK_SIM_DIR"], "k")
            if (_hvd.rank() == 1 and state.epoch == 1
                    and not _os.path.exists(marker)):
                open(marker, "w").close()
                _os._exit(17)
            state.epoch += 1
            state.commit()
        return state.epoch

    out = hvd_spark.run_elastic(
        train, num_proc=2, min_np=1, reset_limit=2, state_dir=sim_dir,
        env={"SPARK_SIM_DIR": sim_dir, "JAX_PLATFORMS": "cpu",
             "HVD_TPU_HEARTBEAT_TIMEOUT_SECONDS": "10"})
    assert out == [3, 3]


# ---------------------------------------------------------------------------
# round 3: direct KerasEstimator / TorchEstimator coverage (pandas data
# path — the same train fn the Spark barrier tasks run; reference suites:
# test_spark_keras.py / test_spark_torch.py tiny end-to-end models)
# ---------------------------------------------------------------------------
def _regression_df(n=256, seed=0):
    import pandas as pd
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype(np.float32)
    w = np.array([[1.0], [-2.0], [0.5], [2.0]], np.float32)
    y = (x @ w).ravel() + 0.05 * rng.randn(n).astype(np.float32)
    df = pd.DataFrame({f"f{i}": x[:, i] for i in range(4)})
    df["label"] = y
    return df


class TestKerasEstimator:
    def test_fit_transform(self, hvd_world, tmp_path):
        keras = pytest.importorskip("keras")
        from horovod_tpu.spark.keras import KerasEstimator
        from horovod_tpu.spark.store import LocalStore

        model = keras.Sequential([
            keras.layers.Input(shape=(4,)),
            keras.layers.Dense(8, activation="relu"),
            keras.layers.Dense(1),
        ])
        est = KerasEstimator(
            model=model, optimizer="adam", loss="mse",
            feature_cols=[f"f{i}" for i in range(4)],
            label_cols=["label"], batch_size=32, epochs=6,
            store=LocalStore(str(tmp_path)))
        df = _regression_df()
        trained = est.fit(df)
        hist = trained.history
        assert hist["loss"][-1] < hist["loss"][0]
        out = trained.transform(df)
        assert len(out) == len(df)
        # spark-ML-style param accessors (reference params plumbing)
        assert est.getEpochs() == 6
        est.setEpochs(2)
        assert est.epochs == 2


class TestTorchEstimator:
    def test_fit_transform(self, hvd_world, tmp_path):
        torch = pytest.importorskip("torch")
        from horovod_tpu.spark.torch import TorchEstimator
        from horovod_tpu.spark.store import LocalStore

        net = torch.nn.Sequential(
            torch.nn.Linear(4, 8), torch.nn.ReLU(), torch.nn.Linear(8, 1))
        est = TorchEstimator(
            model=net,
            optimizer=lambda p: torch.optim.Adam(p, lr=1e-2),
            loss=torch.nn.MSELoss(),
            feature_cols=[f"f{i}" for i in range(4)],
            label_cols=["label"], batch_size=32, epochs=6,
            store=LocalStore(str(tmp_path)))
        df = _regression_df()
        trained = est.fit(df)
        hist = trained.loss_history
        assert hist[-1] < hist[0]
        out = trained.transform(df)
        assert len(out) == len(df)
        preds = np.array([float(np.ravel(v)[0]) for v in out.iloc[:, -1]])
        # trained regressor must beat the zero predictor
        y = df["label"].to_numpy()
        assert np.mean((preds - y) ** 2) < np.mean(y ** 2)


def test_torch_estimator_validation_split(hvd_world, tmp_path):
    """The `validation` param holds out a fraction and records validation
    loss — it must not be a silently-ignored knob. A Dropout layer guards
    the eval-mode contract: val loss is computed with dropout off."""
    torch = pytest.importorskip("torch")
    from horovod_tpu.spark.torch import TorchEstimator
    from horovod_tpu.spark.store import LocalStore

    df = _regression_df()
    net = torch.nn.Sequential(torch.nn.Linear(4, 8), torch.nn.ReLU(),
                              torch.nn.Dropout(0.5), torch.nn.Linear(8, 1))
    def mae(outputs, targets):
        return (outputs - targets).abs().mean()

    t_model = TorchEstimator(
        model=net, optimizer=lambda p: torch.optim.Adam(p, lr=1e-2),
        loss=torch.nn.MSELoss(), metrics={"mae": mae},
        feature_cols=[f"f{i}" for i in range(4)], label_cols=["label"],
        batch_size=32, epochs=3, validation=0.25,
        store=LocalStore(str(tmp_path))).fit(df)
    assert len(t_model.val_loss_history) == 3
    assert all(v > 0 for v in t_model.val_loss_history)
    assert len(t_model.metrics_history["mae"]) == 3
    assert all(v > 0 for v in t_model.metrics_history["mae"])


def test_keras_estimator_validation_split(hvd_world, tmp_path):
    keras = pytest.importorskip("keras")
    from horovod_tpu.spark.keras import KerasEstimator
    from horovod_tpu.spark.store import LocalStore

    df = _regression_df()
    k_model_builder = keras.Sequential([
        keras.layers.Input(shape=(4,)), keras.layers.Dense(1)])
    k_model = KerasEstimator(
        model=k_model_builder, optimizer="adam", loss="mse",
        feature_cols=[f"f{i}" for i in range(4)], label_cols=["label"],
        batch_size=32, epochs=3, validation=0.25,
        store=LocalStore(str(tmp_path))).fit(df)
    assert "val_loss" in k_model.history
    assert len(k_model.history["val_loss"]) == 3


def test_torch_estimator_metrics_list_and_bad_validation(hvd_world,
                                                         tmp_path):
    torch = pytest.importorskip("torch")
    from horovod_tpu.spark.torch import TorchEstimator
    from horovod_tpu.spark.store import LocalStore

    df = _regression_df(n=64)
    net = torch.nn.Linear(4, 1)

    def mae(outputs, targets):
        return (outputs - targets).abs().mean()

    # list-of-callables metrics (the Keras convention) must work too
    m = TorchEstimator(
        model=net, loss=torch.nn.MSELoss(), metrics=[mae],
        feature_cols=[f"f{i}" for i in range(4)], label_cols=["label"],
        batch_size=16, epochs=2, validation=0.25,
        store=LocalStore(str(tmp_path))).fit(df)
    assert len(m.metrics_history["mae"]) == 2

    # out-of-range validation fails fast, not by silently inverting the
    # train/val split
    with pytest.raises(ValueError, match="validation"):
        TorchEstimator(
            model=net, loss=torch.nn.MSELoss(),
            feature_cols=[f"f{i}" for i in range(4)],
            label_cols=["label"], validation=-0.25).fit(df)


# ---------------------------------------------------------------------------
# round 5 (VERDICT r4 item 5): validation column, sample weights, custom
# objects, fsspec remote store — reference spark/keras/estimator.py:105-379
# and spark/common/store.py HDFSStore
# ---------------------------------------------------------------------------

def test_torch_estimator_validation_column(hvd_world, tmp_path):
    """`validation="val_col"` selects rows with value > 0 as validation
    (the reference's column form), instead of a fraction."""
    torch = pytest.importorskip("torch")
    from horovod_tpu.spark.torch import TorchEstimator
    from horovod_tpu.spark.store import LocalStore

    df = _regression_df()
    df["is_val"] = (np.arange(len(df)) % 4 == 0).astype(np.float64)
    net = torch.nn.Linear(4, 1)
    m = TorchEstimator(
        model=net, loss=torch.nn.MSELoss(),
        feature_cols=[f"f{i}" for i in range(4)], label_cols=["label"],
        batch_size=32, epochs=2, validation="is_val",
        store=LocalStore(str(tmp_path))).fit(df)
    assert len(m.val_loss_history) == 2
    assert all(v > 0 for v in m.val_loss_history)


def test_keras_estimator_validation_column(hvd_world, tmp_path):
    keras = pytest.importorskip("keras")
    from horovod_tpu.spark.keras import KerasEstimator
    from horovod_tpu.spark.store import LocalStore

    df = _regression_df()
    df["is_val"] = (np.arange(len(df)) % 4 == 0).astype(np.float64)
    model = keras.Sequential([
        keras.layers.Input(shape=(4,)), keras.layers.Dense(1)])
    k = KerasEstimator(
        model=model, optimizer="adam", loss="mse",
        feature_cols=[f"f{i}" for i in range(4)], label_cols=["label"],
        batch_size=32, epochs=2, validation="is_val",
        store=LocalStore(str(tmp_path))).fit(df)
    assert "val_loss" in k.history and len(k.history["val_loss"]) == 2


def test_torch_estimator_sample_weights(hvd_world, tmp_path):
    """Rows with weight 0 must not influence training: corrupt half the
    labels, zero-weight them, and the model still learns the clean
    relationship (reference `sample_weight_col`)."""
    torch = pytest.importorskip("torch")
    from horovod_tpu.spark.torch import TorchEstimator
    from horovod_tpu.spark.store import LocalStore

    df = _regression_df(n=512)
    corrupt = np.arange(len(df)) % 2 == 0
    df.loc[corrupt, "label"] = 1000.0          # poison half the rows
    df["w"] = (~corrupt).astype(np.float64)    # ...and weight them 0
    torch.manual_seed(0)
    net = torch.nn.Linear(4, 1)
    m = TorchEstimator(
        model=net, optimizer=lambda p: torch.optim.Adam(p, lr=1e-2),
        loss=torch.nn.MSELoss(), sample_weight_col="w",
        feature_cols=[f"f{i}" for i in range(4)], label_cols=["label"],
        batch_size=32, epochs=20, random_seed=1,
        store=LocalStore(str(tmp_path))).fit(df)
    clean = _regression_df(n=512)
    preds = m._predict(
        clean[[f"f{i}" for i in range(4)]].to_numpy().astype(np.float32))
    mse = float(np.mean((preds.ravel()
                         - clean["label"].to_numpy()) ** 2))
    # poisoned rows would drag predictions toward 1000; the clean-data
    # MSE stays small only if weight-0 rows were truly ignored
    assert mse < 10.0, mse


def test_torch_sample_weight_ones_matches_unweighted(hvd_world, tmp_path):
    """An all-ones weight column is exactly the unweighted loss — same
    seed, same trajectory, same final parameters."""
    torch = pytest.importorskip("torch")
    from horovod_tpu.spark.torch import TorchEstimator
    from horovod_tpu.spark.store import LocalStore

    df = _regression_df(n=128)
    df["w"] = 1.0

    def run(weight_col, leaf):
        torch.manual_seed(7)
        net = torch.nn.Linear(4, 1)
        return TorchEstimator(
            model=net, optimizer=lambda p: torch.optim.SGD(p, lr=1e-2),
            loss=torch.nn.MSELoss(), sample_weight_col=weight_col,
            feature_cols=[f"f{i}" for i in range(4)],
            label_cols=["label"], batch_size=32, epochs=3, random_seed=3,
            store=LocalStore(str(tmp_path / leaf))).fit(df)

    m_w = run("w", "weighted")
    m_u = run(None, "unweighted")
    for k in m_u.model.state_dict():
        np.testing.assert_allclose(
            m_w.model.state_dict()[k].numpy(),
            m_u.model.state_dict()[k].numpy(), atol=1e-5)


def test_keras_estimator_sample_weights(hvd_world, tmp_path):
    keras = pytest.importorskip("keras")
    from horovod_tpu.spark.keras import KerasEstimator
    from horovod_tpu.spark.store import LocalStore

    df = _regression_df(n=256)
    corrupt = np.arange(len(df)) % 2 == 0
    df.loc[corrupt, "label"] = 1000.0
    df["w"] = (~corrupt).astype(np.float64)
    model = keras.Sequential([
        keras.layers.Input(shape=(4,)), keras.layers.Dense(1)])
    k = KerasEstimator(
        model=model, optimizer="adam", loss="mse",
        sample_weight_col="w",
        feature_cols=[f"f{i}" for i in range(4)], label_cols=["label"],
        batch_size=32, epochs=25, store=LocalStore(str(tmp_path))).fit(df)
    clean = _regression_df(n=256)
    preds = k._predict(
        clean[[f"f{i}" for i in range(4)]].to_numpy().astype(np.float32))
    mse = float(np.mean((preds.ravel() - clean["label"].to_numpy()) ** 2))
    assert mse < 50.0, mse


def test_keras_custom_objects_roundtrip(hvd_world, tmp_path):
    """A model using a custom layer trains and transforms when the class
    ships via `custom_objects` (reference keras estimator custom_objects);
    without it, deserialization on the worker must fail."""
    keras = pytest.importorskip("keras")
    from horovod_tpu.spark.keras import KerasEstimator
    from horovod_tpu.spark.store import LocalStore

    @keras.saving.register_keras_serializable(package="hvdtest")
    class Doubler(keras.layers.Layer):
        def call(self, x):
            return x * 2.0

    model = keras.Sequential([
        keras.layers.Input(shape=(4,)), Doubler(), keras.layers.Dense(1)])
    df = _regression_df(n=128)
    est = KerasEstimator(
        model=model, optimizer="adam", loss="mse",
        custom_objects={"Doubler": Doubler},
        feature_cols=[f"f{i}" for i in range(4)], label_cols=["label"],
        batch_size=32, epochs=2, store=LocalStore(str(tmp_path)))
    assert est.getCustomObjects() == {"Doubler": Doubler}
    trained = est.fit(df)
    out = trained.transform(df)
    assert len(out) == len(df)
    assert any(isinstance(l, Doubler) for l in trained.model.layers)


def test_fsspec_memory_store_end_to_end(hvd_world):
    """A remote-scheme store (fsspec memory://) carries the whole data
    path: Parquet materialization, worker shard reads, checkpoint sync —
    the reference HDFSStore role (spark/common/store.py)."""
    torch = pytest.importorskip("torch")
    fsspec = pytest.importorskip("fsspec")
    from horovod_tpu.spark.store import FsspecStore, Store
    from horovod_tpu.spark.torch import TorchEstimator

    store = Store.create("memory://hvd-test-store")
    assert isinstance(store, FsspecStore)
    df = _regression_df(n=128)
    est = TorchEstimator(
        model=torch.nn.Linear(4, 1), loss=torch.nn.MSELoss(),
        feature_cols=[f"f{i}" for i in range(4)], label_cols=["label"],
        batch_size=32, epochs=3, store=store, run_id="r5")
    m = est.fit(df)
    assert m.loss_history[-1] < m.loss_history[0]
    # the dataset really lives in the memory filesystem
    fs = fsspec.filesystem("memory")
    files = fs.ls(store.get_train_data_path("r5"), detail=False)
    assert any(f.endswith(".parquet") for f in files)
    # checkpoint sync copies into the remote store
    import tempfile, os as _os
    with tempfile.TemporaryDirectory() as d:
        with open(_os.path.join(d, "ckpt.bin"), "wb") as f:
            f.write(b"state")
        store.sync_fn("r5")(d)
    assert fs.exists(store.get_checkpoint_path("r5") + "/ckpt.bin")


def test_store_create_unknown_scheme_still_errors():
    from horovod_tpu.spark.store import Store
    with pytest.raises(ValueError, match="scheme"):
        Store.create("notascheme9x://bucket/path")


class TestStreamingReader:
    """ParquetBatchIterator — the Petastorm reader role (reference:
    petastorm make_batch_reader feeding estimator workers)."""

    def _dataset(self, tmp_path, n=1000, partitions=3, rgr=64):
        from horovod_tpu.spark.store import write_parquet
        path = str(tmp_path / "ds")
        write_parquet(path, {
            "idx": np.arange(n, dtype=np.int64),
            "x": np.arange(n, dtype=np.float32) * 2.0,
        }, row_group_rows=rgr, partitions=partitions)
        return path

    def test_every_row_exactly_once_across_ranks(self, tmp_path):
        from horovod_tpu.spark.store import ParquetBatchIterator
        path = self._dataset(tmp_path)
        seen = []
        for rank in range(3):
            it = ParquetBatchIterator(path, ["idx"], batch_size=37,
                                      rank=rank, size=3)
            for batch in it:
                seen.extend(batch["idx"].tolist())
        assert sorted(seen) == list(range(1000))

    def test_batch_sizes_and_partial_last(self, tmp_path):
        from horovod_tpu.spark.store import ParquetBatchIterator
        path = self._dataset(tmp_path, n=100, partitions=1, rgr=32)
        sizes = [len(b["idx"]) for b in ParquetBatchIterator(
            path, ["idx"], batch_size=48)]
        assert sizes == [48, 48, 4]
        sizes = [len(b["idx"]) for b in ParquetBatchIterator(
            path, ["idx"], batch_size=48, drop_last=True)]
        assert sizes == [48, 48]

    def test_columns_consistent_within_batch(self, tmp_path):
        from horovod_tpu.spark.store import ParquetBatchIterator
        path = self._dataset(tmp_path)
        for batch in ParquetBatchIterator(path, ["idx", "x"],
                                          batch_size=64, shuffle=True):
            np.testing.assert_allclose(batch["x"],
                                       batch["idx"].astype(np.float32) * 2)

    def test_shuffle_is_seeded_and_epoch_varies(self, tmp_path):
        from horovod_tpu.spark.store import ParquetBatchIterator
        path = self._dataset(tmp_path, n=256, partitions=1, rgr=64)

        def first_batch(seed, epoch):
            it = ParquetBatchIterator(path, ["idx"], batch_size=32,
                                      shuffle=True, seed=seed)
            it.set_epoch(epoch)
            return next(iter(it))["idx"].tolist()

        assert first_batch(1, 0) == first_batch(1, 0)
        assert first_batch(1, 0) != first_batch(1, 1)
        assert first_batch(1, 0) != first_batch(2, 0)
        # shuffled stream still covers every row exactly once
        it = ParquetBatchIterator(path, ["idx"], batch_size=32,
                                  shuffle=True, seed=3)
        assert sorted(i for b in it for i in b["idx"].tolist()) \
            == list(range(256))

    def test_memory_fs(self, tmp_path):
        fsspec = pytest.importorskip("fsspec")
        from horovod_tpu.spark.store import (ParquetBatchIterator,
                                             write_parquet)
        fs = fsspec.filesystem("memory")
        path = "memory://stream-ds"
        write_parquet(path, {"idx": np.arange(64, dtype=np.int64)},
                      row_group_rows=16, fs=fs)
        rows = [i for b in ParquetBatchIterator(
            path, ["idx"], batch_size=10, fs=fs) for i in b["idx"]]
        assert sorted(rows) == list(range(64))


def test_torch_estimator_streaming_matches_memory(hvd_world, tmp_path):
    """streaming=True trains through the row-group reader; with
    shuffle=False the trajectory must EQUAL the in-memory path (same
    batches in the same order)."""
    torch = pytest.importorskip("torch")
    from horovod_tpu.spark.torch import TorchEstimator
    from horovod_tpu.spark.store import LocalStore

    df = _regression_df(n=256)

    def run(streaming, leaf):
        torch.manual_seed(11)
        net = torch.nn.Linear(4, 1)
        return TorchEstimator(
            model=net, optimizer=lambda p: torch.optim.SGD(p, lr=1e-2),
            loss=torch.nn.MSELoss(), shuffle=False,
            feature_cols=[f"f{i}" for i in range(4)],
            label_cols=["label"], batch_size=32, epochs=3,
            streaming=streaming,
            store=LocalStore(str(tmp_path / leaf))).fit(df)

    m_s = run(True, "stream")
    m_m = run(False, "memory")
    for k in m_m.model.state_dict():
        np.testing.assert_allclose(
            m_s.model.state_dict()[k].numpy(),
            m_m.model.state_dict()[k].numpy(), atol=1e-5)
    assert m_s.loss_history[-1] < m_s.loss_history[0]


def test_torch_estimator_streaming_validation_column_and_weights(
        hvd_world, tmp_path):
    torch = pytest.importorskip("torch")
    from horovod_tpu.spark.torch import TorchEstimator
    from horovod_tpu.spark.store import LocalStore

    df = _regression_df(n=256)
    df["is_val"] = (np.arange(len(df)) % 4 == 0).astype(np.float64)
    df["w"] = 1.0
    m = TorchEstimator(
        model=torch.nn.Linear(4, 1), loss=torch.nn.MSELoss(),
        feature_cols=[f"f{i}" for i in range(4)], label_cols=["label"],
        batch_size=32, epochs=2, streaming=True, validation="is_val",
        sample_weight_col="w",
        store=LocalStore(str(tmp_path))).fit(df)
    assert len(m.val_loss_history) == 2
    assert all(v > 0 for v in m.val_loss_history)


def test_torch_estimator_streaming_rejects_fraction_validation(
        hvd_world, tmp_path):
    torch = pytest.importorskip("torch")
    from horovod_tpu.spark.torch import TorchEstimator
    from horovod_tpu.spark.store import LocalStore

    with pytest.raises(ValueError, match="COLUMN"):
        TorchEstimator(
            model=torch.nn.Linear(4, 1), loss=torch.nn.MSELoss(),
            feature_cols=[f"f{i}" for i in range(4)],
            label_cols=["label"], streaming=True, validation=0.25,
            store=LocalStore(str(tmp_path))).fit(_regression_df(n=64))


def test_streaming_batch_larger_than_row_groups(hvd_world, tmp_path):
    """batch_size far above row_group_rows: the chunk-list buffer merges
    many groups per batch (linear, not quadratic) and loses no rows."""
    from horovod_tpu.spark.store import ParquetBatchIterator, write_parquet
    path = str(tmp_path / "tiny-groups")
    write_parquet(path, {"idx": np.arange(10000, dtype=np.int64)},
                  row_group_rows=64, partitions=2)
    batches = list(ParquetBatchIterator(path, ["idx"], batch_size=4096))
    assert [len(b["idx"]) for b in batches] == [4096, 4096, 1808]
    assert sorted(i for b in batches for i in b["idx"].tolist()) \
        == list(range(10000))


def test_streaming_accepts_zero_fraction_validation(hvd_world, tmp_path):
    """validation=0.0 is a no-op fraction in the in-memory path; streaming
    must accept it too (round-5 review finding)."""
    torch = pytest.importorskip("torch")
    from horovod_tpu.spark.torch import TorchEstimator
    from horovod_tpu.spark.store import LocalStore

    m = TorchEstimator(
        model=torch.nn.Linear(4, 1), loss=torch.nn.MSELoss(),
        feature_cols=[f"f{i}" for i in range(4)], label_cols=["label"],
        batch_size=32, epochs=1, streaming=True, validation=0.0,
        store=LocalStore(str(tmp_path))).fit(_regression_df(n=64))
    assert len(m.loss_history) == 1 and not m.val_loss_history


def test_streaming_vector_feature_column(hvd_world, tmp_path):
    """Fixed-size vector columns (list-encoded in Parquet) stream as 2-d
    arrays through the columnar conversion path."""
    from horovod_tpu.spark.store import ParquetBatchIterator, write_parquet
    path = str(tmp_path / "vec")
    vec = np.arange(600, dtype=np.float32).reshape(100, 6)
    write_parquet(path, {"features": vec,
                         "idx": np.arange(100, dtype=np.int64)},
                  row_group_rows=32)
    rows = []
    for b in ParquetBatchIterator(path, ["features", "idx"],
                                  batch_size=16):
        assert b["features"].shape[1:] == (6,)
        for i, r in zip(b["idx"], b["features"]):
            np.testing.assert_allclose(r, vec[i])
            rows.append(int(i))
    assert sorted(rows) == list(range(100))


# ---------------------------------------------------------------------------
# round 6 (ADVICE r5): validation-spec typing, split semantics, store URL
# ---------------------------------------------------------------------------

def test_validation_spec_numeric_string_is_column_name():
    """ADVICE r5 #1: the reference (spark/common/util.py check_validation)
    treats ANY string as a column name — a column literally named '0.2'
    (or '2') must not be coerced into a fraction."""
    from horovod_tpu.spark.estimator import HorovodEstimator

    assert HorovodEstimator(validation="0.2")._validation_spec() == \
        ("column", "0.2")
    assert HorovodEstimator(validation="2")._validation_spec() == \
        ("column", "2")   # previously raised: float('2') out of range
    assert HorovodEstimator(validation="is_val")._validation_spec() == \
        ("column", "is_val")
    # float instances stay fractions, with the range check intact
    assert HorovodEstimator(validation=0.25)._validation_spec() == \
        ("fraction", 0.25)
    with pytest.raises(ValueError, match="validation"):
        HorovodEstimator(validation=1.5)._validation_spec()
    assert HorovodEstimator()._validation_spec() is None


def test_load_split_shard_drops_negative_validation_rows(tmp_path):
    """ADVICE r5 #2: reference split semantics are train = (col == 0),
    val = (col > 0) — NEGATIVE column values fall out of both sets
    instead of being swept into train by ~(col > 0)."""
    from horovod_tpu.spark.estimator import load_split_shard
    from horovod_tpu.spark.store import write_parquet

    path = str(tmp_path / "ds")
    n = 12
    # rows 0-3 train (0), 4-7 validation (+1), 8-11 excluded (-1)
    val_col = np.array([0] * 4 + [1] * 4 + [-1] * 4, np.int64)
    write_parquet(path, {
        "x": np.arange(n, dtype=np.float32),
        "label": np.arange(n, dtype=np.float32),
        "is_val": val_col,
        "wgt": np.ones(n, np.float32) * 2,
    })
    train, val, w_train, w_val = load_split_shard(
        path, ["x"], ["label"], rank=0, size=1,
        sample_weight_col="wgt", validation_spec=("column", "is_val"))
    np.testing.assert_array_equal(train[0], [0, 1, 2, 3])
    np.testing.assert_array_equal(val[0], [4, 5, 6, 7])
    assert len(w_train) == 4 and len(w_val) == 4


def test_fsspec_store_builds_filesystem_from_full_url(monkeypatch):
    """ADVICE r5 #5: the filesystem must come from url_to_fs(prefix) so
    host/port/credentials embedded in the store URL are honored, not
    from the bare scheme (which silently connects to the
    default-configured endpoint)."""
    fsspec = pytest.importorskip("fsspec")
    from horovod_tpu.spark import store as store_mod

    seen = {}
    real = fsspec.core.url_to_fs

    def spy(url, **kw):
        seen["url"] = url
        return real(url, **kw)

    monkeypatch.setattr(fsspec.core, "url_to_fs", spy)
    s = store_mod.FsspecStore("memory://namenode:8020/prefix")
    assert seen["url"] == "memory://namenode:8020/prefix"
    assert s.fs is not None
    # path building still keeps the scheme-full prefix
    assert s.get_train_data_path("r1").startswith(
        "memory://namenode:8020/prefix/runs/r1")
