import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.models import BinaryLR
from distlr_tpu.parallel import (
    batch_sharding,
    feature_sharding,
    make_eval_step,
    make_mesh,
    make_sync_train_step,
    replicated_sharding,
)
from distlr_tpu.parallel.data_parallel import shard_batch
from distlr_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, num_data_shards


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh({"data": 8})


def global_batch(n=64, d=16, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.integers(0, 2, n).astype(np.int32)
    return jnp.asarray(X), jnp.asarray(y), jnp.ones(n, dtype=jnp.float32)


class TestMesh:
    def test_devices_available(self):
        assert len(jax.devices()) == 8  # conftest forced 8 CPU devices

    def test_default_mesh_all_data(self):
        m = make_mesh()
        assert m.axis_names == (DATA_AXIS,) and m.shape[DATA_AXIS] == 8

    def test_2d_mesh(self):
        m = make_mesh({"data": 4, "model": 2})
        assert m.shape == {"data": 4, "model": 2}
        assert num_data_shards(m) == 4

    def test_too_many_devices_raises(self):
        with pytest.raises(ValueError):
            make_mesh({"data": 16})

    def test_shardings(self, mesh8):
        assert batch_sharding(mesh8).spec == jax.sharding.PartitionSpec(DATA_AXIS)
        assert replicated_sharding(mesh8).spec == jax.sharding.PartitionSpec()
        m2 = make_mesh({"data": 4, "model": 2})
        assert feature_sharding(m2).spec == jax.sharding.PartitionSpec(MODEL_AXIS)


class TestSyncStep:
    @pytest.mark.parametrize("n_data", [8, 2, 4])
    def test_psum_equals_single_device_fullbatch(self, n_data):
        """The distributed mean gradient must equal the single-device
        full-batch gradient: the collective is exact, not approximate."""
        mesh = make_mesh({"data": n_data})
        cfg = Config(learning_rate=0.1, l2_c=0.5)
        model = BinaryLR(16)
        batch = global_batch()
        w0 = jnp.asarray(np.random.default_rng(1).standard_normal(16), dtype=jnp.float32)

        step = make_sync_train_step(model, cfg, mesh)
        w1, metrics = step(jnp.array(w0), shard_batch(batch, mesh))

        g_ref = model.grad(w0, batch, cfg)
        w1_ref = w0 - 0.1 * g_ref
        np.testing.assert_allclose(np.asarray(w1), np.asarray(w1_ref), atol=2e-2)
        assert np.isfinite(float(metrics["loss"]))

    def test_mean_vs_per_shard_mean_semantics(self, mesh8):
        """pmean of per-shard mean grads == mean of the full batch when
        shards are equal-sized (the reference's server-side averaging)."""
        cfg = Config(l2_c=0.0)
        model = BinaryLR(8)
        X, y, mask = global_batch(32, 8, seed=5)
        step = make_sync_train_step(model, cfg, mesh8)
        w0 = jnp.zeros(8)
        w1, _ = step(jnp.array(w0), shard_batch((X, y, mask), mesh8))
        manual = np.zeros(8) - cfg.learning_rate * np.mean(
            [np.asarray(model.grad(w0, (X[i * 4 : (i + 1) * 4], y[i * 4 : (i + 1) * 4], mask[i * 4 : (i + 1) * 4]), cfg)) for i in range(8)],
            axis=0,
        )
        np.testing.assert_allclose(np.asarray(w1), manual, atol=2e-2)

    def test_q1_last_gradient_compat(self, mesh8):
        """Q1 mode applies only the last shard's gradient / W (ref src/main.cc:63-77)."""
        cfg = Config(compat_mode="reference", l2_c=0.0)
        assert cfg.sync_last_gradient
        model = BinaryLR(8)
        X, y, mask = global_batch(32, 8, seed=7)
        step = make_sync_train_step(model, cfg, mesh8)
        w0 = jnp.zeros(8)
        w1, _ = step(jnp.array(w0), shard_batch((X, y, mask), mesh8))
        g_last = np.asarray(model.grad(jnp.zeros(8), (X[28:], y[28:], mask[28:]), cfg))
        expect = np.zeros(8) - cfg.learning_rate * g_last / 8
        np.testing.assert_allclose(np.asarray(w1), expect, atol=2e-2)

    def test_weights_replicated_after_step(self, mesh8):
        cfg = Config()
        model = BinaryLR(8)
        step = make_sync_train_step(model, cfg, mesh8)
        w1, _ = step(jnp.zeros(8), shard_batch(global_batch(16, 8), mesh8))
        assert w1.sharding.is_fully_replicated


class TestEvalStep:
    def test_global_masked_accuracy(self, mesh8):
        model = BinaryLR(4)
        w = jnp.asarray([1.0, 0, 0, 0])
        n = 40
        rng = np.random.default_rng(0)
        X = rng.standard_normal((n, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.int32)
        y[:5] = 1 - y[:5]  # corrupt 5 labels
        mask = np.ones(n, dtype=np.float32)
        mask[-8:] = 0.0
        evaluate = make_eval_step(model, mesh8)
        em = evaluate(w, shard_batch((jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask)), mesh8))
        acc = float(em["accuracy"])
        expect = ((X[:, 0] > 0).astype(int) == y)[:-8].mean()
        assert acc == pytest.approx(expect, abs=1e-6)
