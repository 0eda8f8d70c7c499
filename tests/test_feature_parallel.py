import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.models import BinaryLR, SoftmaxRegression, SparseBinaryLR
from distlr_tpu.parallel import make_mesh
from distlr_tpu.parallel.feature_parallel import (
    make_feature_sharded_eval_step,
    make_feature_sharded_train_step,
    shard_batch_2d,
    shard_weights,
)


@pytest.fixture(scope="module")
def mesh42():
    return make_mesh({"data": 4, "model": 2})


def batch(n=32, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return (
        rng.standard_normal((n, d)).astype(np.float32),
        rng.integers(0, 2, n).astype(np.int32),
        np.ones(n, dtype=np.float32),
    )


class TestFeatureShardedBinaryLR:
    # the four-chip host's shapes and their neighbours, beside this file's 4x2
    @pytest.mark.parametrize("data,model_shards", [
        (4, 2), (1, 2), (2, 2), (1, 4), (2, 4), (1, 8)])
    def test_matches_unsharded_step(self, data, model_shards):
        """2D-parallel step == single-device full-batch step: sharding the
        feature axis must not change the math, whatever the mesh."""
        mesh = make_mesh({"data": data, "model": model_shards})
        cfg = Config(learning_rate=0.2, l2_c=0.4, num_feature_dim=16)
        model = BinaryLR(16)
        X, y, mask = batch()
        w0 = np.random.default_rng(1).standard_normal(16).astype(np.float32)

        step = make_feature_sharded_train_step(model, cfg, mesh)
        w_sh = shard_weights(jnp.asarray(w0), mesh)
        b_sh = shard_batch_2d((jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask)), mesh)
        w1, metrics = step(w_sh, b_sh)

        g_ref = model.grad(jnp.asarray(w0), (jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask)), cfg)
        w1_ref = w0 - 0.2 * np.asarray(g_ref)
        np.testing.assert_allclose(np.asarray(w1), w1_ref, atol=3e-2)
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["grad_norm"]) > 0

    def test_weights_stay_sharded(self, mesh42):
        cfg = Config(num_feature_dim=16)
        model = BinaryLR(16)
        step = make_feature_sharded_train_step(model, cfg, mesh42)
        w = shard_weights(jnp.zeros(16), mesh42)
        b = shard_batch_2d(jax.tree.map(jnp.asarray, batch()), mesh42)
        w1, _ = step(w, b)
        spec = w1.sharding.spec
        assert spec == jax.sharding.PartitionSpec("model")

    def test_eval_matches_unsharded(self, mesh42):
        model = BinaryLR(16)
        X, y, mask = batch(40, 16, seed=3)
        mask[-6:] = 0.0
        w = np.random.default_rng(2).standard_normal(16).astype(np.float32)
        evaluate = make_feature_sharded_eval_step(model, mesh42)
        em = evaluate(
            shard_weights(jnp.asarray(w), mesh42),
            shard_batch_2d((jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask)), mesh42),
        )
        acc = float(em["accuracy"])
        ll = float(em["logloss"])
        expect_ll = float(model.logloss(jnp.asarray(w), (jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask))))
        assert ll == pytest.approx(expect_ll, abs=1e-5)
        expect = float(model.accuracy(jnp.asarray(w), (jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask))))
        assert acc == pytest.approx(expect, abs=1e-6)

    def test_converges(self, mesh42):
        cfg = Config(learning_rate=0.5, l2_c=0.0, num_feature_dim=16)
        model = BinaryLR(16)
        rng = np.random.default_rng(5)
        w_true = rng.standard_normal(16)
        X = rng.standard_normal((256, 16)).astype(np.float32)
        y = (X @ w_true > 0).astype(np.int32)
        step = make_feature_sharded_train_step(model, cfg, mesh42)
        b = shard_batch_2d((jnp.asarray(X), jnp.asarray(y), jnp.ones(256)), mesh42)
        w = shard_weights(jnp.zeros(16), mesh42)
        for _ in range(100):
            w, m = step(w, b)
            jax.block_until_ready(w)
        evaluate = make_feature_sharded_eval_step(model, mesh42)
        assert float(evaluate(w, b)["accuracy"]) > 0.95


class TestFeatureShardedInt8Dot:
    def test_matches_single_device_int8dot_step(self, mesh42):
        """Feature-sharded int8_dot == single-device int8_dot within
        quantization noise: the weight shards quantize on a GLOBAL
        scale (pmax), so the forward matches exactly; only the
        per-data-shard residual scale differs from the single-device
        global one."""
        import dataclasses

        d = 16
        cfg = Config(learning_rate=0.2, l2_c=0.0, num_feature_dim=d,
                     feature_dtype="int8_dot", feature_shards=2)
        model = dataclasses.replace(
            BinaryLR(d, int8_dot=True), feature_scale=1.0 / 127.0)
        rng = np.random.default_rng(3)
        X = rng.integers(-127, 128, (32, d)).astype(np.int8)
        y = rng.integers(0, 2, 32).astype(np.int32)
        mask = np.ones(32, np.float32)
        w0 = (0.1 * rng.standard_normal(d)).astype(np.float32)

        step = make_feature_sharded_train_step(model, cfg, mesh42)
        w_sh = shard_weights(jnp.asarray(w0), mesh42)
        b_sh = shard_batch_2d(
            (jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask)), mesh42)
        w1, metrics = step(w_sh, b_sh)

        g_ref = model.grad(
            jnp.asarray(w0),
            (jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask)), cfg)
        w1_ref = w0 - 0.2 * np.asarray(g_ref)
        np.testing.assert_allclose(np.asarray(w1), w1_ref, atol=5e-4)
        assert np.isfinite(float(metrics["loss"]))


class TestFeatureShardedSoftmax:
    def test_matches_unsharded_step(self, mesh42):
        cfg = Config(model="softmax", num_classes=3, num_feature_dim=16, learning_rate=0.1, l2_c=0.2)
        model = SoftmaxRegression(16, 3)
        rng = np.random.default_rng(0)
        X = rng.standard_normal((32, 16)).astype(np.float32)
        y = rng.integers(0, 3, 32).astype(np.int32)
        mask = np.ones(32, dtype=np.float32)
        W0 = rng.standard_normal((16, 3)).astype(np.float32)

        step = make_feature_sharded_train_step(model, cfg, mesh42)
        W1, _ = step(
            shard_weights(jnp.asarray(W0), mesh42),
            shard_batch_2d((jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask)), mesh42),
        )
        g_ref = model.grad(jnp.asarray(W0), (jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask)), cfg)
        np.testing.assert_allclose(np.asarray(W1), W0 - 0.1 * np.asarray(g_ref), atol=3e-2)


class TestValidation:
    def test_requires_model_axis(self):
        mesh = make_mesh({"data": 8})
        with pytest.raises(ValueError, match="model"):
            make_feature_sharded_train_step(BinaryLR(16), Config(num_feature_dim=16), mesh)

    def test_requires_divisible_features(self, mesh42):
        with pytest.raises(ValueError, match="divisible"):
            make_feature_sharded_train_step(BinaryLR(15), Config(num_feature_dim=15), mesh42)

    def test_rejects_sparse_model(self, mesh42):
        with pytest.raises(TypeError, match="dense"):
            make_feature_sharded_train_step(SparseBinaryLR(16), Config(num_feature_dim=16), mesh42)
