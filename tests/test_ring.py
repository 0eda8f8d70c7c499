"""Explicit ppermute ring collectives vs XLA's built-ins."""

import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from distlr_tpu import Config
from distlr_tpu.models import BinaryLR
from distlr_tpu.parallel import make_mesh
from distlr_tpu.parallel.feature_parallel import (
    make_feature_sharded_train_step,
    shard_batch_2d,
    shard_weights,
)
from distlr_tpu.parallel.ring import make_ring_train_step, ring_all_gather, ring_psum


def _mesh1d(s):
    return make_mesh({"model": s})


class TestRingPrimitives:
    @pytest.mark.parametrize("s", [2, 4, 8])
    @pytest.mark.parametrize("n", [64, 61, 7])  # divisible, ragged, n < s
    def test_ring_psum_matches_lax_psum(self, s, n):
        mesh = _mesh1d(s)
        x = np.random.default_rng(0).standard_normal((s, n)).astype(np.float32)

        def ring(v):
            return ring_psum(v, "model")

        def ref(v):
            return lax.psum(v, "model")

        got = shard_map(ring, mesh=mesh, in_specs=P("model"), out_specs=P("model"),
                        check_vma=False)(x.reshape(-1))
        want = shard_map(ref, mesh=mesh, in_specs=P("model"), out_specs=P("model"),
                         check_vma=False)(x.reshape(-1))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("s", [2, 4])
    def test_ring_all_gather_orders_by_rank(self, s):
        mesh = _mesh1d(s)
        x = np.arange(s * 3, dtype=np.float32)

        def gather(v):
            return ring_all_gather(v, "model")

        got = shard_map(gather, mesh=mesh, in_specs=P("model"), out_specs=P(None),
                        check_vma=False)(x)
        # every device holds the full rank-ordered concatenation
        np.testing.assert_allclose(np.asarray(got), x)

    def test_scalar_psum(self):
        mesh = _mesh1d(4)
        x = np.arange(4, dtype=np.float32)

        def ring(v):
            return ring_psum(v, "model")

        got = shard_map(ring, mesh=mesh, in_specs=P("model"), out_specs=P("model"),
                        check_vma=False)(x)
        np.testing.assert_allclose(np.asarray(got), np.full(4, x.sum()))


class TestRingTrainStep:
    def test_matches_psum_feature_sharded_step(self):
        D, B = 64, 32
        mesh = make_mesh({"data": 2, "model": 4})
        cfg = Config(num_feature_dim=D, learning_rate=0.3, l2_c=0.1)
        model = BinaryLR(D)
        rng = np.random.default_rng(1)
        batch_np = (
            rng.standard_normal((B, D)).astype(np.float32),
            rng.integers(0, 2, B).astype(np.int32),
            np.ones(B, np.float32),
        )
        w0 = rng.standard_normal(D).astype(np.float32)

        ring_step = make_ring_train_step(model, cfg, mesh)
        psum_step = make_feature_sharded_train_step(model, cfg, mesh)

        w_r, m_r = ring_step(shard_weights(jnp.asarray(w0), mesh), shard_batch_2d(batch_np, mesh))
        w_p, m_p = psum_step(shard_weights(jnp.asarray(w0), mesh), shard_batch_2d(batch_np, mesh))
        np.testing.assert_allclose(np.asarray(w_r), np.asarray(w_p), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(float(m_r["loss"]), float(m_p["loss"]), rtol=1e-4)

    def test_converges(self):
        D, B = 32, 64
        mesh = make_mesh({"data": 2, "model": 2})
        cfg = Config(num_feature_dim=D, learning_rate=0.5, l2_c=0.0)
        model = BinaryLR(D)
        rng = np.random.default_rng(2)
        X = rng.standard_normal((B, D)).astype(np.float32)
        w_true = rng.standard_normal(D).astype(np.float32)
        y = (X @ w_true > 0).astype(np.int32)
        batch = shard_batch_2d((X, y, np.ones(B, np.float32)), mesh)
        step = make_ring_train_step(model, cfg, mesh)
        w = shard_weights(jnp.zeros(D, jnp.float32), mesh)
        losses = []
        for _ in range(60):
            w, m = step(w, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < 0.35 * losses[0]

    def test_rejects_non_binary_model(self):
        from distlr_tpu.models import SoftmaxRegression

        mesh = make_mesh({"data": 2, "model": 2})
        with pytest.raises(TypeError):
            make_ring_train_step(SoftmaxRegression(16, 4), Config(num_feature_dim=16), mesh)
