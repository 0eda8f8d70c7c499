import jax.numpy as jnp
import numpy as np
import pytest

from distlr_tpu.ops import fused_lr_grad, fused_lr_supported


def _reference_grad(w, X, y, mask):
    z = X.astype(np.float64) @ w
    sig = 1.0 / (1.0 + np.exp(-z))
    return ((sig - y) * mask) @ X


class TestFusedLRGrad:
    def test_matches_reference_interpret(self):
        """Run the kernel in interpreter mode (works on CPU) against a
        float64 numpy oracle; bf16 inputs bound the tolerance."""
        rng = np.random.default_rng(0)
        B, D = 64, 256
        X = rng.standard_normal((B, D)).astype(np.float32)
        y = rng.integers(0, 2, B).astype(np.float64)
        mask = np.ones(B)
        mask[-10:] = 0
        w = (rng.standard_normal(D) * 0.1).astype(np.float32)
        g = np.asarray(
            fused_lr_grad(
                jnp.asarray(w), jnp.asarray(X), jnp.asarray(y.astype(np.int32)),
                jnp.asarray(mask.astype(np.float32)), batch_tile=16, interpret=True,
            )
        )
        g_ref = _reference_grad(w, X, y, mask)
        rel = np.abs(g - g_ref).max() / np.abs(g_ref).max()
        assert rel < 5e-2, f"rel err {rel}"

    def test_accumulates_across_tiles(self):
        """Gradient must equal the sum over batch tiles (grid revisiting
        the same output block accumulates, not overwrites)."""
        rng = np.random.default_rng(1)
        B, D = 64, 128
        X = rng.standard_normal((B, D)).astype(np.float32)
        y = rng.integers(0, 2, B).astype(np.int32)
        mask = np.ones(B, np.float32)
        w = np.zeros(D, np.float32)
        g_4tiles = np.asarray(
            fused_lr_grad(jnp.asarray(w), jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask),
                          batch_tile=16, interpret=True)
        )
        g_1tile = np.asarray(
            fused_lr_grad(jnp.asarray(w), jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask),
                          batch_tile=64, interpret=True)
        )
        np.testing.assert_allclose(g_4tiles, g_1tile, rtol=1e-3, atol=1e-3)

    def test_supported_predicate(self):
        assert fused_lr_supported(4096, 16384, 64)
        assert not fused_lr_supported(4096, 1_000_000, 64)  # VMEM budget
        assert not fused_lr_supported(100, 128, 64)  # B not divisible
        assert not fused_lr_supported(64, 100, 16)   # D not mult of 128
        assert not fused_lr_supported(64, 128, 8)    # tile not mult of 16

    def test_supported_tracks_what_mosaic_compiled_on_the_chip(self):
        """The budget counts the float32 copy of the X tile: these shapes
        all passed the old (w + g + 2 bf16 tiles) estimate and Mosaic
        refused every one on a v5e ("scoped allocation ... exceeded
        scoped vmem limit", 16.02M to 25.33M against 16.00M); the last
        three are the widest the budget admits per tile and compiled
        there (chip_smoke.py's kernel leg compiles them on every run)."""
        for shape in ((128, 32768, 64), (128, 49152, 64), (256, 24576, 128),
                      (64, 196608, 16)):
            assert not fused_lr_supported(*shape), shape
        for shape in ((32, 116480, 16), (128, 31744, 64), (256, 16128, 128)):
            assert fused_lr_supported(*shape), shape

    @pytest.mark.parametrize("shape", [(512, 16384, 64), (4096, 16384, 64)])
    def test_lowers_to_mosaic_for_tpu(self, shape):
        """The chip smoke's two shapes lower for platforms=['tpu'] from
        the CPU (lowering only: compiling needs the chip)."""
        import functools

        import jax
        from jax import export

        B, D, tile = shape
        fn = functools.partial(fused_lr_grad, batch_tile=tile, interpret=False)
        exported = export.export(jax.jit(fn), platforms=["tpu"])(
            jax.ShapeDtypeStruct((D,), jnp.float32),
            jax.ShapeDtypeStruct((B, D), jnp.bfloat16),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.float32),
        )
        assert "tpu_custom_call" in exported.mlir_module()

    def test_unsupported_raises(self):
        with pytest.raises(ValueError, match="unsupported"):
            fused_lr_grad(
                jnp.zeros(100), jnp.zeros((64, 100)), jnp.zeros(64, jnp.int32),
                jnp.ones(64), batch_tile=16,
            )
