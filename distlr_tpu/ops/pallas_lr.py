"""Pallas TPU kernel: single-HBM-pass fused logistic-regression gradient.

The XLA path computes ``g = X^T (sigmoid(X w) - y)`` as two matmuls, so
the (B, D) feature matrix streams HBM -> MXU **twice** per step; for the
wide-feature workloads this framework targets, that HBM traffic IS the
step time (PERF.md, ``step_hbm_roofline``).  This kernel streams X
exactly once:

* the weight vector ``w`` (bf16) and a float32 gradient accumulator live
  in VMEM for the whole kernel,
* the grid walks batch tiles; each (BT, D) tile of X is DMA'd in once,
  used for the forward matvec ``z_t = X_t @ w``, turned into the residual
  ``r_t = (sigmoid(z_t) - y_t) * mask_t`` on the VPU, and immediately
  re-used (still in VMEM) for the backward rank-BT update
  ``g += r_t @ X_t`` on the MXU,
* the final grid step writes the accumulator out.

In theory halved HBM traffic -> up to 2x step throughput while the VMEM
working set fits the 16 MB scoped-VMEM limit.  ``fused_lr_supported``
reports the budget check; callers fall back to the XLA two-matmul path
above it.

Whether the single pass beats the XLA path on the chip is not measured
on today's code (``chip_smoke.py`` only proves the kernel compiles under
Mosaic and agrees with :meth:`BinaryLR.grad`); :class:`BinaryLR` keeps
the XLA path, and the kernel stays as the reference implementation of
the fused formulation (grid pipelining, VMEM accumulators, ``pl.when``
epilogues).

This is the TPU-native answer to the reference's O(B*D^2) scalar hot
loop (``src/lr.cc:35-41``) at the opposite end of the efficiency scale.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM the kernel may use; passed to Mosaic as its limit, so the
# budget below and the compiler count against the same number (it is
# also libtpu's default).
_VMEM_LIMIT = 16 * 1024 * 1024


def fused_lr_supported(batch: int, dim: int, batch_tile: int = 64) -> bool:
    """True where the kernel compiles: shape constraints, and a working
    set inside ``_VMEM_LIMIT``.  Per tile element Mosaic holds the
    double-buffered bf16 X tile and ONE float32 copy of it (the products
    fuse into their reductions); per column the bf16 ``w`` and float32
    ``g`` blocks, both double-buffered, and the float32 accumulator.
    Checked on a v5e against the allocator's own figures: the estimate
    runs 3-6% above them, never below."""
    if batch % batch_tile != 0 or dim % 128 != 0 or batch_tile % 16 != 0:
        return False
    working_set = (
        2 * batch_tile * dim * 2  # double-buffered bf16 X tile
        + batch_tile * dim * 4    # x.astype(f32)
        + dim * (2 * 2 + 2 * 4 + 4)  # w, g (x2 buffers each), accumulator
    )
    return working_set <= _VMEM_LIMIT


def _kernel(x_ref, y_ref, mask_ref, w_ref, g_ref, acc_ref):
    # Matvec-shaped contractions (N=1 / M=1) waste 127/128 of the MXU, so
    # both directions run on the VPU as broadcast-multiply + axis
    # reduction — that keeps the kernel DMA-bound instead of
    # degenerate-matmul-bound.
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:].astype(jnp.float32)  # (BT, D); the only HBM read of this tile
    w = w_ref[:].astype(jnp.float32)  # (1, D), VMEM-resident across the grid
    z = jnp.sum(x * w, axis=1, keepdims=True)  # (BT, 1) forward matvec
    r = (jax.nn.sigmoid(z) - y_ref[:]) * mask_ref[:]  # (BT, 1)
    # backward re-uses the SAME VMEM tile: outer-product accumulation
    acc_ref[:] += jnp.sum(x * r, axis=0, keepdims=True)  # (1, D)

    @pl.when(t == pl.num_programs(0) - 1)
    def _flush():
        g_ref[:] = acc_ref[:]


@functools.partial(jax.jit, static_argnames=("batch_tile", "interpret"))
def fused_lr_grad(
    w,
    X,
    y,
    mask,
    *,
    batch_tile: int = 64,
    interpret: bool = False,
):
    """Unnormalized logistic gradient ``X^T ((sigmoid(Xw) - y) * mask)``.

    One HBM pass over ``X``.  Caller divides by the batch size and adds
    the L2 term (matching :meth:`BinaryLR.grad` semantics).

    Args:
      w: (D,) float32/bfloat16 weights. D must be a multiple of 128.
      X: (B, D) features (cast to bf16 for the MXU). B must be a
        multiple of ``batch_tile`` (pad + mask).
      y: (B,) labels; mask: (B,) validity.
      batch_tile: rows per grid step (multiple of 16 for bf16 tiling).
    """
    B, D = X.shape
    if not fused_lr_supported(B, D, batch_tile):
        raise ValueError(
            f"fused kernel unsupported for B={B} D={D} batch_tile={batch_tile}; "
            "use the XLA path (BinaryLR.grad)"
        )
    grid = (B // batch_tile,)
    g = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((batch_tile, D), lambda t: (t, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((batch_tile, 1), lambda t: (t, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((batch_tile, 1), lambda t: (t, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, D), lambda t: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, D), lambda t: (0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(
        X.astype(jnp.bfloat16),
        y.astype(jnp.float32).reshape(B, 1),
        mask.astype(jnp.float32).reshape(B, 1),
        w.astype(jnp.bfloat16).reshape(1, D),
    )
    return g.reshape(D)
