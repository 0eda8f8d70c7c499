"""Family ``dense``: logistic regression over hashed-to-dense rows, the
``(rows, D)`` matrix times the weights and its transpose times the
residuals.

    loss  = mean(softplus(z) - y z) + l2/2 |w|^2,   z = X w
    grad  = X^T (sigmoid(z) - y) / n + l2 w
    w'    = w - lr grad

Rows arrive as the generator makes them, padded COO (``cols``, ``vals``
of shape ``(n, F)``, pad value 0), and are densified here, in blocks of
rows, to the matrix the deployment multiplies: a whole step's matrix in
float32 is gigabytes.  Matmuls run at the ``highest`` precision.
``precision`` other than float32 holds weights and residuals as that
precision would.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference import logloss_terms, lower

BLOCK_ROWS = 128


# the jitted programs carry names of their own, so that neither a trace
# nor the compile cache can take them for the program's step and eval
@functools.partial(jax.jit, static_argnames=("dim",))
def reference_dense_rows(cols, vals, dim):
    rows = jnp.arange(cols.shape[0])[:, None]
    return jnp.zeros((cols.shape[0], dim), jnp.float32).at[rows, cols].add(vals)


@functools.partial(jax.jit, static_argnames=("precision",))
def reference_dense_block_logits(w, X, precision="float32"):
    with jax.default_matmul_precision("highest"):
        return X @ lower(w, precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def reference_dense_block_grad(X, resid, precision="float32"):
    with jax.default_matmul_precision("highest"):
        return lower(resid, precision) @ X


def _blocks(cols, vals, dim):
    for s in range(0, cols.shape[0], BLOCK_ROWS):
        e = s + BLOCK_ROWS
        yield slice(s, e), reference_dense_rows(cols[s:e], vals[s:e], dim)


def logits(w, cols, vals, precision="float32"):
    return jnp.concatenate([
        reference_dense_block_logits(w, X, precision)
        for _, X in _blocks(cols, vals, w.shape[0])])


def step(w, cols, vals, y, lr, l2, precision="float32"):
    """One SGD step: the loss before the update, the weights after it."""
    n = jnp.float32(y.shape[0])
    z = logits(w, cols, vals, precision)
    loss = jnp.sum(logloss_terms(z, y)) / n + 0.5 * l2 * jnp.sum(w * w)
    resid = jax.nn.sigmoid(z) - y.astype(jnp.float32)
    g = jnp.zeros_like(w)
    for sl, X in _blocks(cols, vals, w.shape[0]):
        g = g + reference_dense_block_grad(X, resid[sl], precision)
    return loss, w - lr * (g / n + l2 * w)


def step_bytes_floor(*, rows: int, dim: int, nnz: int) -> float:
    """Bytes one step cannot avoid moving through HBM on one device: the
    bfloat16 matrix once (the forward and the gradient can share one pass
    over it) and the float32 weights read and written.  It leaves out
    what the program moves beyond that (it reads the matrix twice), so a
    share of the roofline computed from it cannot pass 100%."""
    del nnz
    return rows * dim * 2 + 2 * dim * 4
