"""Family ``dense_ps``: the gradient a parameter-server worker pushes,
dense binary logistic regression over its whole float32 shard.

    z = X w,   g = X^T (sigmoid(z) - y) / n        (no L2 in this family)

The server's rule, ``w' = w - lr g`` on every push, is in :func:`step`.
Rows arrive as the generator makes them, padded COO, and are densified
here in blocks of rows to the float32 matrix the deployment holds; the
matmuls run at the ``highest`` precision.  Nothing of the program is
imported.  An asynchronous run has no trajectory to follow, so what the
benchmark compares is :func:`gradient` at the weights a worker computed
on (``chipbench/drivers/ps_epochs.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference import logloss_terms, lower

BLOCK_ROWS = 128


# names of their own, as in families/dense.py: neither a trace nor the
# compile cache can take them for the program's
@functools.partial(jax.jit, static_argnames=("dim",))
def reference_ps_rows(cols, vals, dim):
    rows = jnp.arange(cols.shape[0])[:, None]
    return jnp.zeros((cols.shape[0], dim), jnp.float32).at[rows, cols].add(vals)


@functools.partial(jax.jit, static_argnames=("precision",))
def reference_ps_block_logits(w, X, precision="float32"):
    with jax.default_matmul_precision("highest"):
        return X @ lower(w, precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def reference_ps_block_grad(X, resid, precision="float32"):
    with jax.default_matmul_precision("highest"):
        return lower(resid, precision) @ X


def _blocks(cols, vals, dim):
    for s in range(0, cols.shape[0], BLOCK_ROWS):
        e = s + BLOCK_ROWS
        yield slice(s, e), reference_ps_rows(cols[s:e], vals[s:e], dim)


def logits(w, cols, vals, precision="float32"):
    return jnp.concatenate([
        reference_ps_block_logits(w, X, precision)
        for _, X in _blocks(cols, vals, w.shape[0])])


def gradient(w, cols, vals, y, precision="float32"):
    """The mean gradient of the rows' logloss at ``w``."""
    w = jnp.asarray(w, jnp.float32)
    cols, vals = jnp.asarray(cols), jnp.asarray(vals)
    g = jnp.zeros_like(w)
    for sl, X in _blocks(cols, vals, w.shape[0]):
        z = reference_ps_block_logits(w, X, precision)
        resid = jax.nn.sigmoid(z) - jnp.asarray(y[sl]).astype(jnp.float32)
        g = g + reference_ps_block_grad(X, resid, precision)
    return g / jnp.float32(len(y))


def step(w, cols, vals, y, lr, l2, precision="float32"):
    """One push as the server applies it: the loss before, the weights
    after.  ``l2`` has to be 0: the server's rule has no such term."""
    z = logits(w, cols, vals, precision)
    loss = jnp.sum(logloss_terms(z, y)) / jnp.float32(y.shape[0])
    return loss, w - lr * (gradient(w, cols, vals, y, precision) + l2 * w)


def step_bytes_floor(*, rows: int, dim: int, nnz: int) -> float:
    """Bytes one worker's gradient cannot avoid moving through HBM: its
    float32 shard once (the forward and the backward pass can share one
    read of it), the weights read and the gradient written.  The program
    reads the shard twice, so a share of the roofline computed from this
    cannot pass 100%."""
    del nnz
    return rows * dim * 4 + 2 * dim * 4
