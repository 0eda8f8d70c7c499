"""Family ``dense_ps_softmax``: the gradient a parameter-server worker
pushes for multinomial (softmax) regression over its whole float32 shard.

    Z = X W,   P = softmax(Z) by rows,   G = X^T (P - onehot(y)) / n

with ``W`` of shape ``[D, K]`` and no L2 term (the server's rule has
none).  The weights and the gradient cross the wire flat, ``[D K]`` in
the program's order (feature-major: entry ``d K + k`` is feature ``d``,
class ``k``), and that is how they come in and go out here.  The
server's rule, ``w' = w - lr g`` on every push, is in :func:`step`.

Rows arrive as the generator makes them (``chipbench/newsgen.py``),
padded COO, and are densified here in blocks of 128 rows to the float32
matrix the deployment holds; both products run at the ``highest``
precision (on a TPU a float32 product is one bfloat16 pass otherwise).
``precision="bfloat16"`` is the control: features, weights and residuals
rounded to bfloat16 before each product, what a step with
``compute_dtype: bfloat16`` multiplies.  Nothing of the program is
imported.  An asynchronous run has no trajectory to follow, so what the
benchmark compares is :func:`gradient` at the weights a worker computed
on and :func:`evaluate` at the weights the servers held
(``chipbench/drivers/ps_softmax_epochs.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import lower

BLOCK_ROWS = 128


def _dense_rows(cols, vals, dim):
    """A block's padded-COO rows as the float32 matrix the deployment
    holds, made on the host (a scatter of 10,000 entries into 8 million
    zeros is what a TPU does slowest) and handed over."""
    X = np.zeros((len(cols), dim), np.float32)
    np.add.at(X, (np.arange(len(cols))[:, None], np.asarray(cols)),
              np.asarray(vals))
    return jnp.asarray(X)


# names of their own, as in families/dense_ps.py: neither a trace nor the
# compile cache can take them for the program's
@functools.partial(jax.jit, static_argnames=("precision",))
def reference_softmax_block_logits(W, X, precision="float32"):
    with jax.default_matmul_precision("highest"):
        return lower(X, precision) @ lower(W, precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def reference_softmax_block_grad(X, resid, precision="float32"):
    with jax.default_matmul_precision("highest"):
        return lower(X, precision).T @ lower(resid, precision)


def _matrix(w, classes: int):
    w = jnp.asarray(w, jnp.float32)
    return w.reshape(-1, classes)


def _blocks(cols, vals, dim):
    for s in range(0, cols.shape[0], BLOCK_ROWS):
        e = s + BLOCK_ROWS
        yield slice(s, e), _dense_rows(cols[s:e], vals[s:e], dim)


def logits(w, cols, vals, classes: int, precision="float32"):
    """``[n, classes]`` logits of the rows at the flat weights ``w``."""
    W = _matrix(w, classes)
    return jnp.concatenate([
        reference_softmax_block_logits(W, X, precision)
        for _, X in _blocks(cols, vals, W.shape[0])])


def gradient(w, cols, vals, y, classes: int, precision="float32"):
    """The mean gradient of the rows' multiclass logloss at the flat
    weights ``w``, flat in the same order."""
    W = _matrix(w, classes)
    G = jnp.zeros_like(W)
    for sl, X in _blocks(cols, vals, W.shape[0]):
        Z = reference_softmax_block_logits(W, X, precision)
        resid = jax.nn.softmax(Z, axis=-1) - jax.nn.one_hot(
            jnp.asarray(y[sl]), classes, dtype=jnp.float32)
        G = G + reference_softmax_block_grad(X, resid, precision)
    return (G / jnp.float32(len(y))).reshape(-1)


def _logloss(Z, y):
    return -jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(Z, axis=-1), jnp.asarray(y)[:, None], 1))


def loss(w, cols, vals, y, classes: int, precision="float32"):
    """Mean multiclass logloss of the rows at ``w``, no L2 term: what
    :func:`gradient` is the gradient of."""
    return _logloss(logits(w, cols, vals, classes, precision), y)


def evaluate(w, cols, vals, y, classes: int, precision="float32"):
    """``(mean -log P[y], argmax accuracy)`` of the rows at ``w``, off
    one forward pass."""
    Z = logits(w, cols, vals, classes, precision)
    hit = jnp.argmax(Z, axis=-1) == jnp.asarray(y)
    return float(_logloss(Z, y)), float(jnp.mean(hit.astype(jnp.float32)))


def step(w, cols, vals, y, lr, classes: int, precision="float32"):
    """One push as the server applies it: the loss before, the flat
    weights after."""
    before = loss(w, cols, vals, y, classes, precision)
    w = jnp.asarray(w, jnp.float32)
    return before, w - lr * gradient(w, cols, vals, y, classes, precision)


def step_bytes_floor(*, rows: int, dim: int, classes: int, nnz: int) -> float:
    """Bytes one worker's gradient cannot avoid moving through HBM: its
    float32 shard once (the two products can share one read of it), the
    weights read and the gradient written.  XLA's two products each read
    the shard, so a share of the roofline computed from this cannot pass
    100%, and stands near 50% for them."""
    del nnz
    return rows * dim * 4 + 2 * dim * classes * 4


def step_flops(*, rows: int, dim: int, classes: int) -> float:
    """The arithmetic the step needs: two products of ``rows x dim x
    classes`` multiply-adds.  Counted once, whatever the passes a
    precision costs the MXU and however few of its columns 20 classes
    fill, so a share of the bfloat16 peak computed from it is of *useful*
    arithmetic and no implementation can pass 100%."""
    return 4 * rows * dim * classes
