"""Family ``sparse_ps_keyed``: what a parameter-server worker pulls,
computes and pushes in one **keyed** round of sparse logistic regression
(Li et al., OSDI 2014, section 5.1): the weights of the keys its
minibatch touches, and a gradient for those keys only.

Which rows: a worker serves its ``R`` rows in file order, ``B`` a round,
every epoch from row 0, the last round of an epoch the rows that are
left, each once (:func:`window`, the rule of
``families/dense_ps_minibatch.py``, written out again here: this file
imports nothing of a sibling's windows and nothing of the program).

Which keys: the sorted unique columns of the window's rows, pad entries
(column 0, value 0) among them as the rows hold them (:func:`keys`).

The gradient, over those keys ``u`` (``w_u`` the pulled weights, one a
key, in the keys' order):

    w[u] = w_u, 0 elsewhere                      (a vector of all D)
    z_r  = sum_f w[cols_rf] vals_rf
    g    = segment_sum((sigmoid(z) - y) mask vals, cols) / max(sum mask, 1)
    g_u  = g[u]

a full-D segment sum, restricted to the keys at the end, in blocks of
rows; float32, and ``jax.default_matmul_precision("highest")`` stated
round every block (there is no product the TPU would lower, and none may
appear unnoticed).  No L2 in this family.  ``precision`` other than
float32 is the control, as in ``families/sparse.py``: weights, gathers,
products and residuals as that precision would hold them.

The server's rule, ``w[u] -= lr g_u`` on arrival, is in :func:`step`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import logloss_terms, lower

BLOCK_ROWS = 4096


def rounds_an_epoch(rows: int, batch: int) -> int:
    return -(-rows // batch)


def window(k: int, rows: int, batch: int) -> slice:
    """The rows round ``k`` (from 0) of a worker's run reads, of a shard
    of ``rows`` rows served ``batch`` at a time in file order, every
    epoch from row 0."""
    first = (k % rounds_an_epoch(rows, batch)) * batch
    return slice(first, min(first + batch, rows))


def keys(cols) -> np.ndarray:
    """The sorted unique columns of a window's rows: the keys of its
    pull and of its push."""
    return np.unique(np.asarray(cols))


# names of their own, as in the sibling families: neither a trace nor the
# compile cache can take them for the program's
@functools.partial(jax.jit, static_argnames=("precision",))
def reference_keyed_logits(w, cols, vals, precision="float32"):
    with jax.default_matmul_precision("highest"):
        prod = lower(lower(w, precision)[cols] * vals, precision)
        return jnp.sum(prod, axis=-1)


@functools.partial(jax.jit, static_argnames=("precision",))
def reference_keyed_block_grad(w, cols, vals, y, mask, precision="float32"):
    """One block of rows: its part of the full-D segment sum, not yet
    divided by the window's count of real rows."""
    with jax.default_matmul_precision("highest"):
        z = reference_keyed_logits(w, cols, vals, precision)
        resid = lower((jax.nn.sigmoid(z) - y.astype(jnp.float32))
                      * mask.astype(jnp.float32), precision)
        contrib = lower(resid[:, None] * vals, precision).reshape(-1)
        return jax.ops.segment_sum(contrib, cols.reshape(-1),
                                   num_segments=w.shape[0])


logits = reference_keyed_logits


def _spread(w_u, u, dim):
    """``w_u`` at its keys in a vector of all ``dim``, zero elsewhere: on
    the host, where a window's own count of keys compiles nothing."""
    w = np.zeros(dim, np.float32)
    w[u] = w_u
    return jnp.asarray(w)


def gradient(w_u, cols, vals, y, mask=None, precision="float32", dim=None):
    """The gradient of the window's mean logloss wrt the weights of its
    keys (:func:`keys` of ``cols``), at ``w_u``; ``mask`` flags the real
    rows (all of them where None).  ``dim``: the key space (the largest
    key and one more where None; the result is the same)."""
    u = keys(cols)
    dim = int(u[-1]) + 1 if dim is None else dim
    mask = np.ones(len(y), bool) if mask is None else np.asarray(mask)
    w = _spread(w_u, u, dim)
    g = jnp.zeros(dim, jnp.float32)
    for s in range(0, len(y), BLOCK_ROWS):
        e = s + BLOCK_ROWS
        g = g + reference_keyed_block_grad(
            w, jnp.asarray(cols[s:e]), jnp.asarray(vals[s:e]),
            jnp.asarray(y[s:e]), jnp.asarray(mask[s:e]), precision)
    n = jnp.float32(max(int(mask.sum()), 1))
    return np.asarray(g / n)[u]


def evaluate(w, cols, vals, y, precision="float32"):
    """``(accuracy, mean logloss)`` of the full weight vector ``w`` on the
    rows, in blocks of rows."""
    wj = jnp.asarray(w, jnp.float32)
    right, total = 0, 0.0
    for s in range(0, len(y), 1 << 20):
        e = s + (1 << 20)
        z = logits(wj, jnp.asarray(cols[s:e]), jnp.asarray(vals[s:e]),
                   precision)
        yb = jnp.asarray(y[s:e])
        right += int(jnp.sum((z > 0) == (yb > 0)))
        total += float(jnp.sum(logloss_terms(z, yb)))
    return right / len(y), total / len(y)


def step(w, cols, vals, y, lr, l2, precision="float32"):
    """One keyed push as the servers apply it: the window's loss before,
    the full vector after (``w[u] -= lr g_u``; every other weight as it
    was).  ``l2`` has to be 0: the server's rule has no such term."""
    w = jnp.asarray(w, jnp.float32)
    u = keys(cols)
    z = logits(w, jnp.asarray(cols), jnp.asarray(vals), precision)
    loss = jnp.sum(logloss_terms(z, jnp.asarray(y))) / jnp.float32(len(y))
    g_u = gradient(np.asarray(w)[u], cols, vals, y, precision=precision,
                   dim=w.shape[0])
    return loss, w.at[jnp.asarray(u)].add(-lr * (jnp.asarray(g_u) + l2 * w[u]))


def step_bytes_floor(*, rows: int, nnz: int, keys: int, dim: int = 0) -> float:
    """Bytes one keyed step cannot avoid moving through HBM: every place
    and value of the window once (``nnz`` entries of 8 bytes), the pulled
    weights read and the gradient written (4 bytes a key each), the
    labels read.  It leaves out what the program moves beyond that (the
    gathered weights and the products written out and read again, the
    mask), so a share of the roofline computed from it cannot pass 100%.
    ``dim``, the key space, moves nothing: a step touches its keys."""
    del dim
    return nnz * 8 + 2 * keys * 4 + rows * 4
