"""Family ``sparse``: logistic regression over padded-COO one-hot rows,
a gather for the logits and a segment sum for the gradient.

    loss  = mean(softplus(z) - y z) + l2/2 |w|^2,   z = sum_f w[cols_f] vals_f
    grad  = segment_sum((sigmoid(z) - y) vals, cols) / n + l2 w
    w'    = w - lr grad

Rows are ``cols``, ``vals`` of shape ``(n, F)``, pad column 0 with pad
value 0, as the generator makes them.  ``precision`` other than float32
is the control: weights, gathers, products and residuals as that
precision would hold them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference import logloss_terms, lower


# the jitted programs carry names of their own, so that neither a trace
# nor the compile cache can take them for the program's step and eval
@functools.partial(jax.jit, static_argnames=("precision",))
def reference_sparse_logits(w, cols, vals, precision="float32"):
    prod = lower(lower(w, precision)[cols] * vals, precision)
    return jnp.sum(prod, axis=-1)


@functools.partial(jax.jit, static_argnames=("precision",))
def reference_sparse_step(w, cols, vals, y, lr, l2, precision="float32"):
    """One SGD step: the loss before the update, the weights after it."""
    z = reference_sparse_logits(w, cols, vals, precision)
    n = jnp.float32(y.shape[0])
    loss = jnp.sum(logloss_terms(z, y)) / n + 0.5 * l2 * jnp.sum(w * w)
    resid = lower(jax.nn.sigmoid(z) - y.astype(jnp.float32), precision)
    contrib = lower(resid[:, None] * vals, precision).reshape(-1)
    g = jax.ops.segment_sum(contrib, cols.reshape(-1),
                            num_segments=w.shape[0]) / n + l2 * w
    return loss, w - lr * g


logits, step = reference_sparse_logits, reference_sparse_step


def step_bytes_floor(*, rows: int, dim: int, nnz: int) -> float:
    """Bytes one step cannot avoid moving through HBM on one device:
    every column index and value once (``nnz`` entries of 8 bytes) and
    the float32 weights read and written.  It leaves out what the program
    moves beyond that, so a share of the roofline computed from it cannot
    pass 100%."""
    del rows
    return nnz * 8 + 2 * dim * 4
