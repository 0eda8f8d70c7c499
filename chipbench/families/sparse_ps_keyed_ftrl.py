"""Family ``sparse_ps_keyed_ftrl``: the keyed sparse-LR round of
``sparse_ps_keyed`` (Li et al., OSDI 2014, section 5.1) under the rule
its users run on the servers: per-coordinate FTRL-Proximal with L1
(McMahan, Holt, Sculley, Young et al., "Ad Click Prediction: a View from
the Trenches", KDD 2013, Algorithm 1).

**The worker's side** is this file's own copy of the float32 sparse-LR
gradient of a window at the weights pulled (which rows: :func:`window`;
which keys: :func:`keys`; the gradient: :func:`gradient`), a full-D
segment sum restricted to the keys at the end, in blocks of rows,
``jax.default_matmul_precision("highest")`` stated round every block
(there is no product the TPU would lower, and none may appear
unnoticed).  It imports nothing of a sibling family and nothing of the
program.  ``precision`` other than float32 is the control.

**The servers' side** is Algorithm 1 written from the paper in plain
float32 ``numpy``.  For coordinate i and a gradient entry g != 0, in
this order, every operation rounded to float32:

    sigma = (sqrt(n + g^2) - sqrt(n)) / alpha
    z    <- z + g - sigma * w
    n    <- n + g^2
    w    <- 0                                            if |z| <= l1
            -(z - sgn(z) * l1) / ((beta + sqrt(n)) / alpha + l2)   else

(:func:`ftrl_step`, over the entries of one push; :func:`closed_form`,
W(z, n), the last line alone; :func:`replay`, an ordered list of keyed
pushes over a table).  An entry g = 0 changes nothing.

Where this departs from the paper, and the program with it:

* the paper's loop steps one **example** at a time, g_i = (p_t - y_t)
  x_i; here an entry is a coordinate of the **mean** gradient of a
  window of rows (the keyed step divides by the window's real rows), so
  alpha and l1 are on the mean's scale;
* the paper recomputes w_{t,i} from (z_i, n_i) lazily, when an example
  next reads it, and stores no w; here w is stored and rewritten by the
  step that moves z (the same number: W(z, n) of the state after the
  step), so a pull reads it without arithmetic;
* the paper's Algorithm 1 steps only the coordinates with x_i != 0, and
  says nothing of an entry whose gradient is 0 with x_i != 0 (p_t = y_t
  to the last bit); here such an entry steps nothing, as an untouched
  coordinate;
* the paper does not say how ``z + g - sigma * w`` associates in
  floating point; here the increment ``g - sigma * w`` is formed first
  and added to z once (one rounding of z a step);
* sgn(0) never matters: z = 0 is inside |z| <= l1 for every l1 >= 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import logloss_terms, lower

BLOCK_ROWS = 4096
F32 = np.float32


# -- the worker's side: rows, keys, gradient ----------------------------
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


# names of their own: neither a trace nor the compile cache can take
# them for the program's, or for the sibling family's
@functools.partial(jax.jit, static_argnames=("precision",))
def reference_ftrl_keyed_logits(w, cols, vals, precision="float32"):
    with jax.default_matmul_precision("highest"):
        prod = lower(lower(w, precision)[cols] * vals, precision)
        return jnp.sum(prod, axis=-1)


@functools.partial(jax.jit, static_argnames=("precision",))
def reference_ftrl_keyed_block_grad(w, cols, vals, y, mask,
                                    precision="float32"):
    """One block of rows: its part of the full-D segment sum, not yet
    divided by the window's count of real rows."""
    with jax.default_matmul_precision("highest"):
        z = reference_ftrl_keyed_logits(w, cols, vals, precision)
        resid = lower((jax.nn.sigmoid(z) - y.astype(jnp.float32))
                      * mask.astype(jnp.float32), precision)
        contrib = lower(resid[:, None] * vals, precision).reshape(-1)
        return jax.ops.segment_sum(contrib, cols.reshape(-1),
                                   num_segments=w.shape[0])


logits = reference_ftrl_keyed_logits


def gradient(w_u, cols, vals, y, mask=None, precision="float32", dim=None):
    """The gradient of the window's mean logloss wrt the weights of its
    keys (:func:`keys` of ``cols``), at ``w_u``; ``mask`` flags the real
    rows (all of them where None).  ``dim``: the key space (the largest
    key and one more where None; the result is the same)."""
    u = keys(cols)
    dim = int(u[-1]) + 1 if dim is None else dim
    mask = np.ones(len(y), bool) if mask is None else np.asarray(mask)
    full = np.zeros(dim, F32)
    full[u] = w_u
    w = jnp.asarray(full)
    g = jnp.zeros(dim, jnp.float32)
    for s in range(0, len(y), BLOCK_ROWS):
        e = s + BLOCK_ROWS
        g = g + reference_ftrl_keyed_block_grad(
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


# -- the servers' side: Algorithm 1 in float32 numpy ---------------------
def closed_form(z, n, *, alpha, beta, l1, l2) -> np.ndarray:
    """W(z, n): the weight Algorithm 1 holds for the state (z, n), in
    float32; exactly 0.0 where ``|z| <= l1`` (a coordinate never stepped
    has z = n = 0 and is among them)."""
    z, n = np.asarray(z, F32), np.asarray(n, F32)
    a, b, r1, r2 = F32(alpha), F32(beta), F32(l1), F32(l2)
    sgn = np.where(z > 0, F32(1.0), F32(-1.0))
    w = -(z - sgn * r1) / ((b + np.sqrt(n)) / a + r2)
    return np.where(np.abs(z) <= r1, F32(0.0), w).astype(F32)


def ftrl_step(w, z, n, g, *, alpha, beta, l1, l2):
    """One push's entries ``g`` on the coordinates whose state is ``(w,
    z, n)`` (arrays of one length, one entry a coordinate: a push names a
    key once).  Returns the three after; an entry ``g == 0`` leaves its
    coordinate as it was, in every bit."""
    w, z, n, g = (np.asarray(a, F32) for a in (w, z, n, g))
    n_new = n + g * g
    sigma = (np.sqrt(n_new) - np.sqrt(n)) / F32(alpha)
    z_new = z + (g - sigma * w)
    w_new = closed_form(z_new, n_new, alpha=alpha, beta=beta, l1=l1, l2=l2)
    stepped = g != 0
    return (np.where(stepped, w_new, w), np.where(stepped, z_new, z),
            np.where(stepped, n_new, n))


def replay(pushes, w, z, n, *, alpha, beta, l1, l2):
    """``pushes``, an ordered list of ``(keys, g)``, applied one at a
    time to the tables ``(w, z, n)`` (full vectors, not changed).
    Returns the tables after the last push and, for each push, the
    weights of its keys as they stood BEFORE it: what a pull of those
    keys returns with every earlier push applied and no later one."""
    w, z, n = (np.array(a, F32) for a in (w, z, n))
    before = []
    for u, g in pushes:
        at = np.asarray(u).astype(np.int64)
        before.append(w[at].copy())
        w[at], z[at], n[at] = ftrl_step(w[at], z[at], n[at], g, alpha=alpha,
                                        beta=beta, l1=l1, l2=l2)
    return (w, z, n), before


def step(w, cols, vals, y, lr, l2, precision="float32", *, state=None,
         alpha=0.1, beta=1.0, l1=0.0):
    """One keyed push as the servers apply it, for
    ``reference.follow_steps``: the window's loss before, the full vector
    after.  ``state``: the tables ``(z, n)``, changed in place (zeros,
    Algorithm 1's start, where None: then the step is the first of every
    key); ``lr`` is no part of the rule and ``l2`` is its lambda_2."""
    del lr
    w = np.asarray(w, F32)
    u = keys(cols)
    zl = logits(jnp.asarray(w), jnp.asarray(cols), jnp.asarray(vals),
                precision)
    loss = jnp.sum(logloss_terms(zl, jnp.asarray(y))) / jnp.float32(len(y))
    g_u = gradient(w[u], cols, vals, y, precision=precision, dim=w.shape[0])
    z, n = state if state is not None else (np.zeros_like(w),
                                            np.zeros_like(w))
    after = w.copy()
    after[u], z[u], n[u] = ftrl_step(w[u], z[u], n[u], g_u, alpha=alpha,
                                     beta=beta, l1=l1, l2=float(l2))
    return loss, jnp.asarray(after)


def step_bytes_floor(*, rows: int, nnz: int, keys: int, dim: int = 0) -> float:
    """Bytes one keyed step cannot avoid moving through HBM: every place
    and value of the window once (``nnz`` entries of 8 bytes), the pulled
    weights read and the gradient written (4 bytes a key each), the
    labels read; the worker's step is ``sparse_ps_keyed``'s, so the floor
    is, written out again.  It leaves out what the program moves beyond
    that, so a share of the roofline computed from it cannot pass 100%.
    ``dim``, the key space, moves nothing: a step touches its keys, and
    the rule's state (z, n) never leaves the servers."""
    del dim
    return nnz * 8 + 2 * keys * 4 + rows * 4
