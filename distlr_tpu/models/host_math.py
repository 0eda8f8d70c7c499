"""NumPy twins of :mod:`distlr_tpu.models.linear`, for the host.

The gradient and the eval of every model as plain float32 numpy/BLAS: the
only gradient the keyed PS plane has (a batch's unique-key count varies,
so a jitted step would recompile every round), the dense step below the
size where jax dispatch dominates (``train.ps_trainer.ps_compute_device``)
and what the online trainer computes (``feedback.online``).  Quirk gates
(Q4 L2/B) are the models' own; ``tests/test_host_math.py`` holds each twin
against its model on seeded rows.
"""

from __future__ import annotations

import numpy as np


def dense_grad(w, X, y, mask, l2_c, l2_scale_by_batch, num_classes=None):
    """f32 numpy mirror of BinaryLR.grad / SoftmaxRegression.grad
    (models/linear.py) for the tiny-step regime where jax dispatch
    dominates; quirk gates (Q4 L2/B) identical."""
    y = np.asarray(y)
    mask = np.asarray(mask, np.float32)
    n = np.float32(max(mask.sum(), 1.0))
    if num_classes is None:
        z = X @ w
        sig = (0.5 * (1.0 + np.tanh(0.5 * z))).astype(np.float32)
        resid = (sig - y.astype(np.float32)) * mask
        g = resid @ X / n
    else:
        z = X @ w  # (B, K)
        z -= z.max(axis=1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(axis=1, keepdims=True)
        p[np.arange(len(y)), y] -= 1.0
        g = X.T @ (p * mask[:, None]) / n
    if l2_c:
        term = np.float32(l2_c) * w
        g = g + (term / n if l2_scale_by_batch else term)
    return np.asarray(g, dtype=np.float32)


def binary_eval_from_logits(z, y, mask) -> tuple[float, float]:
    """(accuracy, logloss) of binary logits — THE masked-mean definition,
    shared by the numpy dense eval and the keyed (sparse/blocked) evals
    so the metrics cannot silently diverge."""
    z = np.asarray(z, np.float64)
    m = np.asarray(mask, np.float64)
    n = max(m.sum(), 1.0)
    acc = float((((z > 0).astype(np.int64) == y) * m).sum() / n)
    ll = float(((np.logaddexp(0.0, z) - y * z) * m).sum() / n)
    return acc, ll


def softmax_eval_from_logits(z, y, mask) -> tuple[float, float]:
    """(accuracy, cross-entropy) of ``(B, K)`` class logits: the same
    masked mean, for the dense and the keyed multiclass evals."""
    z = np.asarray(z, np.float64)
    m = np.asarray(mask, np.float64)
    n = max(m.sum(), 1.0)
    acc = float(((z.argmax(axis=1) == y) * m).sum() / n)
    zs = z - z.max(axis=1, keepdims=True)
    ll = np.log(np.exp(zs).sum(axis=1)) - zs[np.arange(len(y)), y]
    return acc, float((ll * m).sum() / n)


def dense_eval(w, X, y, mask, num_classes=None):
    """f32 numpy ``(accuracy, logloss)`` for the dense models — one
    forward pass, no jax dispatch."""
    z = X @ w
    if num_classes is None:
        return binary_eval_from_logits(z, y, mask)
    return softmax_eval_from_logits(z, y, mask)


def sparse_batch_grad(w_u, pos, vals, y, mask, l2_c, l2_scale_by_batch):
    """Gradient of the sparse one-hot LR loss wrt the batch's UNIQUE
    touched weights (numpy, host-side).

    Mirrors ``SparseBinaryLR.grad`` (models/linear.py) restricted to the
    touched key set: ``w_u`` are the pulled weights for the batch's unique
    columns, ``pos`` maps each (row, slot) to its index in ``w_u``.  The
    scatter is ``np.bincount`` (vectorized C): the step of every keyed
    batch that is streamed from the host, and of the small ones, where
    jit dispatch would dominate.  A worker that keeps its shard on its
    step's device runs the same arithmetic there as one compiled
    program, the pulled vector padded to one key count for the whole
    shard so that no window compiles (``ps_trainer``'s
    ``jit_ps_keyed_grad_step``).

    L2 is applied *lazily* (only the touched coordinates, like every
    sparse parameter server): with ``l2_c > 0`` the effective decay per
    weight scales with how often it is touched, unlike the dense path's
    every-step decay — callers comparing against the dense trainer should
    set ``l2_c = 0`` or account for touch frequency.
    """
    z = (w_u[pos] * vals).sum(axis=-1)
    sig = 0.5 * (1.0 + np.tanh(0.5 * z))  # overflow-stable sigmoid
    n = np.float32(max(mask.sum(), 1))
    resid = ((sig - y) * mask).astype(np.float32)
    contrib = (resid[:, None] * vals).ravel() / n
    g = np.bincount(pos.ravel(), weights=contrib, minlength=len(w_u)).astype(np.float32)
    if l2_c:
        # Decay only genuinely-active keys: COO padding (col 0, val 0)
        # puts key 0 in EVERY batch's unique set, which would give bucket
        # 0 dense-style every-step decay while real features decay per
        # touch.
        active = np.bincount(pos.ravel(), weights=(vals != 0).ravel().astype(np.float32),
                             minlength=len(w_u)) > 0
        term = np.float32(l2_c) * w_u * active
        g += term / n if l2_scale_by_batch else term
    return g


def localise(cols, dim: int, shift: int = 0):
    """``np.unique(cols, return_inverse=True)`` for column ids below
    ``dim``: the sorted unique columns (int64) and, in the ids' stead,
    each entry's place among them (int32, ``cols``' shape; ``shift``
    bits to the left where the caller packs something under it, which
    the table then holds and no pass over the entries makes).  By a table
    of ``dim`` slots and no sort: 7 ms where the sort takes 94 for a
    window of 16,384 x 39 entries over a million columns (PERF.md
    section 6, PR 51); a key space this plane serves is one its servers
    hold whole, so the table is never the larger."""
    seen = np.zeros(dim, bool)
    seen[cols.reshape(-1)] = True
    keys = np.flatnonzero(seen)
    place = np.empty(dim, np.int32)
    place[keys] = np.arange(len(keys), dtype=np.int32) << shift
    return keys, place[cols]


def sparse_softmax_batch_grad(W_u, pos, vals, y, mask, l2_c,
                              l2_scale_by_batch):
    """Gradient of the sparse softmax loss wrt the batch's UNIQUE touched
    (D, K) table rows (numpy, host-side).

    Mirrors ``SparseSoftmaxRegression.grad`` (models/linear.py)
    restricted to the touched row set: ``W_u`` is the ``(n_u, K)``
    pulled slice, ``pos`` maps each (sample, slot) to its row.  Lazy L2
    at ROW granularity with the same active-key discount as the binary
    sparse path (COO padding aliases row 0 in every batch)."""
    z = (W_u[pos] * vals[..., None]).sum(axis=1)      # (B, K)
    z -= z.max(axis=1, keepdims=True)
    p = np.exp(z, dtype=np.float32)
    p /= p.sum(axis=1, keepdims=True)
    p[np.arange(len(y)), y] -= 1.0
    n = np.float32(max(mask.sum(), 1))
    resid = p * np.asarray(mask, np.float32)[:, None]  # (B, K)
    contrib = (vals[..., None] * resid[:, None, :]).reshape(
        -1, W_u.shape[1]) / n                          # (B*F, K)
    g = np.zeros_like(W_u, dtype=np.float32)
    np.add.at(g, pos.ravel(), contrib)
    if l2_c:
        active = np.bincount(
            pos.ravel(), weights=(vals != 0).ravel().astype(np.float32),
            minlength=len(W_u)) > 0
        term = np.float32(l2_c) * W_u * active[:, None]
        g += term / n if l2_scale_by_batch else term
    return g


def expand_block_keys(blocks: np.ndarray, block_size: int) -> np.ndarray:
    """Unique block-row ids -> their flat KV keys (row b owns the
    contiguous range ``[b*R, (b+1)*R)`` of the ``ps_param_dim`` key
    space — the row-major layout of the (num_blocks, R) table)."""
    r = np.arange(block_size, dtype=np.uint64)
    return (blocks.astype(np.uint64)[:, None] * np.uint64(block_size) + r).reshape(-1)


def blocked_batch_grad(t_u, pos, lane_vals, y, mask, l2_c, l2_scale_by_batch):
    """Gradient of the blocked LR loss wrt the batch's UNIQUE touched
    table rows (numpy, host-side).

    Mirrors ``BlockedSparseLR.grad`` (models/linear.py) restricted to the
    touched row set: ``t_u`` is the ``(n_u, R)`` pulled slice, ``pos``
    maps each (sample, group) to its row in ``t_u``.  Like the sparse
    path, L2 is applied lazily — and at ROW granularity: a gathered row
    decays as a unit (all R lanes), because the row is the parameter unit
    of this model (one conjunction's weights).
    """
    z = (t_u[pos] * lane_vals).sum(axis=(-1, -2))
    sig = 0.5 * (1.0 + np.tanh(0.5 * z))  # overflow-stable sigmoid
    n = np.float32(max(mask.sum(), 1))
    resid = ((sig - y) * mask).astype(np.float32)
    contrib = (resid[:, None, None] * lane_vals).reshape(-1, t_u.shape[1]) / n
    g = np.zeros_like(t_u, dtype=np.float32)
    np.add.at(g, pos.reshape(-1), contrib)
    if l2_c:
        # Padded groups (all-zero lanes) alias row pos of block id 0's
        # slot; only rows gathered with a real (nonzero) lane decay.
        touched = (lane_vals != 0).any(axis=-1).reshape(-1)
        active = np.zeros(len(t_u), bool)
        np.logical_or.at(active, pos.reshape(-1), touched)
        term = np.float32(l2_c) * t_u * active[:, None]
        g += term / n if l2_scale_by_batch else term
    return g
