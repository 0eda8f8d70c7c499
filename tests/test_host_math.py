"""``models/host_math.py`` against ``models/linear.py``: each model's NumPy
gradient and eval on the host are its JAX ones, on seeded rows.  The keyed
twins differentiate wrt a batch's UNIQUE rows and decay lazily (the rows a
batch really touches, by a nonzero value), so their L2 term is held
against the model's data gradient plus that rule."""

import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.models import get_model, host_math

D, K, B, NNZ, R, G = 40, 4, 24, 5, 8, 3
TOL = dict(rtol=2e-5, atol=2e-6)


def _cfg(model, l2_c, by_batch):
    blocked = dict(block_size=R, block_groups=G) if model == "blocked_lr" else {}
    return Config(model=model, num_feature_dim=D, num_classes=K,
                  compute_dtype="float32", l2_c=l2_c,
                  l2_scale_by_batch=by_batch, **blocked)


def _mask():
    mask = np.ones(B, bool)
    mask[-3:] = False   # a short last batch: padded and masked
    return mask


def _dense(rng, multiclass):
    X = rng.standard_normal((B, D)).astype(np.float32)
    y = rng.integers(0, K if multiclass else 2, B)
    return X, y, _mask()


def _coo(rng, multiclass):
    cols = rng.integers(0, D, (B, NNZ))
    vals = rng.standard_normal((B, NNZ)).astype(np.float32)
    cols[:, -1], vals[:, -1] = 0, 0.0   # COO padding: col 0, val 0
    return cols, vals, rng.integers(0, K if multiclass else 2, B), _mask()


def _blocks(rng):
    blocks = rng.integers(0, D // R, (B, G))
    lanes = rng.standard_normal((B, G, R)).astype(np.float32)
    lanes[:, -1] = 0.0                  # a padded group: all-zero lanes
    return blocks, lanes, rng.integers(0, 2, B), _mask()


L2 = pytest.mark.parametrize("l2_c,by_batch", [
    (0.0, False), (0.3, False), (0.3, True)],
    ids=["no-l2", "l2", "l2-over-batch"])


@L2
@pytest.mark.parametrize("model", ["binary_lr", "softmax"])
def test_the_dense_twins_are_the_models(model, l2_c, by_batch):
    rng = np.random.default_rng(17)
    cfg = _cfg(model, l2_c, by_batch)
    multi = K if model == "softmax" else None
    X, y, mask = _dense(rng, multi)
    w = rng.standard_normal((D, K) if multi else D).astype(np.float32) * 0.3
    m = get_model(cfg)
    got = host_math.dense_grad(w, X, y, mask, l2_c, by_batch, multi)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, np.asarray(m.grad(w, (X, y, mask), cfg)),
                               **TOL)
    acc, ll = host_math.dense_eval(w, X, y, mask, multi)
    want = m.eval_from_logits(m.logits(w, X), y, mask)
    np.testing.assert_allclose((acc, ll), [float(v) for v in want], rtol=1e-5)
    np.testing.assert_allclose(ll, float(m.logloss(w, (X, y, mask))),
                               rtol=1e-5)


def _lazy_l2(w, touched, n, l2_c, by_batch):
    """The keyed plane's decay: the touched rows alone, as units."""
    term = l2_c * w * touched.reshape((-1,) + (1,) * (w.ndim - 1))
    return term / n if by_batch else term


@L2
@pytest.mark.parametrize("model", ["sparse_lr", "sparse_softmax",
                                   "blocked_lr"])
def test_the_keyed_twins_are_the_models_over_the_touched_rows(model, l2_c,
                                                              by_batch):
    rng = np.random.default_rng(23)
    cfg = _cfg(model, l2_c, by_batch)
    m = get_model(cfg)
    if model == "blocked_lr":
        ids, vals, y, mask = batch = _blocks(rng)
        w = rng.standard_normal((D // R, R)).astype(np.float32) * 0.3
        real = (vals != 0).any(axis=-1)
        twin = host_math.blocked_batch_grad
    else:
        ids, vals, y, mask = batch = _coo(rng, model == "sparse_softmax")
        shape = (D, K) if model == "sparse_softmax" else (D,)
        w = rng.standard_normal(shape).astype(np.float32) * 0.3
        real = vals != 0
        twin = (host_math.sparse_softmax_batch_grad
                if model == "sparse_softmax" else host_math.sparse_batch_grad)
    ub, pos = np.unique(ids, return_inverse=True)
    g_u = twin(w[ub], pos.reshape(ids.shape), vals, y, mask, l2_c, by_batch)
    assert g_u.dtype == np.float32 and g_u.shape == w[ub].shape
    got = np.zeros_like(w)
    got[ub] = g_u
    # the model's data gradient, then the lazy rule in the model's place
    touched = np.zeros(len(w), bool)
    touched[ids[real]] = True
    want = np.asarray(m.grad(w, batch, cfg.replace(l2_c=0.0)))
    want = want + _lazy_l2(w, touched, max(mask.sum(), 1), l2_c, by_batch)
    np.testing.assert_allclose(got, want, **TOL)
    # a row no batch entry touches has no gradient, decay included
    assert not got[~np.isin(np.arange(len(w)), ub)].any()
    # the eval's two numbers off one forward pass
    z = np.asarray(m.logits(w, ids, vals))
    acc, ll = (host_math.softmax_eval_from_logits if model == "sparse_softmax"
               else host_math.binary_eval_from_logits)(z, y, mask)
    np.testing.assert_allclose(
        (acc, ll), (float(m.accuracy(w, batch)), float(m.logloss(w, batch))),
        rtol=1e-5)


def test_expanded_keys_are_a_rows_lanes_in_row_major_order():
    keys = host_math.expand_block_keys(np.array([0, 2, 5]), 4)
    assert keys.dtype == np.uint64
    assert keys.tolist() == [0, 1, 2, 3, 8, 9, 10, 11, 20, 21, 22, 23]
