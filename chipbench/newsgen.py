"""Seeded news20-shaped rows: bag-of-words documents over a vocabulary,
tf-idf values, unit length, one of K topic labels.

The generator of ``news20-ps-async-softmax`` (``datagen.py`` is criteo's:
one id a field, value 1, binary labels).  A row is a document of
``nnz`` distinct words.  Words are drawn from a Zipf law over the
vocabulary's ranks, ``p(r) ~ 1 / (r + ZIPF_SHIFT)``, and a rank's column
is a seeded permutation's (a data set's word ids are in no order of
frequency).  A word's value is a term frequency (1 + a geometric
count, damped by a logarithm) times its inverse document frequency
``log(1 / p(r))`` up to a constant, so the head of the law weighs
least, as tf-idf has it; then the row is scaled to unit Euclidean
length, the form the LIBSVM collection distributes.  Labels come from a
seeded K-class softmax model in feature space, one draw a row: zero-mean
class weights and no bias, so the classes are near balanced.

Rows come back as padded COO, columns ascending: ``cols`` int32 and
``vals`` float32 of shape ``(n, nnz)`` (no pads: every row has ``nnz``
words), ``y`` int32 class ids.  :func:`write_libsvm` writes the
reference-layout text a worker's loader reads, the class id as the
label, each value with the nine significant digits that bring a float32
back bit for bit.
"""

from __future__ import annotations

import os

import numpy as np

ZIPF_SHIFT = 20.0
LABEL_SCALE = 6.0
_BLOCK = 2048   # rows a block: the candidate draws and the logits fit a cache
_DRAWS = 3      # candidates drawn a word wanted, before the distinct are kept


def word_law(vocab: int) -> np.ndarray:
    """``p(r)`` of the ranks ``0 .. vocab-1``."""
    p = 1.0 / (np.arange(vocab, dtype=np.float64) + ZIPF_SHIFT)
    return p / p.sum()


def true_weights(seed: int, vocab: int, classes: int) -> np.ndarray:
    """The labelling model: ``float32[vocab, classes]``."""
    rng = np.random.default_rng([int(seed), 0x2E75])
    return (rng.standard_normal((vocab, classes), np.float32)
            * np.float32(LABEL_SCALE))


def _draw(rng, cdf, shape):
    """Ranks drawn from the law whose cumulative sums are ``cdf``."""
    return np.minimum(np.searchsorted(cdf, rng.random(shape), side="right"),
                      len(cdf) - 1)


def _block(rng, n, nnz, cdf, idf, column_of, w_true):
    # more candidates than words wanted; a row keeps the first ``nnz``
    # distinct ranks it drew, in the order drawn
    ranks = _draw(rng, cdf, (n, _DRAWS * nnz))
    kept = np.empty((n, nnz), np.int64)
    for i in range(n):
        row = ranks[i]
        while True:
            uniq, at = np.unique(row, return_index=True)
            if len(uniq) >= nnz:
                break
            row = np.concatenate([row, _draw(rng, cdf, nnz)])
        kept[i] = row[np.sort(at)[:nnz]]
    tf = 1.0 + np.log(rng.geometric(0.6, (n, nnz)).astype(np.float64))
    vals = tf * idf[kept]
    cols = column_of[kept]
    order = np.argsort(cols, axis=1)
    cols = np.take_along_axis(cols, order, 1).astype(np.int32)
    vals = np.take_along_axis(vals, order, 1)
    vals = (vals / np.linalg.norm(vals, axis=1, keepdims=True)).astype(
        np.float32)
    z = np.einsum("nf,nfk->nk", vals, w_true[cols])
    y = np.argmax(z + rng.gumbel(size=z.shape), axis=1).astype(np.int32)
    return cols, vals, y


def make_rows(seed: int, split: str, n: int, *, vocab: int, classes: int,
              nnz: int):
    """``(cols, vals, y)`` for ``n`` rows of ``split`` ("train"/"test").
    The result depends on the seed, the split and the sizes only."""
    law = word_law(vocab)
    cdf = np.cumsum(law)
    idf = np.log(1.0 / law) - np.log(1.0 / law[0]) + 1.0
    column_of = np.random.default_rng([int(seed), 0xC015]).permutation(vocab)
    w_true = true_weights(seed, vocab, classes)
    split_id = {"train": 1, "test": 2}[split]
    cols = np.empty((n, nnz), np.int32)
    vals = np.empty((n, nnz), np.float32)
    y = np.empty(n, np.int32)
    for k, s in enumerate(range(0, n, _BLOCK)):
        rng = np.random.default_rng([int(seed), split_id, k])
        m = min(_BLOCK, n - s)
        cols[s:s + m], vals[s:s + m], y[s:s + m] = _block(
            rng, m, nnz, cdf, idf, column_of, w_true)
    return cols, vals, y


def write_libsvm(path: str, cols: np.ndarray, vals: np.ndarray,
                 y: np.ndarray) -> None:
    """Reference-layout text: ``label idx:val ...`` with 1-based indices
    and the class id as the label."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for s in range(0, len(y), 2048):
            f.write("".join(
                f"{lab} " + " ".join(
                    f"{c}:{v:.9g}" for c, v in zip(ci, vi)) + "\n"
                for lab, ci, vi in zip(y[s:s + 2048].tolist(),
                                       (cols[s:s + 2048] + 1).tolist(),
                                       vals[s:s + 2048].tolist())))
