"""Seeded Criteo-shaped rows, hashed into one bucket space.

One generator for every configuration: ``fields/<name>.json`` gives the
cardinalities, the configuration's file gives the bucket count and the
row counts, ``--seed`` gives everything else.  A row holds one id per
field, drawn log-uniformly (``floor(V**u)``), hashed together with its
field into ``num_buckets`` buckets, value 1; two ids of a row that land
in one bucket are merged into one entry with the summed value.  Labels
come from a seeded logistic model in bucket space.

Rows come back as padded COO, columns ascending: ``cols`` and ``vals``
of shape ``(n, F)`` with pad column 0 and pad value 0, which is both the
layout the sparse model trains on and, through :func:`write_libsvm`,
what the reference-layout text shards hold.
"""

from __future__ import annotations

import concurrent.futures
import json
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHUNK = 1 << 16   # rows per seeded stream, one thread each
_BLOCK = 1 << 13   # rows per arithmetic block inside a chunk
_THREADS = 4
_MIX = np.uint64(0xBF58476D1CE4E5B9)
_GOLD = np.uint64(0x9E3779B97F4A7C15)


def field_cardinalities(name: str) -> np.ndarray:
    with open(os.path.join(_HERE, "fields", f"{name}.json")) as f:
        doc = json.load(f)
    return np.asarray(
        list(doc["categorical"])
        + [doc["integer_buckets"]] * doc["integer_fields"], np.int64)


def true_weights(seed: int, num_buckets: int, scale: float) -> np.ndarray:
    """The labelling model: one float32 weight per bucket."""
    rng = np.random.default_rng([int(seed), 0x7E57])
    return (rng.standard_normal(num_buckets, np.float32)
            * np.float32(scale))


def _block(rng, n, lay, w_true, bias):
    """One block of rows.  Blocks are small so that every temporary stays
    in the cache: the same arithmetic on 2**18 rows at once runs an order
    of magnitude slower."""
    u = rng.random((n, lay["F"]), np.float32)
    u *= lay["ln_card"]
    np.exp(u, out=u)
    ids = u.astype(np.int64)
    np.minimum(ids, lay["card_m1"], out=ids)
    h = ids.view(np.uint64)
    h += lay["field_salt"]
    h *= _MIX
    h ^= h >> np.uint64(31)
    h *= _GOLD
    h >>= np.uint64(32)          # the top 32 bits, scaled into
    h *= lay["num_buckets"]      # [0, num_buckets): no division
    h >>= np.uint64(32)
    cols = h.astype(np.int32)
    cols.sort(axis=1)
    vals = np.ones(cols.shape, np.float32)
    dup_rows = np.nonzero((cols[:, 1:] == cols[:, :-1]).any(axis=1))[0]
    for r in dup_rows:  # a fraction of a percent of the rows
        c, cnt = np.unique(cols[r], return_counts=True)
        cols[r] = 0
        vals[r] = 0.0
        cols[r, :len(c)] = c
        vals[r, :len(c)] = cnt
    margin = (w_true[cols] * vals).sum(axis=1) + np.float32(bias)
    p = 1.0 / (1.0 + np.exp(-margin))
    y = (rng.random(n, np.float32) < p).astype(np.int32)
    return cols, vals, y


def make_rows(seed: int, split: str, n: int, *, fields: str,
              num_buckets: int, label_scale: float, label_bias: float):
    """``(cols, vals, y)`` for ``n`` rows of ``split`` ("train"/"test").

    Generated in fixed chunks on a few threads; the result depends on
    the seed, the split and the row count only."""
    card = field_cardinalities(fields)
    w_true = true_weights(seed, num_buckets, label_scale)
    split_id = {"train": 1, "test": 2}[split]
    sizes = [min(_CHUNK, n - s) for s in range(0, n, _CHUNK)]
    cols = np.empty((n, len(card)), np.int32)
    vals = np.empty((n, len(card)), np.float32)
    y = np.empty(n, np.int32)

    lay = {
        "F": len(card),
        "ln_card": np.log(card).astype(np.float32),
        "card_m1": card - 1,
        "field_salt": np.arange(1, len(card) + 1, dtype=np.uint64) * _GOLD,
        "num_buckets": np.uint64(num_buckets),
    }

    def work(k):
        rng = np.random.default_rng([int(seed), split_id, k])
        s, e = k * _CHUNK, k * _CHUNK + sizes[k]
        for b in range(s, e, _BLOCK):
            m = min(_BLOCK, e - b)
            cols[b:b + m], vals[b:b + m], y[b:b + m] = _block(
                rng, m, lay, w_true, label_bias)

    with concurrent.futures.ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(work, range(len(sizes))))
    return cols, vals, y


def write_libsvm(path: str, cols: np.ndarray, vals: np.ndarray,
                 y: np.ndarray) -> None:
    """Reference-layout text: ``label idx:val ...`` with 1-based indices,
    pads left out."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    idx = (cols + 1).tolist()
    val = vals.astype(np.int64).tolist()
    with open(path, "w") as f:
        for s in range(0, len(idx), 8192):
            f.write("".join(
                f"{lab} " + " ".join(
                    [f"{c}:{v}" for c, v in zip(ci, vi) if v]) + "\n"
                for lab, ci, vi in zip(y[s:s + 8192].tolist(),
                                       idx[s:s + 8192], val[s:s + 8192])))
