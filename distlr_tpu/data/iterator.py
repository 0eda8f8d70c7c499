"""Epoch-based minibatch iterator over an in-memory shard.

TPU-native re-design of the reference ``distlr::DataIter``
(``include/data_iter.h:16-59``): one constructed iterator serves exactly
one pass (epoch) over its shard; ``batch_size=-1`` means the whole shard
(``data_iter.h:39-43``).  Differences, all deliberate:

* **Static shapes.** XLA compiles one program per distinct batch shape, so
  the final short batch is *padded* to ``batch_size`` and a boolean mask is
  returned — instead of the reference's Q5 wraparound quirk (which silently
  duplicates head samples into the last batch, ``data_iter.h:46-53``).
  ``drop_remainder=True`` gives the classic drop-last behavior; and
  ``wrap_compat=True`` reproduces Q5 exactly for parity experiments.
* Data lives in numpy on host; the training loop moves batches to device
  (``jax.device_put`` / sharding-aware placement in the trainer).
* Optional per-epoch shuffling (the reference never shuffles inside an
  epoch; it reshuffles only by re-running gen_data.py).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Window(NamedTuple):
    """A batch as rows ``[first, first + batch_size)`` of the shard in
    the order the iterator holds it, of which the leading ``rows`` are
    real: fewer than ``batch_size`` in a shard's last, short batch, whose
    window runs past the shard's end (whoever holds the rows keeps
    masked pad rows there)."""

    first: int
    rows: int


class DataIter:
    """One-epoch minibatch iterator with static batch shapes.

    Yields ``(X, y, mask)`` where mask flags real (non-padding) rows.
    """

    def __init__(
        self,
        X: np.ndarray,
        y: np.ndarray,
        batch_size: int = -1,
        *,
        shuffle: bool = False,
        seed: int = 0,
        drop_remainder: bool = False,
        wrap_compat: bool = False,
    ):
        self.X = np.asarray(X)
        self.y = np.asarray(y)
        if self.X.shape[0] != self.y.shape[0]:
            raise ValueError(f"X has {self.X.shape[0]} rows but y has {self.y.shape[0]}")
        n = self.X.shape[0]
        self.num_samples = n
        self.batch_size = n if batch_size == -1 else int(batch_size)
        if self.batch_size <= 0:
            raise ValueError(f"batch_size must be -1 or positive, got {batch_size}")
        self.drop_remainder = drop_remainder
        self.wrap_compat = wrap_compat
        self._order = np.arange(n)
        if shuffle:
            np.random.default_rng(seed).shuffle(self._order)
        self._offset = 0

    @classmethod
    def from_file(cls, path, num_features: int, batch_size: int = -1, *, multiclass: bool = False, **kw):
        from distlr_tpu.data.libsvm import parse_libsvm_file  # noqa: PLC0415
        X, y = parse_libsvm_file(path, num_features, multiclass=multiclass)
        return cls(X, y, batch_size, **kw)

    def has_next(self) -> bool:
        """True while this epoch still has unserved samples
        (mirrors reference ``HasNext``, ``data_iter.h:57-59``)."""
        if self.drop_remainder:
            return self._offset + self.batch_size <= self.num_samples
        return self._offset < self.num_samples

    def _next_idx(self):
        """Row indices + validity mask of the next static-shape batch."""
        if not self.has_next():
            raise StopIteration
        b = self.batch_size
        idx = self._order[self._offset : self._offset + b]
        if len(idx) < b and self.wrap_compat:
            # Q5 parity: wrap around and duplicate head samples, cycling as
            # many times as needed (the reference's NextBatch loop keeps
            # walking modulo the shard, data_iter.h:46-53).
            extra = np.take(self._order, np.arange(b - len(idx)), mode="wrap")
            idx = np.concatenate([idx, extra])
        self._offset += b
        real = len(idx)
        mask = np.ones(b, dtype=bool)
        if real < b:  # pad to static shape
            pad = b - real
            idx = np.concatenate([idx, np.zeros(pad, dtype=idx.dtype)])
            mask[real:] = False
        return idx, mask

    def next_batch(self):
        idx, mask = self._next_idx()
        return self.X[idx], self.y[idx], mask

    def whole_shard(self):
        """``(X, y, mask)`` of the one batch an epoch has, as the arrays
        this iterator holds and no copy of them; None where a batch is
        anything but the whole shard in the order it is held."""
        return self.held_rows() if self.batch_size == self.num_samples else None

    def held_rows(self):
        """``(X, y, mask)`` of every row, as the arrays this iterator
        holds and no copy of them, where each batch of an epoch is a
        :class:`Window` of them (:meth:`next_window`): the rows served in
        the order they are held: no shuffle, and no short last batch for
        Q5 to wrap.  None otherwise."""
        n = self.num_samples
        if ((self.wrap_compat and n % self.batch_size)
                or not np.array_equal(self._order, np.arange(n))):
            return None
        return self.X, self.y, np.ones(n, dtype=bool)

    def next_window(self) -> Window:
        """:meth:`next_batch` for whoever keeps :meth:`held_rows`: where
        the batch lies, and no rows gathered."""
        if not self.has_next():
            raise StopIteration
        first = self._offset
        self._offset += self.batch_size
        return Window(first, min(self.batch_size, self.num_samples - first))

    def __iter__(self):
        while self.has_next():
            yield self.next_batch()

    def reset(self) -> None:
        """Start a new epoch (the reference instead re-reads the file from
        disk every epoch — ``src/main.cc:158-159``; we keep the arrays)."""
        self._offset = 0

    @property
    def num_batches(self) -> int:
        if self.drop_remainder:
            return self.num_samples // self.batch_size
        return -(-self.num_samples // self.batch_size)


class SparseDataIter(DataIter):
    """Padded-COO variant: yields ``(cols, vals, y, mask)`` batches.

    ``cols``/``vals`` are ``(B, NNZ_MAX)`` per-row index/value arrays
    (pad col = 0, pad val = 0) — the ``SparseBinaryLR`` batch layout.
    Same epoch/batching semantics as :class:`DataIter` (the row arrays
    just carry two feature leaves instead of a dense matrix).
    """

    def __init__(self, cols, vals, y, batch_size: int = -1, **kw):
        cols = np.asarray(cols)
        self.vals = np.asarray(vals)
        if cols.shape != self.vals.shape:
            raise ValueError(f"cols {cols.shape} vs vals {self.vals.shape}")
        super().__init__(cols, y, batch_size, **kw)

    @property
    def cols(self) -> np.ndarray:
        return self.X

    @classmethod
    def from_file(cls, path, num_features: int | None = None, batch_size: int = -1,
                  *, nnz_max: int | None = None, multiclass: bool = False,
                  **kw):
        """Parse a libsvm shard WITHOUT densifying (CTR-scale feature
        spaces where ``(N, D)`` dense would not fit host RAM).
        ``multiclass`` keeps integer labels verbatim (sparse_softmax)."""
        from distlr_tpu.data.hashing import csr_to_padded_coo  # noqa: PLC0415
        from distlr_tpu.data.libsvm import parse_libsvm_file  # noqa: PLC0415

        (row_ptr, csr_cols, csr_vals), y = parse_libsvm_file(
            path, num_features, dense=False, multiclass=multiclass
        )
        cols, vals = csr_to_padded_coo(row_ptr, csr_cols, csr_vals, nnz_max=nnz_max)
        return cls(cols, vals, y, batch_size, **kw)

    def next_batch(self):
        idx, mask = self._next_idx()
        return self.X[idx], self.vals[idx], self.y[idx], mask

    def held_rows(self):
        """``(cols, vals, y, mask)`` of every row as this iterator holds
        them, under :meth:`DataIter.held_rows`'s condition (each batch of
        an epoch is a :class:`Window` of them); None otherwise."""
        held = super().held_rows()
        if held is None:
            return None
        cols, y, mask = held
        return cols, self.vals, y, mask

    def drop_rows(self) -> None:
        """Let go of the row arrays: whoever took :meth:`held_rows` keeps
        the rows now (a PS worker's device), and this iterator serves
        :meth:`next_window` alone from here on."""
        self.X = self.vals = self.y = None


class BlockedDataIter(DataIter):
    """Row-blocked variant: yields ``(blocks, lane_vals, y, mask)`` —
    the :class:`distlr_tpu.models.BlockedSparseLR` batch layout.

    ``blocks`` is ``(B, G)`` int32 table-row ids, ``lane_vals`` is
    ``(B, G, R)`` float32 per-lane values (zero = padded lane).  Same
    epoch/batching semantics as :class:`DataIter`.
    """

    def __init__(self, blocks, lane_vals, y, batch_size: int = -1, **kw):
        blocks = np.asarray(blocks)
        self.lane_vals = np.asarray(lane_vals)
        if blocks.shape != self.lane_vals.shape[:2]:
            raise ValueError(
                f"blocks {blocks.shape} vs lane_vals {self.lane_vals.shape}"
            )
        super().__init__(blocks, y, batch_size, **kw)

    @property
    def blocks(self) -> np.ndarray:
        return self.X

    @classmethod
    def from_file(cls, path, num_fields: int, num_blocks: int, block_size: int,
                  batch_size: int = -1, *, seed: int = 0, num_groups: int = 0,
                  **kw):
        """Parse a raw-CTR shard (``write_raw_ctr_shards`` format) and
        hash its field groups into block rows at load time
        (``num_groups``: see ``hashing.split_field_groups``)."""
        from distlr_tpu.data.hashing import encode_blocked, read_raw_ctr_file  # noqa: PLC0415

        raw_ids, y = read_raw_ctr_file(path, num_fields)
        blocks, lane_vals = encode_blocked(
            raw_ids, num_blocks, block_size, seed=seed, num_groups=num_groups
        )
        return cls(blocks, lane_vals, y, batch_size, **kw)

    def next_batch(self):
        idx, mask = self._next_idx()
        return self.X[idx], self.lane_vals[idx], self.y[idx], mask
