"""AdaBatch-style local gradient accumulation.

One accumulator = one worker's "push every k batches" state: gradients
sum into a local full-width f32 buffer, the flush pushes their MEAN
(one PS update of effective batch size ``k * B``), and ``k`` GROWS on a
schedule — multiply by ``growth`` every ``growth_every`` flushes,
capped at ``max_k`` (AdaBatch, arXiv:1712.02029).  Early in a run small
``k`` keeps server weights fresh; as the model stabilizes the growing
span divides push traffic by ``k`` — the cadence axis of the
communication dial whose encoding axis is the wire codec
(:mod:`distlr_tpu.compress.codecs`); the two multiply.

Extracted from the PR-6 online trainer (``feedback/online.py``), which
proved the pattern against a live PS; now shared by it and every
``ps_trainer`` loop variant (``--accum-start``/``--accum-max``).

Not thread-safe: one accumulator per worker, like the gradient buffer
it generalizes.  Within a span the caller should reuse the weights it
pulled at span start (batches of one span ride the same weights — the
span is the self-staleness bound).
"""

from __future__ import annotations

import numpy as np


class GradientAccumulator:
    """Local mean-gradient accumulation with a growing flush span."""

    def __init__(self, dim: int, *, start: int = 1, growth: float = 2.0,
                 growth_every: int = 32, max_k: int = 64, gauge=None):
        if start < 1 or max_k < start:
            raise ValueError(
                f"need 1 <= start <= max_k, got {start}/{max_k}")
        if growth < 1.0:
            raise ValueError(f"growth must be >= 1, got {growth}")
        if growth_every <= 0:
            raise ValueError(
                f"growth_every must be positive, got {growth_every}")
        self.dim = int(dim)
        self.k = int(start)
        self.growth = float(growth)
        self.growth_every = int(growth_every)
        self.max_k = int(max_k)
        #: completed flushes (== pushes issued by the owner)
        self.flushes = 0
        self._gauge = gauge
        if gauge is not None:
            gauge.set(self.k)
        self._buf = np.zeros(self.dim, np.float32)
        self._batches = 0

    # -- feeding -----------------------------------------------------------
    @property
    def batches(self) -> int:
        """Batches accumulated since the last flush (0 = span start:
        time for the caller to refresh its pulled weights)."""
        return self._batches

    @property
    def ready(self) -> bool:
        """True once the current span is full — flush now."""
        return self._batches >= self.k

    def add(self, g: np.ndarray) -> None:
        """Accumulate one full-width dense gradient."""
        self._buf += np.asarray(g, np.float32).reshape(-1)
        self._batches += 1

    def add_rows(self, rows: np.ndarray, g: np.ndarray, vpk: int) -> None:
        """Accumulate a row-keyed gradient: row ``rows[i]`` owns flat
        slots ``[rows[i]*vpk, (rows[i]+1)*vpk)`` (the vals_per_key
        layout); ``g`` holds ``len(rows)*vpk`` values row-major."""
        view = self._buf.reshape(-1, vpk)
        view[np.asarray(rows, np.int64)] += np.asarray(
            g, np.float32).reshape(-1, vpk)
        self._batches += 1

    # -- flushing ----------------------------------------------------------
    def flush_dense(self) -> np.ndarray | None:
        """Mean gradient of the span (None if the span is empty), then
        reset + advance the schedule.  The returned array is a fresh
        buffer the caller may push without copying."""
        if self._batches == 0:
            return None
        g = self._buf / np.float32(self._batches)
        self._reset_and_advance()
        return g

    def flush_keyed(self, vpk: int = 1):
        """Like :meth:`flush_dense` but keyed: ``(row_keys, vals)`` of
        the rows the span actually touched (any nonzero lane), vals
        row-major ``len(keys)*vpk`` — what a sparse/blocked worker
        pushes.  Returns None for an empty span; empty arrays when the
        span's gradients cancelled to exact zeros (schedule still
        advances — sync callers push the empty frame as their BSP
        "present" vote, async callers skip it)."""
        if self._batches == 0:
            return None
        view = (self._buf / np.float32(self._batches)).reshape(-1, vpk)
        rows = np.flatnonzero((view != 0).any(axis=1)).astype(np.uint64)
        vals = view[rows.astype(np.int64)].reshape(-1)
        self._reset_and_advance()
        return rows, vals

    def _reset_and_advance(self) -> None:
        self._buf[:] = 0.0
        self._batches = 0
        self.flushes += 1
        if self.flushes % self.growth_every == 0:
            grown = max(self.k + 1, int(round(self.k * self.growth)))
            self.k = min(self.max_k, grown)
            if self._gauge is not None:
                self._gauge.set(self.k)
