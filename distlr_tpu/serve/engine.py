"""Batched scoring engine — jitted ``predict``/``proba`` over every model
family with bucketed batch shapes and hot-swappable weights.

The training side of this repo turns the reference's worker loop into
jitted steps; this is the same move for the *inference* workload the
ROADMAP's "heavy traffic" north star demands (the reference has no read
path at all — its ``SaveModel`` output is write-only, ``src/lr.cc:73-82``).

Design constraints, in order:

* **Bounded recompiles.** XLA compiles one program per input shape, so an
  engine that jitted whatever batch size arrived would compile per
  request size.  Incoming batches are padded up to a small ladder of
  bucket sizes (default ``{64, 256, 1024}`` capped at ``max_batch_size``)
  — at most ``len(buckets)`` compiled programs per (model, nnz-width)
  pair, and the padding rows are masked out of the returned results.
  Sparse COO batches additionally bucket their NNZ width to powers of two
  (capped at ``cfg.nnz_max`` when set) for the same reason.
* **Atomic weight swap.** ``set_weights`` replaces the device weights
  reference between batches; an in-flight ``score`` call keeps scoring
  against the weights it read at entry (a Python reference read — no
  torn state is observable), so a trainer can publish continuously while
  requests stream (see :mod:`distlr_tpu.serve.reload`).
"""

from __future__ import annotations

import functools
import threading
import time

import jax
import numpy as np

from distlr_tpu.config import Config
from distlr_tpu.models import get_model
from distlr_tpu.obs import dtrace, jaxrt
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.obs.tracing import trace_phase

DEFAULT_BUCKETS = (64, 256, 1024)

_reg = get_registry()
_SCORE_SECONDS = _reg.histogram(
    "distlr_serve_engine_seconds",
    "wall seconds per engine score() call (pad + jit dispatch + readback)",
)
_ROWS_SCORED = _reg.counter(
    "distlr_serve_engine_rows_total", "rows scored across all engines",
)
_BATCHES_SCORED = _reg.counter(
    "distlr_serve_engine_batches_total", "score() calls across all engines",
)
_BUCKET_HITS = _reg.counter(
    "distlr_serve_engine_bucket_hits_total",
    "padded-batch bucket selections", labelnames=("bucket",),
)
_WEIGHT_SWAPS = _reg.counter(
    "distlr_serve_weight_swaps_total",
    "atomic weight publishes into serving engines",
)
_EVICTIONS = _reg.counter(
    "distlr_serve_engine_evictions_total",
    "idle engines that released their device weight table to host "
    "memory (--engine-idle-evict; the next request lazily re-loads)",
)
_EVICT_RELOADS = _reg.counter(
    "distlr_serve_engine_evict_reloads_total",
    "lazy device re-loads of an evicted engine's weight table on the "
    "first request after an idle window",
)
_RESIDENT = _reg.gauge(
    "distlr_serve_engine_resident",
    "engines currently holding their weight table in DEVICE memory "
    "(an evicted cold model version counts 0 until its next request)",
)


def _next_bucket(n: int, ladder: tuple[int, ...]) -> int:
    for b in ladder:
        if n <= b:
            return b
    return ladder[-1]


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


# ONE jitted scorer for the whole process, keyed on the (frozen,
# hashable) model value — engines over the same model share compiled
# programs.  Returns (labels, scores): scores is P(y=1) for binary
# families and the max class probability for softmax families.  The
# batch leaves are not donated: the outputs are (B,)-shaped, so no
# output can reuse a (B, D) input buffer and a donation only earns a
# "donated buffers were not usable" warning per bucket; the padded copy
# made in score() is dropped as soon as the call returns.
@functools.partial(jax.jit, static_argnums=0)
def _jit_score(model, w, rows):
    labels = model.predict(w, *rows)
    p = model.proba(w, *rows)
    scores = p if p.ndim == 1 else p.max(axis=-1)
    return labels, scores


# runtime introspection (obs.jaxrt): per-bucket compile counts — one
# probe for the process-shared scorer, so every engine's recompiles land
# in distlr_jax_compiles_total{site="serve.engine"}
_jit_score_probe = jaxrt.JitCacheProbe(_jit_score, "serve.engine")


class ScoringEngine:
    """Jitted batched scoring over one model family.

    ``rows`` everywhere below is the family's feature-leaf tuple with a
    shared leading (batch) axis — dense: ``(X,)``; sparse COO:
    ``(cols, vals)``; blocked: ``(blocks, lane_vals)`` — i.e. the train
    batch layout minus labels and mask.
    """

    def __init__(self, cfg: Config, weights=None, *,
                 max_batch_size: int = 1024,
                 buckets: tuple[int, ...] = DEFAULT_BUCKETS,
                 idle_evict_s: float = 0.0):
        if max_batch_size <= 0:
            raise ValueError(f"max_batch_size must be positive, got {max_batch_size}")
        if idle_evict_s < 0:
            raise ValueError(
                f"idle_evict_s must be >= 0 (0 = never evict), "
                f"got {idle_evict_s}")
        if cfg.model == "blocked_lr" and cfg.block_size == 0:
            raise ValueError(
                "block_size=0 (auto) must be resolved before serving — pin "
                "the (R, groups) the model was trained with"
            )
        self.cfg = cfg
        self.model = get_model(cfg)
        self.max_batch_size = int(max_batch_size)
        self.buckets = tuple(sorted(
            {b for b in buckets if b < max_batch_size} | {self.max_batch_size}
        ))
        self._lock = threading.Lock()
        self._weights = None
        self.weights_version = 0
        self._bucket_hits: dict[int, int] = {}
        self.batches_scored = 0
        self.rows_scored = 0
        # idle eviction (the cold-model-version satellite): after
        # ``idle_evict_s`` seconds with no score() the device table
        # moves to a host copy (HBM freed); the next request lazily
        # re-loads it.  A hot-reloading cold version keeps publishing
        # into the HOST copy, so staying evicted costs no device work.
        self.idle_evict_s = float(idle_evict_s)
        self._host_weights: np.ndarray | None = None
        self._last_score_at = time.monotonic()
        self._inflight = 0
        self.evictions = 0
        self._evict_stop: threading.Event | None = None
        if self.idle_evict_s > 0:
            self._evict_stop = threading.Event()
            t = threading.Thread(target=self._evict_loop, daemon=True,
                                 name="distlr-engine-evict")
            t.start()
        if weights is not None:
            self.set_weights(weights)

    # -- weights ----------------------------------------------------------
    def set_weights(self, weights) -> int:
        """Publish new weights (host or device array, flat or shaped);
        returns the new version.  Swaps are atomic wrt ``score``: calls
        already past the reference read finish on the old weights, the
        next batch sees the new ones.  An EVICTED engine's publish
        stays host-side (no device work for a cold version)."""
        with trace_phase("weight_swap"):
            host = np.asarray(weights,
                              dtype=np.float32).reshape(self.model.param_shape)
            with self._lock:
                if (self.idle_evict_s > 0 and self._weights is None
                        and self._host_weights is not None):
                    # evicted: keep the fresh table host-side — the next
                    # request's lazy re-load will device_put it
                    self._host_weights = host
                    self.weights_version += 1
                    _WEIGHT_SWAPS.inc()
                    return self.weights_version
            w = jax.device_put(host)
            with self._lock:
                if self._weights is None:
                    _RESIDENT.inc()
                self._weights = w
                if self.idle_evict_s > 0:
                    self._host_weights = host
                self.weights_version += 1
                _WEIGHT_SWAPS.inc()
                version = self.weights_version
        # the swap is when device residency actually changes (the old
        # table frees once in-flight scores release it) — refresh the
        # buffer gauges outside the lock
        jaxrt.maybe_sample_device_bytes()
        return version

    @property
    def has_weights(self) -> bool:
        return self._weights is not None or self._host_weights is not None

    @property
    def resident(self) -> bool:
        """Whether the weight table is in DEVICE memory right now
        (False = evicted cold version awaiting its next request)."""
        return self._weights is not None

    def get_weights(self) -> np.ndarray:
        if self._weights is not None:
            return np.asarray(self._weights)
        if self._host_weights is not None:
            return np.array(self._host_weights)
        raise RuntimeError("engine has no weights loaded")

    # -- idle eviction -----------------------------------------------------
    def _evict_loop(self) -> None:
        tick = max(self.idle_evict_s / 4.0, 0.05)
        while not self._evict_stop.wait(tick):
            self.maybe_evict()

    def maybe_evict(self, now: float | None = None) -> bool:
        """Release the device table if this engine has been idle past
        ``idle_evict_s`` (no-op otherwise; also callable directly by
        tests/ops).  Returns True when an eviction happened."""
        if self.idle_evict_s <= 0:
            return False
        now = time.monotonic() if now is None else now
        with self._lock:
            if (self._weights is None or self._inflight
                    or now - self._last_score_at < self.idle_evict_s):
                return False
            # the host copy is maintained by set_weights; an engine
            # seeded before eviction support still snapshots here
            if self._host_weights is None:
                self._host_weights = np.asarray(self._weights)
            self._weights = None
            self.evictions += 1
            _EVICTIONS.inc()
            _RESIDENT.dec()
        jaxrt.maybe_sample_device_bytes()
        return True

    def _ensure_resident_locked(self) -> None:
        """Lazy re-load of an evicted table (caller holds the lock)."""
        if self._weights is None and self._host_weights is not None:
            self._weights = jax.device_put(self._host_weights)
            _EVICT_RELOADS.inc()
            _RESIDENT.inc()

    # -- scoring ----------------------------------------------------------
    def _pad_rows(self, rows: tuple[np.ndarray, ...], bucket: int):
        padded = []
        n = rows[0].shape[0]
        for leaf in rows:
            leaf = np.ascontiguousarray(leaf)
            if n < bucket:
                pad = [(0, bucket - n)] + [(0, 0)] * (leaf.ndim - 1)
                leaf = np.pad(leaf, pad)
            padded.append(leaf)
        return tuple(padded)

    def _score_bucket(self, rows: tuple[np.ndarray, ...]):
        n = rows[0].shape[0]
        bucket = _next_bucket(n, self.buckets)
        self._bucket_hits[bucket] = self._bucket_hits.get(bucket, 0) + 1
        _BUCKET_HITS.labels(bucket=bucket).inc()
        w = self._weights  # atomic reference read — the swap point
        labels, scores = _jit_score(
            self.model, w, self._pad_rows(rows, bucket))
        # attribute any cache growth to the bucket that just ran — the
        # "bucket B keeps recompiling" signal `launch top` surfaces
        _jit_score_probe.tick(bucket)
        return np.asarray(labels)[:n], np.asarray(scores)[:n]

    def score(self, rows: tuple[np.ndarray, ...]) -> tuple[np.ndarray, np.ndarray]:
        """Score a host batch -> ``(labels (B,) int32, scores (B,) f32)``.

        Batches larger than ``max_batch_size`` are chunked; smaller ones
        are padded up to the nearest bucket.  Sparse COO batches must
        already be at an engine NNZ width (``encode_lines`` guarantees
        this; direct callers should pad with ``_nnz_width``).
        """
        if not self.has_weights:
            raise RuntimeError(
                "engine has no weights loaded yet (set_weights / a weight "
                "source must publish before scoring)"
            )
        n = rows[0].shape[0]
        if n == 0:
            return np.empty(0, np.int32), np.empty(0, np.float32)
        # lazy re-load of an evicted cold version, and an in-flight
        # guard so the evictor can never pull the table out from under
        # a running batch
        with self._lock:
            self._ensure_resident_locked()
            self._inflight += 1
        try:
            labels_out, scores_out = [], []
            # the infer span nests under the batcher's serve.batch span
            # (the flush thread's current context); direct callers with
            # no context pay nothing
            with _SCORE_SECONDS.time(), dtrace.span(
                    "serve.infer",
                    tags={"rows": n, "version": self.weights_version}):
                for lo in range(0, n, self.max_batch_size):
                    chunk = tuple(leaf[lo:lo + self.max_batch_size]
                                  for leaf in rows)
                    lab, sc = self._score_bucket(chunk)
                    labels_out.append(lab)
                    scores_out.append(sc)
        finally:
            with self._lock:
                self._inflight -= 1
                self._last_score_at = time.monotonic()
        self.batches_scored += 1
        self.rows_scored += n
        _BATCHES_SCORED.inc()
        _ROWS_SCORED.inc(n)
        return np.concatenate(labels_out), np.concatenate(scores_out)

    # -- request encoding --------------------------------------------------
    def _nnz_width(self, max_nnz: int) -> int:
        """Static NNZ pad width for a sparse batch: the next power of two
        (>= 8, so tiny requests share one program), capped at
        ``cfg.nnz_max`` when configured — bounded distinct widths ->
        bounded recompiles."""
        width = max(_next_pow2(max_nnz), 8)
        if self.cfg.nnz_max:
            width = min(width, self.cfg.nnz_max)
        return width

    def encode_lines(self, lines: list[str]) -> tuple[np.ndarray, ...]:
        """Parse request lines into this family's feature-leaf tuple.

        Lines are libsvm-formatted feature lists; a leading label token
        is optional (a scoring request has nothing to label) and ignored
        when present.  Blocked models read the raw-CTR line format (field
        number : raw categorical id — the same libsvm grammar), hashing
        with the engine config's seed/grouping so serving buckets
        identically to training.
        """
        from distlr_tpu.data.libsvm import parse_libsvm_lines  # noqa: PLC0415

        # Scoring requests may omit the label; the parser requires one.
        normalized = []
        for ln in lines:
            ln = ln.strip()
            first = ln.split(None, 1)[0] if ln else ""
            normalized.append(ln if first and ":" not in first else "0 " + ln)
        cfg = self.cfg
        if cfg.model == "blocked_lr":
            from distlr_tpu.data.hashing import (  # noqa: PLC0415
                csr_to_raw_ids,
                encode_blocked,
                resolve_ctr_fields,
            )

            (row_ptr, cols, vals), _ = parse_libsvm_lines(
                normalized, None, dense=False
            )
            num_fields = resolve_ctr_fields(cfg.data_dir, cfg.ctr_fields) \
                if (cfg.ctr_fields == 0 and cfg.data_dir) else cfg.ctr_fields
            if not num_fields:
                raise ValueError(
                    "blocked_lr serving needs ctr_fields (or a data_dir "
                    "with a ctr_meta.json manifest)"
                )
            # THE raw-CTR row assembly — shared with read_raw_ctr_file so
            # serving rejects exactly what training rejects (bad field
            # numbers, duplicate/missing fields, corrupt ids)
            raw_ids = csr_to_raw_ids(row_ptr, cols, vals, num_fields,
                                     origin="request")
            blocks, lane_vals = encode_blocked(
                raw_ids, cfg.num_feature_dim // cfg.block_size,
                cfg.block_size, seed=cfg.hash_seed,
                num_groups=cfg.block_groups,
            )
            return blocks, lane_vals
        if cfg.model in ("sparse_lr", "sparse_softmax"):
            from distlr_tpu.data.hashing import csr_to_padded_coo  # noqa: PLC0415

            (row_ptr, cols, vals), _ = parse_libsvm_lines(
                normalized, cfg.num_feature_dim, dense=False
            )
            lengths = np.diff(row_ptr)
            nnz = self._nnz_width(int(lengths.max()) if len(lengths) else 1)
            pc, pv = csr_to_padded_coo(row_ptr, cols, vals, nnz_max=nnz)
            return pc, pv
        X, _ = parse_libsvm_lines(normalized, cfg.num_feature_dim, dense=True)
        if cfg.feature_dtype in ("int8", "int8_dot"):
            # Serving a quantization-trained model: the engine's
            # feature_scale (folded into the model by the caller) defines
            # the grid; requests quantize onto it.
            scale = getattr(self.model, "feature_scale", 1.0)
            X = np.clip(np.rint(X / scale), -127, 127).astype(np.int8)
        return (X,)

    def row_keys(self, rows: tuple[np.ndarray, ...]) -> np.ndarray:
        """PS row keys a request batch touches (``rows`` in this family's
        leaf layout) — what a :class:`~distlr_tpu.serve.hotset.
        HotSetTracker` observes.  Keys are row ids in the PS row space:
        sparse COO column ids, blocked table row ids, or (dense) the
        feature columns any row in the batch exercises.  Sparse padding
        (col 0 / val 0) may contribute key 0 — one spuriously-hot row,
        harmless."""
        if self.cfg.model in ("sparse_lr", "sparse_softmax", "blocked_lr"):
            return np.unique(
                np.asarray(rows[0], dtype=np.int64)).astype(np.uint64)
        X = np.asarray(rows[0])
        return np.flatnonzero((X != 0).any(axis=0)).astype(np.uint64)

    # -- stats -------------------------------------------------------------
    def stats(self) -> dict:
        out = {
            "weights_version": self.weights_version,
            "batches_scored": self.batches_scored,
            "rows_scored": self.rows_scored,
            "bucket_hits": dict(sorted(self._bucket_hits.items())),
            "buckets": list(self.buckets),
        }
        if self.idle_evict_s > 0:
            # additive, like every stats extension: only evict-enabled
            # engines grow the schema
            out["resident"] = self.resident
            out["evictions"] = self.evictions
        return out
