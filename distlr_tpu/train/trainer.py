"""Synchronous SPMD trainer — the role-collapsed successor of the
reference's worker loop.

Reference control flow (``src/main.cc:124-170`` + ``src/lr.cc:28-45``):
each of W worker processes re-reads its libsvm shard every epoch, pulls the
full weight vector, computes a mean gradient over its (full-shard) batch,
pushes it, and blocks on the server's deferred response — the BSP barrier.
Rank 0 evaluates every ``TEST_INTERVAL`` epochs and each worker text-dumps
its weights at the end.

Here the W workers become the ``data`` axis of one mesh and the whole
epoch is minibatch steps of a single jitted SPMD program
(:func:`distlr_tpu.parallel.make_sync_train_step`).  Shard->device-row
mapping preserves the reference semantics: worker i's shard rows live on
mesh position i, and with ``batch_size=-1`` each step consumes every
worker's full shard, exactly one reference "iteration".
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import queue
import threading
import time

import jax
import numpy as np

from distlr_tpu.config import Config
from distlr_tpu.data import DataIter, parse_libsvm_file
from distlr_tpu.data.libsvm import densify_csr
from distlr_tpu.data.sharding import part_name
from distlr_tpu.models import get_model
from distlr_tpu.obs import jaxrt
from distlr_tpu.obs.tracing import loop_span as _loop_span, trace_phase
from distlr_tpu.parallel import (
    feed,
    make_eval_step,
    make_mesh,
    make_sync_train_step,
)
from distlr_tpu.parallel.data_parallel import shard_batch
from distlr_tpu.parallel.mesh import MODEL_AXIS, num_data_shards
from distlr_tpu.train.export import save_model_text
from distlr_tpu.train.metrics import MetricsLogger, StepTimer
from distlr_tpu.utils.logging import get_logger, log_eval_line

log = get_logger(__name__)


class GlobalShardedData:
    """W per-worker shards packed as one global array with lockstep batching.

    Shards are padded to a common length ``n_pad`` and stacked to
    ``(W, n_pad, ...)``; a global minibatch of per-worker size ``b`` is the
    flattened ``(W*b, ...)`` slice ``[:, k*b:(k+1)*b]`` with a validity
    mask.  Laying worker i's rows contiguously at block i makes a plain
    leading-axis ``data`` sharding put each reference-worker's shard on its
    own mesh slot.
    """

    def __init__(self, shards: list[tuple[np.ndarray, ...]]):
        """Each shard is ``(*feature_leaves, y)`` — dense ``(X, y)`` or
        padded-COO sparse ``(cols, vals, y)``; all leaves share the sample
        (leading) axis."""
        if not shards:
            raise ValueError("need at least one shard")
        self.num_shards = len(shards)
        self.shard_sizes = [len(s[-1]) for s in shards]
        n_pad = max(self.shard_sizes)
        if n_pad == 0:
            raise ValueError("all shards are empty — no training data")
        W = self.num_shards
        n_feat_leaves = len(shards[0]) - 1
        # sparse shards may disagree on NNZ_MAX; pad trailing dims to match
        trail = [
            tuple(
                max(s[k].shape[j] for s in shards)
                for j in range(1, shards[0][k].ndim)
            )
            for k in range(n_feat_leaves)
        ]
        self._feats = [
            np.zeros((W, n_pad) + trail[k], dtype=shards[0][k].dtype)
            for k in range(n_feat_leaves)
        ]
        self.y = np.zeros((W, n_pad), dtype=shards[0][-1].dtype)
        self.mask = np.zeros((W, n_pad), dtype=np.float32)
        for i, shard in enumerate(shards):
            n = len(shard[-1])
            for k in range(n_feat_leaves):
                leaf = shard[k]
                sl = (i, slice(0, n)) + tuple(slice(0, d) for d in leaf.shape[1:])
                self._feats[k][sl] = leaf
            self.y[i, :n] = shard[-1]
            self.mask[i, :n] = 1.0
        self.n_pad = n_pad

    @property
    def X(self) -> np.ndarray:
        """The single dense feature matrix (dense datasets only)."""
        if len(self._feats) != 1:
            raise AttributeError("X is only defined for dense (single-leaf) data")
        return self._feats[0]

    @classmethod
    def from_data_dir(
        cls,
        data_dir: str,
        split: str,
        num_shards: int,
        num_features: int,
        *,
        multiclass=False,
        sparse: bool = False,
        nnz_max: int | None = None,
    ):
        """Load ``data_dir/{split}/part-001..W`` (reference layout,
        ``src/main.cc:158-159``). If fewer parts exist than mesh shards,
        parts are round-robined; if more, they are concatenated down.

        ``sparse=True`` keeps rows as padded-COO ``(cols, vals)`` for the
        ``segment_sum`` path instead of densifying (CTR-style data where
        ``(N, D)`` dense would not fit host RAM)."""
        paths = cls._discover_parts(data_dir, split)
        parts = []
        for p in paths:
            # once a file: the read and the tokenizer, then the rows in
            # the layout the model family takes
            with trace_phase("load_parse"):
                (row_ptr, cols, vals), y = parse_libsvm_file(
                    p, num_features, dense=False, multiclass=multiclass
                )
            if sparse:
                from distlr_tpu.data.hashing import csr_to_padded_coo  # noqa: PLC0415

                with trace_phase("load_coo"):
                    pc, pv = csr_to_padded_coo(row_ptr, cols, vals, nnz_max=nnz_max)
                parts.append((pc, pv, y))
            else:
                with trace_phase("load_densify"):
                    parts.append((densify_csr(row_ptr, cols, vals, num_features), y))
        # once a split: the redistribution onto mesh slots and the
        # constructor's copy into the padded (W, n_pad, ...) arrays
        with trace_phase("load_pack"):
            return cls._from_parts(parts, num_shards)

    @staticmethod
    def _discover_parts(data_dir: str, split: str) -> list[str]:
        paths = []
        i = 0
        while True:
            p = os.path.join(data_dir, split, part_name(i))
            if not os.path.exists(p):
                break
            paths.append(p)
            i += 1
        if not paths:
            raise FileNotFoundError(f"no shards under {data_dir}/{split}")
        return paths

    @classmethod
    def from_raw_ctr_dir(cls, data_dir: str, split: str, num_shards: int, cfg):
        """Load raw-CTR shards (``write_raw_ctr_shards`` format) as
        row-blocked leaves ``(blocks, lane_vals, y)`` — the on-disk path
        of the ``blocked_lr`` model.  Hashing happens at load time
        (``encode_blocked``) so train/test share the grouping and seed by
        construction."""
        from distlr_tpu.data.hashing import (  # noqa: PLC0415
            encode_blocked,
            read_raw_ctr_file,
            resolve_ctr_fields,
        )

        num_fields = resolve_ctr_fields(data_dir, cfg.ctr_fields)
        num_blocks = cfg.num_feature_dim // cfg.block_size
        parts = []
        for p in cls._discover_parts(data_dir, split):
            raw_ids, y = read_raw_ctr_file(p, num_fields)
            blocks, lane_vals = encode_blocked(
                raw_ids, num_blocks, cfg.block_size, seed=cfg.hash_seed,
                num_groups=cfg.block_groups,
            )
            parts.append((blocks, lane_vals, y))
        with trace_phase("load_pack"):
            return cls._from_parts(parts, num_shards)

    @classmethod
    def _from_parts(cls, parts, num_shards: int):
        """Redistribute loaded parts onto ``num_shards`` mesh slots
        (round-robin split when fewer parts, interleaved merge when
        more)."""
        if len(parts) != num_shards:

            def _concat(arrs):
                # parts may disagree on trailing dims (per-part NNZ_MAX)
                trail = tuple(
                    max(a.shape[j] for a in arrs) for j in range(1, arrs[0].ndim)
                )
                padded = [
                    np.pad(a, [(0, 0)] + [(0, t - s) for t, s in zip(trail, a.shape[1:])])
                    for a in arrs
                ]
                return np.concatenate(padded)

            leaves = [_concat([p[k] for p in parts]) for k in range(len(parts[0]))]
            shards = [
                tuple(leaf[i::num_shards] for leaf in leaves) for i in range(num_shards)
            ]
        else:
            shards = parts
        return cls(shards)

    @property
    def num_samples(self) -> int:
        return int(sum(self.shard_sizes))

    def num_batches(self, per_worker_batch: int) -> int:
        """How many batches :meth:`batches` yields an epoch."""
        b = self.n_pad if per_worker_batch == -1 else min(per_worker_batch, self.n_pad)
        return -(-self.n_pad // b)

    def batches(self, per_worker_batch: int, *, wrap: bool = False):
        """One epoch of lockstep global batches ``(*feats, y, mask)``
        shaped ``(W*b, ...)``. ``-1`` = full shard per worker (one
        step/epoch).

        ``wrap=True`` reproduces the reference's Q5 final-batch semantics
        (``include/data_iter.h:44-56``): the short final batch wraps to the
        shard head and re-serves leading samples instead of being
        padded+masked.  Lockstep batching can only express this when every
        shard wraps at the same offset, so unequal shard sizes reject
        loudly rather than silently approximating the quirk.
        """
        b = self.n_pad if per_worker_batch == -1 else min(per_worker_batch, self.n_pad)
        # Q5 is defined on REAL per-shard sizes, before padding/clamping:
        # batch=-1 is one whole-shard batch (no wrap possible,
        # data_iter.h:39-43), and a batch larger than the shard cycles it.
        if wrap and per_worker_batch != -1 and any(
            sz % per_worker_batch for sz in self.shard_sizes
        ):
            if any(n != self.n_pad for n in self.shard_sizes):
                raise ValueError(
                    "wrap_final_batch (Q5 compat) requires equal-size shards "
                    f"in the sync trainer (got sizes {self.shard_sizes}); "
                    "per-shard wraparound points diverge otherwise — use the "
                    "PS trainer for Q5 parity on unequal shards, or "
                    "compat_mode='correct'"
                )
            bw, n = per_worker_batch, self.n_pad
            for k in range(-(-n // bw)):
                idx = np.arange(k * bw, (k + 1) * bw) % n
                yield tuple(
                    a[:, idx].reshape((-1,) + a.shape[2:])
                    for a in (*self._feats, self.y, self.mask)
                )
            return

        def _slice(arr, sl, bw):
            out = arr[:, sl]
            if bw < b:  # pad the short final batch to static shape
                pad = [(0, 0), (0, b - bw)] + [(0, 0)] * (arr.ndim - 2)
                out = np.pad(out, pad)
            return out.reshape((-1,) + arr.shape[2:])

        for k in range(-(-self.n_pad // b)):
            sl = slice(k * b, min((k + 1) * b, self.n_pad))
            bw = sl.stop - sl.start
            yield tuple(
                _slice(a, sl, bw) for a in (*self._feats, self.y, self.mask)
            )

    def full_batch(self):
        return tuple(
            a.reshape((-1,) + a.shape[2:]) for a in (*self._feats, self.y, self.mask)
        )


def _prefetch_to_device(shard_fn, host_batches, depth: int):
    """Host->device streaming from ONE background thread for as long as
    ``host_batches`` lasts (a whole ``fit``): yield ``shard_fn(item,
    pacer)`` for each item, with up to ``depth`` of them sliced and
    handed to ``device_put`` ahead of the consumer.

    The reference's ``DataIter`` role streams shards to the compute each
    epoch on the worker's own thread (``include/data_iter.h:16-35``);
    here the host-side work (numpy slice/pad of batch k+1 + the transfer
    dispatch) overlaps step k's device compute.  Without this, every
    step paid the slice + dispatch latency serially
    (SURVEY.md §7 hard part (d); VERDICT r3 item 3).

    What a dispatched ``device_put`` then does, as a trace on the v5e
    shows it (PERF.md section 5): the runtime relays the array into the
    device's layout on its own host threads (``XlaLinearize``), and
    issues the DMA only when the whole array is relaid; DMAs run one at
    a time, 14 GB/s, in the order they were issued, and a program's
    launch waits behind every DMA queued when its inputs became ready.
    ``shard_fn`` is the trainer's ``put``: for a large dense matrix it
    hands the runtime the host's own bytes in pieces, so that the
    relayout is a straight copy hidden under the previous piece's DMA,
    and dispatches the small program that restores the matrix on the
    device (``parallel/feed.py``), from this thread.

    ``depth`` says how many batches are alive ahead of the step (queued
    here; one more in the producer's hand), so what the device holds at
    most.  *When* their bytes are put is the pacer's, which this
    producer owns from its first batch to its last (``feed.Pacer``): a
    piece is put when the piece ``feed.AHEAD`` before it has landed,
    across batches and epochs, so the link's queue is a piece or two
    long and never empty.  Were a whole batch or two put at once (as
    they were: 5,700 samples/s where the link carries 7,080), every
    launch (the restore's, the step's) would wait for all of it, and all
    the device's work would run in series after the last byte with the
    link idle.  The consumer still waits for the batch it takes
    (``h2d_wait`` in :meth:`Trainer.fit`); a batch is handed over when
    its last piece is put, ``feed.AHEAD`` DMAs before it lands.

    Closing the generator ends the thread before it returns: a consumer
    that raises leaves no producer behind.

    Safe because :meth:`GlobalShardedData.batches` yields independent
    arrays (fancy-indexed / reshaped slices, never a reused buffer).
    """
    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()
    pacer = feed.Pacer(stop)
    end = object()
    errs: list[BaseException] = []

    def produce():
        try:
            for item in host_batches:
                if stop.is_set():
                    return
                q.put(shard_fn(item, pacer))
        except feed.Stopped:
            return
        except BaseException as e:  # propagate to the consumer
            errs.append(e)
        q.put(end)

    t = threading.Thread(target=produce, daemon=True,
                         name="distlr-prefetch")
    t.start()
    try:
        while True:
            item = q.get()
            if item is end:
                if errs:
                    raise errs[0]
                return
            yield item
    finally:
        # Consumer may exit early (exception mid-epoch): a producer
        # stuck in q.put (of a batch, or of `end` behind one) is let go
        # until it has observed `stop` and ended.
        stop.set()
        while t.is_alive():
            with contextlib.suppress(queue.Empty):
                q.get_nowait()
            t.join(0.01)


class Trainer:
    """End-to-end sync training: data -> mesh -> SPMD steps -> eval -> export."""

    def __init__(self, cfg: Config, *, mesh=None, metrics: MetricsLogger | None = None):
        self.cfg = cfg
        if mesh is None:
            # honor a local.sh-style DMLC_NUM_WORKER > 1 as the data-axis
            # size; otherwise default to all devices
            shape = cfg.mesh_shape
            if shape is None and cfg.num_workers > 1:
                shape = {"data": cfg.num_workers}
            mesh = make_mesh(shape)
        self.mesh = mesh
        self.model = get_model(cfg)
        self.metrics = metrics or MetricsLogger()
        # A mesh with a 'model' axis selects the 2D data x feature-sharded
        # path (weights partitioned like ps-lite's server key ranges).
        self.feature_sharded = MODEL_AXIS in mesh.axis_names
        if self.feature_sharded and cfg.model in ("sparse_lr",
                                                  "sparse_softmax",
                                                  "blocked_lr"):
            # w[cols] / t[blocks] gathers arbitrary buckets; a partitioned
            # table would turn every gather into a cross-shard collective.
            # Shard the data axis instead (sparse batches are small by
            # construction).
            raise NotImplementedError(
                f"{cfg.model} supports data-parallel meshes only (no 'model' axis)"
            )
        self._build_steps()
        self.timer = StepTimer()
        #: batches this trainer's loop has taken: the ``step`` id its
        #: spans carry, so ids do not repeat across ``fit`` calls
        self.batches_taken = 0
        #: rows over wall of the last ``fit`` call (0 before the first)
        self.fit_samples_per_sec = 0.0
        self.weights = None
        self._train_data: GlobalShardedData | None = None
        self._test_data: GlobalShardedData | None = None

    def _build_steps(self) -> None:
        """(Re)compile the train/eval step closures over the current
        model — called again when load-time feature quantization bakes a
        dequantization scale into the model."""
        cfg = self.cfg
        if self.feature_sharded:
            from distlr_tpu.parallel.feature_parallel import (  # noqa: PLC0415
                make_feature_sharded_eval_step,
                make_feature_sharded_train_step,
                shard_batch_2d,
                shard_weights,
            )

            self.train_step = make_feature_sharded_train_step(self.model, cfg, self.mesh)
            self.eval_step = make_feature_sharded_eval_step(self.model, self.mesh)
            # X is sharded over both axes: plain puts, nothing to pace
            self._shard_batch = lambda b, pacer=None: shard_batch_2d(b, self.mesh)
            self._shard_weights = lambda w: shard_weights(w, self.mesh)
        else:
            self.train_step = make_sync_train_step(self.model, cfg, self.mesh)
            self.eval_step = make_eval_step(self.model, self.mesh)
            self._shard_batch = lambda b, pacer=None: shard_batch(
                b, self.mesh, pacer)
            self._shard_weights = lambda w: jax.device_put(
                w, jax.sharding.NamedSharding(self.mesh, jax.sharding.PartitionSpec())
            )
        # runtime introspection (obs.jaxrt): compile-cache probes for the
        # jitted step closures, ticked per epoch in fit() — a re-build
        # (load-time quantization) re-baselines them
        self._jit_probes = [
            jaxrt.JitCacheProbe(fn, site)
            for fn, site in ((self.train_step, "train.sync.step"),
                             (self.eval_step, "train.sync.eval"))
        ]

    def _quantize_features(self) -> None:
        """Convert loaded dense feature storage to ``cfg.feature_dtype``.

        int8: symmetric per-dataset quantization — one scale from the
        train split's max |x| (the test split reuses it, clipped), folded
        into the model as ``feature_scale`` so the jitted steps dequantize
        on the fly (XLA fuses the convert into the matmul read).
        """
        fd = self.cfg.feature_dtype
        datasets = [d for d in (self._train_data, self._test_data) if d is not None]
        # Datasets can be shared across Trainers (load_data(train=...)),
        # so quantization is recorded on the object: already-quantized
        # datasets keep their stored scale (re-quantizing ints would
        # silently compute scale=1), freshly loaded ones are quantized
        # WITH that scale, and a dtype mismatch fails loudly.
        prev = {d._quant_dtype for d in datasets if getattr(d, "_quant_dtype", None)}
        if prev and prev != {fd}:
            raise ValueError(
                f"dataset was already quantized as {sorted(prev)} by another "
                f"Trainer; this one wants {fd!r}"
            )
        fresh = [d for d in datasets if getattr(d, "_quant_dtype", None) is None]
        if fd == "bfloat16":
            import ml_dtypes  # noqa: PLC0415  (ships with jax)

            for d in fresh:
                d._feats[0] = d._feats[0].astype(ml_dtypes.bfloat16)
                d._quant_dtype, d._quant_scale = fd, 1.0
            return
        prev_scales = {
            d._quant_scale for d in datasets if getattr(d, "_quant_dtype", None)
        }
        if len(prev_scales) > 1:
            raise ValueError(
                f"shared datasets carry inconsistent quantization scales {prev_scales}"
            )
        if prev_scales:
            scale = prev_scales.pop()
        else:
            X = self._train_data._feats[0]
            scale = float(np.abs(X).max()) / 127.0
            if scale == 0.0:  # all-zero features: nothing to represent
                scale = 1.0
        for d in fresh:
            d._feats[0] = np.clip(
                np.rint(d._feats[0] / scale), -127, 127
            ).astype(np.int8)
            d._quant_dtype, d._quant_scale = fd, scale
        self.model = dataclasses.replace(self.model, feature_scale=scale)
        self._build_steps()

    # -- data ---------------------------------------------------------------
    def load_data(self, train: GlobalShardedData | None = None, test: GlobalShardedData | None = None, *, test_only: bool = False):
        """Load the data dir's splits.  ``test_only=True`` skips the
        train split entirely (eval-only workflows: the train ingest is
        the dominant I/O cost and evaluate_metrics never touches it) —
        float32 features only, since quantized dtypes derive their scale
        from the train split.

        One ``load_data`` span covers the call; inside it each file is a
        ``load_parse`` and a ``load_densify`` (or ``load_coo``), each
        split a ``load_pack``, and a quantized ``feature_dtype`` one
        ``load_cast``."""
        with trace_phase("load_data"):
            self._load_splits(train, test, test_only)
        return self

    def _load_splits(self, train, test, test_only: bool) -> None:
        if test_only:
            if train is not None:
                raise ValueError("test_only=True contradicts passing train data")
            if self.cfg.feature_dtype != "float32":
                raise ValueError(
                    "test_only loading requires feature_dtype='float32' "
                    "(quantization scales come from the train split)"
                )
        W = num_data_shards(self.mesh)
        multiclass = self.cfg.model in ("softmax", "sparse_softmax")
        sparse = self.cfg.model in ("sparse_lr", "sparse_softmax")
        if self.cfg.model == "blocked_lr":
            self._test_data = test or GlobalShardedData.from_raw_ctr_dir(
                self.cfg.data_dir, "test", W, self.cfg
            )
            if test_only:
                return
            self._train_data = train or GlobalShardedData.from_raw_ctr_dir(
                self.cfg.data_dir, "train", W, self.cfg
            )
            return
        if test_only:
            self._test_data = test or GlobalShardedData.from_data_dir(
                self.cfg.data_dir, "test", W, self.cfg.num_feature_dim,
                multiclass=multiclass, sparse=sparse, nnz_max=self.cfg.nnz_max,
            )
            return
        self._train_data = train or GlobalShardedData.from_data_dir(
            self.cfg.data_dir, "train", W, self.cfg.num_feature_dim,
            multiclass=multiclass, sparse=sparse, nnz_max=self.cfg.nnz_max,
        )
        self._test_data = test or GlobalShardedData.from_data_dir(
            self.cfg.data_dir, "test", W, self.cfg.num_feature_dim,
            multiclass=multiclass, sparse=sparse, nnz_max=self.cfg.nnz_max,
        )
        if self.cfg.feature_dtype != "float32" and not sparse:
            with trace_phase("load_cast"):
                self._quantize_features()
        elif any(
            getattr(d, "_quant_dtype", None)
            for d in (self._train_data, self._test_data)
        ):
            raise ValueError(
                "dataset was quantized by a previous Trainer; a "
                "feature_dtype='float32' run would train on raw quantized "
                "ints — reload the data or match feature_dtype"
            )

    # -- training -----------------------------------------------------------
    def init_weights(self):
        self.weights = self._shard_weights(self.model.init(self.cfg))
        return self.weights

    def fit(self, *, epochs: int | None = None, eval_fn=None, resume: bool = False):
        """Run the full training loop; returns final weights.

        ``eval_fn(epoch, accuracy)`` is called at each test interval
        (default: print the reference-format line).  With ``resume=True``
        and a configured ``checkpoint_dir``, training restarts from the
        latest saved epoch (the load path the reference never had).
        """
        cfg = self.cfg
        t_fit, samples_at_start = time.perf_counter(), self.timer.samples

        def fit_rate() -> float:
            """Rows this call has trained on over its wall so far, waits
            for data, evals and checkpoints included: the throughput a
            run is billed by (``StepTimer`` divides by time inside steps)."""
            wall = time.perf_counter() - t_fit
            return (self.timer.samples - samples_at_start) / wall if wall > 0 else 0.0

        if self._train_data is None:
            self.load_data()

        ckpt = None
        start_epoch = 0
        if cfg.checkpoint_dir:
            from distlr_tpu.train.checkpoint import Checkpointer  # noqa: PLC0415

            ckpt = Checkpointer(cfg.checkpoint_dir)
            if resume:
                state = ckpt.restore()
                if state is not None:
                    self.weights = self._shard_weights(
                        np.asarray(state["weights"]).reshape(
                            np.asarray(self.model.init(cfg)).shape
                        )
                    )
                    start_epoch = int(state["epoch"])
                    log.info("resumed from checkpoint at epoch %d", start_epoch)
        if self.weights is None:
            self.init_weights()
        epochs = cfg.num_iteration if epochs is None else epochs
        test_batch = None
        if self._test_data is not None:
            with _loop_span("eval_put"):
                test_batch = self._shard_batch(self._test_data.full_batch())

        # exceptions mid-training must not leak the profiler trace or the
        # checkpoint manager (pending async saves)
        with contextlib.ExitStack() as stack:
            if cfg.profile_dir:
                stack.enter_context(jax.profiler.trace(cfg.profile_dir))
            if ckpt is not None:
                stack.callback(ckpt.close)

            # an epoch's batches are counted ahead, so that no thread
            # pulls once more to find the epoch over: every span of the
            # loop belongs to a batch, and no step id is used twice
            steps_per_epoch = self._train_data.num_batches(cfg.batch_size)

            def host_batches(n):
                """``(step id, host batch)`` of every epoch this call
                runs, ids from ``n`` on: ``batch_slice`` is the numpy
                slice, pad and reshape of the next batch (a view on one
                chip, a copy on a mesh), on whichever thread pulls."""
                for _ in range(start_epoch, epochs):
                    epoch_batches = self._train_data.batches(
                        cfg.batch_size, wrap=bool(cfg.wrap_final_batch))
                    for _ in range(steps_per_epoch):
                        with _loop_span("batch_slice", n):
                            hb = next(epoch_batches)
                        yield n, hb
                        n += 1

            def put(item, pacer=None):
                n, hb = item
                # h2d is the host's part of the puts, on the producer's
                # thread its waits for its turn at the link included
                # (h2d_pace); the calls return with the copies still in
                # flight, and the rest is waited for in the consumer's
                # h2d_wait
                with _loop_span("h2d", n):
                    if pacer is not None:
                        pacer.step = n
                    return hb, self._shard_batch(hb, pacer)

            if cfg.prefetch > 1:
                # ONE producer for the whole call: it goes from an
                # epoch's last batch to the next epoch's first as from
                # any batch to the next, under an eval or a checkpoint
                # too.  batch_slice and h2d land on its timeline under
                # the step id of the batch they make — the trace shows
                # the overlap the prefetch buys
                pairs = _prefetch_to_device(
                    put, host_batches(self.batches_taken), cfg.prefetch - 1)
            else:  # prefetch=1: the strictly-serial reference shape
                pairs = (put(item)
                         for item in host_batches(self.batches_taken))
            # closing() runs the generator's finally DETERMINISTICALLY
            # when a step raises — relying on GC leaves the producer
            # thread blocked on the queue for as long as the caller
            # retains the exception traceback (which run_ps_workers
            # does), and a retried fit() would stack a second
            # producer on top.
            stack.enter_context(contextlib.closing(pairs))
            for epoch in range(start_epoch, epochs):
                for n in range(self.batches_taken,
                               self.batches_taken + steps_per_epoch):
                    # data_load = time this consumer spent WAITING for
                    # the next batch to be ON THE DEVICE: queue_wait
                    # until the producer hands it over (with
                    # prefetch=1, the slice and the dispatch
                    # themselves), h2d_wait until its copy has landed.
                    with _loop_span("data_load", n):
                        with _loop_span("queue_wait", n):
                            host_batch, batch = next(pairs)
                        with _loop_span("h2d_wait", n):
                            jax.block_until_ready(batch)
                    self.batches_taken = n + 1
                    # compute = dispatch of the step to its weights
                    # being ready, with the step's own batch resident.
                    # That is the step PLUS whatever the launch waits
                    # for on the device's side: on the TPU runtime a
                    # launch queues behind the copies put before it
                    # (the pieces the producer is ahead by and the one
                    # in flight, with the link busy under them: 42 of
                    # this span's 46 ms in the dense cell), so with
                    # copies in flight part of the wait for input is
                    # still in here (PERF.md section 5)
                    self.timer.start()
                    with _loop_span("compute", n, marks_step=True):
                        self.weights, step_metrics = self.train_step(self.weights, batch)
                        jax.block_until_ready(self.weights)
                    self.timer.stop(int(host_batch[-1].sum()))
                if test_batch is not None and cfg.test_interval > 0 and (epoch + 1) % cfg.test_interval == 0:
                    with _loop_span("eval"):
                        em = self.eval_step(self.weights, test_batch)
                        acc = float(em["accuracy"])
                    self.metrics.log(
                        epoch=epoch + 1,
                        accuracy=acc,
                        # the driver's parity metric (BASELINE.json
                        # epochs-to-logloss), logged at every eval
                        test_logloss=float(em["logloss"]),
                        loss=float(step_metrics["loss"]),
                        samples_per_sec=fit_rate(),
                        step_samples_per_sec=self.timer.samples_per_sec,
                    )
                    if eval_fn is not None:
                        eval_fn(epoch + 1, acc)
                    else:
                        log_eval_line(epoch + 1, acc)
                if (
                    ckpt is not None
                    and cfg.checkpoint_interval > 0
                    and (epoch + 1) % cfg.checkpoint_interval == 0
                ):
                    with _loop_span("checkpoint"):
                        ckpt.save(epoch + 1, self.weights, extra={"epoch": epoch + 1})
                # runtime introspection (obs.jaxrt): epoch-end compile-
                # cache deltas + throttled live device-buffer gauges
                for probe in self._jit_probes:
                    probe.tick()
                jaxrt.maybe_sample_device_bytes()

            if ckpt is not None and epochs > start_epoch and ckpt.latest_step() != epochs:
                with _loop_span("checkpoint"):
                    ckpt.save(epochs, self.weights, extra={"epoch": epochs})
        self.fit_samples_per_sec = fit_rate()
        return self.weights

    def evaluate(self) -> float:
        return self.evaluate_metrics()["accuracy"]

    def evaluate_metrics(self) -> dict:
        """Full-test-set ``{"accuracy", "logloss"}`` as Python floats."""
        with trace_phase("eval"):
            test_batch = self._shard_batch(self._test_data.full_batch())
            em = self.eval_step(self.weights, test_batch)
            return {k: float(v) for k, v in em.items()}

    def save_model(self, path: str | None = None) -> str:
        """Text export, reference format & layout: ``models/part-00{i+1}``
        with i = this host's process index — the reference's per-worker
        model files (Q8, ``src/main.cc:168-169``; single-process runs
        write ``part-001`` as before).  In a ``jax.distributed`` run each
        process exports the same replicated weights to its own file, so
        cross-process agreement is checkable from the artifacts."""
        if path is None:
            path = os.path.join(
                self.cfg.data_dir, "models", part_name(jax.process_index())
            )
            os.makedirs(os.path.dirname(path), exist_ok=True)
        save_model_text(path, np.asarray(self.weights))
        return path
