"""Continuous (online) trainer — train on what you serve.

The third quarter of the loop: a long-running Hogwild worker that
consumes joined training shards (:mod:`distlr_tpu.feedback.join`
output, the repo's libsvm grammar) AS THEY APPEAR and pushes gradients
into the same live PS group the serving engines hot-reload from
(``launch serve --ps-hosts``).  There are no epochs and no exit
barrier: the trainer never votes in barriers, never retires the group,
and tolerates the servers' other clients (the serving tier's pulls, a
batch trainer's pushes) by construction — it is just one more async
client of the Hogwild PS (the lock-free continuous-update regime of
arXiv:1508.05711).

AdaBatch-style local accumulation (arXiv:1712.02029) rides the shared
:class:`~distlr_tpu.compress.GradientAccumulator` (extracted from this
module once the batch trainers adopted the pattern): gradients are
accumulated locally and pushed as a mean every ``k`` batches, with
``k`` GROWING on a schedule (multiply by ``accum_growth`` every
``accum_growth_every`` pushes, capped at ``accum_max``).  Early in the
loop's life small ``k`` keeps served weights fresh; as the model
stabilizes, growing ``k`` cuts push traffic — the cadence axis of the
communication dial whose encoding axis is ``cfg.ps_compress`` (the
negotiated wire codec; this trainer's pushes ride it too).

Multi-worker sharding: any number of online trainers may share one
shard dir.  A trainer takes a shard by atomically renaming it to
``<shard>.claim`` (exactly one rename wins; losers skip), consumes it,
then retires it to ``<shard>.done`` — and a ``.claim`` whose owner died
is reclaimed after ``claim_stale_s`` (claim time is the file's mtime,
touched at claim).  ``claim_stale_s`` must exceed the worst-case
consume time of one shard, or a slow-but-alive worker's shard gets
double-trained (Hogwild-tolerable, but logged).

Requires an ASYNC server group: against a sync (BSP) group a lone
online push would block forever in the deferred-reply barrier.
"""

from __future__ import annotations

import json
import os

import numpy as np

from distlr_tpu import sync
from distlr_tpu.compress import GradientAccumulator
from distlr_tpu.config import Config
from distlr_tpu.models import host_math
from distlr_tpu.obs import dtrace
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.ps import KVWorker, RetryPolicy
from distlr_tpu.train.ps_trainer import RowKeys, keyed_model, ps_param_dim
from distlr_tpu.utils.logging import get_logger

log = get_logger(__name__)

_reg = get_registry()
_SHARDS_CONSUMED = _reg.counter(
    "distlr_feedback_shards_consumed_total",
    "joined training shards consumed by the online trainer",
)
_EXAMPLES = _reg.counter(
    "distlr_feedback_examples_trained_total",
    "joined examples the online trainer computed gradients over",
)
_PUSHES = _reg.counter(
    "distlr_feedback_online_pushes_total",
    "gradient pushes issued by the online trainer (after AdaBatch "
    "local accumulation)",
)
_LAG = _reg.gauge(
    "distlr_feedback_shard_lag",
    "joined shards written but not yet consumed by the online trainer "
    "(the loop's freshness debt)",
)
_ACCUM_K = _reg.gauge(
    "distlr_feedback_accum_batches",
    "current AdaBatch accumulation span: batches per push",
)

#: models the online loop supports: dense full-vector pushes
#: (binary_lr / softmax), keyed sparse pushes (sparse_lr), and keyed
#: per-class rows (sparse_softmax — each feature key owns its
#: num_classes lanes, pushed vals_per_key=K when the group's range
#: boundaries align, expanded per-lane keys otherwise)
_SUPPORTED = ("binary_lr", "softmax", "sparse_lr", "sparse_softmax")


class OnlineTrainer:
    """Shard-watching Hogwild worker over a live async PS group."""

    #: client id: out of the way of batch-trainer ranks (0..) and the
    #: serving pull client (4095)
    ONLINE_CLIENT_ID = 0x0E00

    def __init__(self, cfg: Config, hosts: str, shard_dir: str, *,
                 accum_start: int = 1, accum_growth: float = 2.0,
                 accum_growth_every: int = 32, accum_max: int = 64,
                 poll_interval_s: float = 0.5, idle_flush_s: float = 2.0,
                 client_id: int | None = None, seed_init: bool = True,
                 worker_id: int = 0, claim_stale_s: float = 300.0,
                 ns_base: int = 0, ns_total_dim: int | None = None,
                 route=None):
        if cfg.model == "blocked_lr":
            # named rejection, not a generic unsupported-model error: the
            # blocked path's raw-CTR hashing happens at shard INGEST
            # (write_raw_ctr_shards) while feedback shards carry already-
            # hashed libsvm rows — re-deriving the grouped (R, groups)
            # row layout from them is not possible, so blocked models
            # keep training through `launch ps`
            raise ValueError(
                "online training does not support blocked_lr: feedback "
                "shards are hashed libsvm rows, but blocked_lr's grouped "
                "row layout is only derivable from RAW categorical "
                "shards at ingest time — train blocked models with "
                "`launch ps` on raw-CTR data instead")
        if cfg.model not in _SUPPORTED:
            raise ValueError(
                f"online training supports {_SUPPORTED}, got {cfg.model!r}")
        if worker_id < 0:
            raise ValueError(f"worker_id must be >= 0, got {worker_id}")
        self.cfg = cfg
        self.shard_dir = shard_dir
        self.dim = ps_param_dim(cfg)
        self.poll_interval_s = float(poll_interval_s)
        self.idle_flush_s = float(idle_flush_s)
        self.worker_id = int(worker_id)
        self.claim_stale_s = float(claim_stale_s)
        #: multi-tenant namespace scoping (ISSUE 10): when the group
        #: hosts several model namespaces, train only the slice
        #: ``[ns_base, ns_base + dim)`` — each tenant's online trainer
        #: watches its own shard subdir and pushes into its own
        #: namespace of the shared group
        wire_dim = int(ns_total_dim) if ns_total_dim else self.dim
        worker = KVWorker(
            hosts, wire_dim,
            client_id=self.ONLINE_CLIENT_ID + worker_id if client_id is None
            else client_id,
            timeout_ms=cfg.ps_timeout_ms,
            sync_group=False,  # Hogwild client: no barriers, keyed shortcut
            retry=RetryPolicy.from_config(cfg),
            compress=cfg.ps_compress,
            # elastic fleet: with a membership route provider (`launch
            # online --ps-ctl`), a live reshard costs this trainer one
            # routing re-negotiation — never a restart
            route=route,
        )
        self.kv = (worker if wire_dim == self.dim and not ns_base
                   else worker.namespace(int(ns_base), self.dim))
        if seed_init:
            # idempotent: seeds an unseeded group with zeros (FTRL's
            # natural origin), no-ops against live weights — so the
            # online trainer can be the loop's FIRST trainer or join an
            # already-trained group without a flag.  (In a multi-
            # namespace group the first namespace's seed initializes the
            # whole table to zeros — later namespaces' no-ops land on
            # the same zeros.)
            self.kv.push_init(np.zeros(self.dim, np.float32))
        self._accum = GradientAccumulator(
            self.dim, start=accum_start, growth=accum_growth,
            growth_every=accum_growth_every, max_k=accum_max,
            gauge=_ACCUM_K)
        self._w_cache: np.ndarray | None = None
        self.shards_consumed = 0
        self.examples = 0
        self.pushes = 0
        self._num_classes = (cfg.num_classes
                             if cfg.model in ("softmax", "sparse_softmax")
                             else None)
        # keyed models: the gradient over a batch's unique rows, and how
        # the connection addresses them (sparse_softmax: one feature key
        # owns K class lanes; the keyed trainers' one rule, ``RowKeys``)
        self._rows = self._keyed_grad = None
        keyed = keyed_model(cfg)
        if keyed is not None:
            self._rows = RowKeys(self.kv, keyed[0])
            self._keyed_grad = keyed[1]

    @property
    def accum_k(self) -> int:
        """Current AdaBatch span (batches per push)."""
        return self._accum.k

    # -- gradient plumbing -------------------------------------------------
    def _dense_batch(self, X, y) -> None:
        cfg = self.cfg
        if self._accum.batches == 0:
            # pull once per accumulation span: batches within a span ride
            # the same weights (AdaBatch local accumulation; the span is
            # the self-staleness bound)
            self._w_cache = self.kv.pull()
        K = self._num_classes
        w = (self._w_cache.reshape(cfg.num_feature_dim, K) if K
             else self._w_cache)
        g = host_math.dense_grad(w, X, y, np.ones(len(y), np.float32),
                                 cfg.l2_c, bool(cfg.l2_scale_by_batch), K)
        self._accum.add(g)

    def _keyed_batch(self, pc, pv, y) -> None:
        """Pull the batch's unique rows, accumulate their gradient at the
        connection's own key granularity (a sparse_softmax feature key
        owns its K class lanes of the row-major (D, K) table)."""
        cfg, rows = self.cfg, self._rows
        ub, pos = np.unique(pc, return_inverse=True)
        keys = rows.keys(ub)
        w_u = self.kv.pull(keys=keys, vals_per_key=rows.vpk)
        if rows.width > 1:
            w_u = w_u.reshape(-1, rows.width)
        g_u = self._keyed_grad(
            w_u, pos.reshape(pc.shape), pv, y, np.ones(len(y), np.float32),
            cfg.l2_c, bool(cfg.l2_scale_by_batch))
        self._accum.add_rows(keys, g_u, rows.vpk)

    def _flush_push(self) -> None:
        """Push the accumulated MEAN gradient (one Hogwild update of
        batch size span*B); the accumulator advances its own AdaBatch
        schedule per flush."""
        vpk = 1 if self._rows is None else self._rows.vpk
        if self._rows is None:
            keys, g = None, self._accum.flush_dense()
        else:
            keys, g = self._accum.flush_keyed(vpk) or (None, None)
        if g is None:
            return
        # async Hogwild: a keyed span that cancelled to zeros pushes nothing
        if keys is None or keys.size:
            self.kv.wait(self.kv.push(g, keys=keys, vals_per_key=vpk))
        self._w_cache = None
        self.pushes += 1
        _PUSHES.inc()

    # -- shard consumption -------------------------------------------------
    def _scan(self) -> list[str]:
        # ".libsvm.claim" / ".libsvm.done" fail the endswith filter, so
        # the scan (and the lag gauge) only ever see unclaimed work
        try:
            names = sorted(os.listdir(self.shard_dir))
        except OSError:
            return []
        return [os.path.join(self.shard_dir, n) for n in names
                if n.startswith("shard-") and n.endswith(".libsvm")]

    def _claim(self, path: str) -> str | None:
        """Take exclusive ownership of a shard via the ``.claim`` rename
        protocol: the atomic rename is the lock (exactly one of N
        workers wins; losers get ENOENT and move on).  The claim
        file's mtime records CLAIM time."""
        claim = path + ".claim"
        # Fresh mtime BEFORE the claim becomes visible: rename preserves
        # the shard's own (arbitrarily old) mtime, and a claim that is
        # born looking stale can be stolen back by a peer's
        # _reclaim_stale before our utime lands — then consume crashes
        # on the vanished file instead of losing the race cleanly.
        try:
            os.utime(path)
        except OSError:
            return None  # shard vanished (a peer claimed or consumed it)
        try:
            os.rename(path, claim)
        except OSError:
            return None  # a peer worker won the race (or shard vanished)
        return claim

    def _reclaim_stale(self) -> None:
        """Return orphaned claims to the pool: a worker that died
        mid-shard leaves a ``.claim`` nobody will finish; after
        ``claim_stale_s`` (measured from claim time) any worker renames
        it back.  Racing reclaimers are safe — one rename wins."""
        if self.claim_stale_s <= 0:
            return
        try:
            names = os.listdir(self.shard_dir)
        except OSError:
            return
        now = sync.wall()
        for nm in names:
            if not nm.endswith(".libsvm.claim"):
                continue
            p = os.path.join(self.shard_dir, nm)
            try:
                if now - os.path.getmtime(p) < self.claim_stale_s:
                    continue
                os.rename(p, p[:-len(".claim")])
            except OSError:
                continue  # raced a peer reclaimer, or owner just finished
            log.warning("online[%d]: reclaimed stale claim %s (owner "
                        "presumed dead)", self.worker_id, nm)

    @staticmethod
    def _sidecar_path(path: str) -> str:
        """Trace sidecar of a shard (written by the joiner before the
        shard became visible).  ``path`` may be the claimed name — the
        sidecar always lives next to the ORIGINAL shard name."""
        if path.endswith(".claim"):
            path = path[:-len(".claim")]
        return path + ".trace"

    def _shard_traces(self, path: str) -> list:
        """Distinct trace contexts the shard's records carried, in
        first-appearance order ([] = untraced shard / no sidecar)."""
        try:
            with open(self._sidecar_path(path)) as f:
                tokens = json.load(f)
        except (OSError, ValueError):
            return []
        out, seen = [], set()
        for tok in tokens:
            if not tok or tok in seen:
                continue
            seen.add(tok)
            try:
                out.append(dtrace.parse_token(tok))
            except ValueError:
                continue
        return out

    def consume_shard(self, path: str) -> int:
        """Train over one joined shard; returns examples consumed.

        Distributed tracing: the consume interval runs under the FIRST
        trace the shard carried (so this shard's flush pushes — and the
        servers' apply spans under them — chain back to that request's
        score->label->join timeline), and is retrospectively attributed
        to every OTHER trace in the shard's sidecar."""
        from distlr_tpu.data.hashing import csr_to_padded_coo  # noqa: PLC0415
        from distlr_tpu.data.libsvm import parse_libsvm_lines  # noqa: PLC0415

        with open(path) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        if not lines:
            return 0
        traces = self._shard_traces(path)
        shard = os.path.basename(path)
        cfg = self.cfg
        B = cfg.batch_size if cfg.batch_size > 0 else 256
        n = 0
        t0_wall, t0 = sync.wall(), sync.monotonic()
        with dtrace.use(traces[0] if traces else None), dtrace.span(
                "online.consume",
                tags={"shard": shard, "records": len(lines),
                      "worker": self.worker_id}):
            if self._rows is not None:
                (row_ptr, cols, vals), y = parse_libsvm_lines(
                    lines, cfg.num_feature_dim, dense=False,
                    multiclass=cfg.model == "sparse_softmax")
                feats = csr_to_padded_coo(row_ptr, cols, vals,
                                          nnz_max=cfg.nnz_max)
                batch_fn = self._keyed_batch
            else:
                X, y = parse_libsvm_lines(
                    lines, cfg.num_feature_dim, dense=True,
                    multiclass=self._num_classes is not None)
                feats, batch_fn = (X,), self._dense_batch
            for lo in range(0, len(y), B):
                yb = y[lo:lo + B]
                batch_fn(*(a[lo:lo + B] for a in feats), yb)
                self.examples += len(yb)
                _EXAMPLES.inc(len(yb))
                if self._accum.ready:
                    self._flush_push()
                n += len(yb)
        dur = sync.monotonic() - t0
        for ctx in traces[1:]:
            # the other traces coalesced into this shard each get the
            # same interval attributed (ring + journal), so "where did
            # my label go" has an answer for every request
            dtrace.record_span("online.consume", ctx, t0_wall, dur,
                               tags={"shard": shard, "shared": True})
        self.shards_consumed += 1
        _SHARDS_CONSUMED.inc()
        return n

    # -- the loop ----------------------------------------------------------
    def run(self, *, stop: sync.Event | None = None,
            max_shards: int = 0, idle_exit_s: float | None = None) -> dict:
        """Consume shards until ``stop`` is set, ``max_shards`` shards
        were trained (0 = unbounded), or nothing new appeared for
        ``idle_exit_s`` seconds (None = wait forever) — the latter two
        are the scriptable exits benches and tests use; production runs
        pass neither and live as long as the serving tier."""
        stop = stop or sync.Event()
        idle_since = sync.monotonic()
        consumed_this_run = 0
        while not stop.is_set():
            # every cycle, not just idle ones: under sustained traffic
            # `pending` may never drain, and a dead peer's orphaned
            # .claim must still re-pool (its shard re-enters next scan)
            self._reclaim_stale()
            pending = self._scan()
            _LAG.set(len(pending))
            if not pending:
                now = sync.monotonic()
                if (self._accum.batches
                        and now - idle_since >= self.idle_flush_s):
                    # traffic lull: a partial accumulation span must not
                    # strand its gradients locally forever
                    self._flush_push()
                if idle_exit_s is not None and now - idle_since >= idle_exit_s:
                    break
                stop.wait(self.poll_interval_s)
                continue
            for path in pending:
                if stop.is_set():
                    break
                claimed = self._claim(path)
                if claimed is None:
                    continue  # a peer worker owns this shard
                try:
                    n = self.consume_shard(claimed)
                except FileNotFoundError:
                    # claim outlived claim_stale_s before we opened it
                    # and a peer reclaimed: the shard re-pooled, a live
                    # worker owns it — lose the race, don't die
                    log.warning(
                        "online[%d]: claim on %s stolen before consume "
                        "(raise claim_stale_s?)", self.worker_id,
                        os.path.basename(path))
                    continue
                # consumed shards step aside (audit trail kept), so the
                # scan and the lag gauge only ever see fresh work; the
                # trace sidecar retires with its shard
                try:
                    os.replace(claimed, path + ".done")
                    side = self._sidecar_path(path)
                    if os.path.exists(side):
                        os.replace(side, side + ".done")
                except OSError:
                    # our claim outlived claim_stale_s and a peer
                    # reclaimed it mid-consume: the shard may train
                    # twice — Hogwild-tolerable, but worth a line
                    log.warning("online[%d]: claim on %s expired while "
                                "consuming (raise claim_stale_s?)",
                                self.worker_id, os.path.basename(path))
                idle_since = sync.monotonic()
                consumed_this_run += 1
                log.info("online[%d]: consumed %s (%d examples, k=%d, "
                         "%d pushes)", self.worker_id,
                         os.path.basename(path), n,
                         self.accum_k, self.pushes)
                if max_shards and consumed_this_run >= max_shards:
                    self._flush_push()
                    _LAG.set(len(self._scan()))
                    return self.stats()
        self._flush_push()
        return self.stats()

    def stats(self) -> dict:
        return {
            "shards_consumed": self.shards_consumed,
            "examples": self.examples,
            "pushes": self.pushes,
            "accum_k": self.accum_k,
            "pending": len(self._scan()),
        }

    def close(self) -> None:
        self.kv.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
