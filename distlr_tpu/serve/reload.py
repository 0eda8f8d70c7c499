"""Hot weight reload — keep a serving engine fresh while training runs.

Two weight sources behind one ``poll() -> (version, weights) | None``
interface:

* :class:`CheckpointWatcher` — watch an orbax checkpoint directory for
  new steps (the trainer's ``checkpoint_interval`` cadence); version is
  the checkpoint step.
* :class:`LivePSWatcher` — pull live weights from a running native KV
  server group through :class:`distlr_tpu.ps.KVWorker`, chunked keyed
  pulls for CTR-scale tables (``KVWorker.pull_chunked``).  Pulls don't
  vote in barriers or count as gradient pushes, so a trainer and a
  serving tier run against the SAME server group simultaneously — the
  whole point of continuous async training (PAPER.md): the model serving
  traffic is seconds old, not checkpoint-interval old.

  With a :class:`~distlr_tpu.serve.hotset.HotSetTracker` attached, polls
  refresh only the traffic's hot row slice (``pull_rows_into``) against
  a cached full table — at D=1M with a concentrated key distribution a
  refresh moves <1% of the full-table bytes.  Cold rows stay at their
  last full-refresh value (the staleness trade); a full refresh runs
  whenever tracker coverage drops below ``min_coverage`` or every
  ``full_refresh_every`` polls.

:class:`HotReloader` polls a source on a background thread and publishes
into ``engine.set_weights`` — an atomic reference swap the engine applies
between batches, so in-flight requests finish on the weights they
started with and nothing is dropped during a swap.  Poll timing is
JITTERED (``interval_s`` ± ``jitter``): N engine replicas launched
together would otherwise pull the PS in lockstep forever, stacking N
chunked table reads onto the same server receive loops at the same
instant every interval.
"""

from __future__ import annotations

import random

from distlr_tpu import sync

import numpy as np

from distlr_tpu.obs import dtrace
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.utils.logging import get_logger

log = get_logger(__name__)

_reg = get_registry()
_RELOADS = _reg.counter(
    "distlr_serve_reloads_total",
    "live-PS weight reloads by kind (full table vs hot working-set slice)",
    labelnames=("kind",),
)
_RELOAD_ROWS = _reg.counter(
    "distlr_serve_reload_rows_total",
    "parameter rows fetched by live-PS weight reloads",
    labelnames=("kind",),
)


class CheckpointWatcher:
    """Poll an orbax checkpoint dir; report each NEW latest step once."""

    def __init__(self, directory: str):
        self._dir = directory
        self._last_step: int | None = None

    def poll(self):
        from distlr_tpu.train.checkpoint import Checkpointer  # noqa: PLC0415

        with Checkpointer(self._dir) as ckpt:
            step = ckpt.latest_step()
            if step is None or step == self._last_step:
                return None
            state = ckpt.restore(step)
        self._last_step = step
        return step, np.asarray(state["weights"]).reshape(-1)

    def close(self) -> None:
        pass


class LivePSWatcher:
    """Pull the current weights from a live KV server group each poll.

    There is no server-side "new version" signal (the reference protocol
    has none); every poll returns the current table with a monotonically
    increasing local version, and the poll INTERVAL is the staleness
    bound.  ``vals_per_key``/``chunk_rows``: see
    :meth:`distlr_tpu.ps.KVWorker.pull_chunked`.

    ``hot_tracker``: a :class:`~distlr_tpu.serve.hotset.HotSetTracker`
    fed by the front-end; when set, polls refresh only the hot row slice
    into a cached table (see module docstring), falling back to a full
    refresh when ``coverage() < min_coverage``, every
    ``full_refresh_every`` polls (0 = never forced), or on the first
    poll (no cached table yet).
    """

    #: client_id for serving pulls — out of the way of trainer worker ranks
    SERVE_CLIENT_ID = 4095

    def __init__(self, hosts: str | None, dim: int, *, vals_per_key: int = 1,
                 chunk_rows: int = 1 << 16, timeout_ms: int = 10_000,
                 client_id: int | None = None, hot_tracker=None,
                 min_coverage: float = 0.95, full_refresh_every: int = 10,
                 retry=None, ns_base: int = 0,
                 ns_total_dim: int | None = None, route=None):
        from distlr_tpu.ps import KVWorker  # noqa: PLC0415

        # refused before a connection is opened
        if not 0.0 < min_coverage <= 1.0:
            raise ValueError(
                f"min_coverage must be in (0, 1], got {min_coverage}")
        if full_refresh_every < 0:
            raise ValueError(
                f"full_refresh_every must be >= 0, got {full_refresh_every}")
        self.hosts = hosts
        self.dim = dim
        #: multi-tenant namespace scoping (ISSUE 10): when the group
        #: hosts several model namespaces, ``ns_total_dim`` is the
        #: group's TOTAL key space and ``[ns_base, ns_base + dim)`` the
        #: slice this engine serves — every pull (full, chunked, and
        #: hot-slice) addresses only that slice, so N versions' watchers
        #: share one server group without reading each other's rows.
        self.ns_base = int(ns_base)
        self._wire_dim = int(ns_total_dim) if ns_total_dim else int(dim)
        if self.ns_base < 0 or self.ns_base + dim > self._wire_dim:
            raise ValueError(
                f"namespace [{ns_base}, {ns_base + dim}) outside the "
                f"group's key space [0, {self._wire_dim})")
        worker = KVWorker(
            hosts, self._wire_dim,
            client_id=self.SERVE_CLIENT_ID if client_id is None else client_id,
            timeout_ms=timeout_ms,
            # pull-only client: never votes in a BSP barrier, so the
            # async-group push shortcut flag is irrelevant either way
            sync_group=True,
            # pulls are idempotent, so a RetryPolicy rides every op: a
            # PS blip mid-poll costs a reconnect+retry INSIDE the poll
            # instead of failing the cycle
            retry=retry,
            # elastic fleet: with a membership route provider, serving
            # pulls follow a live reshard in-place (re-route, not a
            # dead watcher).  NB: a resize that breaks vals_per_key
            # range alignment falls back like construction did — equal
            # ranges over dim % (vpk * S) == 0 always stay aligned.
            route=route,
        )
        self.kv = (worker if self._wire_dim == dim and not self.ns_base
                   else worker.namespace(self.ns_base, dim))
        # A failed poll leaves the native handle poisoned (every later
        # op on that stream fails fast).  Without this flag the watcher
        # would be dead FOREVER after one blip — the server would serve
        # its last-good weights for the rest of its life while the PS
        # recovered minutes ago.  Set on poll failure; the next poll
        # reconnects first.
        self._needs_reconnect = False
        # re-verify initialization after every reconnect, not just at
        # bootstrap: the outage we just rode out may have been a full PS
        # replacement, and a freshly-spawned unseeded group serves zeros
        self._check_init = True
        #: requested row width — the unit the engine's row keys and the
        #: hot tracker are stated in, even when the wire falls back to
        #: flat keys below
        self.row_width = max(int(vals_per_key), 1)
        self.vals_per_key = self.row_width
        if self.vals_per_key > 1 and not self.kv.supports_vals_per_key(
                self.vals_per_key):
            # same fallback rule as the keyed trainer: rows that straddle
            # a range boundary ride flat keys, identical semantics
            log.info("serve pull: vals_per_key=%d rows straddle range "
                     "boundaries; using flat keys", self.vals_per_key)
            self.vals_per_key = 1
        self.chunk_rows = int(chunk_rows)
        self.hot_tracker = hot_tracker
        self.min_coverage = float(min_coverage)
        self.full_refresh_every = int(full_refresh_every)
        self._version = 0
        self._table: np.ndarray | None = None
        self._since_full = 0
        self.full_reloads = 0
        self.hot_reloads = 0
        self.last_kind: str | None = None
        self.last_rows = 0

    def _pull_full(self) -> np.ndarray:
        return self.kv.pull_chunked(
            vals_per_key=self.vals_per_key, chunk_rows=self.chunk_rows)

    def _hot_pull_keys(self, row_keys: np.ndarray) -> np.ndarray:
        """Tracker row ids -> the key space the wire actually uses: when
        vals_per_key fell back to flat keys, each R-lane row id expands
        to its R flat slots (ascending in, ascending out)."""
        if self.vals_per_key == self.row_width:
            return row_keys
        r = self.row_width
        return (row_keys[:, None] * r
                + np.arange(r, dtype=np.uint64)[None, :]).reshape(-1)

    def poll(self):
        if self._needs_reconnect:
            # rebuild the poisoned handle before touching the wire; a
            # still-down PS raises here and the reloader counts one more
            # degraded cycle (last-good weights keep serving)
            self.kv.reconnect()
            self._needs_reconnect = False
            self._check_init = True
        # each poll is its own distributed-trace root (deterministically
        # sampled, like requests), so the hot-reload leg — serving pulls
        # and the servers' kv.pull handler spans — shows up on the
        # merged timeline next to the request and feedback tracks
        ctx = dtrace.new_trace()
        try:
            with dtrace.use(ctx), dtrace.span(
                    "serve.reload", tags={"hosts": self.hosts}):
                return self._poll_inner()
        except OSError:
            self._needs_reconnect = True
            raise

    def _poll_inner(self):
        if self._check_init:
            # Initialization gate — at bootstrap AND after every
            # reconnect: an UNINITIALIZED rank answers pulls with zeros
            # (HandlePull), and publishing those would swap garbage into
            # the engine (at startup it would also make wait_for_weights
            # "succeed" on a group no trainer has seeded; after an
            # outage, the group we reconnected to may be a freshly
            # respawned unseeded replacement).  EVERY rank must be
            # seeded — one respawned-but-unseeded rank would zero its
            # whole key slice in an otherwise-valid pull.  Report
            # nothing instead — last-good weights keep serving, and the
            # startup timeout diagnoses "reachable but uninitialized"
            # via describe_unready.
            if not all(self.kv.stats(r).get("initialized")
                       for r in range(self.kv.num_servers)):
                return None
            self._check_init = False
        if self.hot_tracker is None:
            w = self._pull_full()
            self._version += 1
            self.full_reloads += 1
            self.last_kind, self.last_rows = "full", w.size // self.row_width
            _RELOADS.labels(kind="full").inc()
            _RELOAD_ROWS.labels(kind="full").inc(self.last_rows)
            return self._version, w
        full = (self._table is None
                or self.hot_tracker.coverage() < self.min_coverage
                or (self.full_refresh_every > 0
                    and self._since_full >= self.full_refresh_every))
        if full:
            self._table = np.ascontiguousarray(
                self._pull_full(), dtype=np.float32)
            self._since_full = 0
            self.full_reloads += 1
            rows = self._table.size // self.row_width
            # publish the snapshot so the coverage window restarts over
            # the fresh table (everything is hot right after a full pull)
            self.hot_tracker.hot_keys()
            kind = "full"
        else:
            keys = self._hot_pull_keys(self.hot_tracker.hot_keys())
            if keys.size == 0:
                # idle replica: nothing hot to refresh and the cached
                # table is already published — reporting a "new" version
                # here would make the reloader re-upload an identical
                # D-dim table to the device every poll
                return None
            pulled = self.kv.pull_rows_into(
                self._table, keys, vals_per_key=self.vals_per_key,
                chunk_rows=self.chunk_rows)
            rows = pulled if self.vals_per_key == self.row_width \
                else pulled // self.row_width
            self._since_full += 1
            self.hot_reloads += 1
            kind = "hot"
        self._version += 1
        self.last_kind, self.last_rows = kind, rows
        _RELOADS.labels(kind=kind).inc()
        _RELOAD_ROWS.labels(kind=kind).inc(rows)
        # hand out a COPY: the next hot poll scatters into self._table in
        # place, and jax.device_put of an aligned float32 host array can
        # be zero-copy — returning the live buffer would let in-flight
        # requests read torn, half-patched weights (the atomic-swap
        # contract says they finish on the weights they started with)
        return self._version, self._table.copy()

    def describe_unready(self) -> str:
        """One probe's diagnosis of WHY no weights came: "PS unreachable"
        (nothing listening / partitioned) reads very differently from
        "PS reachable but uninitialized" (servers up, no trainer init
        push yet) — a 30 s silent timeout used to collapse both."""
        from distlr_tpu.ps import KVWorker  # noqa: PLC0415

        try:
            # a FRESH short-lived probe: this watcher's own handle may be
            # poisoned by the very failure being diagnosed
            with KVWorker(self.hosts, self._wire_dim,
                          client_id=self.SERVE_CLIENT_ID,
                          timeout_ms=2000) as probe:
                # every rank, like the init gate: one unseeded rank is
                # enough to withhold weights, so one must be enough to
                # flip this diagnosis
                unseeded = [r for r in range(probe.num_servers)
                            if not probe.stats(r).get("initialized")]
        except OSError as e:
            return (f"PS unreachable at {self.hosts}: "
                    f"{type(e).__name__}: {e}")
        if unseeded:
            return (f"PS reachable at {self.hosts} but UNINITIALIZED "
                    f"(server rank(s) {unseeded} unseeded) — no trainer "
                    "has pushed initial weights there yet (training job "
                    "down, or a respawned rank awaiting re-seed?)")
        return (f"PS reachable and initialized at {self.hosts}; polls "
                "are failing for another reason (see reload warnings)")

    def stats(self) -> dict:
        rec = {
            "mode": "hot" if self.hot_tracker is not None else "full",
            "full_reloads": self.full_reloads,
            "hot_reloads": self.hot_reloads,
            "last_kind": self.last_kind,
            "last_rows": self.last_rows,
        }
        if self.ns_base or self._wire_dim != self.dim:
            rec["namespace"] = [self.ns_base, self.dim, self._wire_dim]
        if self.hot_tracker is not None:
            rec["hot_set"] = self.hot_tracker.stats()
        return rec

    def close(self) -> None:
        self.kv.close()


class HotReloader:
    """Background poller: source -> ``engine.set_weights`` swaps.

    Poll errors are counted and logged, never fatal — a serving tier must
    keep answering on its last good weights when the trainer's PS group
    restarts or the checkpoint dir is mid-write (both sources' errors are
    transient by design).  While degraded, each failing poll cycle logs
    ONE rate-limited warning (at most one per ``warn_every_s``), and
    recovery logs once — silence used to be indistinguishable from
    health.

    Each wait is drawn from ``interval_s * (1 ± jitter)`` so replicas
    launched together DESYNCHRONIZE instead of pulling the PS in
    lockstep forever (each reloader seeds its own RNG); ``jitter=0``
    restores the fixed cadence.
    """

    #: floor between degraded-cycle warnings (seconds)
    warn_every_s = 10.0

    def __init__(self, engine, source, *, interval_s: float = 1.0,
                 jitter: float = 0.2, _seed: int | None = None):
        if interval_s <= 0:
            raise ValueError(f"interval_s must be positive, got {interval_s}")
        if not 0.0 <= jitter < 1.0:
            raise ValueError(f"jitter must be in [0, 1), got {jitter}")
        self.engine = engine
        self.source = source
        self.interval_s = float(interval_s)
        self.jitter = float(jitter)
        self._rng = random.Random(_seed)
        self.reloads = 0
        self.errors = 0
        self.last_version = None
        self._degraded_since: float | None = None
        self._last_warn = float("-inf")
        self._stop = sync.Event()
        # serializes source.poll(): wait_for_weights (caller thread) can
        # overlap the background loop, and sources keep per-poll state
        self._poll_lock = sync.Lock()
        self._thread = sync.Thread(
            target=self._run, daemon=True, name="distlr-hot-reload"
        )

    def _next_wait(self) -> float:
        if not self.jitter:
            return self.interval_s
        return self.interval_s * (
            1.0 + self.jitter * (2.0 * self._rng.random() - 1.0))

    def _poll_once(self) -> bool:
        with self._poll_lock:
            try:
                got = self.source.poll()
            except Exception as e:
                self.errors += 1
                now = sync.monotonic()
                if self._degraded_since is None:
                    self._degraded_since = now
                # one warning per degraded poll cycle, rate-limited: a
                # 100-cycle outage logs ~outage/warn_every_s lines, not
                # 100 and not (the old behavior past error #100) zero
                if now - self._last_warn >= self.warn_every_s:
                    self._last_warn = now
                    log.warning(
                        "weight source poll DEGRADED for %.0fs (%d errors; "
                        "serving last-good weights%s): %s",
                        now - self._degraded_since, self.errors,
                        f", version {self.last_version}"
                        if self.last_version is not None else " — none yet",
                        e)
                return False
            if got is None:
                if self._degraded_since is not None:
                    # transport is back but the source still has nothing
                    # to publish (e.g. the replacement PS group is up but
                    # unseeded): that is NOT recovery — keep the degraded
                    # clock running and keep warning, rate-limited, or
                    # the log would read "recovered" while the engine
                    # serves stale last-good weights indefinitely
                    now = sync.monotonic()
                    if now - self._last_warn >= self.warn_every_s:
                        self._last_warn = now
                        log.warning(
                            "weight source DEGRADED for %.0fs (%d errors; "
                            "transport answered but published no weights "
                            "— serving last-good%s)",
                            now - self._degraded_since, self.errors,
                            f", version {self.last_version}"
                            if self.last_version is not None
                            else ", none yet")
                return False
            if self._degraded_since is not None:
                log.info("weight source recovered after %.0fs degraded "
                         "(%d errors total)",
                         sync.monotonic() - self._degraded_since, self.errors)
                self._degraded_since = None
                self._last_warn = float("-inf")
            version, weights = got
            self.engine.set_weights(weights)
            self.reloads += 1
            self.last_version = version
            return True

    def _run(self):
        while not self._stop.wait(self._next_wait()):
            self._poll_once()

    def start(self) -> "HotReloader":
        self._thread.start()
        return self

    def wait_for_weights(self, timeout_s: float = 30.0) -> None:
        """Block until the engine has weights (first successful poll) —
        the serve front-end's startup gate when no initial weights were
        given."""
        deadline = sync.monotonic() + timeout_s
        while not self.engine.has_weights:
            if self._poll_once():
                return
            if sync.monotonic() >= deadline:
                # Name WHY (satellite of ISSUE 5): "PS unreachable" and
                # "PS reachable but uninitialized" both used to read as
                # the same 30 s silence — the operator's next move is
                # completely different for the two.
                detail = ""
                describe = getattr(self.source, "describe_unready", None)
                if callable(describe):
                    try:
                        detail = f": {describe()}"
                    except Exception as e:  # the diagnosis must not mask
                        detail = f" (diagnosis failed: {e})"
                raise TimeoutError(
                    f"no weights from {type(self.source).__name__} within "
                    f"{timeout_s:.0f}s{detail}"
                )
            sync.sleep(min(self.interval_s, 0.2))

    def stats(self) -> dict:
        rec = {
            "reloads": self.reloads,
            "reload_errors": self.errors,
            "last_version": self.last_version,
            "interval_s": self.interval_s,
        }
        source_stats = getattr(self.source, "stats", None)
        if callable(source_stats):
            rec["source"] = source_stats()
        return rec

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=10.0)
        self.source.close()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
