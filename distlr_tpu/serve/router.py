"""Serving-tier routing front-end — one listener over N engine replicas.

The piece that turns "a scoring process" into "a serving tier": Hogwild
training tolerates slightly-stale replicas (arXiv:1508.05711), so N
independently-reloading :class:`~distlr_tpu.serve.server.ScoringServer`
replicas can answer the same traffic — this router is the control plane
that lets them die, reload, and rejoin under live load without the
front-end dropping accepted requests.

Speaks exactly the replica line protocol (libsvm line / JSON batch /
``STATS``), so clients cannot tell a router from a single engine:

* **load balancing** — least-in-flight among healthy replicas, rotated
  tie-break so idle-time traffic still spreads.
* **admission control** — a bounded per-replica in-flight budget
  (``max_inflight``); a request that finds every HEALTHY replica's
  budget full gets an explicit ``ERR SHED`` reply and ticks
  ``distlr_route_shed_total`` (overload = scale up), while a tier with
  zero healthy replicas answers ``ERR ROUTE`` and ticks the error
  counter (outage = page someone).  Never a silent hang: every
  accepted byte is answered or refused loudly.
* **failure detection** — passive (``eject_after`` consecutive
  transport failures ejects a replica from rotation) and active
  (periodic ``STATS`` probes catch a silently-dead replica without
  traffic); ejected replicas are probed on exponential backoff and
  reinstated on the first success.
* **retry-once failover** — scoring is idempotent, so a request whose
  replica dies mid-exchange is transparently retried on another replica
  (once); application-level ``ERR`` replies from a replica (malformed
  input) pass through untouched — they are deterministic, not failures.
* **label fan-out** — a ``LABEL <id> <y>`` feedback line
  (:mod:`distlr_tpu.feedback`) is BROADCAST to every healthy replica:
  only the replica that scored request ``id`` holds its spool entry,
  and the router deliberately does not track which one that was (ids
  are caller-minted; tracking them would make the router stateful).
  The reply is the best outcome any replica reported (``joined`` >
  ``duplicate`` > ``pending``); replicas that never saw the id answer
  ``pending`` and age the orphan label out of their window.  A
  ``MODEL``-scoped connection fans only to that model's replicas.
* **multi-tenant model registry** (additive, like STATS/TRACE) — the
  replica spec may name several model versions
  (``v1=h:p+h:p,v2=h:p``, :func:`distlr_tpu.serve.tenant.
  parse_model_spec`); requests address a version by ``MODEL <id>``
  connection scoping or a per-request ``@<id>`` prefix, each tenant
  can carry a token-bucket admission quota (``ERR SHED tenant`` —
  its own counter, distinct from capacity sheds), a SHADOW mirror
  (a fraction of the tenant's traffic replayed fire-and-forget
  against a candidate version, score distributions compared via PSI,
  never touching the primary reply), and a SPLIT (the canary ramp's
  weighted primary/candidate routing, driven by ``launch rollout``
  over the same line protocol: ``SPLIT``/``SHADOW``/``PROMOTE``/
  ``MODELS`` admin lines).

Stdlib-only and jax-free: ``python -m distlr_tpu.launch route`` starts
in well under a second and never competes with replicas for a chip.
"""

from __future__ import annotations

import json
import random
import socket
import socketserver
from distlr_tpu import sync
from distlr_tpu.obs import dtrace
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.serve import balance as _balance
from distlr_tpu.serve import tenant as _tenant
from distlr_tpu.utils.logging import get_logger

log = get_logger(__name__)

_reg = get_registry()
_REQ_SECONDS = _reg.histogram(
    "distlr_route_request_seconds",
    "wall seconds per routed request line (admission to reply, incl. "
    "retries)", labelnames=("listener",),
)
_REQUESTS = _reg.counter(
    "distlr_route_requests_total",
    "request lines answered from a replica", labelnames=("listener",),
)
_ERRORS = _reg.counter(
    "distlr_route_errors_total",
    "accepted request lines that failed on every tried replica",
    labelnames=("listener",),
)
_SHED = _reg.counter(
    "distlr_route_shed_total",
    "request lines shed at admission (no healthy replica with a free "
    "in-flight slot)", labelnames=("listener",),
)
_RETRIES = _reg.counter(
    "distlr_route_retries_total",
    "transparent retries on another replica after a transport failure",
    labelnames=("listener",),
)
_REPLICA_UP = _reg.gauge(
    "distlr_route_replica_up",
    "1 while the replica is in rotation (0 = ejected)",
    labelnames=("replica",),
)
_REPLICA_INFLIGHT = _reg.gauge(
    "distlr_route_replica_inflight",
    "requests currently in flight to the replica", labelnames=("replica",),
)
_EJECTIONS = _reg.counter(
    "distlr_route_ejections_total",
    "replica ejections after consecutive transport failures",
    labelnames=("replica",),
)
_REINSTATES = _reg.counter(
    "distlr_route_reinstates_total",
    "ejected replicas reinstated by a successful backoff probe",
    labelnames=("replica",),
)
_EJECT_SUPPRESSED = _reg.counter(
    "distlr_route_eject_suppressed_total",
    "ejections suppressed by the last-healthy floor (the replica "
    "crossed eject_after consecutive failures but is the only healthy "
    "replica left in one of its model pools)",
    labelnames=("replica",),
)
_LABELS = _reg.counter(
    "distlr_route_labels_total",
    "LABEL feedback lines fanned out to replicas, by best outcome "
    "(joined/duplicate/pending/failed)",
    labelnames=("listener", "outcome"),
)


class _Replica:
    """One engine replica: address, bounded in-flight budget, a pool of
    persistent connections, and health state (owned by the router's
    health lock except for the connection pool's own lock)."""

    def __init__(self, addr: str, *, max_inflight: int, timeout_s: float):
        host, _, port = addr.rpartition(":")
        if not host or not port.isdigit():
            raise ValueError(f"replica must be host:port, got {addr!r}")
        if "[" in host or "]" in host or ":" in host:
            # fail at construction, not as per-request gaierrors after
            # the router already announced ROUTING
            raise ValueError(
                f"IPv6 replica addresses are not supported, got {addr!r} "
                "(use a hostname or IPv4 host:port)")
        self.addr = addr
        self.host, self.port = host, int(port)
        self.timeout_s = timeout_s
        #: model ids this address is registered under (multi-tenant):
        #: an address under SEVERAL ids hosts multiple engines and gets
        #: @-addressed lines; an address under exactly one id serves
        #: that model as its default engine and gets bare lines — so
        #: pre-tenant replicas interop byte-identically
        self.models: set[str] = set()
        self._sem = sync.BoundedSemaphore(max_inflight)
        self._pool_lock = sync.Lock()
        self._idle: list[tuple] = []
        self.healthy = True
        self.consecutive_errors = 0
        self.inflight = 0
        self.requests = 0
        self.errors = 0
        self.ejections = 0
        self.reinstates = 0
        self.backoff_s = 0.0
        self.next_probe_at = 0.0
        self.last_ok = 0.0      # monotonic: last successful exchange/probe
        self.last_probe = 0.0
        self._up_g = _REPLICA_UP.labels(replica=addr)
        self._inflight_g = _REPLICA_INFLIGHT.labels(replica=addr)
        self._up_g.set(1.0)
        self._inflight_g.set(0.0)

    # -- in-flight budget (admission control) -----------------------------
    def try_acquire(self) -> bool:
        if self._sem.acquire(blocking=False):
            self.inflight += 1
            self._inflight_g.inc()
            return True
        return False

    def release(self) -> None:
        self.inflight -= 1
        self._inflight_g.dec()
        self._sem.release()

    # -- connection pool ---------------------------------------------------
    def _dial(self):
        s = socket.create_connection((self.host, self.port),
                                     timeout=self.timeout_s)
        return s, s.makefile("rwb")

    def _checkin(self, conn) -> None:
        with self._pool_lock:
            if self.healthy:
                self._idle.append(conn)
                return
        self._close(conn)

    @staticmethod
    def _close(conn) -> None:
        sock, f = conn
        for closer in (f.close, sock.close):
            try:
                closer()
            except OSError:
                pass

    def drain_pool(self) -> None:
        with self._pool_lock:
            idle, self._idle = self._idle, []
        for conn in idle:
            self._close(conn)

    def _roundtrip(self, conn, line: str) -> str:
        sock, f = conn
        f.write((line + "\n").encode())
        f.flush()
        reply = f.readline()
        if not reply:
            raise ConnectionError(
                f"replica {self.addr} closed the connection")
        return reply.decode().rstrip("\n")

    def exchange(self, line: str) -> str:
        """One request/reply toward this replica.  Raises on transport
        failure (the retry/eject trigger); an ``ERR ...`` reply from the
        replica is a successful exchange.

        A failure on a POOLED connection is retried once on a freshly
        dialed one before it propagates: an idle socket gone stale (the
        replica restarted cleanly between bursts) is evidence about the
        socket, not the replica — without this, ``eject_after`` stale
        pool entries would eject a healthy replica.  Scores are
        idempotent, so the maybe-delivered first write is safe to
        resend."""
        conn = None
        with self._pool_lock:
            if self._idle:
                conn = self._idle.pop()
        if conn is not None:
            try:
                reply = self._roundtrip(conn, line)
            except Exception:
                self._close(conn)
                conn = None  # stale pooled socket: fall through to a dial
            else:
                self._checkin(conn)
                return reply
        conn = self._dial()
        try:
            reply = self._roundtrip(conn, line)
        except Exception:
            self._close(conn)
            raise
        self._checkin(conn)
        return reply


class _RouterHandler(socketserver.StreamRequestHandler):
    def handle(self):
        try:
            self._serve_lines()
        except ConnectionResetError:
            pass  # peer RST mid-read (client died, chaos reset): not an error

    def _serve_lines(self):
        router: ScoringRouter = self.server.router  # type: ignore[attr-defined]
        scope: str | None = None  # MODEL <id> connection scoping
        for raw in self.rfile:
            try:
                line = raw.decode("utf-8", errors="replace").strip()
            except Exception:
                continue
            if not line:
                continue
            if line == "MODEL" or line.startswith("MODEL "):
                reply, scope = router.handle_model_line(line, scope)
            else:
                reply = router.handle_line(line, model=scope)
            try:
                self.wfile.write((reply + "\n").encode())
                self.wfile.flush()
            except (BrokenPipeError, ConnectionResetError):
                return


class _TCPServer(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True


class ScoringRouter:
    """Health-checked load-balancing front-end over engine replicas.

    ``replicas``: list (or comma-separated string) of ``host:port``
    addresses of running :class:`ScoringServer` listeners (or nested
    routers — the protocol is identical), or a multi-model registry
    spec / mapping (``v1=h:p+h:p,v2=h:p`` — see
    :func:`distlr_tpu.serve.tenant.parse_model_spec`).  One address may
    serve several models (a :class:`ScoringServer` hosting multiple
    engines): it shares ONE health state and in-flight budget.

    ``quotas``: per-tenant token-bucket admission
    (``model=rate[:burst]`` spec or a ready mapping — see
    :func:`distlr_tpu.serve.tenant.parse_quota_spec`).
    """

    def __init__(self, replicas, *, host: str = "127.0.0.1", port: int = 0,
                 max_inflight: int = 64, eject_after: int = 3,
                 health_interval_s: float = 1.0,
                 probe_backoff_s: float = 0.5,
                 probe_backoff_max_s: float = 30.0,
                 backend_timeout_s: float = 30.0, retries: int = 1,
                 quotas=None, shadow_block: int = 256,
                 shadow_queue_max: int = 256, seed: int | None = None):
        models = _tenant.parse_model_spec(replicas)
        if not 0 <= port < 1 << 16:
            raise ValueError(f"port must be in [0, 65536), got {port}")
        if max_inflight <= 0:
            raise ValueError(f"max_inflight must be positive, got {max_inflight}")
        if eject_after < 1:
            raise ValueError(f"eject_after must be >= 1, got {eject_after}")
        if health_interval_s <= 0:
            raise ValueError(
                f"health_interval_s must be positive, got {health_interval_s}")
        if probe_backoff_s <= 0 or probe_backoff_max_s < probe_backoff_s:
            raise ValueError(
                "need 0 < probe_backoff_s <= probe_backoff_max_s, got "
                f"{probe_backoff_s}/{probe_backoff_max_s}")
        if backend_timeout_s <= 0:
            raise ValueError(
                f"backend_timeout_s must be positive, got {backend_timeout_s}")
        if retries < 0:
            raise ValueError(f"retries must be >= 0, got {retries}")
        by_addr: dict[str, _Replica] = {}
        self._model_replicas: dict[str, list[_Replica]] = {}
        for model, addrs in models.items():
            reps = []
            for a in addrs:
                rep = by_addr.get(a)
                if rep is None:
                    rep = by_addr[a] = _Replica(
                        a, max_inflight=max_inflight,
                        timeout_s=backend_timeout_s)
                rep.models.add(model)
                reps.append(rep)
            self._model_replicas[model] = reps
        self._by_addr = by_addr
        self.replicas = list(by_addr.values())
        self.model_ids = list(models)
        self.default_model = self.model_ids[0]
        self.quotas = _tenant.parse_quota_spec(quotas)
        unknown = sorted(set(self.quotas) - set(self.model_ids))
        if unknown:
            raise ValueError(
                f"quota names unregistered model(s) {unknown}; hosted: "
                f"{self.model_ids}")
        #: canary split / shadow state: tenant -> (candidate, fraction)
        self._splits: dict[str, tuple[str, float]] = {}
        self._shadows: dict[str, tuple[str, float]] = {}
        #: post-PROMOTE identity: tenant -> the model id its traffic is
        #: actually addressed as on the wire (replica-list swap alone is
        #: not enough — one address can host BOTH engines, and the
        #: promoted tenant's lines must select the candidate's engine)
        self._serve_as: dict[str, str] = {}
        self._rng = random.Random(seed)
        self._per_model = {m: {"requests": 0, "shed": 0}
                           for m in self.model_ids}
        self._shadow_block = int(shadow_block)
        self._shadow_queue_max = int(shadow_queue_max)
        self._shadow_mirror: _tenant.ShadowMirror | None = None
        _tenant.set_model_count(len(self.model_ids))
        self.max_inflight = int(max_inflight)
        self.eject_after = int(eject_after)
        self.health_interval_s = float(health_interval_s)
        self.probe_backoff_s = float(probe_backoff_s)
        self.probe_backoff_max_s = float(probe_backoff_max_s)
        self.backend_timeout_s = float(backend_timeout_s)
        self.probe_timeout_s = min(float(backend_timeout_s), 2.0)
        self._retries = int(retries)
        self._lock = sync.Lock()   # health state + rotation counter
        self._rr = 0
        self._t0 = sync.monotonic()
        self._tcp = _TCPServer((host, port), _RouterHandler,
                               bind_and_activate=True)
        self._tcp.router = self  # type: ignore[attr-defined]
        self.host, self.port = self._tcp.server_address[:2]
        listener = f"{self.host}:{self.port}"
        self._req_seconds = _REQ_SECONDS.labels(listener=listener)
        self._requests_c = _REQUESTS.labels(listener=listener)
        self._errors_c = _ERRORS.labels(listener=listener)
        self._shed_c = _SHED.labels(listener=listener)
        self._retries_c = _RETRIES.labels(listener=listener)
        # construction-time baselines: registry children are
        # process-lifetime, STATS reports this router instance's deltas
        # (same contract as ScoringServer)
        self._req_base = self._requests_c.value
        self._err_base = self._errors_c.value
        self._shed_base = self._shed_c.value
        self._retry_base = self._retries_c.value
        self._stop = sync.Event()
        self._started = False
        self._accept_thread = sync.Thread(
            target=self._tcp.serve_forever, daemon=True,
            name="distlr-route-accept")
        self._health_thread = sync.Thread(
            target=self._health_loop, daemon=True, name="distlr-route-health")

    # -- replica selection / health ---------------------------------------
    def _acquire(self, excluded: list,
                 model: str | None = None) -> _Replica | None:
        """A healthy replica (of ``model``'s registry slice when given)
        with a free in-flight slot: least in-flight first, rotating
        tie-break so serial traffic still spreads."""
        with self._lock:
            pool = (self.replicas if model is None
                    else self._model_replicas.get(model, []))
            cands = [r for r in pool
                     if r.healthy and r not in excluded]
            # least in-flight + rotating tie-break: the policy ordering
            # lives in serve.balance (fleetsim runs the same function)
            ordered, self._rr = _balance.order_candidates(cands, self._rr)
            for rep in ordered:
                if rep.try_acquire():
                    return rep
            return None

    def _release(self, rep: _Replica) -> None:
        with self._lock:
            rep.release()

    def _note_success(self, rep: _Replica) -> None:
        with self._lock:
            _balance.note_success(rep, sync.monotonic())

    def _note_failure(self, rep: _Replica) -> None:
        with self._lock:
            _balance.note_failure(rep)
            verdict = _balance.eject_verdict(rep, self._pools_locked(rep),
                                             self.eject_after)
            if verdict == "eject":
                self._eject_locked(rep)
            elif verdict == "floor":
                self._floor_locked(rep)

    def _pools_locked(self, rep: _Replica) -> list:
        """The replica lists of every model ``rep`` serves — what the
        last-healthy ejection floor arbitrates over."""
        return [self._model_replicas.get(m, []) for m in sorted(rep.models)]

    def _eject_locked(self, rep: _Replica) -> None:
        _balance.eject(rep, sync.monotonic(), self.probe_backoff_s)
        self._post_eject_locked(rep)

    def _post_eject_locked(self, rep: _Replica) -> None:
        """The effectful half of an ejection (state transition already
        applied by :mod:`~distlr_tpu.serve.balance`)."""
        rep._up_g.set(0.0)
        _EJECTIONS.labels(replica=rep.addr).inc()
        log.warning("replica %s ejected after %d consecutive failures; "
                    "probing with %.2fs backoff", rep.addr,
                    rep.consecutive_errors, rep.backoff_s)
        rep.drain_pool()  # pooled sockets to a suspect replica are suspect

    def _floor_locked(self, rep: _Replica) -> None:
        """The ejection the last-healthy floor suppressed (ISSUE 19:
        fleetsim's cascade counterexample): keep the replica in
        rotation, count it, and warn once per streak threshold."""
        _EJECT_SUPPRESSED.labels(replica=rep.addr).inc()
        if rep.consecutive_errors == self.eject_after:
            log.warning(
                "replica %s crossed the eject threshold (%d consecutive "
                "failures) but is the LAST healthy replica of a pool it "
                "serves; keeping it in rotation (ejection floor)",
                rep.addr, rep.consecutive_errors)

    def _probe(self, rep: _Replica) -> bool:
        """Active health check: a STATS round trip on a fresh connection.
        Success reinstates an ejected replica; failure backs off (or
        counts toward ejection for a replica still in rotation)."""
        try:
            with socket.create_connection(
                    (rep.host, rep.port), timeout=self.probe_timeout_s) as s:
                f = s.makefile("rwb")
                f.write(b"STATS\n")
                f.flush()
                reply = f.readline()
            ok = bool(reply)
            if ok:
                try:
                    doc = json.loads(reply)
                    if isinstance(doc, dict) and doc.get("replicas_up") == 0:
                        # a nested child router answers STATS even when
                        # its whole tier is down — don't reinstate a
                        # subtree that cannot serve anything
                        ok = False
                except ValueError:
                    pass
        except OSError:
            ok = False
        with self._lock:
            outcome = _balance.probe_result(
                rep, ok, sync.monotonic(),
                probe_backoff_s=self.probe_backoff_s,
                probe_backoff_max_s=self.probe_backoff_max_s,
                eject_after=self.eject_after,
                pools=self._pools_locked(rep))
            if outcome == "reinstated":
                rep._up_g.set(1.0)
                _REINSTATES.labels(replica=rep.addr).inc()
                log.info("replica %s reinstated", rep.addr)
            elif outcome == "ejected":
                self._post_eject_locked(rep)
            elif outcome == "floor":
                self._floor_locked(rep)
        return ok

    def _health_loop(self) -> None:
        tick = max(0.01, min(self.health_interval_s, 0.25))
        while not self._stop.wait(tick):
            now = sync.monotonic()
            # snapshot: ADDREPLICA/DELREPLICA mutate the list mid-run
            for rep in list(self.replicas):
                with self._lock:
                    due = _balance.probe_due(rep, now,
                                             self.health_interval_s,
                                             self.probe_backoff_s)
                if due:
                    self._probe(rep)

    # -- label fan-out ------------------------------------------------------
    #: reply preference when replicas disagree: a join beats a duplicate
    #: (someone already joined it) beats a pending hold
    _LABEL_ORDER = {"joined": 0, "duplicate": 1, "pending": 2}

    def _broadcast_label(self, line: str, model: str | None = None) -> str:
        with self._lock:
            pool = (self.replicas if model is None
                    else self._model_replicas.get(model, []))
            targets = [r for r in pool if r.healthy]
        best: str | None = None
        for rep in targets:
            with self._lock:
                admitted = rep.try_acquire()
            if not admitted:
                continue  # saturated replica: its window will age the id
            try:
                reply = rep.exchange(line)
            except Exception:  # noqa: BLE001 — transport failure
                self._note_failure(rep)
                continue
            finally:
                self._release(rep)
            self._note_success(rep)
            if reply.startswith("OK"):
                outcome = reply[2:].strip() or "joined"
                if (best is None or self._LABEL_ORDER.get(outcome, 3)
                        < self._LABEL_ORDER.get(best, 3)):
                    best = outcome
                if best in ("joined", "duplicate"):
                    # terminal: only the scoring replica can join, and a
                    # duplicate means it already did — fanning further
                    # would park the label in every remaining replica's
                    # bounded pending buffer (and cost their RTTs) for
                    # nothing
                    break
            # ERR (replica without a feedback sink, malformed id):
            # deterministic, not a transport failure — just not a hit
        listener = f"{self.host}:{self.port}"
        _LABELS.labels(listener=listener,
                       outcome=best if best is not None else "failed").inc()
        if best is not None:
            return f"OK {best}"
        self._errors_c.inc()
        return ("ERR LABEL: no replica accepted the label (are the "
                "replicas running a feedback sink?)")

    # -- multi-tenant control plane ---------------------------------------
    def handle_model_line(self, line: str,
                          scope: str | None) -> tuple[str, str | None]:
        """``MODEL <id>`` connection scoping (additive): subsequent
        unaddressed lines route to that model's replicas.  Returns
        ``(reply, new_scope)`` — an unknown id keeps the old scope."""
        parts = line.split()
        if len(parts) != 2:
            self._errors_c.inc()
            return "ERR MODEL: need MODEL <id>", scope
        if parts[1] not in self._model_replicas:
            self._errors_c.inc()
            return (f"ERR MODEL: unknown model {parts[1]!r} (hosted: "
                    f"{','.join(self.model_ids)})", scope)
        return f"OK MODEL {parts[1]}", parts[1]

    def _check_models_locked(self, tenant: str, candidate: str) -> None:
        for m in (tenant, candidate):
            if m not in self._model_replicas:
                raise ValueError(
                    f"unknown model {m!r} (hosted: "
                    f"{','.join(self.model_ids)})")
        if tenant == candidate:
            raise ValueError(f"tenant and candidate are both {tenant!r}")

    def set_split(self, tenant: str, candidate: str, weight: float) -> None:
        """Canary split: route ``weight`` of ``tenant``'s scoring
        traffic to ``candidate``; 0 clears (the rollback)."""
        if not 0.0 <= weight <= 1.0:
            raise ValueError(f"weight must be in [0, 1], got {weight}")
        with self._lock:
            self._check_models_locked(tenant, candidate)
            if weight == 0.0:
                self._splits.pop(tenant, None)
            else:
                self._splits[tenant] = (candidate, float(weight))
        log.info("split: %s -> %s at %.3f", tenant, candidate, weight)

    def set_shadow(self, tenant: str, candidate: str,
                   fraction: float) -> None:
        """Shadow mirror: replay ``fraction`` of ``tenant``'s scoring
        traffic against ``candidate`` off the reply path; 0 clears."""
        if not 0.0 <= fraction <= 1.0:
            raise ValueError(f"fraction must be in [0, 1], got {fraction}")
        with self._lock:
            self._check_models_locked(tenant, candidate)
            if fraction == 0.0:
                self._shadows.pop(tenant, None)
            else:
                self._shadows[tenant] = (candidate, float(fraction))
                if self._shadow_mirror is None:
                    self._shadow_mirror = _tenant.ShadowMirror(
                        self._exchange_for_model,
                        queue_max=self._shadow_queue_max,
                        block=self._shadow_block)
        log.info("shadow: %s -> %s at %.3f", tenant, candidate, fraction)

    def promote(self, tenant: str, candidate: str) -> None:
        """The ramp's terminal transition: ``tenant``'s registry slice
        becomes ``candidate``'s replicas (the candidate version now IS
        the tenant's primary); any split/shadow for the tenant clears.
        The candidate id stays addressable — old version replicas are
        simply no longer reachable under the tenant's id."""
        with self._lock:
            self._check_models_locked(tenant, candidate)
            self._model_replicas[tenant] = list(
                self._model_replicas[candidate])
            self._serve_as[tenant] = self._serve_as.get(candidate,
                                                        candidate)
            self._splits.pop(tenant, None)
            self._shadows.pop(tenant, None)
        log.info("promoted: %s now serves %s's replicas", tenant, candidate)

    def add_replica(self, model: str, addr: str) -> None:
        """Elastic scale-up: register a (possibly brand-new) replica
        address under ``model`` mid-run.  The new replica enters
        rotation immediately and rides the existing health machinery —
        a dead address is probed, ejected, and backoff-reinstated like
        any launch-time replica.  An unknown ``model`` id creates a new
        registry slice (a new version joining the fleet)."""
        with self._lock:
            rep = self._by_addr.get(addr)
            if rep is None:
                rep = _Replica(addr, max_inflight=self.max_inflight,
                               timeout_s=self.backend_timeout_s)
                self._by_addr[addr] = rep
                self.replicas.append(rep)
            if model not in self._model_replicas:
                self._model_replicas[model] = []
                self.model_ids.append(model)
                self._per_model[model] = {"requests": 0, "shed": 0}
                _tenant.set_model_count(len(self.model_ids))
            pool = self._model_replicas[model]
            if rep in pool:
                raise ValueError(
                    f"replica {addr} already registered under {model!r}")
            rep.models.add(model)
            pool.append(rep)
        log.info("replica %s added under model %s", addr, model)

    def remove_replica(self, model: str, addr: str) -> None:
        """Elastic scale-down: take the replica out of ``model``'s
        rotation.  In-flight requests on it complete (the budget object
        lives until released) — removal never fails an accepted
        request; new traffic simply stops selecting it.  An address
        registered under no model afterwards is fully forgotten (pool
        drained)."""
        with self._lock:
            rep = self._by_addr.get(addr)
            pool = self._model_replicas.get(model)
            if rep is None or pool is None or rep not in pool:
                raise ValueError(
                    f"replica {addr} not registered under {model!r}")
            pool.remove(rep)
            rep.models.discard(model)
            gone = not any(rep in p for p in self._model_replicas.values())
            if gone:
                self.replicas.remove(rep)
                del self._by_addr[addr]
                rep._up_g.set(0.0)
        if gone:
            rep.drain_pool()
        log.info("replica %s removed from model %s%s", addr, model,
                 " (forgotten)" if gone else "")

    def _handle_admin(self, line: str) -> str:
        parts = line.split()
        verb = parts[0]
        try:
            if verb in ("SPLIT", "SHADOW"):
                if len(parts) != 4:
                    raise ValueError(
                        f"need {verb} <tenant> <candidate> <fraction>")
                frac = float(parts[3])
                (self.set_split if verb == "SPLIT"
                 else self.set_shadow)(parts[1], parts[2], frac)
                return f"OK {verb} {parts[1]} {parts[2]} {frac:g}"
            if verb in ("ADDREPLICA", "DELREPLICA"):
                if len(parts) != 3:
                    raise ValueError(f"need {verb} <model> <host:port>")
                (self.add_replica if verb == "ADDREPLICA"
                 else self.remove_replica)(parts[1], parts[2])
                return f"OK {verb} {parts[1]} {parts[2]}"
            if len(parts) != 3:
                raise ValueError("need PROMOTE <tenant> <candidate>")
            self.promote(parts[1], parts[2])
            return f"OK PROMOTE {parts[1]} {parts[2]}"
        except ValueError as e:
            self._errors_c.inc()
            return f"ERR {verb}: {e}"

    def models_json(self) -> dict:
        """The registry as the ``MODELS`` reply (what ``launch rollout``
        reads before ramping)."""
        with self._lock:
            return {
                "default": self.default_model,
                "models": {
                    m: {
                        "replicas": [r.addr for r in reps],
                        "up": sum(r.healthy for r in reps),
                    }
                    for m, reps in self._model_replicas.items()
                },
                "splits": {t: list(sc) for t, sc in self._splits.items()},
                "shadows": {t: list(sc) for t, sc in self._shadows.items()},
                "serves_as": dict(self._serve_as),
            }

    def _exchange_for_model(self, model: str, line: str) -> str:
        """One admission-controlled exchange toward a model's replicas
        (the shadow mirror's send path): no retry, failures raise."""
        rep = self._acquire([], model)
        if rep is None:
            raise ConnectionError(f"no capacity toward model {model!r}")
        try:
            wire = f"@{model} {line}" if len(rep.models) > 1 else line
            reply = rep.exchange(wire)
        except Exception:
            self._note_failure(rep)
            raise
        finally:
            self._release(rep)
        self._note_success(rep)
        return reply

    # -- request path ------------------------------------------------------
    def handle_line(self, line: str, model: str | None = None) -> str:
        """One routed line.  Scoring requests mint (or join, via an
        incoming ``TRACE <tid>/<sid>`` prefix from a parent router or a
        traced client) a distributed-trace context; sampled contexts are
        forwarded to the chosen replica as the same additive prefix, so
        one trace follows the request through router -> engine -> (via
        the feedback loop) the PS wire.  LABEL lines continue their
        REQUEST's trace at the scoring replica instead of minting one,
        and replies never carry the prefix.  ``model`` is the
        connection's ``MODEL`` scope; a per-request ``@<id>`` prefix
        (parsed after TRACE) overrides it."""
        if line == "STATS":
            return json.dumps(self.stats())
        if line == "MODELS":
            return json.dumps(self.models_json())
        if line.startswith(("SPLIT ", "SHADOW ", "PROMOTE ",
                            "ADDREPLICA ", "DELREPLICA ")):
            return self._handle_admin(line)
        if line.startswith("@"):
            # a model-ADDRESSED label must broadcast to that model's
            # replicas like a scoped one — falling through to the
            # scoring path would deliver it to exactly one replica and
            # strand it in every other's pending buffer
            prefix, _, rest = line.partition(" ")
            if rest.startswith("LABEL ") or rest == "LABEL":
                mid = prefix[1:]
                if mid not in self._model_replicas:
                    self._errors_c.inc()
                    return (f"ERR MODEL: unknown model {mid!r} (hosted: "
                            f"{','.join(self.model_ids)})")
                return self._broadcast_label(rest, mid)
        if line.startswith("LABEL ") or line == "LABEL":
            return self._broadcast_label(line, model)
        ctx = None
        if line.startswith("TRACE "):
            parts = line.split(" ", 2)
            if len(parts) != 3:
                self._errors_c.inc()
                return "ERR TRACE: need TRACE <trace_id>/<span_id> <line>"
            try:
                ctx = dtrace.parse_token(parts[1])
            except ValueError as e:
                self._errors_c.inc()
                return f"ERR TRACE: {e}"
            line = parts[2]
        else:
            ctx = dtrace.new_trace()  # None until dtrace.configure ran
        if ctx is None:
            return self._route_line(line, model)
        with dtrace.use(ctx), dtrace.span(
                "route.request",
                tags={"listener": f"{self.host}:{self.port}"}) as sp:
            reply = self._route_line(line, model)
            if reply.startswith("ERR "):
                sp.tags["error"] = reply.split(":", 1)[0]
            return reply

    def _route_line(self, line: str, scope: str | None = None) -> str:
        # tenant resolution: @-prefix > connection scope > default model
        if line.startswith("@"):
            prefix, _, rest = line.partition(" ")
            tenant, line = prefix[1:], rest.strip()
            if not tenant or not line:
                self._errors_c.inc()
                return "ERR MODEL: need @<id> <request line>"
            if tenant not in self._model_replicas:
                self._errors_c.inc()
                return (f"ERR MODEL: unknown model {tenant!r} (hosted: "
                        f"{','.join(self.model_ids)})")
        else:
            tenant = scope if scope is not None else self.default_model
        # per-tenant admission quota, BEFORE any replica is touched: a
        # tenant over budget must not consume in-flight slots.  The
        # reply is deliberately distinct from the capacity shed — quota
        # = "this tenant is over budget", capacity = "scale the tier up"
        q = self.quotas.get(tenant)
        if q is not None and not q.try_admit():
            _tenant.count_tenant_shed(tenant)
            with self._lock:
                self._per_model[tenant]["shed"] += 1
            return (f"ERR SHED tenant: {tenant!r} over admission quota "
                    f"({q.rate:g} req/s)")
        # canary split: a fraction of the tenant's traffic serves from
        # the candidate version's replicas (weighted draw per request)
        with self._lock:
            split = self._splits.get(tenant)
            shadow = self._shadows.get(tenant)
            serve_model = tenant
            if split is not None and self._rng.random() < split[1]:
                serve_model = split[0]
            # canary-served requests don't mirror (candidate vs
            # candidate would read as perfect agreement) — decided
            # BEFORE the serve_as remap, which renames the PROMOTED
            # tenant's own primary and must not disable its shadow
            canary = serve_model != tenant
            # post-PROMOTE identity: the tenant's traffic addresses the
            # promoted version's engine on the wire
            serve_model = self._serve_as.get(serve_model, serve_model)
            mirror = (shadow is not None and not canary
                      and self._rng.random() < shadow[1])
        # sampled context -> the replica exchange carries the additive
        # prefix (the replica strips it; retries resend it verbatim —
        # scores are idempotent and the span ids do not change).  The
        # @-model prefix is PER REPLICA (below): only addresses hosting
        # several models need it — a pre-tenant single-engine replica
        # keeps parsing every byte it always parsed
        tok = dtrace.token()
        t0 = sync.monotonic()
        excluded: list[_Replica] = []
        last_err = "no healthy replica in rotation"
        shed_only = True  # every failure so far was overload, not death
        for attempt in range(self._retries + 1):
            rep = self._acquire(excluded, serve_model)
            if rep is None:
                if attempt == 0:
                    with self._lock:
                        pool = self._model_replicas.get(serve_model, [])
                        any_healthy = any(r.healthy for r in pool)
                    if not any_healthy:
                        # total outage, not overload: shed means "scale
                        # up"; this means "the tier is down" — it must
                        # tick the error counter, not the shed counter
                        self._errors_c.inc()
                        return ("ERR ROUTE: no healthy replica in "
                                "rotation (all ejected)")
                    # admission refusal — the request was never accepted
                    self._shed_c.inc()
                    return ("ERR SHED: no replica with free capacity "
                            "(load shed)")
                break  # accepted, but no retry target left: fail loudly
            if attempt > 0:
                # counted only once a replacement replica was actually
                # acquired — a failed exchange with nowhere to go is an
                # error, not a retry
                self._retries_c.inc()
            routed = (f"@{serve_model} {line}" if len(rep.models) > 1
                      else line)
            wire = f"TRACE {tok} {routed}" if tok else routed
            try:
                reply = rep.exchange(wire)
            except Exception as e:  # noqa: BLE001 — any transport failure
                last_err = f"{type(e).__name__}: {e}"
                shed_only = False
                self._note_failure(rep)
                excluded.append(rep)
                continue
            finally:
                self._release(rep)
            if reply.startswith(("ERR SHED", "ERR ROUTE")):
                # only routers emit these (an engine's ERR carries the
                # exception name): a nested child tier answering SHED is
                # overloaded — retry a sibling but DON'T count toward
                # ejection (overload is not death); a child answering
                # ROUTE has a dead subtree — retry AND eject, so it
                # stops eating traffic
                last_err = reply
                if reply.startswith("ERR ROUTE"):
                    shed_only = False
                    self._note_failure(rep)
                excluded.append(rep)
                continue
            self._note_success(rep)
            self._req_seconds.observe(sync.monotonic() - t0)
            self._requests_c.inc()
            _tenant.count_request(tenant)
            with self._lock:
                self._per_model[tenant]["requests"] += 1
            if mirror:
                # fire-and-forget, strictly AFTER the reply is final:
                # nothing below can change the bytes the client gets
                scores = _tenant.extract_scores(reply)
                sm = self._shadow_mirror
                if scores and sm is not None:
                    sm.submit(tenant, shadow[0], line, scores)
            return reply
        if shed_only and excluded:
            # every tried child shed: the tier-wide truth is still
            # overload ("scale up"), not outage ("page someone")
            self._shed_c.inc()
            return last_err
        self._errors_c.inc()
        return (f"ERR ROUTE: request failed on {len(excluded)} "
                f"replica(s): {last_err}")

    # -- stats -------------------------------------------------------------
    def stats(self) -> dict:
        """Same scalar schema as :meth:`ScoringServer.stats` (requests/
        errors/qps/p50_ms/p99_ms/shed/retries/replica_count) plus the
        per-replica state list — one parser covers both tiers."""
        n_req = int(self._requests_c.value - self._req_base)
        n_err = int(self._errors_c.value - self._err_base)
        elapsed = max(sync.monotonic() - self._t0, 1e-9)
        with self._lock:
            reps = [{
                "addr": r.addr,
                "healthy": r.healthy,
                "inflight": r.inflight,
                "requests": r.requests,
                "errors": r.errors,
                "ejections": r.ejections,
                "reinstates": r.reinstates,
            } for r in self.replicas]
            per_model = {}
            for m in self.model_ids:
                pool = self._model_replicas[m]
                pm = {
                    "requests": self._per_model[m]["requests"],
                    "shed": self._per_model[m]["shed"],
                    "replicas": len(pool),
                    "replicas_up": sum(r.healthy for r in pool),
                }
                if m in self._splits:
                    pm["split"] = list(self._splits[m])
                if m in self._shadows:
                    pm["shadow"] = list(self._shadows[m])
                q = self.quotas.get(m)
                if q is not None:
                    pm["quota"] = q.stats()
                per_model[m] = pm
        rec = {
            "requests": n_req,
            "errors": n_err,
            "qps": round(n_req / elapsed, 2),
            "p50_ms": round(self._req_seconds.percentile(0.50) * 1e3, 3),
            "p99_ms": round(self._req_seconds.percentile(0.99) * 1e3, 3),
            "shed": int(self._shed_c.value - self._shed_base),
            "retries": int(self._retries_c.value - self._retry_base),
            "replica_count": len(reps),
            "replicas_up": sum(r["healthy"] for r in reps),
            "replicas": reps,
            # multi-tenant additions (additive, like shed/retries were)
            "models": len(self.model_ids),
            "per_model": per_model,
        }
        sm = self._shadow_mirror
        if sm is not None:
            rec["shadow"] = sm.stats()
        return rec

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "ScoringRouter":
        self._started = True
        self._accept_thread.start()
        self._health_thread.start()
        log.info("routing on %s:%d over %d replica(s): %s",
                 self.host, self.port, len(self.replicas),
                 ",".join(r.addr for r in self.replicas))
        return self

    def serve_forever(self) -> None:
        """Foreground mode for the CLI: start, then block until stopped."""
        self.start()
        try:
            while self._accept_thread.is_alive():
                self._accept_thread.join(timeout=1.0)
        except KeyboardInterrupt:
            pass
        finally:
            self.stop()

    def stop(self) -> None:
        self._stop.set()
        if self._started:
            # shutdown() blocks forever unless serve_forever actually
            # ran (the MetricsServer.stop() bug class from ISSUE 3) —
            # a router stopped before start() just closes the socket
            self._tcp.shutdown()
        self._tcp.server_close()
        if self._shadow_mirror is not None:
            self._shadow_mirror.stop()
        if self._health_thread.is_alive():
            self._health_thread.join(timeout=10.0)
        for rep in self.replicas:
            rep.drain_pool()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
