"""Threaded TCP scoring front-end — stdlib only.

Line protocol, one request per line, one reply line per request:

* **libsvm mode** — a libsvm-formatted feature line (leading label token
  optional, ignored if present); reply: ``<label> <score>`` where score
  is P(y=1) (binary families) or the winning class probability (softmax
  families).
* **JSON mode** — a line starting with ``{``:
  ``{"rows": ["<libsvm line>", ...]}``; reply:
  ``{"labels": [...], "scores": [...]}``.  The batch travels as ONE
  microbatcher request (a single client can fill a bucket by itself).
* **STATS** — reply: one JSON line of engine/batcher/latency counters
  (p50/p99 ms, QPS, occupancy, reload stats).
* **ID mode** — ``ID <token> <libsvm line>``: score the line like
  libsvm mode AND journal it under the caller-supplied request id
  (``token``) so a later label can join it (additive, like STATS;
  requires a feedback sink — without one the id is simply ignored).
  JSON mode's additive twin is an optional ``"ids"`` list parallel to
  ``"rows"`` (entries may be null).
* **LABEL** — ``LABEL <request_id> <label>``: a delayed label event for
  a previously scored request (the feedback loop's return path,
  :mod:`distlr_tpu.feedback`); reply ``OK <outcome>`` where outcome is
  ``joined`` / ``pending`` / ``duplicate``, or ``ERR`` when the server
  runs no feedback sink.
* **model addressing** (additive, like STATS/TRACE — multi-tenant
  serving): one server can host several model versions as multiple
  :class:`~distlr_tpu.serve.engine.ScoringEngine`\\ s.  ``MODEL <id>``
  scopes the CONNECTION to a hosted model (reply ``OK MODEL <id>``);
  a per-request ``@<id> `` prefix addresses one line (it may wrap ID
  mode and JSON mode: ``@v2 ID r1 1:1``).  Unaddressed lines score on
  the default (first) engine — pre-tenant clients interop unchanged.
* Malformed input -> ``ERR <reason>`` for that line; the connection
  stays up (one bad row from one client must not drop its neighbors).

Concurrency model: one thread per connection (stdlib
``ThreadingTCPServer``); all connections funnel into one
:class:`~distlr_tpu.serve.batcher.MicroBatcher`, so cross-connection
coalescing happens exactly when traffic is concurrent — the serving
analogue of lockstep global batches in the sync trainer.
"""

from __future__ import annotations

import json
import socket
import socketserver
import threading
import time

import numpy as np

from distlr_tpu.obs import dtrace
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.serve.batcher import MicroBatcher
from distlr_tpu.train.metrics import MetricsLogger
from distlr_tpu.utils.logging import get_logger

log = get_logger(__name__)

_reg = get_registry()
#: Per-listener series ("host:port" label): several servers can share one
#: process (tests, multi-engine front-ends) without aliasing counts.  The
#: STATS reply answers from these — the old hand-rolled percentile deque
#: is gone; p50/p99 are histogram-bucket estimates now (same fixed-bucket
#: memory no matter how many requests pass).
_REQ_SECONDS = _reg.histogram(
    "distlr_serve_request_seconds",
    "wall seconds per front-end request line", labelnames=("listener",),
)
_REQUESTS = _reg.counter(
    "distlr_serve_requests_total", "request lines answered OK",
    labelnames=("listener",),
)
_ERRORS = _reg.counter(
    "distlr_serve_errors_total", "request lines answered ERR",
    labelnames=("listener",),
)


class _Handler(socketserver.StreamRequestHandler):
    def handle(self):
        srv: ScoringServer = self.server.scoring_server  # type: ignore[attr-defined]
        srv._track(self.connection)
        try:
            self._serve_lines(srv)
        except ConnectionResetError:
            pass  # peer RST mid-read (client died, chaos reset): not an error
        finally:
            srv._untrack(self.connection)

    def _serve_lines(self, srv: "ScoringServer"):
        scope: str | None = None  # MODEL <id> connection scoping
        for raw in self.rfile:
            try:
                line = raw.decode("utf-8", errors="replace").strip()
            except Exception:
                continue
            if not line:
                continue
            if line == "MODEL" or line.startswith("MODEL "):
                reply, scope = srv.handle_model_line(line, scope)
            else:
                reply = srv.handle_line(line, model=scope)
            try:
                self.wfile.write((reply + "\n").encode())
                self.wfile.flush()
            except (BrokenPipeError, ConnectionResetError):
                return


class _TCPServer(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True


class ScoringServer:
    """Engine(s) + microbatcher(s) behind a line-protocol TCP listener.

    Single-model (the pre-tenant form): pass ``engine``.  Multi-tenant:
    pass ``engines`` — an ordered ``{model_id: ScoringEngine}`` mapping;
    the FIRST entry is the default engine unaddressed lines score on,
    and each engine gets its own microbatcher (coalescing is per model:
    two versions' rows must never share a padded batch).
    """

    def __init__(self, engine=None, *, engines: dict | None = None,
                 host: str = "127.0.0.1", port: int = 0,
                 max_wait_ms: float = 2.0, reloader=None,
                 extra_reloaders=(),
                 metrics: MetricsLogger | None = None, hot_tracker=None,
                 feedback=None):
        if not 0 <= port < 1 << 16:
            raise ValueError(f"port must be in [0, 65536), got {port}")
        if engines is None:
            if engine is None:
                raise ValueError("need an engine (or an engines mapping)")
            engines = {"default": engine}
            # single-engine compat: feedback records carry no model id,
            # shards stay flat — byte-identical to the pre-tenant loop
            self._multi = False
        else:
            if engine is not None:
                raise ValueError("pass engine OR engines, not both")
            if not engines:
                raise ValueError("engines mapping must name >= 1 model")
            engines = dict(engines)
            self._multi = True
        self.engines = engines
        self._default_id = next(iter(engines))
        self.engine = engines[self._default_id]
        self.reloader = reloader
        #: extra per-engine reloaders (multi-tenant live-PS serving) the
        #: server owns for lifecycle only — stopped with the listener
        self._extra_reloaders = list(extra_reloaders)
        #: HotSetTracker fed from request traffic (hot-row keyed reload);
        #: None = full-table refresh semantics, no tracking overhead.
        #: Tracks the DEFAULT engine's key space only — each model
        #: version has its own namespace, and mixing their keys would
        #: poison the hot set.
        self.hot_tracker = hot_tracker
        #: FeedbackSink (distlr_tpu.feedback): journals scored requests,
        #: joins LABEL lines, feeds the drift detector.  None = the loop
        #: is open (pre-feedback behavior, zero overhead).
        self.feedback = feedback
        self._batchers = {
            mid: MicroBatcher(
                eng.score,
                max_batch_size=eng.max_batch_size,
                max_wait_ms=max_wait_ms,
            )
            for mid, eng in engines.items()
        }
        self.batcher = self._batchers[self._default_id]
        self._model_requests = {mid: 0 for mid in engines}
        self.metrics = metrics or MetricsLogger()
        self._t0 = time.monotonic()
        self._tcp = _TCPServer((host, port), _Handler, bind_and_activate=True)
        self._tcp.scoring_server = self  # type: ignore[attr-defined]
        self.host, self.port = self._tcp.server_address[:2]
        listener = f"{self.host}:{self.port}"
        self._req_seconds = _REQ_SECONDS.labels(listener=listener)
        self._requests_c = _REQUESTS.labels(listener=listener)
        self._errors_c = _ERRORS.labels(listener=listener)
        # Registry children are process-lifetime: a restarted server on
        # the same FIXED port resolves the same label set, so STATS
        # reports deltas against construction-time baselines (the scrape
        # stays cumulative, as Prometheus counters should).  Percentiles
        # still aggregate the listener's full process history.
        self._req_base = self._requests_c.value
        self._err_base = self._errors_c.value
        self._conn_lock = threading.Lock()
        self._active_conns: set = set()
        self._started = False
        self._thread = threading.Thread(
            target=self._tcp.serve_forever, daemon=True,
            name="distlr-serve-accept",
        )

    # -- request handling --------------------------------------------------
    def _track(self, conn) -> None:
        with self._conn_lock:
            self._active_conns.add(conn)

    def _untrack(self, conn) -> None:
        with self._conn_lock:
            self._active_conns.discard(conn)

    def _score_lines(self, lines: list[str], ids: list | None = None,
                     model: str | None = None):
        mid = self._default_id if model is None else model
        engine = self.engines[mid]
        batcher = self._batchers[mid]
        with dtrace.span("serve.encode",
                         tags={"rows": len(lines), "model": mid}):
            rows = engine.encode_lines(lines)
        if self.hot_tracker is not None and mid == self._default_id:
            self.hot_tracker.observe(engine.row_keys(rows))
        # version read BEFORE scoring: a swap racing the batch means the
        # journal attributes at most one version early, never one that
        # did not exist when the request entered
        version = engine.weights_version
        # the score span covers microbatch queue wait + the engine call;
        # the batcher's own serve.batch span (under the same trace)
        # isolates the engine half, so queue time reads as the gap
        with dtrace.span("serve.score"):
            labels, scores = batcher.submit(
                rows, ctx=dtrace.current()).result()
        labels, scores = np.asarray(labels), np.asarray(scores)
        self._model_requests[mid] += 1
        if self.feedback is not None:
            self.feedback.scored(lines, rows, scores, version=version,
                                 ids=ids, trace=dtrace.current_ids(),
                                 model=mid if self._multi else None)
        return labels, scores

    def _handle_label(self, line: str) -> str:
        if self.feedback is None:
            raise ValueError(
                "this server runs no feedback sink (start with "
                "--feedback-spool to close the loop)")
        parts = line.split()
        if len(parts) != 3:
            raise ValueError("LABEL needs exactly: LABEL <request_id> <0|1>")
        y = float(parts[2])
        if y not in (0.0, 1.0):
            raise ValueError(f"label must be 0 or 1, got {parts[2]!r}")
        return f"OK {self.feedback.label(parts[1], int(y))}"

    def handle_model_line(self, line: str,
                          scope: str | None) -> tuple[str, str | None]:
        """``MODEL <id>`` connection scoping: subsequent unaddressed
        lines on this connection score on ``<id>``.  Returns
        ``(reply, new_scope)`` — an unknown id keeps the old scope."""
        parts = line.split()
        if len(parts) != 2:
            self._errors_c.inc()
            return "ERR MODEL: need MODEL <id>", scope
        if parts[1] not in self.engines:
            self._errors_c.inc()
            return (f"ERR MODEL: unknown model {parts[1]!r} (hosted: "
                    f"{','.join(self.engines)})", scope)
        return f"OK MODEL {parts[1]}", parts[1]

    def handle_line(self, line: str, model: str | None = None) -> str:
        """One request line -> one reply line.  An additive ``TRACE
        <tid>/<sid> <line>`` prefix (minted by the router, or by any
        traced client) joins this request to a distributed trace; a
        server reached directly mints its own root for scoring lines.
        ``model`` is the connection's ``MODEL`` scope (a per-request
        ``@<id>`` prefix inside the line overrides it).  Replies never
        carry the prefix — clients see identical bytes."""
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
        elif line != "STATS" and not line.startswith("LABEL"):
            # LABEL lines continue their REQUEST's trace via the spool
            # record instead of minting a second trace per label
            ctx = dtrace.new_trace()
        if ctx is None:
            return self._handle_request(line, model)
        with dtrace.use(ctx), dtrace.span(
                "serve.request",
                tags={"listener": f"{self.host}:{self.port}"}):
            return self._handle_request(line, model)

    def _handle_request(self, line: str, model: str | None = None) -> str:
        t0 = time.monotonic()
        if line.startswith("@"):
            # per-request model addressing (additive): "@<id> <line>"
            prefix, _, rest = line.partition(" ")
            model, line = prefix[1:], rest.strip()
            if not model or not line:
                self._errors_c.inc()
                return "ERR MODEL: need @<id> <request line>"
        if model is not None and model not in self.engines:
            self._errors_c.inc()
            return (f"ERR MODEL: unknown model {model!r} (hosted: "
                    f"{','.join(self.engines)})")
        try:
            if line == "STATS":
                return json.dumps(self.stats())
            if line.startswith("LABEL ") or line == "LABEL":
                return self._handle_label(line)
            if line.startswith("{"):
                req = json.loads(line)
                batch = req.get("rows")
                if not isinstance(batch, list) or not batch:
                    raise ValueError('JSON request needs a non-empty "rows" list')
                ids = req.get("ids")
                if ids is not None and (not isinstance(ids, list)
                                        or len(ids) != len(batch)):
                    raise ValueError(
                        '"ids" must be a list parallel to "rows"')
                labels, scores = self._score_lines(
                    [str(r) for r in batch],
                    None if ids is None
                    else [None if i is None else str(i) for i in ids],
                    model)
                reply = json.dumps({
                    "labels": [int(v) for v in labels],
                    "scores": [round(float(v), 6) for v in scores],
                })
            else:
                ids = None
                if line.startswith("ID "):
                    parts = line.split(None, 2)
                    if len(parts) != 3:
                        raise ValueError(
                            "ID mode needs: ID <request_id> <features>")
                    line, ids = parts[2], [parts[1]]
                labels, scores = self._score_lines([line], ids, model)
                reply = f"{int(labels[0])} {float(scores[0]):.6g}"
        except Exception as e:
            self._errors_c.inc()
            return f"ERR {type(e).__name__}: {e}"
        self._req_seconds.observe(time.monotonic() - t0)
        self._requests_c.inc()
        return reply

    # -- stats -------------------------------------------------------------
    def stats(self) -> dict:
        """STATS reply, answered from the obs registry (schema unchanged
        from the pre-registry accumulator: requests/errors/qps/p50_ms/
        p99_ms + batcher/engine sub-objects — pinned by the regression
        test in tests/test_serve.py)."""
        n_req = int(self._requests_c.value - self._req_base)
        n_err = int(self._errors_c.value - self._err_base)
        elapsed = max(time.monotonic() - self._t0, 1e-9)
        rec = {
            "requests": n_req,
            "errors": n_err,
            "qps": round(n_req / elapsed, 2),
            "p50_ms": round(self._req_seconds.percentile(0.50) * 1e3, 3),
            "p99_ms": round(self._req_seconds.percentile(0.99) * 1e3, 3),
            # Routing-tier schema parity (additive — ISSUE 4): the
            # ScoringRouter's STATS carries the same scalar keys with
            # live values; a single engine behind no router never sheds
            # or retries and IS its own one-replica tier, so a scraper
            # parses either reply with one schema.
            "shed": 0,
            "retries": 0,
            "replica_count": 1,
            # Multi-tenant additions (additive, like shed/retries were):
            # hosted-model count and per-model request/engine state.  A
            # single-engine server reports models=1 under "default".
            "models": len(self.engines),
            "per_model": {
                mid: {
                    "requests": self._model_requests[mid],
                    "shed": 0,
                    "engine": eng.stats(),
                }
                for mid, eng in self.engines.items()
            },
            "batcher": self.batcher.stats(),
            "engine": self.engine.stats(),
        }
        if self.reloader is not None:
            rec["reload"] = self.reloader.stats()
        if self.feedback is not None:
            # additive, like "reload": the pinned scalar schema above is
            # untouched when no sink runs
            rec["feedback"] = self.feedback.stats()
        # mirror into the structured metrics stream (train/metrics.py
        # conventions: one flat record per observation) — unless the
        # logger was closed by stop(): final stats after shutdown must
        # still be readable, only the mirror is gone
        if not self.metrics.closed:
            self.metrics.log(
                requests=rec["requests"], qps=rec["qps"],
                p50_ms=rec["p50_ms"], p99_ms=rec["p99_ms"],
                occupancy=rec["batcher"]["mean_occupancy"],
            )
        return rec

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "ScoringServer":
        self._started = True
        if self.feedback is not None:
            self.feedback.start()  # window-expiry / idle-flush ticker
        self._thread.start()
        log.info("serving %s on %s:%d (max_batch=%d, buckets=%s, "
                 "models=%s)",
                 self.engine.cfg.model, self.host, self.port,
                 self.engine.max_batch_size, list(self.engine.buckets),
                 ",".join(self.engines))
        return self

    def serve_forever(self) -> None:
        """Foreground mode for the CLI: start, then block until stopped."""
        self.start()
        try:
            while self._thread.is_alive():
                self._thread.join(timeout=1.0)
        except KeyboardInterrupt:
            pass
        finally:
            self.stop()

    def stop(self) -> None:
        if self._started:
            # shutdown() blocks forever unless serve_forever actually
            # ran (the MetricsServer.stop() bug class from ISSUE 3)
            self._tcp.shutdown()
        self._tcp.server_close()
        for batcher in self._batchers.values():
            batcher.close()
        if self.reloader is not None:
            self.reloader.stop()
        for rl in self._extra_reloaders:
            rl.stop()
        if self.feedback is not None:
            self.feedback.stop()  # flushes the partial shard
        self.metrics.close()

    def abort(self) -> None:
        """Crash-simulation shutdown (failover drills, router tests):
        stop accepting AND sever every active connection mid-stream, so
        clients see a transport error exactly as if the process were
        SIGKILLed — none of the orderly drain :meth:`stop` performs.
        The listener port is released, so a respawned server can rebind
        it (the eject -> reinstate lifecycle the router e2e exercises).
        """
        if self._started:
            self._tcp.shutdown()
        self._tcp.server_close()
        with self._conn_lock:
            conns = list(self._active_conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        # shared teardown (a SIGKILLed process takes its reload poller
        # and metrics sink with it too); shutdown/server_close above are
        # idempotent, so delegating keeps the two lifecycles in lockstep
        self.stop()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


def score_lines_over_tcp(host: str, port: int, lines: list[str],
                         *, timeout_s: float = 30.0) -> list[str]:
    """Tiny client helper (tests/benchmarks): send ``lines``, return the
    reply line for each, over one connection."""
    replies = []
    with socket.create_connection((host, port), timeout=timeout_s) as s:
        f = s.makefile("rwb")
        for ln in lines:
            f.write((ln.strip() + "\n").encode())
            f.flush()
            reply = f.readline()
            if not reply:
                raise ConnectionError("server closed mid-stream")
            replies.append(reply.decode().rstrip("\n"))
    return replies
