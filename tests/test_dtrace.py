"""Fleet-wide distributed tracing (ISSUE 8).

Covers the dtrace core (context/token/sampler, spans, journal, flight
ring), the serve-protocol ``TRACE`` prefix (server + router, replies
byte-identical), the KV-wire trailer (negotiated capability, byte-exact
wire accounting, ``--trace-sample 0`` = byte-identical, old-server
fallback to client-only spans), trace-agg journal merging (valid Chrome
JSON, clock alignment, chaos instants), the alert-triggered flight
recorder, and the acceptance e2e: one routed score request + one LABEL
produce a SINGLE merged trace whose router -> engine -> feedback ->
online-trainer -> PS-client -> native-server spans share one trace_id
with correct parent links.
"""

import glob
import json
import os
import socket
import time

import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.obs import dtrace
from distlr_tpu.ps import KVWorker, RetryPolicy, ServerGroup

D = 32


@pytest.fixture(autouse=True)
def _reset_tracer():
    yield
    dtrace.reset_for_tests()


def _counter_total(name: str) -> float:
    from distlr_tpu.obs.registry import get_registry

    fam = get_registry().get(name)
    if fam is None:
        return 0.0
    return float(sum(child.value for _v, child in fam.children()))


def _read_journal(run_dir: str, stem: str) -> list[dict]:
    path = os.path.join(run_dir, "spans", stem + ".jsonl")
    out = []
    with open(path) as f:
        for line in f:
            if line.strip():
                out.append(json.loads(line))
    return out


# ---------------------------------------------------------------------------
# core: context, sampler, spans, ring
# ---------------------------------------------------------------------------

class TestCore:
    def test_token_roundtrip(self):
        ctx = dtrace.TraceContext(0xDEADBEEF, 0x1234, True)
        back = dtrace.parse_token(ctx.token())
        assert (back.trace_id, back.span_id) == (0xDEADBEEF, 0x1234)
        assert back.sampled  # propagated contexts are sampled by definition
        with pytest.raises(ValueError, match="malformed trace token"):
            dtrace.parse_token("not-a-token")

    def test_sampler_deterministic_and_monotone(self):
        ids = [dtrace.is_sampled(i, 0.5) for i in range(1, 2000)]
        assert ids == [dtrace.is_sampled(i, 0.5) for i in range(1, 2000)]
        frac = sum(ids) / len(ids)
        assert 0.4 < frac < 0.6  # hash-uniform, not exact
        # a trace sampled at rate r stays sampled at every r' > r (the
        # decision is a threshold on one hash)
        for i in range(1, 500):
            if dtrace.is_sampled(i, 0.1):
                assert dtrace.is_sampled(i, 0.7)
        assert not any(dtrace.is_sampled(i, 0.0) for i in range(1, 100))
        assert all(dtrace.is_sampled(i, 1.0) for i in range(1, 100))

    def test_unconfigured_process_pays_nothing(self):
        assert dtrace.new_trace() is None
        assert dtrace.token() is None
        with dtrace.span("noop") as sp:
            assert sp is None

    def test_span_nesting_and_journal_parent_links(self, tmp_path):
        run = str(tmp_path)
        dtrace.configure(run, "unit", 3, sample=1.0)
        ctx = dtrace.new_trace()
        assert ctx is not None and ctx.sampled
        with dtrace.use(ctx):
            with dtrace.span("outer", tags={"k": "v"}) as outer:
                with dtrace.span("inner") as inner:
                    pass
        dtrace.flush()
        recs = _read_journal(run, "unit-3")
        assert recs[0]["type"] == "meta" and recs[0]["role"] == "unit"
        spans = {r["name"]: r for r in recs if r["type"] == "span"}
        assert set(spans) == {"outer", "inner"}
        tid = f"{ctx.trace_id:016x}"
        assert spans["outer"]["trace"] == spans["inner"]["trace"] == tid
        assert spans["inner"]["parent"] == f"{outer.span_id:016x}"
        assert spans["outer"]["parent"] is None  # root span of the trace
        assert spans["inner"]["span"] == f"{inner.span_id:016x}"
        assert spans["outer"]["args"] == {"k": "v"}

    def test_unsampled_spans_ring_only(self, tmp_path):
        run = str(tmp_path)
        dtrace.configure(run, "unit", 0, sample=0.0)
        ctx = dtrace.new_trace()
        assert ctx is not None and not ctx.sampled
        with dtrace.use(ctx), dtrace.span("quiet"):
            pass
        dtrace.flush()
        recs = _read_journal(run, "unit-0")
        assert all(r["type"] != "span" for r in recs)  # journal: meta only
        path = dtrace.flight_dump("unit-test")
        dump = json.load(open(path))
        assert any(r.get("name") == "quiet" for r in dump["spans"])

    def test_flight_ring_is_bounded(self, tmp_path):
        dtrace._TRACER.configure(str(tmp_path), "unit", 0, sample=0.0,
                                 flight_capacity=16)
        for i in range(100):
            dtrace.event("crumb", i=i)
        path = dtrace.flight_dump("bound-test")
        doc = json.load(open(path))
        assert len(doc["spans"]) == 16  # ring kept only the newest 16
        assert doc["spans"][-1]["args"] == {"i": 99}


# ---------------------------------------------------------------------------
# trace-agg: merge, clock alignment, chaos instants, CLI
# ---------------------------------------------------------------------------

def _write_journal(run_dir, stem, recs):
    d = os.path.join(run_dir, "spans")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, stem + ".jsonl"), "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


class TestTraceAgg:
    def test_merge_emits_valid_chrome_json(self, tmp_path):
        run = str(tmp_path)
        _write_journal(run, "client-0", [
            {"type": "meta", "role": "client", "rank": 0},
            {"type": "span", "name": "ps.push", "trace": "ab", "span": "01",
             "parent": None, "ts": 1000.0, "dur": 50.0, "tid": 7,
             "args": {}},
            {"type": "instant", "name": "chaos.reset", "ts": 1010.0,
             "tid": 7, "args": {"link": 0, "trace": "ab"}},
        ])
        out = os.path.join(run, "merged.json")
        doc = dtrace.write_merged_trace([run], out)
        on_disk = json.load(open(out))
        assert on_disk["traceEvents"] == doc["traceEvents"]
        phases = {e["ph"] for e in doc["traceEvents"]}
        assert phases == {"M", "X", "i"}
        x = [e for e in doc["traceEvents"] if e["ph"] == "X"][0]
        assert x["args"]["trace"] == "ab" and x["dur"] == 50.0
        assert doc["otherData"]["spans"] == 1
        assert doc["otherData"]["trace_ids"] == ["ab"]

    def test_clock_alignment_shifts_server_journal(self, tmp_path):
        run = str(tmp_path)
        _write_journal(run, "worker-0", [
            {"type": "meta", "role": "worker", "rank": 0},
            {"type": "clock", "peer": "10.0.0.9:7001", "offset_s": 2.0},
            {"type": "span", "name": "ps.push", "trace": "ab", "span": "01",
             "parent": None, "ts": 1_000_000.0, "dur": 10.0, "tid": 1,
             "args": {}},
        ])
        _write_journal(run, "kvserver-0", [
            # the server's clock runs 2 s AHEAD; its meta names its
            # listen address so the port pairs it with the probe above
            {"type": "meta", "role": "kvserver", "listen": "0.0.0.0:7001"},
            {"type": "span", "name": "kv.push", "trace": "ab", "span": "02",
             "parent": "01", "ts": 3_000_000.0, "dur": 5.0, "tid": 2,
             "args": {}},
        ])
        doc = dtrace.merge_run_dirs([run])
        by_name = {e["name"]: e for e in doc["traceEvents"]
                   if e["ph"] == "X"}
        assert by_name["ps.push"]["ts"] == 1_000_000.0
        # 3_000_000 us - 2 s offset = 1_000_000 us: on the client clock
        assert by_name["kv.push"]["ts"] == 1_000_000.0
        assert doc["otherData"]["clock_offsets"] == {"7001": 2.0}

    def test_trace_agg_cli(self, tmp_path):
        from distlr_tpu.launch import main

        run = str(tmp_path / "run")
        _write_journal(run, "client-0", [
            {"type": "span", "name": "x", "trace": "01", "span": "02",
             "parent": None, "ts": 0.0, "dur": 1.0, "tid": 0, "args": {}},
        ])
        out = str(tmp_path / "trace.json")
        assert main(["trace-agg", "--obs-run-dir", run, "--out", out]) == 0
        assert json.load(open(out))["otherData"]["spans"] == 1
        # an empty run dir is a loud failure, not a silent empty trace
        empty = str(tmp_path / "empty")
        os.makedirs(empty)
        assert main(["trace-agg", "--obs-run-dir", empty,
                     "--out", out]) == 1


# ---------------------------------------------------------------------------
# serve protocol: TRACE prefix at the server and the router
# ---------------------------------------------------------------------------

def _mk_engine():
    from distlr_tpu.serve import ScoringEngine

    cfg = Config(model="binary_lr", num_feature_dim=D, l2_c=0.0)
    engine = ScoringEngine(cfg, max_batch_size=64)
    engine.set_weights(np.linspace(-1, 1, D).astype(np.float32))
    return engine


class TestServeProtocol:
    def test_trace_prefix_strips_and_reply_is_identical(self, tmp_path):
        from distlr_tpu.serve import ScoringServer

        srv = ScoringServer(_mk_engine())
        try:
            plain = srv.handle_line("3:1 5:1")
            dtrace.configure(str(tmp_path), "serve", 0, sample=1.0)
            tok = dtrace.TraceContext(0xA1, 0xB2, True).token()
            traced = srv.handle_line(f"TRACE {tok} 3:1 5:1")
            assert traced == plain  # replies never carry the prefix
            assert srv.handle_line("TRACE broken").startswith("ERR TRACE")
            assert srv.handle_line("TRACE nothex/zz 3:1").startswith(
                "ERR TRACE")
            dtrace.flush()
            recs = _read_journal(str(tmp_path), "serve-0")
            req = [r for r in recs if r.get("name") == "serve.request"]
            assert req and req[0]["trace"] == f"{0xA1:016x}"
            assert req[0]["parent"] == f"{0xB2:016x}"
            # the engine/batcher joined the same trace
            names = {r.get("name") for r in recs}
            assert {"serve.encode", "serve.score", "serve.batch",
                    "serve.infer"} <= names
        finally:
            srv.stop()

    def test_direct_request_mints_own_root(self, tmp_path):
        from distlr_tpu.serve import ScoringServer

        dtrace.configure(str(tmp_path), "serve", 0, sample=1.0)
        srv = ScoringServer(_mk_engine())
        try:
            assert not srv.handle_line("3:1").startswith("ERR")
        finally:
            srv.stop()
        dtrace.flush()
        req = [r for r in _read_journal(str(tmp_path), "serve-0")
               if r.get("name") == "serve.request"]
        assert req and req[0]["parent"] is None  # a root, not a join

    def test_router_propagates_trace_to_replica(self, tmp_path):
        from distlr_tpu.serve import ScoringServer
        from distlr_tpu.serve.router import ScoringRouter

        dtrace.configure(str(tmp_path), "tier", 0, sample=1.0)
        srv = ScoringServer(_mk_engine()).start()
        router = ScoringRouter([f"{srv.host}:{srv.port}"]).start()
        try:
            reply = router.handle_line("3:1 5:1")
            assert not reply.startswith("ERR"), reply
        finally:
            router.stop()
            srv.stop()
        dtrace.flush()
        recs = _read_journal(str(tmp_path), "tier-0")
        spans = {r["name"]: r for r in recs if r.get("type") == "span"}
        route, serve = spans["route.request"], spans["serve.request"]
        assert route["parent"] is None
        assert serve["trace"] == route["trace"]
        assert serve["parent"] == route["span"]


# ---------------------------------------------------------------------------
# KV wire: negotiation, byte-exact trailer accounting, fallbacks
# ---------------------------------------------------------------------------

def _wire_sent(w: KVWorker) -> int:
    return int(w._lib.kv_last_wire_sent(w._h))


#: one dense default-key push to the one server these tests spawn: the
#: header, one u64 row key for the run of D values (D <= the protocol's
#: vals_per_key cap, so the whole vector is one row), 4 B a value
DENSE_PUSH = 24 + 8 + D * 4


class TestKVWire:
    def test_sample_zero_wire_byte_identical(self, tmp_path):
        """The regression pin: with tracing off (unconfigured, or
        ``--trace-sample 0``) every push frame is exactly the pre-trace
        protocol — header(24) + 8 B a row key + 4 B/val, nothing else."""
        with ServerGroup(1, 1, D, sync=False) as group:
            w = KVWorker(group.hosts, D, client_id=1, timeout_ms=10_000,
                         sync_group=False)
            try:
                w.push_init(np.zeros(D, np.float32))
                w.wait(w.push(np.ones(D, np.float32)))
                assert _wire_sent(w) == DENSE_PUSH
                assert not w.trace_active
            finally:
                w.close()
            # configured but sample 0 — the --trace-sample 0 contract
            dtrace.configure(str(tmp_path), "w", 0, sample=0.0)
            w = KVWorker(group.hosts, D, client_id=2, timeout_ms=10_000,
                         sync_group=False)
            try:
                assert not w.trace_active  # no negotiation at sample 0
                ctx = dtrace.new_trace()
                with dtrace.use(ctx):
                    w.wait(w.push(np.ones(D, np.float32)))
                assert _wire_sent(w) == DENSE_PUSH
            finally:
                w.close()

    def test_sampled_op_carries_16_byte_trailer_and_server_logs_span(
            self, tmp_path):
        run = str(tmp_path)
        dtrace.configure(run, "w", 0, sample=1.0)
        with ServerGroup(1, 1, D, sync=False,
                         trace_journal_dir=os.path.join(run, "spans"),
                         ) as group:
            w = KVWorker(group.hosts, D, client_id=1, timeout_ms=10_000,
                         sync_group=False)
            try:
                assert w.trace_active
                w.push_init(np.zeros(D, np.float32))
                base = _wire_sent(w)  # untraced op: no trailer
                assert base == DENSE_PUSH
                ctx = dtrace.new_trace()
                with dtrace.use(ctx):
                    w.wait(w.push(np.ones(D, np.float32)))
                    assert _wire_sent(w) == DENSE_PUSH + 16
                    out = w.pull()
                assert out.shape == (D,)  # the stamped pull round-tripped
            finally:
                w.close()
            dtrace.flush()
            # the server journals a handler span AFTER sending its reply
            # (TraceLog rides the handler thread, off the reply path), so
            # the client's round trip completing does not prove the span
            # line exists yet — a SIGTERM landing in that window loses
            # the tail span (observed as a loaded-machine flake).  Give
            # the handler thread a beat before tearing the group down.
            time.sleep(0.1)
        # the server's journal flush is batched; its SIGTERM/exit path
        # flushes the tail — read AFTER the group stops
        py = _read_journal(run, "w-0")
        srv = _read_journal(run, "kvserver-0")
        client_push = [r for r in py if r.get("name") == "ps.push"]
        assert client_push, py
        srv_spans = [r for r in srv if r.get("type") == "span"]
        assert {r["name"] for r in srv_spans} == {"kv.push", "kv.pull"}
        tid = f"{ctx.trace_id:016x}"
        for r in srv_spans:
            assert r["trace"] == tid
            assert r["args"]["optimizer"] == "sgd"
        # the server handler span parents under the CLIENT's op span
        push_srv = next(r for r in srv_spans if r["name"] == "kv.push")
        assert push_srv["parent"] == client_push[0]["span"]
        assert push_srv["args"]["codec"] == "none"
        # the hello doubled as a clock probe -> journaled offset
        assert any(r.get("type") == "clock" for r in py)

    def test_pre_trace_server_degrades_to_client_only_spans(self, tmp_path):
        run = str(tmp_path)
        dtrace.configure(run, "w", 0, sample=1.0)
        # --compress=0 answers kHello like a pre-capability binary
        with ServerGroup(1, 1, D, sync=False, compress=False,
                         trace_journal_dir=os.path.join(run, "spans"),
                         ) as group:
            w = KVWorker(group.hosts, D, client_id=1, timeout_ms=10_000,
                         sync_group=False)
            try:
                assert not w.trace_active  # graceful fallback, no error
                w.push_init(np.zeros(D, np.float32))
                ctx = dtrace.new_trace()
                with dtrace.use(ctx):
                    w.wait(w.push(np.ones(D, np.float32)))
                # no trailer on the wire against an old server
                assert _wire_sent(w) == DENSE_PUSH
            finally:
                w.close()
        dtrace.flush()
        py = _read_journal(run, "w-0")
        assert any(r.get("name") == "ps.push" for r in py)  # client-only


# ---------------------------------------------------------------------------
# chaos: fault events record the faulted op's trace id
# ---------------------------------------------------------------------------

class TestChaosTraceTag:
    def test_fault_event_carries_trace_id(self, tmp_path):
        from distlr_tpu.chaos import parse_plan

        run = str(tmp_path)
        dtrace.configure(run, "w", 0, sample=1.0)
        plan = parse_plan({"seed": 5, "faults": [
            {"kind": "delay", "links": "*", "delay_ms": 1},
        ]})
        with ServerGroup(1, 1, D, sync=False, via_chaos=plan) as group:
            w = KVWorker(group.hosts, D, client_id=1, timeout_ms=10_000,
                         sync_group=False)
            try:
                assert w.trace_active
                w.push_init(np.zeros(D, np.float32))
                ctx = dtrace.new_trace()
                with dtrace.use(ctx):
                    w.wait(w.push(np.ones(D, np.float32)))
            finally:
                w.close()
            events = group.chaos.events()
        tid = f"{ctx.trace_id:016x}"
        traced = [e for e in events if ("trace", tid) in e]
        assert traced, events
        # untraced ops (hello, init push) delayed WITHOUT a trace tag —
        # the schema is additive, absent unless the frame carried one
        untraced = [e for e in events
                    if not any(isinstance(kv, tuple) and kv[0] == "trace"
                               for kv in e[2:])]
        assert untraced, events


# ---------------------------------------------------------------------------
# flight recorder: alert-triggered dumps capture the seconds BEFORE
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_manual_trigger_cli_dumps_ring(self, tmp_path):
        from distlr_tpu.launch import main

        run = str(tmp_path / "run")
        dtrace.configure(run, "proc", 2, sample=0.0)
        ctx = dtrace.new_trace()
        with dtrace.use(ctx), dtrace.span("before.trigger"):
            pass
        assert main(["flightrec", "--obs-run-dir", run]) == 0
        deadline = time.monotonic() + 5.0
        dumps = []
        while not dumps and time.monotonic() < deadline:
            dumps = glob.glob(os.path.join(run, "flightrec",
                                           "proc-2-*.json"))
            time.sleep(0.05)
        assert dumps, "watcher never dumped"
        doc = json.load(open(dumps[0]))
        assert doc["reason"] == "manual"
        assert any(r.get("name") == "before.trigger" for r in doc["spans"])

    def test_ps_retry_alert_trips_dump_with_pre_alert_spans(self, tmp_path):
        """Acceptance: trip ``distlr_alert_ps_retry_rate`` under a chaos
        plan and the dump contains spans recorded BEFORE the firing
        scrape."""
        from distlr_tpu.chaos import parse_plan
        from distlr_tpu.obs import write_metrics_snapshot
        from distlr_tpu.obs.federate import AlertThresholds, FleetScraper
        from distlr_tpu.obs.registry import get_registry

        run = str(tmp_path / "run")
        dtrace.configure(run, "worker", 0, sample=0.0)
        # breadcrumbs the postmortem must surface (ring-only: unsampled)
        ctx = dtrace.new_trace()
        with dtrace.use(ctx), dtrace.span("pre.alert.step"):
            pass

        before = _counter_total("distlr_ps_retries_total")
        plan = parse_plan({"seed": 7, "faults": [
            {"kind": "reset", "links": [0], "after_ops": 3},
        ]})
        with ServerGroup(1, 1, D, sync=False, via_chaos=plan) as group:
            w = KVWorker(group.hosts, D, client_id=1, timeout_ms=5000,
                         sync_group=False,
                         retry=RetryPolicy(attempts=4, backoff_ms=10.0,
                                           deadline_s=20.0))
            try:
                w.push_init(np.zeros(D, np.float32))
                for _ in range(8):  # op 3 eats the reset -> retried
                    w.pull()
            finally:
                w.close()
        assert _counter_total("distlr_ps_retries_total") > before

        os.makedirs(os.path.join(run, "snapshots"), exist_ok=True)
        write_metrics_snapshot(os.path.join(run, "snapshots",
                                            "worker-0.json"),
                               get_registry())
        scraper = FleetScraper(run, thresholds=AlertThresholds(
            retry_rate=1e-9))
        scraper.scrape_once()
        alerts = {a["name"]: a for a in scraper.fleet_json()["alerts"]}
        assert alerts["distlr_alert_ps_retry_rate"]["firing"]

        deadline = time.monotonic() + 5.0
        dumps = []
        while not dumps and time.monotonic() < deadline:
            dumps = glob.glob(os.path.join(run, "flightrec",
                                           "worker-0-*.json"))
            time.sleep(0.05)
        assert dumps, "alert fired but no flight-recorder dump appeared"
        doc = json.load(open(dumps[0]))
        assert "distlr_alert_ps_retry_rate" in doc["reason"]
        assert any(r.get("name") == "pre.alert.step" for r in doc["spans"])
        # a STILL-firing alert on the next scrape must not re-trigger
        seq0 = len(glob.glob(os.path.join(run, "flightrec", "*.json")))
        scraper.scrape_once()
        time.sleep(0.6)
        assert len(glob.glob(os.path.join(run, "flightrec",
                                          "*.json"))) == seq0


# ---------------------------------------------------------------------------
# `launch top`: e2e serve-latency column (satellite)
# ---------------------------------------------------------------------------

class TestTopLatencyColumn:
    def test_route_latency_rendered(self):
        from distlr_tpu.obs.top import render_fleet

        fleet = {
            "updated": time.time(), "run_dir": "/tmp/x",
            "totals": {"ranks": 1, "up": 1, "stale": 0, "down": 0,
                       "samples_per_s": 0.0},
            "alerts": [],
            "ranks": [{"role": "route", "rank": 0, "state": "up",
                       "route_requests": 100, "route_p50_ms": 1.25,
                       "route_p99_ms": 9.5}],
        }
        frame = render_fleet(fleet, color=False)
        assert "e2e p50/p99" in frame
        assert "1.25/9.50" in frame

    def test_fleet_json_carries_route_percentiles(self, tmp_path):
        """The aggregator extracts route p50/p99 from the routing
        tier's latency histogram snapshot."""
        from distlr_tpu.obs import write_metrics_snapshot
        from distlr_tpu.obs.federate import FleetScraper
        from distlr_tpu.obs.registry import get_registry
        from distlr_tpu.serve.router import _REQ_SECONDS, _REQUESTS

        _REQUESTS.labels(listener="t:1").inc()
        for v in (0.001, 0.002, 0.01):
            _REQ_SECONDS.labels(listener="t:1").observe(v)
        run = str(tmp_path)
        os.makedirs(os.path.join(run, "snapshots"))
        write_metrics_snapshot(os.path.join(run, "snapshots",
                                            "route-0.json"),
                               get_registry())
        scraper = FleetScraper(run)
        scraper.scrape_once()
        row = [r for r in scraper.fleet_json()["ranks"]
               if r["role"] == "route"][0]
        assert row["route_p50_ms"] > 0
        assert row["route_p99_ms"] >= row["route_p50_ms"]


# ---------------------------------------------------------------------------
# acceptance e2e: one request, one label, ONE merged trace
# ---------------------------------------------------------------------------

class TestEndToEnd:
    def test_routed_request_and_label_share_one_merged_trace(self, tmp_path):
        from distlr_tpu.feedback import FeedbackSink, OnlineTrainer
        from distlr_tpu.launch import main
        from distlr_tpu.serve import ScoringServer
        from distlr_tpu.serve.router import ScoringRouter

        run = str(tmp_path / "run")
        dtrace.configure(run, "tier", 0, sample=1.0)
        cfg = Config(model="binary_lr", num_feature_dim=D, batch_size=8,
                     l2_c=0.0, sync_mode=False, ps_timeout_ms=20_000)
        group = ServerGroup(
            1, 1, D, sync=False, optimizer="ftrl", ftrl_alpha=1.0,
            ftrl_beta=1.0,
            trace_journal_dir=os.path.join(run, "spans")).start()
        sink = FeedbackSink(
            str(tmp_path / "spool"), str(tmp_path / "shards"),
            model="binary_lr", window_s=30.0, shard_records=1)
        srv = ScoringServer(_mk_engine(), feedback=sink).start()
        router = ScoringRouter([f"{srv.host}:{srv.port}"]).start()
        trainer = None
        try:
            with socket.create_connection((router.host, router.port),
                                          timeout=20.0) as s:
                f = s.makefile("rwb")

                def ask(line):
                    f.write((line + "\n").encode())
                    f.flush()
                    return f.readline().decode().rstrip("\n")

                assert not ask("ID e2e-1 3:1 5:1").startswith("ERR")
                assert ask("LABEL e2e-1 1") == "OK joined"
            # shard_records=1: the join wrote the shard synchronously
            trainer = OnlineTrainer(cfg, group.hosts,
                                    str(tmp_path / "shards"),
                                    accum_start=1, poll_interval_s=0.05)
            stats = trainer.run(max_shards=1, idle_exit_s=10.0)
            assert stats["shards_consumed"] == 1 and stats["pushes"] >= 1
        finally:
            if trainer is not None:
                trainer.close()
            router.stop()
            srv.stop()
            sink.stop()
            dtrace.flush()
            time.sleep(0.2)
            group.stop()

        out = str(tmp_path / "merged.json")
        assert main(["trace-agg", "--obs-run-dir", run, "--out", out]) == 0
        doc = json.load(open(out))
        # valid Chrome/Perfetto trace-event JSON
        assert isinstance(doc["traceEvents"], list)
        assert all(e["ph"] in ("M", "X", "i") for e in doc["traceEvents"])

        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        by_name = {}
        for e in spans:
            by_name.setdefault(e["name"], []).append(e)

        # the request's trace: the one serve.request belongs to
        req = by_name["route.request"][0]
        tid = req["args"]["trace"]
        chain = ["route.request", "serve.request", "feedback.spool",
                 "feedback.join", "online.consume", "ps.push", "kv.push"]
        for name in chain + ["serve.encode", "serve.score", "serve.batch",
                             "serve.infer"]:
            ours = [e for e in by_name.get(name, [])
                    if e["args"].get("trace") == tid]
            assert ours, f"span {name!r} missing from trace {tid}"
        # correct parent links down the whole causal chain; the online
        # trainer's pushes ride the label's trace into the FTRL server
        ids = {}
        for name in chain:
            e = [x for x in by_name[name]
                 if x["args"].get("trace") == tid][0]
            ids[name] = (e["args"]["span"], e["args"]["parent"])
        assert ids["route.request"][1] is None
        for child, parent in zip(chain[1:], chain):
            assert ids[child][1] == ids[parent][0], (
                f"{child} should parent under {parent}: {ids}")
        kv_push = [x for x in by_name["kv.push"]
                   if x["args"].get("trace") == tid][0]
        assert kv_push["args"]["optimizer"] == "ftrl"  # the FTRL apply
        # exactly ONE trace ties them all together
        assert tid in doc["otherData"]["trace_ids"]
