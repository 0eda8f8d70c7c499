"""Fleet observability tests (ISSUE 3, distlr_tpu/obs/federate + top).

Covers the federation contract: endpoint discovery, the merge math
(counters sum, gauges keep per-rank identity, histograms merge
bucket-wise, mismatched boundaries rejected loudly), scrape meta-series
flipping on a down rank, derived ``distlr_alert_*`` gauges, the fleet
smoke (two dummy metric-emitting processes + the aggregator CLI), the
``launch top`` renderer, and the acceptance e2e: a real multi-process
async PS run (1 server host + 2 worker processes) federated into one
scrape that carries every rank role/rank-labeled, the alert gauges, and
a non-empty pushes-behind staleness histogram.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

from distlr_tpu.data.synthetic import write_synthetic_shards
from distlr_tpu.obs import (
    AlertThresholds,
    FleetMergeError,
    FleetScraper,
    MetricsRegistry,
    MetricsServer,
    discover_endpoints,
    evaluate_alerts,
    merge_snapshots,
    write_endpoint,
)
from distlr_tpu.obs.top import render_fleet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rank_registry(rank: int) -> MetricsRegistry:
    reg = MetricsRegistry()
    reg.counter("fleet_ops_total", "ops", ("op",)).labels(op="push").inc(
        10 + rank)
    reg.gauge("fleet_rate", "per-rank rate", ("instance",)).labels(
        instance="0").set(100.0 * (rank + 1))
    h = reg.histogram("fleet_lat_seconds", "lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5 + rank)  # rank 1's lands past le=1.0
    return reg


class TestEndpointDiscovery:
    def test_write_and_discover_roundtrip(self, tmp_path):
        run = str(tmp_path)
        write_endpoint(run, "worker", 1, "127.0.0.1", 9101)
        write_endpoint(run, "ps-server", 0, "127.0.0.1", 9100)
        eps = discover_endpoints(run)
        assert [(e["role"], e["rank"], e["port"]) for e in eps] == [
            ("ps-server", 0, 9100), ("worker", 1, 9101)]
        assert all(e["pid"] == os.getpid() for e in eps)

    def test_unparseable_files_skipped(self, tmp_path):
        run = str(tmp_path)
        write_endpoint(run, "worker", 0, "127.0.0.1", 9100)
        with open(os.path.join(run, "endpoints", "garbage.json"), "w") as f:
            f.write("{not json")
        assert len(discover_endpoints(run)) == 1

    def test_empty_dir(self, tmp_path):
        assert discover_endpoints(str(tmp_path)) == []

    def test_same_rank_republish_warns_on_collision(self, tmp_path):
        """Two processes claiming one (role, rank) — e.g. two ps-server
        hosts sharing a run dir without --process-id — must be called
        out loudly: the merge keys on (role, rank), so the overwritten
        publisher would neither scrape nor alert."""
        import logging

        records = []

        class _Catch(logging.Handler):
            def emit(self, record):
                records.append(record.getMessage())

        # the repo's loggers set propagate=False, so attach directly
        logger = logging.getLogger("distlr_tpu.obs.federate")
        catch = _Catch(level=logging.WARNING)
        logger.addHandler(catch)
        try:
            run = str(tmp_path)
            write_endpoint(run, "ps-server", 0, "10.0.0.1", 9100)
            write_endpoint(run, "ps-server", 0, "10.0.0.2", 9100)
            assert any("already published" in m for m in records), records
            # same process re-announcing the same endpoint stays silent
            records.clear()
            write_endpoint(run, "ps-server", 0, "10.0.0.2", 9100)
            assert not records
        finally:
            logger.removeHandler(catch)


class TestMergeMath:
    def test_counters_sum_across_ranks(self):
        snaps = {("w", r): _rank_registry(r).snapshot() for r in (0, 1)}
        reg, conflicts = merge_snapshots(snaps)
        assert conflicts == []
        assert reg.get("fleet_ops_total").labels(op="push").value == 21

    def test_gauges_keep_per_rank_identity(self):
        snaps = {("w", r): _rank_registry(r).snapshot() for r in (0, 1)}
        reg, _ = merge_snapshots(snaps)
        g = reg.get("fleet_rate")
        assert g.labelnames == ("role", "rank", "instance")
        assert g.labels(role="w", rank="0", instance="0").value == 100.0
        assert g.labels(role="w", rank="1", instance="0").value == 200.0

    def test_gauge_rank_label_collision_renamed(self):
        """A gauge already labeled `rank` keeps it as exported_rank (the
        Prometheus federation convention), never silently aliased."""
        reg0 = MetricsRegistry()
        reg0.gauge("up_g", "", ("rank",)).labels(rank="7").set(1)
        merged, _ = merge_snapshots({("srv", 3): reg0.snapshot()})
        g = merged.get("up_g")
        assert g.labelnames == ("role", "rank", "exported_rank")
        assert g.labels(role="srv", rank="3", exported_rank="7").value == 1

    def test_histograms_merge_bucketwise(self):
        snaps = {("w", r): _rank_registry(r).snapshot() for r in (0, 1)}
        reg, _ = merge_snapshots(snaps)
        h = reg.get("fleet_lat_seconds")
        snap = h._default().snapshot()
        # rank0: 0.05, 0.5; rank1: 0.05, 1.5 -> le=0.1 holds 2, le=1.0
        # holds 3 cumulative, +Inf holds all 4
        assert snap["buckets"][0.1] == 2
        assert snap["buckets"][1.0] == 3
        assert snap["count"] == 4
        assert snap["sum"] == pytest.approx(0.05 + 0.5 + 0.05 + 1.5)
        assert 0.1 <= h.percentile(0.5) <= 1.0

    def test_mismatched_buckets_rejected_loudly(self):
        a = _rank_registry(0)
        b = MetricsRegistry()
        b.histogram("fleet_lat_seconds", "lat", buckets=(0.25,)).observe(0.1)
        snaps = {("w", 0): a.snapshot(), ("w", 1): b.snapshot()}
        with pytest.raises(FleetMergeError, match="bucket boundaries"):
            merge_snapshots(snaps)
        # scraper mode: dropped + named, never silently summed
        reg, conflicts = merge_snapshots(snaps, on_conflict="drop")
        assert conflicts == ["w-1:fleet_lat_seconds"]
        assert reg.get("fleet_lat_seconds")._default().count == 2  # rank 0 only

    def test_type_conflict_rejected(self):
        a = MetricsRegistry()
        a.counter("thing", "").inc()
        b = MetricsRegistry()
        b.gauge("thing", "").set(1)
        with pytest.raises(FleetMergeError, match="type/labels"):
            merge_snapshots({("w", 0): a.snapshot(), ("w", 1): b.snapshot()})

    def test_alert_gauges_always_declared(self):
        reg, _ = merge_snapshots({})
        alerts = evaluate_alerts(reg, thresholds=AlertThresholds(),
                                 rank_ages={("w", 0): 0.1})
        text = reg.prometheus_text()
        assert "distlr_alert_barrier_wait_stall" in text
        assert "distlr_alert_ps_push_errors" in text
        assert 'distlr_alert_scrape_stale{role="w",rank="0"' in text
        assert not any(a["firing"] for a in alerts)

    def test_barrier_wait_alert_fires_on_straggler(self):
        src = MetricsRegistry()
        ph = src.histogram("distlr_phase_seconds", "", ("phase",),
                           buckets=(0.001, 0.01, 0.1, 1.0, 10.0))
        st = src.histogram("distlr_train_step_seconds", "", ("loop",),
                           buckets=(0.001, 0.01, 0.1, 1.0, 10.0))
        for _ in range(100):
            st.labels(loop="ps").observe(0.005)       # median step ~5 ms
            ph.labels(phase="barrier_wait").observe(5.0)  # wedged barrier
        reg, _ = merge_snapshots({("w", 0): src.snapshot()})
        alerts = evaluate_alerts(reg, thresholds=AlertThresholds(),
                                 rank_ages={})
        fired = {a["name"] for a in alerts if a["firing"]}
        assert "distlr_alert_barrier_wait_stall" in fired

    def test_barrier_wait_alert_ignores_other_phases(self):
        """No barrier_wait series -> the alert must stay silent, not
        borrow another phase's histogram as its p99."""
        src = MetricsRegistry()
        ph = src.histogram("distlr_phase_seconds", "", ("phase",),
                           buckets=(0.001, 0.01, 0.1, 1.0, 10.0))
        st = src.histogram("distlr_train_step_seconds", "", ("loop",),
                           buckets=(0.001, 0.01, 0.1, 1.0, 10.0))
        for _ in range(100):
            st.labels(loop="ps").observe(0.005)
            ph.labels(phase="eval").observe(5.0)  # slow, but NOT a barrier
        reg, _ = merge_snapshots({("w", 0): src.snapshot()})
        alerts = evaluate_alerts(reg, thresholds=AlertThresholds(),
                                 rank_ages={})
        stall = [a for a in alerts
                 if a["name"] == "distlr_alert_barrier_wait_stall"]
        assert stall and not stall[0]["firing"]

    def test_push_error_alert_fires(self):
        src = MetricsRegistry()
        ops = src.counter("distlr_ps_client_ops_total", "", ("op", "status"))
        ops.labels(op="push", status="ok").inc(50)
        ops.labels(op="push", status="error").inc(50)
        reg, _ = merge_snapshots({("w", 0): src.snapshot()})
        alerts = evaluate_alerts(reg, thresholds=AlertThresholds(),
                                 rank_ages={})
        fired = {a["name"]: a for a in alerts if a["firing"]}
        assert "distlr_alert_ps_push_errors" in fired
        assert reg.get("distlr_fleet_push_error_rate").value == 0.5

    def test_retry_rate_alert_fires_before_errors(self):
        """A degraded-but-absorbed network: every op SUCCEEDS (no error
        alert) yet the retry fraction crosses threshold — the chaos
        layer's 'faults are costing retries' signal (ISSUE 5)."""
        src = MetricsRegistry()
        ops = src.counter("distlr_ps_client_ops_total", "", ("op", "status"))
        ops.labels(op="pull", status="ok").inc(100)
        retries = src.counter("distlr_ps_retries_total", "", ("op",))
        retries.labels(op="pull").inc(20)  # 20% retried, all recovered
        reg, _ = merge_snapshots({("w", 0): src.snapshot()})
        alerts = evaluate_alerts(reg, thresholds=AlertThresholds(),
                                 rank_ages={})
        fired = {a["name"]: a for a in alerts if a["firing"]}
        assert "distlr_alert_ps_retry_rate" in fired
        assert "distlr_alert_ps_push_errors" not in fired
        assert reg.get("distlr_fleet_ps_retry_rate").value == \
            pytest.approx(0.2)
        assert fired["distlr_alert_ps_retry_rate"]["labels"][
            "threshold"] == "0.05"

    def test_retry_rate_alert_silent_without_ops(self):
        reg, _ = merge_snapshots({})
        alerts = evaluate_alerts(reg, thresholds=AlertThresholds(),
                                 rank_ages={})
        retry = [a for a in alerts
                 if a["name"] == "distlr_alert_ps_retry_rate"]
        assert retry and not retry[0]["firing"]

    def test_gave_up_alert_surfaces_abandoned_rank(self):
        """distlr_ps_supervisor_events_total{event="gave-up"} > 0 must
        derive distlr_alert_ps_gave_up=1 — a dead-and-abandoned server
        rank becomes a firing alert in `launch top`, not just a counter
        nobody watches (ISSUE 5 satellite)."""
        src = MetricsRegistry()
        ev = src.counter("distlr_ps_supervisor_events_total", "", ("event",))
        ev.labels(event="respawned").inc(3)
        ev.labels(event="gave-up").inc()
        reg, _ = merge_snapshots({("ps-server", 0): src.snapshot()})
        alerts = evaluate_alerts(reg, thresholds=AlertThresholds(),
                                 rank_ages={})
        fired = {a["name"]: a for a in alerts if a["firing"]}
        assert "distlr_alert_ps_gave_up" in fired
        assert fired["distlr_alert_ps_gave_up"]["labels"]["threshold"] == "0"
        assert 'distlr_alert_ps_gave_up{threshold="0"} 1' \
            in reg.prometheus_text()

    def test_gave_up_alert_ignores_recovered_respawns(self):
        src = MetricsRegistry()
        ev = src.counter("distlr_ps_supervisor_events_total", "", ("event",))
        ev.labels(event="respawned").inc(2)
        ev.labels(event="reseeded").inc(2)
        reg, _ = merge_snapshots({("ps-server", 0): src.snapshot()})
        alerts = evaluate_alerts(reg, thresholds=AlertThresholds(),
                                 rank_ages={})
        gave = [a for a in alerts if a["name"] == "distlr_alert_ps_gave_up"]
        assert gave and not gave[0]["firing"]


class TestFleetScraper:
    def _fleet(self, tmp_path, n=2, **kw):
        run = str(tmp_path)
        servers = []
        for r in range(n):
            srv = MetricsServer(registry=_rank_registry(r), port=0).start()
            write_endpoint(run, "worker", r, srv.host, srv.port)
            servers.append(srv)
        kw.setdefault("interval_s", 0.2)
        # wide enough that MetricsServer.stop()'s up-to-0.5s
        # serve_forever poll latency cannot age a rank past it mid-test
        kw.setdefault("stale_after_s", 2.0)
        return FleetScraper(run, **kw), servers

    def test_merged_scrape_and_meta_series(self, tmp_path):
        fs, servers = self._fleet(tmp_path)
        try:
            fs.scrape_once()
            text = fs.prometheus_text()
            assert 'fleet_ops_total{op="push"} 21' in text
            assert 'distlr_fleet_scrape_up{role="worker",rank="0"} 1' in text
            assert 'distlr_fleet_scrape_up{role="worker",rank="1"} 1' in text
            assert 'distlr_fleet_ranks{state="up"} 2' in text
            fleet = fs.fleet_json()
            assert fleet["totals"] == {
                "ranks": 2, "up": 2, "stale": 0, "down": 0,
                "samples_per_s": 0.0}
        finally:
            for s in servers:
                s.stop()

    def test_down_rank_flips_up_without_corrupting_merge(self, tmp_path):
        fs, servers = self._fleet(tmp_path)
        try:
            fs.scrape_once()
            servers[1].stop()
            fs.scrape_once()
            text = fs.prometheus_text()
            # meta-series flips immediately...
            assert 'distlr_fleet_scrape_up{role="worker",rank="1"} 0' in text
            assert 'distlr_fleet_scrape_up{role="worker",rank="0"} 1' in text
            assert 'distlr_fleet_scrape_stale{role="worker",rank="1"} 1' in text
            # ...while the STALE rank's last-known counters stay merged,
            # so fleet totals remain monotonic across a transient miss
            assert 'fleet_ops_total{op="push"} 21' in text
            # past stale_after the rank goes down: dropped from the
            # merge (families stay valid, only rank 0 summed) + alert
            time.sleep(2.1)
            fs.scrape_once()
            text = fs.prometheus_text()
            assert 'distlr_fleet_ranks{state="down"} 1' in text
            assert 'fleet_ops_total{op="push"} 10' in text
            assert fs.merged.get("fleet_lat_seconds")._default().count == 2
            stale = [ln for ln in text.splitlines()
                     if ln.startswith("distlr_alert_scrape_stale")
                     and 'rank="1"' in ln]
            assert stale and stale[0].endswith(" 1")
        finally:
            for s in servers:
                s.stop()

    def test_never_scraped_rank_keeps_fleet_json_valid(self, tmp_path):
        """A rank that is down from birth (endpoint file but no server)
        has an infinite scrape age; /fleet.json must stay strict RFC
        JSON (no bare Infinity token) — non-Python consumers reject the
        scrape exactly when the outage makes it matter."""
        run = str(tmp_path)
        write_endpoint(run, "worker", 0, "127.0.0.1", 1)  # nothing listens
        fs = FleetScraper(run, interval_s=0.2, timeout_s=0.3)
        fs.scrape_once()
        body = json.dumps(fs.fleet_json())
        assert "Infinity" not in body and "NaN" not in body
        fleet = json.loads(body)
        assert fleet["totals"]["down"] == 1
        stale = [a for a in fleet["alerts"]
                 if a["name"] == "distlr_alert_scrape_stale"]
        assert stale and stale[0]["firing"] and stale[0]["value"] is None

    def test_snapshot_file_source_merges(self, tmp_path):
        """Portless one-shot processes federate through banked
        snapshots/<role>-<rank>.json files."""
        from distlr_tpu.obs import write_metrics_snapshot

        run = str(tmp_path)
        snap_dir = os.path.join(run, "snapshots")
        write_metrics_snapshot(os.path.join(snap_dir, "bench-0.json"),
                               _rank_registry(0))
        fs = FleetScraper(run, interval_s=0.2)
        fs.scrape_once()
        text = fs.prometheus_text()
        assert 'fleet_ops_total{op="push"} 10' in text
        assert 'distlr_fleet_scrape_up{role="bench",rank="0"} 1' in text


class TestTopRenderer:
    def test_render_frame_plain(self):
        fleet = {
            "updated": time.time(), "run_dir": "/tmp/run",
            "ranks": [
                {"role": "ps", "rank": 0, "state": "up", "steps": 120,
                 "samples_per_s": 5400.0, "step_p50_ms": 1.2,
                 "pull_p50_ms": 0.2, "pull_p99_ms": 0.9,
                 "push_p50_ms": 0.3, "push_p99_ms": 1.1,
                 "staleness_s": 0.004, "staleness_pushes_p50": 1.0,
                 "staleness_pushes_p99": 3.0},
                {"role": "ps-server", "rank": 0, "state": "down",
                 "age_s": 12.0},
            ],
            "alerts": [{"name": "distlr_alert_scrape_stale",
                        "labels": {"role": "ps-server", "rank": "0"},
                        "firing": True, "value": 12.0, "threshold": 10.0}],
            "totals": {"ranks": 2, "up": 1, "stale": 0, "down": 1,
                       "samples_per_s": 5400.0},
        }
        frame = render_fleet(fleet, color=False)
        assert "1/2 up" in frame
        assert "ALERT distlr_alert_scrape_stale" in frame
        assert "ps-server" in frame and "down" in frame
        assert "0.20/0.90" in frame  # pull p50/p99
        assert "\x1b[" not in frame  # color off = no ANSI
        colored = render_fleet(fleet, color=True)
        assert "\x1b[31m" in colored  # down rank renders red

    def test_render_empty_fleet(self):
        frame = render_fleet({"totals": {}, "ranks": [], "alerts": []},
                             color=False)
        assert "no ranks discovered" in frame


#: Jax-free metric emitter the fleet smoke spawns twice: a registry with
#: one counter/gauge/histogram each, served on an ephemeral port and
#: published into the run dir.
_EMITTER = r"""
import sys, time
from distlr_tpu.obs import MetricsRegistry, MetricsServer, write_endpoint
run, rank = sys.argv[1], int(sys.argv[2])
reg = MetricsRegistry()
reg.counter("smoke_ops_total", "ops", ("op",)).labels(op="x").inc(5 + rank)
reg.gauge("distlr_train_samples_per_second", "rate", ("loop", "instance")
          ).labels(loop="ps", instance=str(rank)).set(100.0 * (rank + 1))
h = reg.histogram("distlr_train_step_seconds", "step", ("loop",))
for _ in range(10):
    h.labels(loop="ps").observe(0.01)
srv = MetricsServer(registry=reg, port=0).start()
write_endpoint(run, "dummy", rank, srv.host, srv.port)
print("READY", flush=True)
time.sleep(300)
"""


def _wait_metrics_line(proc, deadline=30) -> str:
    t0 = time.monotonic()
    while True:
        line = proc.stdout.readline()
        if line.startswith("METRICS "):
            return "http://" + line.split()[1]
        if not line or time.monotonic() - t0 > deadline:
            raise AssertionError(f"no METRICS line (got {line!r})")


def _poll_fleet(url, predicate, deadline_s=45) -> str:
    t0 = time.monotonic()
    text = ""
    while time.monotonic() - t0 < deadline_s:
        try:
            text = urllib.request.urlopen(
                url + "/metrics", timeout=2).read().decode()
            if predicate(text):
                return text
        except Exception:
            pass
        time.sleep(0.3)
    raise AssertionError(
        f"fleet scrape never satisfied predicate; last scrape:\n{text[-4000:]}")


class TestFleetSmoke:
    """The fleet half of the observability e2e: two dummy
    metric-emitting processes + the real aggregator CLI, one merged
    scrape with both ranks and at least one derived alert gauge."""

    def test_two_emitters_one_merged_scrape(self, tmp_path):
        run = str(tmp_path)
        procs = []
        try:
            for rank in range(2):
                p = subprocess.Popen(
                    [sys.executable, "-c", _EMITTER, run, str(rank)],
                    stdout=subprocess.PIPE, text=True, cwd=REPO)
                procs.append(p)
            for p in procs:
                assert p.stdout.readline().strip() == "READY"
            agg = subprocess.Popen(
                [sys.executable, "-m", "distlr_tpu.launch", "obs-agg",
                 "--obs-run-dir", run, "--metrics-port", "0",
                 "--interval", "0.3"],
                stdout=subprocess.PIPE, text=True, cwd=REPO)
            procs.append(agg)
            url = _wait_metrics_line(agg)
            text = _poll_fleet(url, lambda t: 'smoke_ops_total{op="x"} 11' in t)
            # both ranks present, per-rank identity on the gauge
            assert ('distlr_train_samples_per_second'
                    '{role="dummy",rank="0",loop="ps",instance="0"} 100'
                    in text)
            assert ('distlr_train_samples_per_second'
                    '{role="dummy",rank="1",loop="ps",instance="1"} 200'
                    in text)
            assert 'distlr_fleet_scrape_up{role="dummy",rank="0"} 1' in text
            assert 'distlr_fleet_scrape_up{role="dummy",rank="1"} 1' in text
            # at least one derived alert gauge in the same scrape
            assert "distlr_alert_ps_push_errors" in text
            assert "distlr_alert_barrier_wait_stall" in text
            # /fleet.json carries the structured summary top renders
            fleet = json.load(urllib.request.urlopen(url + "/fleet.json",
                                                     timeout=2))
            assert fleet["totals"]["up"] == 2
            frame = render_fleet(fleet, color=False)
            assert "dummy" in frame
        finally:
            for p in procs:
                p.kill()
            for p in procs:
                p.wait()


@pytest.fixture(scope="module")
def fleet_data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("fleetdata")
    write_synthetic_shards(str(d), 800, 24, num_parts=2, seed=17, sparsity=0.0)
    return str(d)


class TestPsFleetEndToEnd:
    """ISSUE-3 acceptance: a local async ps run — 1 ps-server process
    hosting 2 native servers + 2 worker processes — every process with a
    metrics endpoint in one --obs-run-dir, plus `launch obs-agg`; a
    SINGLE fleet /metrics scrape carries every rank's series labeled
    role/rank, the distlr_alert_* gauges, and a non-empty
    distlr_train_staleness_pushes histogram."""

    def test_fleet_scrape_of_live_ps_run(self, fleet_data_dir, tmp_path):
        run = str(tmp_path / "obsrun")
        common = ["--num-feature-dim", "24", "--num-workers", "2",
                  "--num-servers", "2", "--obs-run-dir", run,
                  "--metrics-port", "0"]
        procs = []
        try:
            server = subprocess.Popen(
                [sys.executable, "-m", "distlr_tpu.launch", "ps-server",
                 "--async", *common],
                stdout=subprocess.PIPE, text=True, cwd=REPO)
            procs.append(server)
            _wait_metrics_line(server, deadline=60)
            hosts_line = server.stdout.readline().strip()
            assert hosts_line.startswith("HOSTS "), hosts_line
            hosts = hosts_line.split(None, 1)[1]
            # a long run the test terminates once the scrape satisfies —
            # a finished worker would retire the servers mid-assertion
            for rank in ("0", "1"):
                w = subprocess.Popen(
                    [sys.executable, "-m", "distlr_tpu.launch", "ps",
                     "--async", "--hosts", hosts, "--worker-ranks", rank,
                     "--data-dir", fleet_data_dir, "--batch-size", "50",
                     "--num-iteration", "1000000", "--test-interval", "50",
                     "--cpu-devices", "1", *common],
                    stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                    text=True, cwd=REPO)
                procs.append(w)
            agg = subprocess.Popen(
                [sys.executable, "-m", "distlr_tpu.launch", "obs-agg",
                 "--obs-run-dir", run, "--metrics-port", "0",
                 "--interval", "0.5"],
                stdout=subprocess.PIPE, text=True, cwd=REPO)
            procs.append(agg)
            url = _wait_metrics_line(agg)

            def satisfied(t: str) -> bool:
                counts = [
                    int(ln.rsplit(" ", 1)[1]) for ln in t.splitlines()
                    if ln.startswith("distlr_train_staleness_pushes_count")
                ]
                return (
                    'role="ps",rank="0"' in t
                    and 'role="ps",rank="1"' in t
                    and 'role="ps-server",rank="0"' in t
                    and sum(counts) > 0
                )

            text = _poll_fleet(url, satisfied, deadline_s=120)
            # every fleet process answered the same scrape
            assert 'distlr_fleet_scrape_up{role="ps",rank="0"} 1' in text
            assert 'distlr_fleet_scrape_up{role="ps",rank="1"} 1' in text
            assert 'distlr_fleet_scrape_up{role="ps-server",rank="0"} 1' in text
            # per-rank gauge identity (each worker's own throughput)
            assert 'distlr_train_samples_per_second{role="ps",rank="0"' in text
            assert 'distlr_train_samples_per_second{role="ps",rank="1"' in text
            # counters federate into fleet totals
            assert "distlr_train_steps_total" in text
            assert "distlr_ps_client_ops_total" in text
            # derived alert gauges ride the same scrape
            for alert in ("distlr_alert_barrier_wait_stall",
                          "distlr_alert_ps_push_errors",
                          "distlr_alert_scrape_stale",
                          "distlr_alert_weight_age"):
                assert alert in text, alert
            # the Hogwild pushes-behind histogram is non-empty
            assert "distlr_train_staleness_pushes_bucket" in text
            # the dashboard renders the same fleet
            fleet = json.load(urllib.request.urlopen(url + "/fleet.json",
                                                     timeout=2))
            assert fleet["totals"]["up"] >= 3
            frame = render_fleet(fleet, color=False)
            assert "ps-server" in frame
        finally:
            for p in procs:
                try:
                    p.send_signal(signal.SIGTERM)
                except OSError:
                    pass
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()


class TestAlertThresholdOverrides:
    """Satellite (ISSUE 4): alert thresholds were static constructor
    defaults — they now resolve per run from defaults < JSON thresholds
    file < explicit CLI flags, and the distlr_alert_* threshold labels
    must reflect the EFFECTIVE values."""

    def test_resolve_precedence(self, tmp_path):
        p = tmp_path / "thresholds.json"
        p.write_text(json.dumps({"push_error_rate": 0.5,
                                 "barrier_wait_ratio": 4.0}))
        t = AlertThresholds.resolve(str(p), push_error_rate=0.25,
                                    weight_age_ratio=None)
        assert t.push_error_rate == 0.25      # CLI flag beats the file
        assert t.barrier_wait_ratio == 4.0    # file beats the default
        assert t.weight_age_ratio == 10.0     # None override = default
        assert t.scrape_stale_s == 10.0

    def test_resolve_rejects_unknown_keys(self, tmp_path):
        p = tmp_path / "thresholds.json"
        p.write_text(json.dumps({"push_eror_rate": 0.5}))  # typo
        with pytest.raises(ValueError, match="push_eror_rate"):
            AlertThresholds.resolve(str(p))
        with pytest.raises(ValueError, match="nope"):
            AlertThresholds.resolve(None, nope=1.0)
        p.write_text("[1, 2]")
        with pytest.raises(ValueError, match="JSON object"):
            AlertThresholds.resolve(str(p))

    def test_resolve_rejects_non_numeric_values(self, tmp_path):
        """A wrongly-typed value (over-quoted JSON) must fail at startup
        with the key named, not crash alert evaluation mid-cycle."""
        p = tmp_path / "thresholds.json"
        p.write_text(json.dumps({"push_error_rate": "0.25"}))
        with pytest.raises(ValueError, match="push_error_rate.*number"):
            AlertThresholds.resolve(str(p))
        p.write_text(json.dumps({"scrape_stale_s": True}))
        with pytest.raises(ValueError, match="scrape_stale_s"):
            AlertThresholds.resolve(str(p))
        # integral floats coerce cleanly; barrier_min_count stays an int
        t = AlertThresholds.resolve(None, barrier_min_count=4.0,
                                    push_error_rate=1)
        assert t.barrier_min_count == 4
        assert t.push_error_rate == 1.0
        # ...but a fractional count must fail loudly, never truncate to
        # an effective value the operator never wrote
        with pytest.raises(ValueError, match="barrier_min_count.*integer"):
            AlertThresholds.resolve(None, barrier_min_count=8.7)

    def test_labels_reflect_effective_values(self):
        src = MetricsRegistry()
        ops = src.counter("distlr_ps_client_ops_total", "", ("op", "status"))
        ops.labels(op="push", status="ok").inc(60)
        ops.labels(op="push", status="error").inc(40)
        reg, _ = merge_snapshots({("w", 0): src.snapshot()})
        alerts = evaluate_alerts(
            reg, thresholds=AlertThresholds(push_error_rate=0.25,
                                            barrier_wait_ratio=4.0),
            rank_ages={})
        text = reg.prometheus_text()
        assert 'distlr_alert_ps_push_errors{threshold="0.25"} 1' in text
        assert ('distlr_alert_barrier_wait_stall'
                '{threshold="4x_step_p50"}') in text
        push = next(a for a in alerts
                    if a["name"] == "distlr_alert_ps_push_errors")
        assert push["firing"] and push["threshold"] == 0.25

    def test_obs_agg_cli_flags_and_file(self, tmp_path):
        """End to end through the CLI: `launch obs-agg --once` over a
        banked snapshot, with a thresholds file AND a flag override —
        the scrape's threshold labels carry the effective values."""
        from distlr_tpu.obs import write_metrics_snapshot

        run = tmp_path / "run"
        src = MetricsRegistry()
        ops = src.counter("distlr_ps_client_ops_total", "", ("op", "status"))
        ops.labels(op="push", status="ok").inc(60)
        ops.labels(op="push", status="error").inc(40)
        write_metrics_snapshot(str(run / "snapshots" / "worker-0.json"), src)
        tf = tmp_path / "thresholds.json"
        tf.write_text(json.dumps({"barrier_wait_ratio": 4.0,
                                  "push_error_rate": 0.9}))
        r = subprocess.run(
            [sys.executable, "-m", "distlr_tpu.launch", "obs-agg",
             "--obs-run-dir", str(run), "--once",
             "--thresholds-file", str(tf),
             "--alert-push-error-rate", "0.25",   # flag beats the file
             "--stale-after", "3"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        text = r.stdout
        assert 'distlr_alert_ps_push_errors{threshold="0.25"} 1' in text
        assert ('distlr_alert_barrier_wait_stall'
                '{threshold="4x_step_p50"}') in text
        # scrape_stale_s rode --stale-after into the per-rank alert label
        assert 'threshold="3s"' in text

    def test_obs_agg_rejects_bad_thresholds_file(self, tmp_path):
        tf = tmp_path / "bad.json"
        tf.write_text(json.dumps({"not_a_threshold": 1}))
        r = subprocess.run(
            [sys.executable, "-m", "distlr_tpu.launch", "obs-agg",
             "--obs-run-dir", str(tmp_path), "--once",
             "--thresholds-file", str(tf)],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r.returncode == 2
        assert "not_a_threshold" in r.stderr
