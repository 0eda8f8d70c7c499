"""Tests for the unified observability layer (distlr_tpu/obs).

Covers the ISSUE-2 acceptance contract: exact counts under thread
hammering, histogram bucket math, the Prometheus text format (golden),
Chrome trace-event validity, and an end-to-end short PS training run
whose /metrics scrape carries trainer + PS-server + PS-client series and
whose trace records every pipeline phase.
"""

import json
import os
import threading
import urllib.request

import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.data.synthetic import write_synthetic_shards
from distlr_tpu.obs import (
    MetricsRegistry,
    MetricsServer,
    PhaseTracer,
    get_registry,
    get_tracer,
    start_metrics_server,
    write_metrics_snapshot,
)
from distlr_tpu.train.metrics import MetricsLogger, StepTimer


class TestRegistryConcurrency:
    def test_counter_exact_under_hammering(self):
        reg = MetricsRegistry()
        c = reg.counter("hammer_total", "x", labelnames=("t",))
        n_threads, n_incs = 8, 10_000

        def hammer(i):
            child = c.labels(t=i % 2)
            for _ in range(n_incs):
                child.inc()

        threads = [threading.Thread(target=hammer, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = sum(child.value for _, child in c.children())
        assert total == n_threads * n_incs  # exact, not approximate
        assert c.labels(t=0).value == n_threads * n_incs / 2

    def test_histogram_exact_count_under_hammering(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", "x", buckets=(0.5,))
        n_threads, n_obs = 8, 5_000

        def hammer():
            for k in range(n_obs):
                h.observe(0.1 if k % 2 else 0.9)

        threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert h.count == n_threads * n_obs
        snap = h._default().snapshot()
        assert snap["buckets"][0.5] == n_threads * n_obs / 2
        assert snap["inf"] == n_threads * n_obs

    def test_cells_are_exact_under_hammering_and_fold_in(self):
        """A cell a thread: no lock on the way in, every count there at
        any read, and in the series' own once the cell is folded."""
        reg = MetricsRegistry()
        c = reg.counter("t_cells_total", "h", labelnames=("op",))
        h = reg.histogram("t_cells_seconds", "h")
        n, threads = 20_000, 8
        series, hist = c.labels(op="push"), h._default()
        cells = [(series.cell(), hist.cell()) for _ in range(threads)]

        def work(mine):
            count, seconds = mine
            for i in range(n):
                count.inc(2)
                seconds.observe(0.001 * (i % 3))

        ts = [threading.Thread(target=work, args=(m,)) for m in cells]
        for t in ts:
            t.start()
        series.inc(5)  # the locked way beside them
        for t in ts:
            t.join()
        want = 2 * n * threads + 5
        assert series.value == want and h.count == n * threads
        for count, seconds in cells:  # their writers are gone
            count.retired = seconds.retired = True
        assert series.value == want and h.count == n * threads
        assert series._cells == [] and hist._cells == []
        assert series.value == want and h.count == n * threads
        assert h.sum == pytest.approx(threads * sum(
            0.001 * (i % 3) for i in range(n)))
        assert f"t_cells_total{{op=\"push\"}} {want}" in reg.prometheus_text()

    def test_a_gauge_can_be_derived_when_it_is_read(self):
        reg = MetricsRegistry()
        c = reg.counter("t_num_total", "h")
        g = reg.gauge("t_ratio", "h")
        g.set_function(lambda: c.value / 4)
        c.inc(6)
        assert g.value == 1.5
        assert "t_ratio 1.5" in reg.prometheus_text()
        assert reg.snapshot()["t_ratio"]["series"][0]["value"] == 1.5

    def test_counter_rejects_negative(self):
        c = MetricsRegistry().counter("c_total")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_duplicate_declaration_idempotent_and_checked(self):
        reg = MetricsRegistry()
        a = reg.counter("dup_total", "x", labelnames=("op",))
        b = reg.counter("dup_total", "x", labelnames=("op",))
        assert a is b  # call sites in different modules may both declare
        with pytest.raises(ValueError):  # different labels = different meaning
            reg.counter("dup_total", "x", labelnames=("other",))
        with pytest.raises(ValueError):  # different kind entirely
            reg.gauge("dup_total")
        # histograms: the bucket ladder is part of the contract — a
        # re-declaration with different buckets would silently observe
        # into the wrong ladder
        h = reg.histogram("dup_seconds", "x", buckets=(0.1, 1.0))
        assert reg.histogram("dup_seconds", "x", buckets=(0.1, 1.0)) is h
        with pytest.raises(ValueError):
            reg.histogram("dup_seconds", "x", buckets=(0.5,))

    def test_label_resolution(self):
        reg = MetricsRegistry()
        c = reg.counter("lab_total", "x", labelnames=("op", "status"))
        c.labels(op="push", status="ok").inc(2)
        assert c.labels("push", "ok").value == 2  # positional == by-name
        with pytest.raises(ValueError):
            c.labels(op="push")  # missing label
        with pytest.raises(ValueError):
            c.inc()  # labeled family has no default child


class TestHistogramMath:
    def test_bucket_boundaries_are_le(self):
        h = MetricsRegistry().histogram("h", "x", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 100.0):
            h.observe(v)
        snap = h._default().snapshot()
        # le semantics: a value equal to a boundary lands IN that bucket
        assert snap["buckets"][1.0] == 2   # 0.5, 1.0
        assert snap["buckets"][2.0] == 4   # + 1.5, 2.0
        assert snap["buckets"][4.0] == 6   # + 3.0, 4.0
        assert snap["inf"] == 7            # + 100.0
        assert snap["count"] == 7
        assert snap["sum"] == pytest.approx(112.0)

    def test_percentile_interpolation(self):
        h = MetricsRegistry().histogram("h", "x", buckets=(1.0, 2.0, 4.0))
        for _ in range(100):
            h.observe(1.5)  # all mass in the (1, 2] bucket
        # any interior quantile interpolates inside that bucket
        assert 1.0 <= h.percentile(0.5) <= 2.0
        assert 1.0 <= h.percentile(0.99) <= 2.0
        # empty histogram: defined zero, not a crash
        empty = MetricsRegistry().histogram("e", "x", buckets=(1.0,))
        assert empty.percentile(0.5) == 0.0
        # overflow observations clamp to the top finite boundary
        top = MetricsRegistry().histogram("t", "x", buckets=(1.0, 2.0))
        top.observe(50.0)
        assert top.percentile(0.99) == 2.0
        with pytest.raises(ValueError):
            h.percentile(1.5)

    def test_timer_contextmanager(self):
        h = MetricsRegistry().histogram("h_seconds", "x")
        with h.time():
            pass
        assert h.count == 1
        assert h.sum >= 0.0


class TestPrometheusText:
    def test_golden_exposition(self):
        """Pin the exact text format: scrapers parse bytes, not intent."""
        reg = MetricsRegistry()
        reg.counter("app_ops_total", "ops by kind",
                    labelnames=("op",)).labels(op="push").inc(3)
        reg.gauge("app_temp", "current temperature").set(1.5)
        h = reg.histogram("app_lat_seconds", "latency", buckets=(0.01, 0.1))
        h.observe(0.005)
        h.observe(0.5)
        assert reg.prometheus_text() == (
            "# HELP app_lat_seconds latency\n"
            "# TYPE app_lat_seconds histogram\n"
            'app_lat_seconds_bucket{le="0.01"} 1\n'
            'app_lat_seconds_bucket{le="0.1"} 1\n'
            'app_lat_seconds_bucket{le="+Inf"} 2\n'
            "app_lat_seconds_sum 0.505\n"
            "app_lat_seconds_count 2\n"
            "# HELP app_ops_total ops by kind\n"
            "# TYPE app_ops_total counter\n"
            'app_ops_total{op="push"} 3\n'
            "# HELP app_temp current temperature\n"
            "# TYPE app_temp gauge\n"
            "app_temp 1.5\n"
        )

    def test_label_escaping(self):
        reg = MetricsRegistry()
        reg.counter("esc_total", "", labelnames=("p",)).labels(
            p='a"b\\c\nd'
        ).inc()
        text = reg.prometheus_text()
        assert '\\"' in text and "\\\\" in text and "\\n" in text

    def test_json_snapshot_roundtrips(self):
        reg = MetricsRegistry()
        reg.counter("s_total", "", labelnames=("k",)).labels(k="v").inc(2)
        reg.histogram("s_seconds", "", buckets=(1.0,)).observe(0.5)
        snap = json.loads(json.dumps(reg.snapshot()))  # JSON-serializable
        assert snap["s_total"]["series"][0] == {"labels": {"k": "v"},
                                                "value": 2}
        hs = snap["s_seconds"]["series"][0]
        assert hs["count"] == 1 and hs["buckets"]["1"] == 1


class TestTracer:
    def test_chrome_trace_json_valid(self, tmp_path):
        tracer = PhaseTracer(registry=MetricsRegistry())
        with tracer.phase("compute"):
            pass
        done = threading.Event()

        def other():
            with tracer.phase("h2d"):
                done.set()

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert done.is_set()
        path = str(tmp_path / "trace.json")
        tracer.dump_chrome_trace(path)
        doc = json.load(open(path))  # valid JSON by construction
        events = doc["traceEvents"]
        assert {e["name"] for e in events} == {"compute", "h2d"}
        for e in events:
            assert e["ph"] == "X"
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        # the two spans ran on different threads: distinct tids
        assert len({e["tid"] for e in events}) == 2

    def test_breakdown_survives_event_cap(self):
        tracer = PhaseTracer(registry=MetricsRegistry(), max_events=2)
        for _ in range(5):
            with tracer.phase("x"):
                pass
        assert tracer.breakdown()["x"]["count"] == 5  # aggregation uncapped
        doc = tracer.chrome_trace()
        assert len(doc["traceEvents"]) == 2  # timeline bounded
        assert doc["otherData"]["dropped_events"] == 3

    def test_reset(self):
        tracer = PhaseTracer(registry=MetricsRegistry())
        with tracer.phase("x"):
            pass
        tracer.reset()
        assert tracer.breakdown() == {}
        assert tracer.chrome_trace()["traceEvents"] == []


class TestExporters:
    def test_http_endpoints(self):
        reg = MetricsRegistry()
        reg.counter("up_total").inc()
        with start_metrics_server(registry=reg, port=0) as srv:
            base = f"http://127.0.0.1:{srv.port}"
            text = urllib.request.urlopen(base + "/metrics").read().decode()
            assert "up_total 1" in text
            js = json.loads(
                urllib.request.urlopen(base + "/metrics.json").read())
            assert js["up_total"]["series"][0]["value"] == 1
            assert urllib.request.urlopen(
                base + "/healthz").read() == b"ok\n"
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(base + "/nope")

    def test_write_snapshot(self, tmp_path):
        reg = MetricsRegistry()
        reg.gauge("g").set(2)
        path = str(tmp_path / "metrics.prom")
        write_metrics_snapshot(path, reg)
        assert "g 2" in open(path).read()

    def test_write_snapshot_json_twin(self, tmp_path):
        """A .json path banks the machine-readable registry snapshot —
        what the fleet aggregator consumes."""
        reg = MetricsRegistry()
        reg.counter("c_total").inc(3)
        path = str(tmp_path / "metrics.json")
        write_metrics_snapshot(path, reg)
        doc = json.load(open(path))
        assert doc["c_total"]["series"][0]["value"] == 3
        assert doc["c_total"]["type"] == "counter"

    def test_snapshot_env_multiple_paths(self):
        """DISTLR_METRICS_SNAPSHOT may name several os.pathsep-separated
        targets (text + JSON twins banked from one process)."""
        from distlr_tpu.obs import snapshot_env_paths

        val = os.pathsep.join(["a.prom", "b.json"])
        assert snapshot_env_paths(val) == ["a.prom", "b.json"]
        assert snapshot_env_paths("") == []

    def test_stop_without_start_does_not_deadlock(self):
        """Regression: stop() before/without start() used to block
        forever inside HTTPServer.shutdown() (waiting on an event only
        serve_forever sets); it must return immediately and release the
        port, and stay idempotent."""
        srv = MetricsServer(registry=MetricsRegistry(), port=0)
        t = threading.Thread(target=srv.stop, daemon=True)
        t.start()
        t.join(timeout=5)
        assert not t.is_alive(), "stop() without start() deadlocked"
        srv.stop()  # idempotent
        with pytest.raises(RuntimeError, match="stopped"):
            srv.start()  # a stopped server cannot come back

    def test_stop_idempotent_after_start(self):
        srv = MetricsServer(registry=MetricsRegistry(), port=0).start()
        srv.stop()
        srv.stop()


class TestMetricsLoggerLifecycle:
    """Satellite: close()/file lifecycle of the structured logger."""

    def test_log_after_close_raises(self, tmp_path):
        m = MetricsLogger(str(tmp_path / "m.jsonl"))
        m.log(epoch=1, accuracy=0.5)
        m.close()
        assert m.closed
        with pytest.raises(RuntimeError, match="closed"):
            m.log(epoch=2, accuracy=0.6)  # was: ValueError from a dead file
        # the sink holds exactly the pre-close records
        recs = [json.loads(ln) for ln in open(tmp_path / "m.jsonl")]
        assert [r["epoch"] for r in recs] == [1]

    def test_log_after_close_raises_without_sink_too(self):
        m = MetricsLogger()
        m.close()
        with pytest.raises(RuntimeError, match="closed"):
            m.log(x=1)

    def test_context_manager(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with MetricsLogger(path) as m:
            m.log(epoch=1, loss=0.1)
        assert m.closed
        assert json.loads(open(path).read())["loss"] == 0.1

    def test_close_idempotent(self):
        m = MetricsLogger()
        m.close()
        m.close()

    def test_numeric_fields_mirror_to_registry(self):
        reg = MetricsRegistry()
        with MetricsLogger(registry=reg) as m:
            m.log(epoch=3, accuracy=0.75, note="text-is-skipped", flag=True)
        g = reg.get("distlr_train_last")
        assert g.labels(field="accuracy").value == 0.75
        assert g.labels(field="epoch").value == 3
        mirrored = {v for v, _ in g.children()}
        assert ("note",) not in mirrored and ("flag",) not in mirrored


class TestStepTimerRegistry:
    def test_stop_feeds_registry_series(self):
        reg = MetricsRegistry()
        t = StepTimer(loop="unit", registry=reg)
        t.start()
        t.stop(128)
        t.start()
        t.stop(64)
        assert reg.get("distlr_train_steps_total").labels(loop="unit").value == 2
        assert reg.get("distlr_train_samples_total").labels(loop="unit").value == 192
        assert reg.get("distlr_train_step_seconds").labels(loop="unit").count == 2
        assert reg.get("distlr_train_samples_per_second").labels(
            loop="unit", instance="0").value == pytest.approx(t.samples_per_sec)

    def test_rate_gauge_is_per_instance(self):
        """N concurrent timers (Hogwild workers) must not last-writer-wins
        one shared throughput gauge."""
        reg = MetricsRegistry()
        a = StepTimer(loop="ps", instance="0", registry=reg)
        b = StepTimer(loop="ps", instance="1", registry=reg)
        a.start()
        a.stop(100)
        b.start()
        b.stop(200)
        g = reg.get("distlr_train_samples_per_second")
        assert g.labels(loop="ps", instance="0").value == pytest.approx(
            a.samples_per_sec)
        assert g.labels(loop="ps", instance="1").value == pytest.approx(
            b.samples_per_sec)
        # counters stay shared/additive under the loop label
        assert reg.get("distlr_train_samples_total").labels(
            loop="ps").value == 300


@pytest.fixture(scope="module")
def obs_data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("obsdata")
    write_synthetic_shards(str(d), 800, 24, num_parts=2, seed=11, sparsity=0.0)
    return str(d)


class TestEndToEnd:
    def test_e2e_metrics_and_trace(self, obs_data_dir, tmp_path):
        """One short async PS run: /metrics serves non-zero trainer,
        PS-server, and PS-client series; the Chrome trace holds >= 5
        distinct pipeline phases (the ISSUE-2 acceptance run)."""
        from distlr_tpu.train.ps_trainer import run_ps_local

        tracer = get_tracer()
        tracer.reset()
        reg = get_registry()

        def val(name, **labels):
            fam = reg.get(name)
            if fam is None:
                return 0.0
            try:
                return fam.labels(**labels).value if labels else fam.value
            except ValueError:
                return 0.0

        before = {
            "pull": val("distlr_ps_client_ops_total", op="pull", status="ok"),
            "push": val("distlr_ps_client_ops_total", op="push_pull",
                        status="ok"),
            "steps": val("distlr_train_steps_total", loop="ps"),
            "spawns": sum(
                c.value for _, c in reg.get(
                    "distlr_ps_server_spawns_total").children())
            if reg.get("distlr_ps_server_spawns_total") else 0,
        }
        cfg = Config(
            data_dir=obs_data_dir, num_feature_dim=24, num_iteration=3,
            learning_rate=0.2, l2_c=0.0, batch_size=100, test_interval=1,
            sync_mode=False, num_workers=2, num_servers=1,
            ps_timeout_ms=60_000,
        )
        run_ps_local(cfg, save=False, eval_fn=lambda *_: None)

        with start_metrics_server(port=0) as srv:
            text = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics").read().decode()

        # PS-client series: the async dense loop pulls once and rides
        # fused push_pulls; both counters moved and both scrape non-zero
        assert val("distlr_ps_client_ops_total", op="pull",
                   status="ok") > before["pull"]
        assert val("distlr_ps_client_ops_total", op="push_pull",
                   status="ok") > before["push"]
        assert 'distlr_ps_client_ops_total{op="pull",status="ok"}' in text
        assert 'distlr_ps_client_ops_total{op="push_pull",status="ok"}' in text
        assert "distlr_ps_client_op_seconds_bucket" in text
        assert 'distlr_ps_client_bytes_total{op="pull",direction="received"}' in text
        # trainer series
        assert val("distlr_train_steps_total", loop="ps") > before["steps"]
        assert 'distlr_train_steps_total{loop="ps"}' in text
        assert "distlr_train_staleness_seconds" in text  # async run
        # PS-server series
        spawns_now = sum(
            c.value
            for _, c in reg.get("distlr_ps_server_spawns_total").children())
        assert spawns_now > before["spawns"]
        assert "distlr_ps_server_spawns_total" in text

        # trace: all pipeline phases present, file is valid Chrome JSON
        phases = tracer.phase_names()
        assert {"pull", "compute", "push", "barrier_wait", "eval"} <= phases
        assert len(phases) >= 5
        trace_path = str(tmp_path / "trace.json")
        tracer.dump_chrome_trace(trace_path)
        doc = json.load(open(trace_path))
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"pull", "compute", "push", "barrier_wait", "eval"} <= names

    def test_launch_obs_flags_wire_through(self, obs_data_dir, tmp_path):
        """`--metrics-port 0 --trace-path ...` through the real CLI: the
        METRICS line announces a live endpoint during the run and the
        trace file exists afterwards."""
        import subprocess
        import sys

        trace = str(tmp_path / "sync_trace.json")
        r = subprocess.run(
            [sys.executable, "-m", "distlr_tpu.launch", "sync",
             "--data-dir", obs_data_dir, "--num-feature-dim", "24",
             "--num-iteration", "2", "--test-interval", "1",
             "--cpu-devices", "2",
             "--metrics-port", "0", "--trace-path", trace],
            capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert r.returncode == 0, r.stderr[-2000:]
        announced = [ln for ln in r.stdout.splitlines()
                     if ln.startswith("METRICS ")]
        assert announced, r.stdout
        doc = json.load(open(trace))
        names = {e["name"] for e in doc["traceEvents"]}
        # the sync trainer's pipeline phases (h2d rides the prefetch thread)
        assert {"data_load", "h2d", "compute", "eval"} <= names
