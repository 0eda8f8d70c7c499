"""Fleet-wide continuous profiling (ISSUE 9).

Covers the sampling profiler core (bounded folded-stack table under
deep/recursive stacks, dtrace span-tag attribution, deterministic
profwindow journal schema), prof-agg merge validity (speedscope JSON
loads, per-role tracks present, collapsed-stack format), the
alert-triggered burst e2e across a multi-process fleet, incident
capture unification (ONE alert edge -> exactly one flight dump + one
burst window, cross-referenced), the obs-agg scrape history +
``launch top --replay`` satellite, the JAX runtime introspection
series, the native kv_server per-handler CPU extension, and the
``launch prof-agg``/``profrec`` CLI contracts.
"""

import glob
import io
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from distlr_tpu.obs import dtrace, profile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset():
    yield
    profile.reset_for_tests()
    dtrace.reset_for_tests()


def _read_windows(run_dir: str, stem: str) -> list[dict]:
    path = os.path.join(run_dir, "profiles", stem + ".jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _busy_thread(stop: threading.Event, span: str | None = None):
    def body():
        if span is not None:
            ctx = dtrace.new_trace()
            with dtrace.use(ctx), dtrace.span(span):
                while not stop.is_set():
                    sum(i * i for i in range(500))
        else:
            while not stop.is_set():
                sum(i * i for i in range(500))

    t = threading.Thread(target=body, daemon=True, name="busy")
    t.start()
    return t


# ---------------------------------------------------------------------------
# sampler core
# ---------------------------------------------------------------------------

class TestSampler:
    def test_fold_stack_names_and_truncation(self):
        def deep(n):
            if n == 0:
                return sys._getframe()
            return deep(n - 1)

        frame = deep(150)
        folded = profile.fold_stack(frame, "train.step", max_depth=16)
        parts = folded.split(";")
        assert parts[0] == "train.step"
        assert parts[1] == "(truncated)"  # deeper-than-cap marker
        assert len(parts) == 18  # tag + marker + 16 frames
        assert all(p == "test_profile.deep" for p in parts[2:])

    def test_table_bounded_with_overflow_bucket(self):
        p = profile.SamplingProfiler(None, "t", 0, max_stacks=4)
        for i in range(100):
            p._record(f"-;mod.f{i}")
        with p._lock:
            assert len(p._table) <= 5  # 4 distinct + "(overflow)"
            assert p._table["(overflow)"] == 96
            assert p._window_samples == 100

    def test_recursive_stacks_stay_bounded(self, tmp_path):
        """A deeply recursive workload cannot blow the table: depth is
        capped inside the fold and distinct stacks by max_stacks."""
        run = str(tmp_path)
        stop = threading.Event()

        def dive(n):
            if n <= 0:
                time.sleep(0.001)
                return 0
            return dive(n - 1)

        def runner():
            while not stop.is_set():
                dive(200)  # far past the fold's MAX_DEPTH cap

        t = threading.Thread(target=runner, daemon=True)
        t.start()
        p = profile.SamplingProfiler(run, "t", 0, hz=200,
                                     window_s=60, max_stacks=8).start()
        time.sleep(0.4)
        p.stop()
        stop.set()
        t.join()
        wins = _read_windows(run, "t-0")
        assert wins, "no final window journaled"
        for w in wins:
            assert len(w["stacks"]) <= 9  # max_stacks + overflow
            for folded in w["stacks"]:
                assert len(folded.split(";")) <= profile.MAX_DEPTH + 2

    def test_span_tag_attribution(self, tmp_path):
        run = str(tmp_path)
        dtrace.configure(run, "t", 0, sample=0.0)
        stop = threading.Event()
        t = _busy_thread(stop, span="serve.request")
        p = profile.SamplingProfiler(run, "t", 0, hz=100,
                                     window_s=60).start()
        time.sleep(0.4)
        p.stop()
        stop.set()
        t.join()
        wins = _read_windows(run, "t-0")
        tagged = {k: v for w in wins for k, v in w["stacks"].items()
                  if k.startswith("serve.request;")}
        assert tagged, "no samples tagged with the active span"
        assert any("test_profile.body" in k for k in tagged)

    def test_journal_schema_deterministic(self, tmp_path):
        run = str(tmp_path)
        p = profile.SamplingProfiler(run, "serve", 3, hz=100, window_s=60)
        p._record("-;mod.a;mod.b", 7)
        doc = p.flush_window(kind="window")
        assert doc == _read_windows(run, "serve-3")[0]
        assert sorted(doc) == ["hz", "kind", "pid", "rank", "role",
                               "samples", "stacks", "t0", "t1", "type",
                               "unit"]
        assert doc["type"] == "profwindow"
        assert doc["unit"] == "samples"
        assert doc["samples"] == 7
        assert doc["stacks"] == {"-;mod.a;mod.b": 7}
        assert doc["role"] == "serve" and doc["rank"] == 3
        # empty windows stay off disk
        assert p.flush_window(kind="window") is None

    def test_top_frames_rank_by_leaf_self_time(self):
        p = profile.SamplingProfiler(None, "t", 0)
        p._record("-;mod.a;mod.hot", 8)
        p._record("-;mod.b;mod.hot", 2)
        p._record("-;mod.cold", 1)
        top = p.top_frames(2)
        assert top[0] == {"frame": "mod.hot", "samples": 10,
                          "share": round(10 / 11, 4)}
        assert top[1]["frame"] == "mod.cold"


# ---------------------------------------------------------------------------
# prof-agg merge
# ---------------------------------------------------------------------------

def _write_journal(run: str, stem: str, windows: list[dict]) -> None:
    d = os.path.join(run, "profiles")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, stem + ".jsonl"), "w") as f:
        for w in windows:
            f.write(json.dumps(w) + "\n")


def _win(role, stacks, unit="samples", **kw):
    return {"type": "profwindow", "role": role, "kind": "window",
            "t0": 1.0, "t1": 2.0, "unit": unit,
            "samples": sum(stacks.values()), "stacks": stacks, **kw}


class TestProfAgg:
    def test_merge_tracks_and_collapsed(self, tmp_path):
        run = str(tmp_path)
        _write_journal(run, "serve-0", [
            _win("serve", {"-;m.f": 3}), _win("serve", {"-;m.f": 2,
                                                        "-;m.g": 1}),
        ])
        _write_journal(run, "kvserver-0", [
            _win("kvserver", {"kvserver;push": 500}, unit="cpu_us"),
        ])
        tracks = profile.merge_run_dirs(run)
        assert sorted(tracks) == ["kvserver-0", "serve-0"]
        assert tracks["serve-0"]["stacks"] == {"-;m.f": 5, "-;m.g": 1}
        assert tracks["serve-0"]["windows"] == 2
        assert tracks["kvserver-0"]["unit"] == "cpu_us"
        out = str(tmp_path / "fleet.collapsed")
        n = profile.write_collapsed(tracks, out)
        lines = open(out).read().splitlines()
        assert n == len(lines) == 3
        assert "serve-0;-;m.f 5" in lines
        assert "kvserver-0;kvserver;push 500" in lines

    def test_speedscope_json_loads_with_per_role_tracks(self, tmp_path):
        run = str(tmp_path)
        _write_journal(run, "route-0", [_win("route", {"-;r.h": 4})])
        _write_journal(run, "online-1", [_win("online", {"-;o.c": 6})])
        out = str(tmp_path / "fleet.speedscope.json")
        profile.write_speedscope(profile.merge_run_dirs(run), out)
        doc = json.load(open(out))  # must parse as strict JSON
        assert doc["$schema"].startswith("https://www.speedscope.app")
        names = [p["name"] for p in doc["profiles"]]
        assert names == ["online-1", "route-0"]
        for p in doc["profiles"]:
            assert p["type"] == "sampled"
            assert len(p["samples"]) == len(p["weights"])
            assert p["endValue"] == sum(p["weights"])
            for s in p["samples"]:
                for fi in s:
                    assert 0 <= fi < len(doc["shared"]["frames"])

    def test_torn_tail_line_skipped(self, tmp_path):
        run = str(tmp_path)
        _write_journal(run, "serve-0", [_win("serve", {"-;m.f": 3})])
        with open(os.path.join(run, "profiles", "serve-0.jsonl"), "a") as f:
            f.write('{"type":"profwindow","stacks":{"-;m.g"')  # torn
        tracks = profile.merge_run_dirs(run)
        assert tracks["serve-0"]["stacks"] == {"-;m.f": 3}

    def test_prof_agg_cli_contract(self, tmp_path):
        from distlr_tpu.launch import main

        run = str(tmp_path / "run")
        _write_journal(run, "serve-0", [_win("serve", {"-;m.f": 3})])
        out = str(tmp_path / "fleet")
        assert main(["prof-agg", "--obs-run-dir", run, "--out", out]) == 0
        assert os.path.exists(out + ".collapsed")
        json.load(open(out + ".speedscope.json"))
        # empty run dir is a named error, not a zero-track artifact
        empty = str(tmp_path / "empty")
        os.makedirs(empty)
        assert main(["prof-agg", "--obs-run-dir", empty,
                     "--out", out]) == 1


# ---------------------------------------------------------------------------
# bursts + incident unification
# ---------------------------------------------------------------------------

class TestBurst:
    def test_profrec_trigger_bursts_once(self, tmp_path):
        run = str(tmp_path)
        stop = threading.Event()
        t = _busy_thread(stop)
        p = profile.configure(run, "worker", 1, hz=50, window_s=30,
                              burst_s=0.3)
        try:
            time.sleep(0.2)
            profile.trigger(run, "debugging")
            deadline = time.monotonic() + 5
            bursts = []
            while not bursts and time.monotonic() < deadline:
                time.sleep(0.05)
                try:
                    bursts = [w for w in _read_windows(run, "worker-1")
                              if w["kind"] == "burst"]
                except OSError:
                    pass
        finally:
            stop.set()
            t.join()
            profile.stop()
        assert len(bursts) == 1
        b = bursts[0]
        assert b["incident"] == 0
        assert b["reason"] == "debugging"
        assert b["hz"] == p.burst_hz
        # the same trigger seq must not re-burst
        wins = _read_windows(run, "worker-1")
        assert sum(w["kind"] == "burst" for w in wins) == 1

    def test_one_incident_one_flight_dump_one_burst_window(self, tmp_path):
        """Incident unification: ONE alert edge (the flight recorder's
        trigger) produces exactly one flight dump AND one profile burst
        window sharing the incident seq, and the dump references the
        profile journal."""
        run = str(tmp_path)
        dtrace.configure(run, "worker", 0, sample=0.0)
        profile.configure(run, "worker", 0, hz=50, window_s=30,
                          burst_s=0.3)
        ctx = dtrace.new_trace()
        with dtrace.use(ctx), dtrace.span("pre.alert"):
            pass
        dtrace.trigger(run, alert="distlr_alert_test")  # the edge
        deadline = time.monotonic() + 5
        dumps = []
        while not dumps and time.monotonic() < deadline:
            dumps = glob.glob(os.path.join(run, "flightrec",
                                           "worker-0-*.json"))
            time.sleep(0.05)
        assert dumps, "alert edge produced no flight dump"
        time.sleep(0.6)  # burst completes
        profile.stop()
        doc = json.load(open(dumps[0]))
        assert doc["profile_journal"] == os.path.join(
            run, "profiles", "worker-0.jsonl")
        assert doc["profile_incident_seq"] == 0
        bursts = [w for w in _read_windows(run, "worker-0")
                  if w["kind"] == "burst"]
        assert len(bursts) == 1
        assert bursts[0]["incident"] == 0
        assert "distlr_alert_test" in bursts[0]["reason"]
        assert len(dumps) == 1

    def test_alert_burst_e2e_multi_process_fleet(self, tmp_path):
        """Acceptance: an alert edge seen by the REAL aggregator makes
        every process of a multi-process fleet — this one and a
        subprocess — journal exactly one burst window each."""
        from distlr_tpu.obs import write_metrics_snapshot
        from distlr_tpu.obs.federate import AlertThresholds, FleetScraper
        from distlr_tpu.obs.registry import get_registry

        run = str(tmp_path / "run")
        os.makedirs(run)
        child_src = (
            "import sys, time\n"
            "from distlr_tpu.obs import dtrace, profile\n"
            "run = sys.argv[1]\n"
            "dtrace.configure(run, 'peer', 1, sample=0.0)\n"
            "profile.configure(run, 'peer', 1, hz=50, window_s=30, "
            "burst_s=0.3)\n"
            "print('READY', flush=True)\n"
            "time.sleep(30)\n"
        )
        child = subprocess.Popen([sys.executable, "-c", child_src, run],
                                 stdout=subprocess.PIPE, text=True,
                                 cwd=REPO)
        try:
            assert child.stdout.readline().strip() == "READY"
            dtrace.configure(run, "worker", 0, sample=0.0)
            profile.configure(run, "worker", 0, hz=50, window_s=30,
                              burst_s=0.3)
            # a supervisor gave-up event: the structurally-0 threshold
            # alert fires on any count — the cheapest real alert edge
            get_registry().counter(
                "distlr_ps_supervisor_events_total", "", ("event",)
            ).labels(event="gave-up").inc()
            os.makedirs(os.path.join(run, "snapshots"), exist_ok=True)
            write_metrics_snapshot(
                os.path.join(run, "snapshots", "worker-0.json"),
                get_registry())
            scraper = FleetScraper(run, thresholds=AlertThresholds())
            scraper.scrape_once()
            assert any(a["name"] == "distlr_alert_ps_gave_up"
                       and a["firing"]
                       for a in scraper.fleet_json()["alerts"])

            deadline = time.monotonic() + 8
            got = {}
            while len(got) < 2 and time.monotonic() < deadline:
                time.sleep(0.1)
                for stem in ("worker-0", "peer-1"):
                    try:
                        wins = _read_windows(run, stem)
                    except OSError:
                        continue
                    bursts = [w for w in wins if w["kind"] == "burst"]
                    if bursts:
                        got[stem] = bursts
            assert sorted(got) == ["peer-1", "worker-0"], got
            for stem, bursts in got.items():
                assert len(bursts) == 1, (stem, bursts)
                assert bursts[0]["incident"] == 0
            # a STILL-firing alert on the next scrape is not a new edge
            scraper.scrape_once()
            time.sleep(0.8)
            for stem in ("worker-0", "peer-1"):
                bursts = [w for w in _read_windows(run, stem)
                          if w["kind"] == "burst"]
                assert len(bursts) == 1, stem
        finally:
            profile.stop()
            child.terminate()
            child.wait(timeout=10)
            if child.stdout:
                child.stdout.close()


# ---------------------------------------------------------------------------
# obs-agg scrape history + `launch top --replay` (satellite)
# ---------------------------------------------------------------------------

class TestScrapeHistory:
    def test_history_journal_and_replay(self, tmp_path):
        from distlr_tpu.obs import write_metrics_snapshot
        from distlr_tpu.obs.federate import FleetScraper
        from distlr_tpu.obs.registry import get_registry
        from distlr_tpu.obs.top import run_top_replay

        run = str(tmp_path)
        os.makedirs(os.path.join(run, "snapshots"))
        write_metrics_snapshot(os.path.join(run, "snapshots",
                                            "serve-0.json"),
                               get_registry())
        scraper = FleetScraper(run)
        scraper.scrape_once()
        time.sleep(0.01)
        scraper.scrape_once()
        hist = os.path.join(run, "history.jsonl")
        frames = [json.loads(line) for line in open(hist)]
        assert len(frames) == 2
        assert all(f["totals"]["ranks"] == 1 for f in frames)
        buf = io.StringIO()
        assert run_top_replay(hist, color=False, out=buf) == 0
        assert "replayed 2 frames" in buf.getvalue()
        assert "serve" in buf.getvalue()

    def test_history_rotates_at_bound(self, tmp_path, monkeypatch):
        from distlr_tpu.obs import federate
        from distlr_tpu.obs.federate import FleetScraper

        monkeypatch.setattr(federate, "HISTORY_MAX_LINES", 3)
        run = str(tmp_path)
        scraper = FleetScraper(run)
        for _ in range(7):
            scraper.scrape_once()
        hist = os.path.join(run, "history.jsonl")
        n = len(open(hist).readlines())
        n1 = len(open(hist + ".1").readlines())
        # 7 scrapes through a 3-line bound: the current segment stays
        # under the cap and exactly one full rotation survives
        assert 1 <= n <= 3 and n1 == 3

    def test_replay_missing_file_is_error(self, tmp_path):
        from distlr_tpu.obs.top import run_top_replay

        buf = io.StringIO()
        assert run_top_replay(str(tmp_path / "nope.jsonl"),
                              color=False, out=buf) == 1

    def test_top_cli_replay_flag(self, tmp_path, capsys):
        from distlr_tpu.launch import main

        hist = tmp_path / "history.jsonl"
        hist.write_text(json.dumps({
            "updated": time.time(), "run_dir": "x",
            "totals": {"ranks": 1, "up": 1, "stale": 0, "down": 0,
                       "samples_per_s": 0.0},
            "alerts": [], "ranks": [{"role": "serve", "rank": 0,
                                     "state": "up"}],
        }) + "\n")
        assert main(["top", "--replay", str(hist), "--no-color"]) == 0
        out = capsys.readouterr().out
        assert "replayed 1 frames" in out


# ---------------------------------------------------------------------------
# JAX runtime introspection + `launch top` columns (satellites)
# ---------------------------------------------------------------------------

class TestJaxIntrospection:
    def test_engine_compiles_counted_per_bucket(self):
        import numpy as np

        from distlr_tpu.config import Config
        from distlr_tpu.obs.registry import get_registry
        from distlr_tpu.serve import ScoringEngine

        def bucket_count(bucket):
            fam = get_registry().get("distlr_jax_compiles_total")
            if fam is None:
                return 0.0
            return sum(c.value for v, c in fam.children()
                       if v == ("serve.engine", str(bucket)))

        cfg = Config(model="binary_lr", num_feature_dim=48, l2_c=0.0)
        engine = ScoringEngine(cfg, max_batch_size=256)
        engine.set_weights(np.ones(48, np.float32))
        b64 = bucket_count(64)
        engine.score((np.ones((3, 48), np.float32),))
        assert bucket_count(64) == b64 + 1  # first 64-bucket compile
        engine.score((np.ones((5, 48), np.float32),))
        assert bucket_count(64) == b64 + 1  # cache hit: no recompile
        gauge = get_registry().get("distlr_jax_device_buffer_bytes")
        assert gauge is not None and gauge.value > 0

    def test_fleet_json_and_top_render_jax_columns(self, tmp_path):
        from distlr_tpu.obs import jaxrt, write_metrics_snapshot
        from distlr_tpu.obs.federate import FleetScraper
        from distlr_tpu.obs.registry import get_registry
        from distlr_tpu.obs.top import render_fleet

        jaxrt._COMPILES.labels(site="serve.engine", bucket="64").inc(2)
        jaxrt._DEVICE_BYTES.set(3_000_000)
        run = str(tmp_path)
        os.makedirs(os.path.join(run, "snapshots"))
        write_metrics_snapshot(os.path.join(run, "snapshots",
                                            "serve-0.json"),
                               get_registry())
        scraper = FleetScraper(run)
        scraper.scrape_once()
        row = [r for r in scraper.fleet_json()["ranks"]
               if r["role"] == "serve"][0]
        assert row["jax_compiles"] >= 2
        assert row["device_mb"] == 3.0
        frame = render_fleet(scraper.fleet_json(), color=False)
        assert "compiles" in frame and "dev MB" in frame


# ---------------------------------------------------------------------------
# native kv_server CPU extension
# ---------------------------------------------------------------------------

class TestNativeCpu:
    def test_stats_carry_cpu_seconds_and_gauge_mirrors(self, tmp_path):
        import numpy as np

        from distlr_tpu.obs.registry import get_registry
        from distlr_tpu.ps import KVWorker, ServerGroup

        d = str(tmp_path / "prof")
        with ServerGroup(1, 1, 64, sync=False, prof_journal_dir=d,
                         prof_window_s=0.4) as g:
            with KVWorker(g.hosts, 64, client_id=1,
                          sync_group=False) as kv:
                kv.push_init(np.zeros(64, np.float32))
                for _ in range(300):
                    kv.push(np.ones(64, np.float32))
                s = kv.stats(0)
                assert isinstance(s["cpu_push_seconds"], float)
                assert s["cpu_push_seconds"] > 0
                assert s["total_pushes"] == 301  # v1 fields intact
            g.health()
            fam = get_registry().get("distlr_kv_server_cpu_seconds")
            vals = dict(fam.children())
            assert vals[("0", "push")].value > 0
            time.sleep(0.6)  # at least one native window elapses
        wins = [json.loads(line)
                for line in open(os.path.join(d, "kvserver-0.jsonl"))]
        assert wins
        assert all(w["type"] == "profwindow" and w["unit"] == "cpu_us"
                   for w in wins)
        assert any("kvserver;push" in w["stacks"] for w in wins)
        # the native journal merges through the same reader
        run = str(tmp_path)
        os.makedirs(os.path.join(run, "profiles"), exist_ok=True)
        os.replace(os.path.join(d, "kvserver-0.jsonl"),
                   os.path.join(run, "profiles", "kvserver-0.jsonl"))
        tracks = profile.merge_run_dirs(run)
        assert "kvserver-0" in tracks
        assert tracks["kvserver-0"]["unit"] == "cpu_us"

    def test_stats_reply_length_negotiated_by_aux(self):
        """Mixed-vintage pin: the kStats request's aux advertises how
        many stats the client accepts — aux 0 (a pre-extension client,
        whose strict length check demands exactly six) gets the 6-slot
        v1 reply; the extension replies at most kStatsVals (11 since the
        membership round appended the epoch slot, 15 since the BSP
        barrier's tail, 16 since run_frames, 17 since lock_wait_seconds,
        19 since the release's fan-out, 24 since a push's phases, 25
        since ``mapped_frames``, 27 since FTRL's two, 28 since
        ``ftrl_packed_steps``)."""
        import socket
        import struct

        from distlr_tpu.ps import ServerGroup

        with ServerGroup(1, 1, 8, sync=False) as g:
            port = g.ports[0]
            with socket.create_connection(("127.0.0.1", port)) as s:
                # MsgHeader: magic u32, op u8, flags u8, aux u16,
                # client_id u32, ts u32, num_keys u64; op 6 = kStats
                for aux, expect_slots in ((0, 12), (10, 20), (11, 22),
                                          (15, 30), (16, 32), (17, 34),
                                          (19, 38), (24, 48), (25, 50),
                                          (27, 54), (28, 56), (64, 56)):
                    s.sendall(struct.pack("<IBBHIIQ", 0xD157C0DE, 6, 0,
                                          aux, 1, 1, 0))
                    hdr = s.recv(24, socket.MSG_WAITALL)
                    nk = struct.unpack("<IBBHIIQ", hdr)[6]
                    s.recv(nk * 4, socket.MSG_WAITALL)
                    assert nk == expect_slots, (aux, nk)


# ---------------------------------------------------------------------------
# launch wiring: _obs_scope arms/stops the profiler
# ---------------------------------------------------------------------------

class TestLaunchWiring:
    def test_profrec_cli_contract(self, tmp_path, capsys):
        from distlr_tpu.launch import main

        run = str(tmp_path / "run")
        os.makedirs(run)
        assert main(["profrec", "--obs-run-dir", run]) == 0
        out = capsys.readouterr().out
        assert "PROFREC" in out
        doc = json.load(open(os.path.join(run, "profiles",
                                          profile.TRIGGER_NAME)))
        assert doc["seq"] == 0 and doc["reason"] == "manual"
        # re-trigger bumps the seq (edge-triggered consumers)
        assert main(["profrec", "--obs-run-dir", run]) == 0
        doc = json.load(open(os.path.join(run, "profiles",
                                          profile.TRIGGER_NAME)))
        assert doc["seq"] == 1

    def test_gen_data_like_command_journals_profile(self, tmp_path):
        """Any launch subcommand under --obs-run-dir leaves a profile
        journal behind (the always-on half), and --prof-hz 0 disables
        it."""
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        run = str(tmp_path / "run")
        rc = subprocess.run(
            [sys.executable, "-m", "distlr_tpu.launch", "eval",
             "--model-file", "/nonexistent", "--obs-run-dir", run,
             "--prof-hz", "200", "--prof-window", "60"],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=120,
        )
        # the command itself fails (bogus model file) AFTER the obs
        # scope armed — the profiler ran regardless (the journal dir is
        # created at arming; the window file needs >=1 sample, which a
        # fast-failing command may not reach deterministically)
        assert rc.returncode != 0
        assert os.path.isdir(os.path.join(run, "profiles"))
        run2 = str(tmp_path / "run2")
        subprocess.run(
            [sys.executable, "-m", "distlr_tpu.launch", "eval",
             "--model-file", "/nonexistent", "--obs-run-dir", run2,
             "--prof-hz", "0"],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=120,
        )
        assert not os.path.exists(os.path.join(run2, "profiles"))
