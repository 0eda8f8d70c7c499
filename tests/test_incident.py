"""Incident engine + fleet-wide structured logging (ISSUE 18).

Covers the FleetLogger core (bounded ring, level gating, rate-limited
dedupe with suppressed counts, bounded dedupe table, journal record
cap, eager WARN+ flushes, stdlib tee with template dedupe identity,
dtrace trace/span stamping), the fleet-wide journal reader behind
``launch logs``, the incident engine (kHello clock-shift alignment,
exactly-one-bundle-per-seq idempotence, artifact collection across
every journal family, retention, manual drills), obs-agg's edge ->
settle -> assemble wiring (no re-trigger while an alert stays firing),
the ``launch logs`` / ``launch incident`` CLI contracts, and the
acceptance e2e: a real ps+serve+route+online fleet under a chaos plan
producing ONE bundle whose timeline orders chaos-fault -> alert-edge
-> autopilot rollback correctly.
"""

import json
import logging
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from distlr_tpu.obs import dtrace, incident, profile
from distlr_tpu.obs import log as fleetlog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset():
    yield
    fleetlog.reset_for_tests()
    profile.reset_for_tests()
    dtrace.reset_for_tests()


def _counter_total(name: str) -> float:
    from distlr_tpu.obs.registry import get_registry

    fam = get_registry().get(name)
    if fam is None:
        return 0.0
    return sum(child.value for _v, child in fam.children())


def _journal_lines(run: str, stem: str) -> list[dict]:
    with open(os.path.join(run, "logs", stem + ".jsonl")) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


# ---------------------------------------------------------------------------
# FleetLogger units
# ---------------------------------------------------------------------------

class TestFleetLogger:
    def test_validation(self):
        with pytest.raises(ValueError, match="level"):
            fleetlog.FleetLogger(None, "t", 0, level="loud")
        with pytest.raises(ValueError, match="ring"):
            fleetlog.FleetLogger(None, "t", 0, ring=0)
        with pytest.raises(ValueError, match="dedupe_s"):
            fleetlog.FleetLogger(None, "t", 0, dedupe_s=-1.0)

    def test_ring_bounded_and_keeps_below_level(self, tmp_path):
        lg = fleetlog.FleetLogger(str(tmp_path), "t", 0, ring=8,
                                  dedupe_s=0.0)
        for i in range(30):
            lg.debug_seen = lg.emit("debug", f"d{i}")  # below level=info
        lg.emit("info", "kept")
        lg.flush()
        ring = lg.tail(100)
        assert len(ring) == 8  # bounded
        assert ring[-1]["msg"] == "kept"
        # below-level records live in the ring but never in the journal
        recs = [d for d in _journal_lines(str(tmp_path), "t-0")
                if d["type"] == "record"]
        assert [r["msg"] for r in recs] == ["kept"]

    def test_dedupe_window_suppresses_then_closes_with_count(self):
        lg = fleetlog.FleetLogger(None, "t", 0, dedupe_s=0.3)
        first = lg.emit("info", "boom")
        assert "suppressed" not in first
        for _ in range(3):
            lg.emit("info", "boom")
        assert lg.stats()["suppressed"] == 3
        time.sleep(0.35)
        closing = lg.emit("info", "boom")
        assert closing["suppressed"] == 3

    def test_distinct_templates_do_not_collide(self):
        lg = fleetlog.FleetLogger(None, "t", 0, dedupe_s=5.0)
        a = lg.emit("info", "rank 1 timed out", template="rank %d timed out")
        b = lg.emit("info", "rank 2 timed out", template="rank %d timed out")
        c = lg.emit("info", "other message")
        assert "suppressed" not in a and "suppressed" not in c
        assert lg.stats()["suppressed"] == 1  # b collapsed into a's window
        assert b["msg"] == "rank 2 timed out"

    def test_dedupe_table_bounded(self, monkeypatch):
        monkeypatch.setattr(fleetlog, "DEDUPE_TABLE_MAX", 8)
        lg = fleetlog.FleetLogger(None, "t", 0, dedupe_s=0.05)
        for i in range(8):
            lg.emit("info", f"m{i}")
        time.sleep(0.1)  # all 8 windows expire with nothing pending
        for i in range(8, 13):
            lg.emit("info", f"m{i}")
        # the prune on insert drops expired no-pending entries
        assert len(lg._dedupe) <= 8

    def test_journal_record_cap_drops_loudly(self, tmp_path, monkeypatch):
        monkeypatch.setattr(fleetlog, "MAX_JOURNAL_RECORDS", 10)
        before = _counter_total("distlr_log_journal_dropped_total")
        lg = fleetlog.FleetLogger(str(tmp_path), "t", 0, dedupe_s=0.0)
        for i in range(15):
            lg.emit("info", f"m{i}")
        lg.flush()
        recs = [d for d in _journal_lines(str(tmp_path), "t-0")
                if d["type"] == "record"]
        assert len(recs) == 10
        assert _counter_total("distlr_log_journal_dropped_total") \
            - before == 5
        # the ring keeps running past the cap
        assert lg.tail(1)[0]["msg"] == "m14"

    def test_warn_flushes_eagerly_info_buffers(self, tmp_path):
        lg = fleetlog.FleetLogger(str(tmp_path), "t", 0, dedupe_s=0.0)
        # the meta line is flushed eagerly at open
        assert _journal_lines(str(tmp_path), "t-0")[0]["type"] == "meta"
        lg.emit("info", "buffered")
        assert len(_journal_lines(str(tmp_path), "t-0")) == 1
        lg.emit("warning", "urgent")
        lines = _journal_lines(str(tmp_path), "t-0")
        assert [d.get("msg") for d in lines[1:]] == ["buffered", "urgent"]
        lg.close()

    def test_stdlib_tee_keeps_stderr_handlers(self, tmp_path):
        from distlr_tpu.utils.logging import get_logger

        log = get_logger("distlr_tpu.test_incident_tee")
        handlers_before = list(log.handlers)
        fleetlog.configure(str(tmp_path), "worker", 3, dedupe_s=5.0)
        try:
            for i in range(3):
                log.warning("rank %d timed out", i)
            fleetlog.flush()
            recs = [d for d in _journal_lines(str(tmp_path), "worker-3")
                    if d["type"] == "record"]
            # pre-format template is the dedupe identity: one journaled
            assert len(recs) == 1
            assert recs[0]["msg"] == "rank 0 timed out"
            assert recs[0]["logger"] == "distlr_tpu.test_incident_tee"
            assert recs[0]["role"] == "worker" and recs[0]["rank"] == 3
            assert fleetlog.fleet_logger().stats()["suppressed"] == 2
        finally:
            fleetlog.stop()
        # the human-readable stderr path is untouched, tee detached
        assert [h for h in log.handlers
                if not isinstance(h, fleetlog._JournalHandler)] \
            == handlers_before
        assert not any(isinstance(h, fleetlog._JournalHandler)
                       for h in log.handlers)

    def test_trace_ids_stamped(self, tmp_path):
        run = str(tmp_path)
        dtrace.configure(run, "serve", 0, sample=1.0)
        lg = fleetlog.FleetLogger(run, "serve", 0, dedupe_s=0.0)
        bare = lg.emit("info", "outside any trace")
        assert "trace" not in bare
        ctx = dtrace.new_trace()
        with dtrace.use(ctx), dtrace.span("req.handle"):
            rec = lg.emit("info", "inside the request")
        assert rec["trace"] == f"{ctx.trace_id:016x}"
        assert len(rec["span"]) == 16
        lg.close()

    def test_module_emit_noop_until_configured(self, tmp_path):
        assert not fleetlog.is_configured()
        assert fleetlog.emit("info", "dropped") is None
        lg = fleetlog.configure(str(tmp_path), "cli", 0)
        try:
            assert fleetlog.is_configured()
            assert fleetlog.emit("info", "kept")["role"] == "cli"
            assert fleetlog.fleet_logger() is lg
        finally:
            fleetlog.stop()
        assert fleetlog.emit("info", "dropped again") is None

    def test_read_records_merges_filters_and_tails(self, tmp_path):
        run = str(tmp_path)
        a = fleetlog.FleetLogger(run, "serve", 0, level="debug",
                                 dedupe_s=0.0)
        b = fleetlog.FleetLogger(run, "online", 1, level="debug",
                                 dedupe_s=0.0)
        a.emit("info", "pull ok")
        b.emit("warning", "claim stolen")
        a.emit("error", "pull FAILED hard")
        a.flush(), b.flush()
        recs = fleetlog.read_records(run)
        assert [r["msg"] for r in recs] == [
            "pull ok", "claim stolen", "pull FAILED hard"]
        assert [r["msg"] for r in fleetlog.read_records(run,
                                                        level="warning")] \
            == ["claim stolen", "pull FAILED hard"]
        assert [r["msg"] for r in fleetlog.read_records(run, grep="FAILED")] \
            == ["pull FAILED hard"]
        assert [r["msg"] for r in fleetlog.read_records(run, limit=1)] \
            == ["pull FAILED hard"]
        a.close(), b.close()


# ---------------------------------------------------------------------------
# incident engine units
# ---------------------------------------------------------------------------

def _write_jsonl(path: str, docs: list[dict]) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for d in docs:
            f.write(json.dumps(d) + "\n")


class TestIncidentEngine:
    def test_clock_shift_merge(self, tmp_path):
        """A peer journal whose meta.listen port was clock-probed is
        shifted onto the observer's clock — record for record the PR-8
        kHello offsets — so a skewed rank's WARN sorts where it
        actually happened."""
        agg = str(tmp_path / "agg")
        ps = str(tmp_path / "ps")
        t0 = 1_700_000_000.0
        # the observer measured ps's clock +2s ahead
        _write_jsonl(os.path.join(agg, "spans", "agg-0.jsonl"), [
            {"type": "meta", "role": "agg", "rank": 0},
            {"type": "clock", "peer": "10.0.0.2:9001", "offset_s": 2.0},
        ])
        _write_jsonl(os.path.join(ps, "spans", "ps-0.jsonl"), [
            {"type": "meta", "role": "ps", "rank": 0,
             "listen": "0.0.0.0:9001"},
        ])
        shifts, offsets = incident.clock_shifts([agg, ps])
        assert offsets == {"9001": 2.0}
        assert shifts == {"agg-0": 0.0, "ps-0": -2.0}
        # ps logged at raw ts t0+1.5 on its own (fast) clock: truly
        # t0-0.5, i.e. BEFORE agg's t0 record
        _write_jsonl(os.path.join(agg, "logs", "agg-0.jsonl"), [
            {"type": "record", "ts": t0, "level": "warning",
             "role": "agg", "rank": 0, "logger": "x", "msg": "edge seen"},
        ])
        _write_jsonl(os.path.join(ps, "logs", "ps-0.jsonl"), [
            {"type": "record", "ts": t0 + 1.5, "level": "error",
             "role": "ps", "rank": 0, "logger": "x", "msg": "died first"},
        ])
        out = incident.assemble([agg, ps], seq=0, reason="skewtest",
                                detected_ts=t0 + 1.0,
                                per_dir_seqs=[None, None])
        assert out == incident.bundle_dir(agg, 0)
        doc = incident.load(agg, 0)
        logs = [e for e in doc["timeline"] if e["kind"] == "log"]
        assert [e["src"] for e in logs] == ["ps-0", "agg-0"]
        assert logs[0]["t"] == pytest.approx(t0 - 0.5)
        assert doc["clock_shifts"] == {"ps-0": -2.0}
        ts = [e["t"] for e in doc["timeline"]]
        assert ts == sorted(ts)

    def test_assemble_is_idempotent_per_seq(self, tmp_path):
        run = str(tmp_path)
        _write_jsonl(os.path.join(run, "logs", "a-0.jsonl"), [
            {"type": "record", "ts": 100.0, "level": "warning",
             "role": "a", "rank": 0, "logger": "x", "msg": "w"},
        ])
        first = incident.assemble(run, seq=4, reason="r",
                                  detected_ts=100.0)
        assert first is not None
        # the exactly-one-bundle contract: same seq assembles ONCE
        assert incident.assemble(run, seq=4, reason="r",
                                 detected_ts=101.0) is None
        assert [d["seq"] for d in incident.list_incidents(run)] == [4]
        assert incident.latest_seq(run) == 4

    def test_assemble_collects_every_artifact_family(self, tmp_path):
        from distlr_tpu.autopilot.actuators import Actuators
        from distlr_tpu.autopilot.daemon import AutopilotDaemon
        from distlr_tpu.autopilot.policy import PolicyConfig, PolicyEngine

        run = str(tmp_path)
        dtrace.configure(run, "worker", 0, sample=1.0)
        profile.configure(run, "worker", 0, hz=50, window_s=30,
                          burst_s=0.3)
        fleetlog.configure(run, "worker", 0, dedupe_s=0.0)
        ctx = dtrace.new_trace()
        with dtrace.use(ctx), dtrace.span("train.step"):
            fleetlog.emit("warning", "step latency blew the budget",
                          logger="worker.train")
        dtrace.instant("chaos.reset", tags={"link": 0, "fault": 2})
        # a real autopilot decision, journaled through the daemon so the
        # line carries BOTH the policy clock "t" and the wall "ts" the
        # collector anchors on
        daemon = AutopilotDaemon(
            PolicyEngine(PolicyConfig(hysteresis_ticks=1, cooldown_s=0.0)),
            _ScriptActuators({"ps": 1, "engine": 1, "worker": 1}),
            fetch=lambda: {"ranks": [{"role": "online", "rank": 0,
                                      "shard_lag": 50.0}]},
            journal_dir=run, clock=time.monotonic)
        decision = daemon.tick_once()
        assert decision.rule == "worker_up"
        _write_jsonl(os.path.join(run, "rollout", "ramp.jsonl"), [
            {"t": time.time(), "event": "stage", "stage": 1,
             "weight": 0.25},
        ])
        detected = time.time()
        dtrace.trigger(run, alert="distlr_alert_test")
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not [
                f for f in os.listdir(os.path.join(run, "flightrec"))
                if f.startswith("worker-0-")]:
            time.sleep(0.05)
        time.sleep(0.6)  # the burst window closes
        profile.stop()
        dtrace.flush()
        fleetlog.flush()
        out = incident.assemble(
            run, seq=0, reason="distlr_alert_test", detected_ts=detected,
            alerts=[{"name": "distlr_alert_test", "firing": True}],
            settle_s=3.0)
        assert out is not None
        doc = incident.load(run, 0)
        kinds = doc["events"]
        for kind in ("alert_edge", "chaos", "log", "flight_dump",
                     "profiler_burst", "autopilot", "rollout"):
            assert kinds.get(kind, 0) >= 1, (kind, kinds)
        assert doc["flight_dumps"] and doc["bursts"]
        ts = [e["t"] for e in doc["timeline"]]
        assert ts == sorted(ts)
        # the daemon's wall anchor is what placed the decision in the
        # window — the policy-clock "t" (monotonic) lies far outside it
        ap = [e for e in doc["timeline"] if e["kind"] == "autopilot"]
        assert ap and ap[0]["rule"] == "worker_up"
        assert abs(ap[0]["t"] - detected) < 30.0
        text = open(os.path.join(out, "POSTMORTEM.md")).read()
        for heading in ("## Detection", "## Evidence", "## Actions taken",
                        "## Timeline"):
            assert heading in text
        assert "**distlr_alert_test**" in text
        assert "worker up -> 2" in text
        assert "step latency blew the budget" in text

    def test_render_rebuilds_postmortem_and_prune_retains(self, tmp_path):
        run = str(tmp_path)
        _write_jsonl(os.path.join(run, "logs", "a-0.jsonl"), [
            {"type": "record", "ts": 50.0, "level": "error", "role": "a",
             "rank": 0, "logger": "x", "msg": "w"},
        ])
        for seq in range(3):
            assert incident.assemble(run, seq=seq, reason=f"r{seq}",
                                     detected_ts=50.0 + seq) is not None
        pm = os.path.join(incident.bundle_dir(run, 2), "POSTMORTEM.md")
        os.remove(pm)
        assert incident.render(run, 2) == pm
        assert os.path.exists(pm)
        assert incident.render(run, 9) is None
        assert incident.prune(run, keep=1) == 2
        assert [d["seq"] for d in incident.list_incidents(run)] == [2]

    def test_manual_trigger_drill(self, tmp_path):
        run = str(tmp_path)
        dtrace.configure(run, "worker", 0, sample=0.0)
        with dtrace.span("warm.ring"):
            pass
        out = incident.manual_trigger(run, "drill", settle_s=0.8)
        assert out is not None
        doc = incident.load(run, 0)
        assert doc["trigger"] == "manual" and doc["reason"] == "drill"
        assert doc["events"].get("flight_dump", 0) >= 1
        # the drill's seq is taken: a second drill bumps to seq 1
        out2 = incident.manual_trigger(run, "drill2", settle_s=0.6)
        assert out2 is not None and incident.latest_seq(run) == 1


class _ScriptActuators:
    """test_autopilot's scripted Actuators stance: apply() mutates the
    counts current() reports, so the policy sees its actions land."""

    def __init__(self, counts):
        self.counts = dict(counts)
        self.applied = []

    def current(self):
        return dict(self.counts)

    def apply(self, actuator, to_count):
        self.applied.append((actuator, int(to_count)))
        self.counts[actuator] = int(to_count)
        return f"scripted {actuator}={to_count}"

    def close(self):
        pass


# ---------------------------------------------------------------------------
# obs-agg wiring: edge -> settle -> assemble, no re-trigger while firing
# ---------------------------------------------------------------------------

class TestScraperIncidents:
    def test_edge_assembles_once_while_alert_stays_firing(self, tmp_path):
        from distlr_tpu.obs import write_metrics_snapshot
        from distlr_tpu.obs.federate import AlertThresholds, FleetScraper
        from distlr_tpu.obs.registry import get_registry

        run = str(tmp_path)
        dtrace.configure(run, "worker", 0, sample=0.0)
        with dtrace.span("warm.ring"):
            pass
        fleetlog.configure(run, "worker", 0)
        try:
            # the structurally-0 supervisor gave-up alert: fires on any
            # count — the cheapest deterministic edge (test_profile's)
            get_registry().counter(
                "distlr_ps_supervisor_events_total", "", ("event",)
            ).labels(event="gave-up").inc()
            os.makedirs(os.path.join(run, "snapshots"), exist_ok=True)
            write_metrics_snapshot(
                os.path.join(run, "snapshots", "worker-0.json"),
                get_registry())
            scraper = FleetScraper(run, thresholds=AlertThresholds(),
                                   incident_settle_s=0.4)
            scraper.scrape_once()  # the edge: queued, not yet assembled
            assert incident.latest_seq(run) is None
            deadline = time.monotonic() + 8
            while incident.latest_seq(run) is None \
                    and time.monotonic() < deadline:
                time.sleep(0.1)
                scraper.scrape_once()
            assert incident.latest_seq(run) == 0
            doc = incident.load(run, 0)
            assert doc["events"].get("flight_dump", 0) >= 1
            # WARN+ records of this process (obs-agg's own edge warning
            # among them) rode into the bundle
            assert doc["events"].get("log", 0) >= 1
            # a STILL-firing alert on later scrapes is not a new edge:
            # exactly one bundle, ever
            for _ in range(3):
                time.sleep(0.2)
                scraper.scrape_once()
            assert os.listdir(os.path.join(run, "incidents")) == ["0000"]
            # fleet.json carries the incident seq for `launch top`
            assert scraper.fleet_json()["incident"]["last"] == 0
        finally:
            fleetlog.stop()


# ---------------------------------------------------------------------------
# CLI contracts
# ---------------------------------------------------------------------------

def _cli_env():
    return {**os.environ, "JAX_PLATFORMS": "cpu", "DISTLR_CPU_DEVICES": "1"}


class TestLogsCLI:
    def test_launch_logs_trace_e2e(self, tmp_path):
        """One request's log+span story: records stamped inside the
        trace interleave with that trace's spans, across a subprocess
        CLI invocation."""
        run = str(tmp_path)
        dtrace.configure(run, "serve", 0, sample=1.0)
        fleetlog.configure(run, "serve", 0, dedupe_s=0.0)
        try:
            ctx = dtrace.new_trace()
            with dtrace.use(ctx), dtrace.span("req.score"):
                rec = fleetlog.emit("info", "scored request 7",
                                    logger="serve.engine")
            fleetlog.emit("info", "unrelated background chatter")
            dtrace.flush()
            fleetlog.flush()
        finally:
            fleetlog.stop()
        trace_id = rec["trace"]
        out = subprocess.run(
            [sys.executable, "-m", "distlr_tpu.launch", "logs",
             "--obs-run-dir", run, "--trace", trace_id, "--json"],
            capture_output=True, text=True, cwd=REPO, env=_cli_env(),
            timeout=120)
        assert out.returncode == 0, out.stderr
        events = [json.loads(ln) for ln in out.stdout.splitlines()
                  if ln.strip()]
        kinds = {e.get("kind", "record") for e in events}
        assert "span" in kinds  # the trace's spans interleaved
        msgs = [e.get("msg") for e in events if "msg" in e]
        assert msgs == ["scored request 7"]
        spans = [e for e in events if e.get("kind") == "span"]
        assert spans[0]["name"] == "req.score"
        assert spans[0]["trace"] == trace_id
        # an unknown trace matches nothing: exit 1
        miss = subprocess.run(
            [sys.executable, "-m", "distlr_tpu.launch", "logs",
             "--obs-run-dir", run, "--trace", "00000000deadbeef"],
            capture_output=True, text=True, cwd=REPO, env=_cli_env(),
            timeout=120)
        assert miss.returncode == 1

    def test_launch_logs_filters_inprocess(self, tmp_path, capsys):
        from distlr_tpu import launch

        run = str(tmp_path)
        lg = fleetlog.FleetLogger(run, "serve", 0, dedupe_s=0.0)
        lg.emit("info", "pull ok")
        lg.emit("warning", "pull DEGRADED")
        lg.emit("error", "pull failed")
        lg.close()
        assert launch.main(["logs", "--obs-run-dir", run,
                            "--level", "warning", "--json"]) == 0
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.splitlines() if ln.strip()]
        assert [r["msg"] for r in lines] == ["pull DEGRADED", "pull failed"]
        assert launch.main(["logs", "--obs-run-dir", run,
                            "--grep", "DEGRADED"]) == 0
        assert "pull DEGRADED" in capsys.readouterr().out
        assert launch.main(["logs", "--obs-run-dir", run, "--tail", "1",
                            "--json"]) == 0
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.splitlines() if ln.strip()]
        assert [r["msg"] for r in lines] == ["pull failed"]
        # nothing matched -> 1; no run dir -> 2
        assert launch.main(["logs", "--obs-run-dir", run,
                            "--grep", "nope"]) == 1
        assert launch.main(["logs"]) == 2


class TestIncidentCLI:
    def test_list_show_render_contract(self, tmp_path, capsys):
        from distlr_tpu import launch

        run = str(tmp_path)
        assert launch.main(["incident", "list",
                            "--obs-run-dir", run]) == 1  # nothing yet
        _write_jsonl(os.path.join(run, "logs", "a-0.jsonl"), [
            {"type": "record", "ts": 60.0, "level": "warning", "role": "a",
             "rank": 0, "logger": "x", "msg": "w"},
        ])
        assert incident.assemble(run, seq=0, reason="drill",
                                 detected_ts=60.0) is not None
        capsys.readouterr()
        assert launch.main(["incident", "list", "--obs-run-dir", run]) == 0
        listing = capsys.readouterr().out
        assert "0000" in listing and "drill" in listing
        assert launch.main(["incident", "show", "--obs-run-dir", run]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["seq"] == 0 and doc["timeline"]
        pm = os.path.join(incident.bundle_dir(run, 0), "POSTMORTEM.md")
        os.remove(pm)
        assert launch.main(["incident", "render",
                            "--obs-run-dir", run]) == 0
        assert os.path.exists(pm)
        assert "INCIDENT" in capsys.readouterr().out
        assert launch.main(["incident", "show", "--seq", "7",
                            "--obs-run-dir", run]) == 1
        assert launch.main(["incident", "list"]) == 2  # needs run dir

    def test_trigger_drill_cli(self, tmp_path, capsys):
        from distlr_tpu import launch

        run = str(tmp_path)
        dtrace.configure(run, "worker", 0, sample=0.0)
        with dtrace.span("warm.ring"):
            pass
        assert launch.main(["incident", "--trigger", "game-day",
                            "--incident-settle", "0.6",
                            "--obs-run-dir", run]) == 0
        assert "INCIDENT" in capsys.readouterr().out
        doc = incident.load(run, 0)
        assert doc["reason"] == "game-day" and doc["trigger"] == "manual"


# ---------------------------------------------------------------------------
# acceptance e2e: chaos fleet -> one bundle, correctly ordered
# ---------------------------------------------------------------------------

def _read_announcement(proc, prefix: str, deadline_s: float = 120.0) -> str:
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(f"process exited before announcing "
                               f"{prefix!r} (rc={proc.poll()})")
        line = line.strip()
        if line.startswith(prefix):
            return line[len(prefix):].strip()
    raise RuntimeError(f"timed out waiting for {prefix!r}")


def _plant_shards(shard_dir: str, start: int, n: int) -> None:
    """Joined feedback shards, written atomically so the online
    trainer never reads a torn file."""
    os.makedirs(shard_dir, exist_ok=True)
    for i in range(start, start + n):
        body = "".join(
            f"{(i + j) % 2} " + " ".join(
                f"{k}:{0.1 * ((i + j + k) % 7):.1f}" for k in range(1, 9))
            + "\n" for j in range(3))
        tmp = os.path.join(shard_dir, f".shard-{i:05d}.tmp")
        with open(tmp, "w") as f:
            f.write(body)
        os.replace(tmp, os.path.join(shard_dir, f"shard-{i:05d}.libsvm"))


class TestIncidentAcceptance:
    def test_chaos_fleet_one_bundle_ordered_postmortem(self, tmp_path):
        """The ISSUE-18 acceptance run: a real 4-role fleet (each role
        its own process) whose PS links run through chaos fabrics; the
        injected resets drive the ps-retry-rate alert, obs-agg's edge
        assembles exactly ONE bundle, and its POSTMORTEM timeline
        orders chaos-fault -> alert-edge -> autopilot rollback."""
        from distlr_tpu.autopilot.daemon import AutopilotDaemon
        from distlr_tpu.autopilot.policy import PolicyConfig, PolicyEngine
        from distlr_tpu.chaos import ChaosFabric, parse_plan
        from distlr_tpu.obs.federate import AlertThresholds, FleetScraper
        from distlr_tpu.ps import KVWorker

        d = 64
        run = str(tmp_path / "run")
        os.makedirs(run)
        shards = str(tmp_path / "shards")
        os.makedirs(shards)

        # this process is the obs-agg rank: traces (the fabrics journal
        # their chaos instants here), structured logs (federate's edge
        # warning), and an armed profiler (the incident's burst ref)
        dtrace.configure(run, "agg", 0, sample=0.0)
        fleetlog.configure(run, "agg", 0)
        profile.configure(run, "agg", 0, hz=25, window_s=30, burst_s=0.3)

        # serve's PS link: every op from #8 on is severed -> its weight
        # watcher exhausts the retry budget (2 in-place retries per
        # poll, then the DEGRADED warning) and the fleet retry ratio
        # climbs monotonically.  online's link: sparse resets -> its
        # pushes absorb unknown-outcome faults without dying.
        serve_plan = parse_plan({"faults": [
            {"kind": "reset", "after_ops": n} for n in range(8, 320)]})
        # sparse: after any reset the next 12 ops are clean, so the
        # retry ladder always lands a re-issue — online jitters but
        # never dies (a dense plan can align resets with every re-issue)
        online_plan = parse_plan({"faults": [
            {"kind": "reset", "after_ops": n} for n in range(26, 400, 13)]})

        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "DISTLR_CPU_DEVICES": "1"}
        common = ["--obs-run-dir", run, "--num-feature-dim", str(d),
                  "--model", "binary_lr"]
        procs: list[subprocess.Popen] = []

        def launch_role(name: str, *args) -> subprocess.Popen:
            p = subprocess.Popen(
                [sys.executable, "-m", "distlr_tpu.launch", *args],
                stdout=subprocess.PIPE,
                stderr=open(str(tmp_path / f"{name}.stderr"), "w"),
                text=True, cwd=REPO, env=env)
            procs.append(p)
            return p

        # a fully severed serve link re-issues every pull twice, so the
        # cumulative retry ratio can exceed ANY finite probability-style
        # bound: quiet means 1e9, not 1.1
        quiet = AlertThresholds(
            barrier_wait_ratio=1e9, push_error_rate=1.1, scrape_stale_s=1e9,
            weight_age_ratio=1e9, retry_rate=1e9, shadow_psi=1e9)
        armed = AlertThresholds(
            barrier_wait_ratio=1e9, push_error_rate=1.1, scrape_stale_s=1e9,
            weight_age_ratio=1e9, retry_rate=0.05, shadow_psi=1e9)

        try:
            ps = launch_role("ps", "ps-server", "--async",
                             "--num-workers", "1", *common)
            hosts = _read_announcement(ps, "HOSTS ")
            # seed THROUGH the direct hosts: bring-up costs no fault ops
            with KVWorker(hosts, d, client_id=9, sync_group=False) as kv:
                kv.push_init(np.zeros(d, np.float32))
            with ChaosFabric(hosts, serve_plan) as fab_serve, \
                    ChaosFabric(hosts, online_plan) as fab_online:
                srv = launch_role(
                    "serve", "serve", "--ps-hosts", fab_serve.hosts,
                    "--reload-interval", "1.5",
                    "--ps-retry-attempts", "2",
                    "--ps-retry-backoff", "20", *common)
                online = launch_role(
                    "online", "online", "--hosts", fab_online.hosts,
                    "--shard-dir", shards, "--poll-interval", "1.0",
                    "--ps-retry-attempts", "5",
                    "--ps-retry-backoff", "20", *common)
                serve_addr = _read_announcement(srv, "SERVING ")
                rt = launch_role("route", "route",
                                 "--replicas", serve_addr, *common)
                route_addr = _read_announcement(rt, "ROUTING ")
                _read_announcement(online, "ONLINE ")

                # liveness traffic through the router
                host, port = route_addr.rsplit(":", 1)
                with socket.create_connection((host, int(port)),
                                              timeout=30.0) as s:
                    f = s.makefile("rwb")
                    for i in range(8):
                        f.write(f"ID warm-{i} 1:0.5 2:0.25 3:0.1\n"
                                .encode())
                        f.flush()
                        f.readline()

                scraper = FleetScraper(run, thresholds=quiet,
                                       incident_settle_s=2.5)
                # only the worker band may act: the engine band outranks it
                # and rides a cumulative p99, so warm-up requests that ran
                # slow beside busy test workers latched it and it took
                # every tick (up to 8, down, up, ...)
                daemon = AutopilotDaemon(
                    PolicyEngine(PolicyConfig(
                        hysteresis_ticks=1, cooldown_s=0.0,
                        rollback_window_s=600.0, lag_high=3.0,
                        route_p99_high_ms=1e9, shed_rate_high=1e9,
                        staleness_high=1e9, push_rate_high=1e9)),
                    _ScriptActuators({"ps": 1, "engine": 1, "worker": 1}),
                    fetch=scraper.fleet_json,
                    alert_poll=lambda: [
                        a["name"]
                        for a in scraper.fleet_json().get("alerts", [])
                        if a.get("firing")],
                    journal_dir=run)

                # phase 2: a feedback backlog arms the worker band; the
                # autopilot scales BEFORE any alert fires (the action a
                # later rollback undoes).  The planted orphan claim is
                # online's guaranteed WARN: reclaimed as owner-presumed-
                # dead on its next cycle.
                orphan = os.path.join(shards, "shard-orphan.libsvm.claim")
                with open(orphan, "w") as f:
                    f.write("1 1:0.5 2:0.25\n")
                os.utime(orphan, (time.time() - 3600, time.time() - 3600))
                # a backlog held there by looking: the lag gauge is a count
                # taken at each of the trainer's scans, and a fixed trickle
                # that it out-consumes reads 0 once the first batch is gone
                backlog = 60

                def unclaimed() -> int:
                    return sum(n.endswith(".libsvm") for n in os.listdir(shards))

                planted = 0
                decision = None
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    short = backlog - unclaimed()
                    if short > 0:
                        _plant_shards(shards, planted, short)
                        planted += short
                    scraper.scrape_once()
                    decision = daemon.tick_once()
                    if decision.rule == "worker_up":
                        break
                    time.sleep(0.3)
                assert decision is not None \
                    and decision.rule == "worker_up", (
                        "no worker_up before chaos: "
                        f"last={decision and decision.to_json()}")

                # phase 3: burn ops into the reset bands, then arm the
                # retry-rate alert.  serve's polls now exhaust their
                # retries every cycle, so the fleet ratio only climbs.
                _plant_shards(shards, 5000, 20)
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline \
                        and not any(e[1] == "reset"
                                    for e in fab_serve.events()):
                    time.sleep(0.3)
                assert any(e[1] == "reset" for e in fab_serve.events()), \
                    "no serve-link reset fired"
                scraper.thresholds = armed

                detected = None
                deadline = time.monotonic() + 90
                while time.monotonic() < deadline:
                    scraper.scrape_once()
                    dtrace.flush()
                    fleet = scraper.fleet_json()
                    firing = [a["name"] for a in fleet.get("alerts", [])
                              if a.get("firing")]
                    if detected is None and \
                            "distlr_alert_ps_retry_rate" in firing:
                        detected = time.time()
                    # tick only once the alert is visible: a pre-edge
                    # tick would scale workers AGAIN (backlog is still
                    # high) and the rollback would undo 3->2, not 2->1
                    if firing:
                        daemon.tick_once()
                    if incident.latest_seq(run) is not None:
                        break
                    time.sleep(0.3)
                assert detected is not None, "retry-rate alert never fired"
                assert incident.latest_seq(run) == 0, "no bundle assembled"

                # a still-firing alert on later scrapes is not a new edge
                for _ in range(3):
                    scraper.scrape_once()
                    time.sleep(0.2)
                assert os.listdir(os.path.join(run, "incidents")) \
                    == ["0000"]
        finally:
            for p in procs:
                try:
                    p.send_signal(signal.SIGTERM)
                except OSError:
                    pass
            for p in procs:
                try:
                    p.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
                if p.stdout:
                    p.stdout.close()
                if p.stderr:
                    p.stderr.close()
            profile.stop()
            fleetlog.stop()

        doc = incident.load(run, 0)
        events = doc["timeline"]
        ts = [e["t"] for e in events]
        assert ts == sorted(ts), "timeline is not clock-ordered"

        edges = [e for e in events if e["kind"] == "alert_edge"]
        assert len(edges) == 1
        edge_t = edges[0]["t"]
        assert "distlr_alert_ps_retry_rate" in edges[0]["alerts"]

        # chaos-fault -> alert-edge: the faults that CAUSED the alert
        # precede it on the timeline
        chaos = [e for e in events if e["kind"] == "chaos"]
        assert chaos and any(e["t"] < edge_t for e in chaos)
        assert any(e["fault"] == "chaos.reset" for e in chaos)

        # alert-edge -> rollback: the autopilot undid its youngest
        # action after the edge
        rollbacks = [e for e in events if e["kind"] == "autopilot"
                     and e.get("rule") == "rollback_on_alert"]
        assert rollbacks, "no rollback decision in the bundle"
        assert rollbacks[0]["t"] > edge_t
        assert rollbacks[0]["action"]["actuator"] == "worker"
        assert rollbacks[0]["action"]["to"] == 1

        # correlated WARN+ logs from >= 3 roles of the same fleet
        warn_roles = {e["src"].rsplit("-", 1)[0] for e in events
                      if e["kind"] == "log"
                      and e["level"] in ("warning", "error")}
        assert len(warn_roles) >= 3, warn_roles

        # the bundle cross-references the PR-8 flight dump and the PR-9
        # burst for the SAME incident seq
        dump_roles = {e["src"].rsplit("-", 1)[0] for e in events
                      if e["kind"] == "flight_dump"}
        assert len(dump_roles) >= 3, dump_roles
        assert doc["flight_dumps"]
        assert doc["bursts"], "no profiler burst ref for the seq"
        assert doc["per_dir_seqs"] == [0]

        text = open(os.path.join(doc["path"], "POSTMORTEM.md")).read()
        for heading in ("## Detection", "## Evidence", "## Actions taken",
                        "## Timeline"):
            assert heading in text
        assert "rollback_on_alert" in text
        assert "distlr_alert_ps_retry_rate" in text

        # `launch incident render` reproduces the postmortem (the CLI
        # acceptance criterion)
        from distlr_tpu import launch

        pm = os.path.join(doc["path"], "POSTMORTEM.md")
        os.remove(pm)
        assert launch.main(["incident", "render",
                            "--obs-run-dir", run]) == 0
        assert os.path.exists(pm)
