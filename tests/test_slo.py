"""SLO engine tests (ISSUE 17, distlr_tpu/obs/tsdb + slo).

Covers the embedded fleet time-series store (ring bounds + loud drops,
rollup-tier stitching past the raw ring, the shared ``delta_rate`` /
``RateWindow`` arithmetic the top/autopilot trackers dedupe onto, the
Prometheus-shaped query mini-language incl. histogram quantiles and
error propagation), recording rules, the SLO spec loader's validation,
error-budget / multi-window burn-rate math, the scraper integration
(gauges + burn alerts + /query endpoint + history-rotation drop
accounting), the ``launch rollout --slo`` scoped burn-rate gate with a
ramp auto-rolling-back on a fast burn, the ``launch fleet-query`` CLI,
and the acceptance e2e: a real serving tier under a clean-then-chaos
loadgen run with an SLO file — the budget consumes monotonically, the
fast window fires before the slow one, exactly one flight-recorder
dump + profiler burst lands on the burn edge, and ``fleet-query``
reproduces the route p99 the router's own STATS reports.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from distlr_tpu.obs import MetricsRegistry, MetricsServer, write_endpoint
from distlr_tpu.obs.federate import AlertThresholds, FleetScraper
from distlr_tpu.obs.registry import percentile_from_counts
from distlr_tpu.obs.slo import (
    DEFAULT_BURN_WINDOWS,
    SLO,
    SLOEngine,
    SLOSpecError,
    load_slo_file,
    load_slo_spec,
)
from distlr_tpu.obs.top import render_fleet
from distlr_tpu.obs.tsdb import (
    FleetTSDB,
    RateWindow,
    RecordingRule,
    default_rules,
    delta_rate,
    load_history,
)

from distlr_tpu.serve.loadgen import run_load

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _frame(t: float, req: float, shed: float = 0.0) -> dict:
    """One synthetic /fleet.json doc: a route rank's cumulative
    counters + a fleet total."""
    return {
        "updated": t,
        "ranks": [{"role": "route", "rank": 0,
                   "route_requests": req, "route_shed": shed,
                   "state": "up"}],
        "totals": {"samples_per_s": 5.0},
    }


def _feed(db: FleetTSDB, rows) -> None:
    for t, req, shed in rows:
        db.ingest(_frame(t, req, shed))


# ---------------------------------------------------------------------------
# the one shared rate arithmetic
# ---------------------------------------------------------------------------

class TestDeltaRate:
    def test_basic_rate(self):
        assert delta_rate(0.0, 10.0, 2.0, 30.0) == 10.0

    def test_missing_endpoints_are_none(self):
        assert delta_rate(0.0, None, 1.0, 5.0) is None
        assert delta_rate(0.0, 5.0, 1.0, None) is None

    def test_time_not_advancing_is_none(self):
        assert delta_rate(1.0, 0.0, 1.0, 5.0) is None
        assert delta_rate(2.0, 0.0, 1.0, 5.0) is None

    def test_counter_reset_clamps_to_zero(self):
        assert delta_rate(0.0, 100.0, 1.0, 3.0) == 0.0


class TestRateWindow:
    """The pinned autopilot ``_RateWindow`` semantics, now owned by the
    tsdb module (tests/test_autopilot.py re-imports the alias)."""

    def test_rate_over_horizon(self):
        w = RateWindow(10.0)
        w.push(0.0, {"pushes": 0.0})
        w.push(5.0, {"pushes": 50.0})
        assert w.rate("pushes") == 10.0

    def test_keeps_one_obs_past_horizon(self):
        w = RateWindow(4.0)
        for t in range(8):
            w.push(float(t), {"k": float(10 * t)})
        # the oldest retained obs is AT/past the horizon, so the window
        # spans at least window_s once enough history exists
        t0 = w._obs[0][0]
        assert 7.0 - t0 >= 4.0
        assert w.rate("k") == 10.0

    def test_insufficient_or_missing_is_none(self):
        w = RateWindow(10.0)
        assert w.rate("k") is None
        w.push(0.0, {"k": 1.0})
        assert w.rate("k") is None
        w.push(1.0, {"other": 2.0})
        assert w.rate("k") is None


class TestLoadHistory:
    def test_accepts_both_t_and_updated_stamps(self, tmp_path):
        """Live aggregator rows stamp ``updated``; older fixtures stamp
        ``t``.  Recognizing only ``t`` silently seeded nothing from
        every REAL history file — the satellite-1 bug."""
        p = tmp_path / "history.jsonl"
        with open(p, "w") as f:
            f.write(json.dumps({"t": 1.0, "ranks": []}) + "\n")
            f.write("{torn line\n")
            f.write(json.dumps({"updated": 2.0, "ranks": []}) + "\n")
            f.write(json.dumps({"no_stamp": True}) + "\n")
            f.write(json.dumps([1, 2]) + "\n")
        rows = load_history(str(p))
        assert [t for t, _ in rows] == [1.0, 2.0]

    def test_limit_takes_the_tail(self, tmp_path):
        p = tmp_path / "h.jsonl"
        with open(p, "w") as f:
            for i in range(10):
                f.write(json.dumps({"updated": float(i)}) + "\n")
        assert [t for t, _ in load_history(str(p), limit=3)] == [7.0, 8.0,
                                                                 9.0]

    def test_missing_file_is_empty(self, tmp_path):
        assert load_history(str(tmp_path / "nope.jsonl")) == []

    def test_autopilot_seeds_from_live_history(self, tmp_path):
        """End to end through the daemon: a REAL-shaped history file
        (``updated`` stamps) primes the rate window before tick 1."""
        from distlr_tpu.autopilot import (
            Actuators,
            AutopilotDaemon,
            PolicyConfig,
            PolicyEngine,
        )

        with open(tmp_path / "history.jsonl", "w") as f:
            for i in range(5):
                f.write(json.dumps({
                    "updated": 1000.0 + i,
                    "ranks": [{"role": "online", "rank": 0,
                               "pushes": 100.0 * i}],
                }) + "\n")
        daemon = AutopilotDaemon(
            PolicyEngine(PolicyConfig()), Actuators(),
            fetch=lambda: {"ranks": []}, rate_window_s=60.0)
        assert daemon.seed_rates_from_history(str(tmp_path)) == 5
        assert daemon._rates.rate("pushes") == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

class TestFleetTSDB:
    def test_constructor_validation(self):
        with pytest.raises(ValueError, match="raw_points"):
            FleetTSDB(raw_points=1)
        with pytest.raises(ValueError, match="retention"):
            FleetTSDB(rollup_retention_s=0.0)

    def test_ingest_fleet_rows_and_totals(self):
        db = FleetTSDB()
        n = db.ingest(_frame(10.0, 100.0, 5.0))
        assert n > 0
        names = {s["name"] for s in db.series_names()}
        assert {"route_requests", "route_shed",
                "fleet:samples_per_s"} <= names
        # rank is identity (a label), never its own series
        assert "rank" not in names
        assert db.latest_time() == 10.0

    def test_duplicate_and_stale_frames_are_dropped(self):
        db = FleetTSDB()
        assert db.ingest(_frame(10.0, 100.0)) > 0
        assert db.ingest(_frame(10.0, 200.0)) == 0
        assert db.ingest(_frame(9.0, 200.0)) == 0
        assert db.ingest({"updated": None, "ranks": []}) == 0
        assert db.stats()["frames"] == 1

    def test_raw_ring_bound_counts_drops(self):
        db = FleetTSDB(raw_points=4)
        _feed(db, [(float(10 * i), 100.0 * i, 0.0) for i in range(1, 8)])
        st = db.stats()
        # 3 series x 7 frames, ring holds 4 -> 3 evictions per series
        assert st["dropped"]["raw"] == 9
        assert st["points"] == 21

    def test_rollup_tiers_answer_past_the_raw_ring(self):
        """A long-window rate must survive raw eviction: the 10s/60s
        rollup buckets cover the history the ring dropped."""
        db = FleetTSDB(raw_points=2)
        _feed(db, [(float(10 * i), 100.0 * i, 0.0) for i in range(1, 11)])
        # raw holds only t in {90, 100}; the 100s window stitches the
        # rollup tiers back to t=10 and the rate is still exact
        assert db.query("rate(route_requests)", window_s=100.0) \
            == pytest.approx(10.0)

    def test_rollup_retention_evicts_loudly(self):
        db = FleetTSDB(raw_points=512, rollup_retention_s=30.0)
        _feed(db, [(float(10 * i), 100.0 * i, 0.0) for i in range(1, 11)])
        assert db.stats()["dropped"]["rollup"] > 0

    def test_record_none_records_nothing(self):
        db = FleetTSDB()
        db.record("derived", None, 1.0, None)
        assert db.series_names() == []
        db.record("derived", None, 1.0, 2.5)
        assert db.query("derived", now=1.0) == 2.5

    def test_count_dropped_external_tier(self):
        db = FleetTSDB()
        db.count_dropped("history", 7)
        db.count_dropped("history", 0)
        assert db.stats()["dropped"]["history"] == 7


# ---------------------------------------------------------------------------
# the query mini-language
# ---------------------------------------------------------------------------

class TestQueryLanguage:
    def _db(self):
        db = FleetTSDB()
        _feed(db, [(10.0, 100.0, 0.0), (20.0, 150.0, 10.0),
                   (30.0, 200.0, 10.0)])
        return db

    def test_rate_increase_and_last(self):
        db = self._db()
        assert db.query("rate(route_requests)", window_s=60.0) == 5.0
        assert db.query("increase(route_requests)", window_s=60.0) == 100.0
        assert db.query("last(route_requests)") == 200.0
        assert db.query("route_requests") == 200.0  # bare name = last

    def test_over_time_aggregations(self):
        db = self._db()
        assert db.query("avg_over_time(fleet:samples_per_s)",
                        window_s=60.0) == 5.0
        assert db.query("min_over_time(route_requests)",
                        window_s=60.0) == 100.0
        assert db.query("max_over_time(route_requests)",
                        window_s=60.0) == 200.0
        assert db.query("sum_over_time(route_shed)", window_s=60.0) == 20.0
        assert db.query("count_over_time(route_requests)",
                        window_s=60.0) == 3.0

    def test_label_matchers_select_series(self):
        db = self._db()
        db.record("route_requests", {"role": "route", "rank": "1"},
                  30.0, 999.0)
        assert db.query("last(route_requests{rank=0})") == 200.0
        assert db.query("last(route_requests{role=route,rank=1})") == 999.0
        assert db.query("last(route_requests{rank=7})") is None

    def test_window_bounds_the_data(self):
        db = self._db()
        # only the t=30 point is inside (25, 30]: one point, no rate
        assert db.query("rate(route_requests)", window_s=5.0) is None
        assert db.query("avg_over_time(route_requests)",
                        window_s=5.0) == 200.0

    def test_arithmetic_parens_and_unary_minus(self):
        db = self._db()
        assert db.query("rate(route_requests) * 2 + 1",
                        window_s=60.0) == 11.0
        assert db.query("(rate(route_requests) + 1) / 2",
                        window_s=60.0) == 3.0
        assert db.query("-rate(route_requests)", window_s=60.0) == -5.0

    def test_none_propagates_and_division_by_zero_is_none(self):
        db = self._db()
        assert db.query("rate(nope) + 1", window_s=60.0) is None
        assert db.query("1 / rate(route_shed{rank=7})",
                        window_s=60.0) is None
        assert db.query("rate(route_requests) / rate(ghost)",
                        window_s=60.0) is None
        # division by a present-but-zero denominator reads None, not inf
        db2 = FleetTSDB()
        _feed(db2, [(10.0, 100.0, 0.0), (20.0, 100.0, 0.0)])
        assert db2.query("1 / rate(route_requests)", window_s=60.0) is None

    def test_empty_store_is_none(self):
        assert FleetTSDB().query("rate(route_requests)") is None

    def test_syntax_errors_raise(self):
        db = self._db()
        for bad in ("rate(", "{oops}", "rate(route_requests) garbage(",
                    "route_requests route_shed", "1 +", "last(a{k})",
                    "histogram_quantile(1.5, h)"):
            with pytest.raises(ValueError):
                db.query(bad)

    def test_histogram_quantile_matches_percentile_from_counts(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", "", buckets=(0.1, 1.0))
        h.observe(0.05)
        db = FleetTSDB()
        db.ingest({"updated": 10.0, "ranks": [], "totals": {}},
                  reg.snapshot())
        h.observe(0.5)
        h.observe(2.0)
        db.ingest({"updated": 20.0, "ranks": [], "totals": {}},
                  reg.snapshot())
        got = db.query("histogram_quantile(0.5, lat_seconds)",
                       window_s=60.0)
        # the window's delta is the two NEW observations: (0, 1, 1)
        # across (0.1, 1.0, +Inf)
        assert got == pytest.approx(
            percentile_from_counts((0.1, 1.0), [0, 1, 1], 0.5))
        # an empty delta (no new observations) is None, not 0
        db.ingest({"updated": 30.0, "ranks": [], "totals": {}},
                  reg.snapshot())
        assert db.query("histogram_quantile(0.5, lat_seconds)",
                        window_s=5.0) is None


class TestRecordingRules:
    def test_syntax_checked_eagerly(self):
        with pytest.raises(ValueError):
            RecordingRule("r", "rate(")
        with pytest.raises(ValueError, match="window_s"):
            RecordingRule("r", "rate(x)", window_s=0.0)
        with pytest.raises(ValueError, match="name"):
            RecordingRule("", "rate(x)")

    def test_evaluate_records_a_derived_series(self):
        db = FleetTSDB()
        _feed(db, [(10.0, 100.0, 0.0), (20.0, 200.0, 0.0)])
        rule = RecordingRule("fleet:req_rate", "rate(route_requests)",
                             window_s=60.0)
        assert rule.evaluate(db, 20.0) == 10.0
        assert db.query("fleet:req_rate", now=20.0) == 10.0
        # None results record nothing — absence stays distinguishable
        rule2 = RecordingRule("fleet:ghost", "rate(ghost)", 60.0)
        assert rule2.evaluate(db, 20.0) is None
        assert db.query("fleet:ghost", now=20.0) is None

    def test_default_rules_cover_the_three_unified_rates(self):
        # + the ERROR-log rate behind `launch top`'s log_errors column
        assert {r.name for r in default_rules()} == {
            "fleet:push_rate", "fleet:shed_rate", "fleet:req_rate",
            "fleet:log_error_rate"}


# ---------------------------------------------------------------------------
# SLO spec + budget math
# ---------------------------------------------------------------------------

def _ratio_spec(**over) -> dict:
    spec = {"name": "avail", "objective": 0.9, "window_s": 100.0,
            "sli": {"kind": "ratio", "bad": "increase(route_shed)",
                    "total": "increase(route_requests)"}}
    spec.update(over)
    return spec


class TestSLOSpec:
    def test_defaults_are_the_sre_workbook_pairs(self):
        slo = SLO(_ratio_spec())
        assert slo.burn_windows == DEFAULT_BURN_WINDOWS

    def test_clock_scale_shrinks_every_window(self):
        slo = SLO(_ratio_spec(), clock_scale=0.01)
        assert slo.window_s == pytest.approx(1.0)
        assert slo.burn_windows[0][1:3] == (3.0, 36.0)
        assert slo.burn_windows[0][3] == 14.4  # factors never scale

    def test_validation_errors(self):
        for bad, match in [
            ({"objective": 1.0}, "objective"),
            ({"objective": 0.0}, "objective"),
            ({"window_s": 0.0}, "window_s"),
            ({"sli": {"kind": "nope"}}, "kind"),
            ({"sli": {"kind": "ratio", "bad": "rate("}}, None),
            ({"sli": {"kind": "threshold", "expr": "x", "bound": 1,
                      "op": "!="}}, "op"),
            ({"labels": "v2"}, "labels"),
        ]:
            with pytest.raises(SLOSpecError, match=match):
                SLO(_ratio_spec(**bad))
        with pytest.raises(SLOSpecError, match="missing required"):
            SLO({"name": "x", "objective": 0.9})

    def test_bad_burn_windows(self):
        with pytest.raises(SLOSpecError, match="short < long"):
            SLO(_ratio_spec(), burn_windows=(("w", 10.0, 5.0, 2.0),))
        with pytest.raises(SLOSpecError, match="factor"):
            SLO(_ratio_spec(), burn_windows=(("w", 5.0, 10.0, 0.0),))

    def test_load_slo_spec_document_validation(self):
        with pytest.raises(SLOSpecError, match="top level"):
            load_slo_spec([1])
        with pytest.raises(SLOSpecError, match="clock_scale"):
            load_slo_spec({"clock_scale": 0, "slos": [_ratio_spec()]})
        with pytest.raises(SLOSpecError, match="non-empty"):
            load_slo_spec({"slos": []})
        with pytest.raises(SLOSpecError, match="duplicate"):
            load_slo_spec({"slos": [_ratio_spec(), _ratio_spec()]})
        with pytest.raises(SLOSpecError, match="burn_windows"):
            load_slo_spec({"burn_windows": {}, "slos": [_ratio_spec()]})

    def test_load_slo_file_roundtrip_and_errors(self, tmp_path):
        p = tmp_path / "slo.json"
        p.write_text(json.dumps({
            "slos": [_ratio_spec(labels={"candidate": "v2"})],
            "rules": [{"name": "fleet:x", "expr": "rate(route_requests)",
                       "window_s": 15.0}],
        }))
        slos, rules = load_slo_file(str(p))
        assert [s.name for s in slos] == ["avail"]
        assert slos[0].labels == {"candidate": "v2"}
        assert [(r.name, r.window_s) for r in rules] == [("fleet:x", 15.0)]
        with pytest.raises(SLOSpecError, match="cannot read"):
            load_slo_file(str(tmp_path / "missing.json"))
        p.write_text("{not json")
        with pytest.raises(SLOSpecError, match="valid JSON"):
            load_slo_file(str(p))
        p.write_text(json.dumps({"slos": [_ratio_spec()],
                                 "rules": [{"name": "r", "expr": "bad("}]}))
        with pytest.raises(SLOSpecError, match="bad rule"):
            load_slo_file(str(p))


class TestSLOMath:
    def _db(self):
        db = FleetTSDB()
        # 10 req/s; sheds start at t=30: 5/s of the 10/s go bad
        _feed(db, [(10.0, 100.0, 0.0), (20.0, 200.0, 0.0),
                   (30.0, 300.0, 0.0), (40.0, 400.0, 50.0),
                   (50.0, 500.0, 100.0)])
        return db

    def test_ratio_bad_fraction_burn_and_budget(self):
        db = self._db()
        slo = SLO(_ratio_spec())
        # over the 20s tail: bad=100, total=200 -> frac 0.5, burn 5x
        assert slo.bad_fraction(db, 20.0, 50.0) == pytest.approx(0.5)
        assert slo.burn_rate(db, 20.0, 50.0) == pytest.approx(5.0)
        # over the SLO window (40s): frac 0.25 -> burn 2.5 -> overspent
        assert slo.budget_remaining(db, 50.0) == pytest.approx(-1.5)

    def test_no_traffic_is_unknown_not_compliance(self):
        db = FleetTSDB()
        _feed(db, [(10.0, 100.0, 0.0), (20.0, 100.0, 0.0)])  # idle
        slo = SLO(_ratio_spec())
        assert slo.bad_fraction(db, 60.0, 20.0) is None
        assert slo.budget_remaining(db, 20.0) is None

    def test_threshold_sli_records_bad_ticks(self):
        db = self._db()
        slo = SLO({"name": "shed_frac", "objective": 0.9, "window_s": 40.0,
                   "sli": {"kind": "threshold",
                           "expr": "increase(route_shed) / "
                                   "increase(route_requests)",
                           "op": "<=", "bound": 0.1}})
        for t in (20.0, 30.0, 40.0, 50.0):
            slo.observe(db, t)
        # ticks at 20/30 were good (no shed), 40/50 bad (frac > 0.1)
        assert db.query("avg_over_time(slo:shed_frac:bad)",
                        window_s=40.0, now=50.0) == pytest.approx(0.5)
        assert slo.bad_fraction(db, 40.0, 50.0) == pytest.approx(0.5)
        assert slo.burn_rate(db, 40.0, 50.0) == pytest.approx(5.0)

    def test_threshold_with_no_data_records_nothing(self):
        db = FleetTSDB()
        _feed(db, [(10.0, 100.0, 0.0)])
        slo = SLO({"name": "t", "objective": 0.5, "window_s": 60.0,
                   "sli": {"kind": "threshold", "expr": "rate(ghost)",
                           "op": "<", "bound": 1.0}})
        slo.observe(db, 10.0)
        assert db.query("last(slo:t:bad)", now=10.0) is None
        assert slo.bad_fraction(db, 60.0, 10.0) is None


class TestSLOEngine:
    def test_gauges_alerts_and_summaries(self):
        db = TestSLOMath()._db()
        slos = load_slo_spec({
            "burn_windows": [
                {"name": "fast", "short_s": 10, "long_s": 20, "factor": 4},
                {"name": "slow", "short_s": 20, "long_s": 40, "factor": 4},
            ],
            "slos": [_ratio_spec(labels={"candidate": "v2"})],
        })
        reg = MetricsRegistry()
        alerts: list = []
        summaries = SLOEngine(slos).evaluate(db, reg, 50.0, alerts)

        # fast fires (10s burn 5x, 20s burn 5x); slow does not (40s
        # window burn 2.5x < 4): the multi-window AND-gate in action
        assert len(alerts) == 2
        fast = next(a for a in alerts if a["labels"]["window"] == "fast")
        slow = next(a for a in alerts if a["labels"]["window"] == "slow")
        assert fast["name"] == "distlr_alert_slo_burn"
        assert fast["firing"] and not slow["firing"]
        assert fast["threshold"] == 4.0
        # attribution labels ride the alert dicts (the rollout gate's
        # scoped evidence), never the gauge labelnames
        assert fast["labels"] == {"slo": "avail", "window": "fast",
                                  "candidate": "v2"}

        text = reg.prometheus_text()
        assert 'distlr_slo_budget_remaining{slo="avail"} -1.5' in text
        assert ('distlr_slo_burn_rate{slo="avail",window="fast"} 5'
                in text)
        assert ('distlr_alert_slo_burn{slo="avail",window="fast",'
                'threshold="4"} 1') in text
        assert ('distlr_alert_slo_burn{slo="avail",window="slow",'
                'threshold="4"} 0') in text

        (s,) = summaries
        assert s["name"] == "avail"
        assert s["budget_remaining"] == pytest.approx(-1.5)
        assert s["burn"]["fast"]["firing"] is True
        assert s["burn"]["slow"]["firing"] is False
        assert s["burn"]["fast"]["long"] == pytest.approx(5.0)

    def test_no_data_holds_previous_firing_state(self):
        """A missed scrape (empty window) neither pages nor resolves:
        resolving on absence would flap the pager and re-edge the
        flight recorder after every stall."""
        db = TestSLOMath()._db()
        eng = SLOEngine(load_slo_spec({
            "burn_windows": [{"name": "fast", "short_s": 10,
                              "long_s": 20, "factor": 4}],
            "slos": [_ratio_spec()],
        }))
        reg = MetricsRegistry()

        def firing_at(now):
            alerts: list = []
            (s,) = eng.evaluate(db, reg, now, alerts)
            assert alerts[0]["firing"] == s["burn"]["fast"]["firing"]
            return s["burn"]["fast"]["firing"]

        assert firing_at(50.0) is True      # mid-burn: pages
        # far future: both windows empty -> holds the page
        assert firing_at(500.0) is True
        # traffic resumes, clean: resolves on DATA, not absence
        _feed(db, [(500.0, 1000.0, 100.0), (510.0, 1100.0, 100.0)])
        assert firing_at(510.0) is False
        # and an empty window now holds the all-clear
        assert firing_at(900.0) is False

    def test_no_data_exports_nan_not_zero(self):
        db = FleetTSDB()
        reg = MetricsRegistry()
        alerts: list = []
        (s,) = SLOEngine([SLO(_ratio_spec())]).evaluate(
            db, reg, 10.0, alerts)
        assert s["budget_remaining"] is None
        g = reg.get("distlr_slo_budget_remaining")
        assert math.isnan(g.labels(slo="avail").value)
        assert not any(a["firing"] for a in alerts)
        assert all(a["value"] is None for a in alerts)


class TestTopBudgetLines:
    def test_render_fleet_shows_slo_budgets(self):
        fleet = _frame(time.time(), 100.0, 0.0)
        fleet.update(interval_s=1.0, scrapes=1, alerts=[],
                     totals={"ranks": 1, "up": 1, "stale": 0, "down": 0,
                             "samples_per_s": 0.0})
        base = render_fleet(fleet, color=False)
        assert "SLO" not in base  # no slo key: byte-identical legacy view
        fleet["slo"] = [{
            "name": "avail", "objective": 0.9, "window_s": 100.0,
            "budget_remaining": 0.42,
            "burn": {"fast": {"short": 5.0, "long": 5.0, "factor": 4.0,
                              "firing": True},
                     "slow": {"short": None, "long": None, "factor": 4.0,
                              "firing": False}},
        }]
        frame = render_fleet(fleet, color=False)
        assert "SLO avail" in frame
        assert "42.0%" in frame
        assert "fast 5.00x" in frame and "FIRING" in frame
        assert "slow -" in frame


# ---------------------------------------------------------------------------
# scraper integration: gauges + alerts + /query + history accounting
# ---------------------------------------------------------------------------

def _write_route_snapshot(run: str, requests: int, shed: int) -> None:
    reg = MetricsRegistry()
    reg.counter("distlr_route_requests_total", "", ("model",)).labels(
        model="v1").inc(requests)
    reg.counter("distlr_route_shed_total", "", ("model",)).labels(
        model="v1").inc(shed)
    d = os.path.join(run, "snapshots")
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, ".route-0.tmp")
    with open(tmp, "w") as f:
        json.dump(reg.snapshot(), f)
    os.replace(tmp, os.path.join(d, "route-0.json"))


def _quiet_thresholds() -> AlertThresholds:
    """Thresholds no pre-existing global-registry state can trip — the
    only alert edges left are the SLO engine's own."""
    return AlertThresholds(barrier_wait_ratio=1e9, push_error_rate=1.1,
                           scrape_stale_s=1e9, weight_age_ratio=1e9,
                           retry_rate=1.1, shadow_psi=1e9)


class TestScraperIntegration:
    def _slo_doc(self) -> dict:
        return {
            "burn_windows": [
                {"name": "fast", "short_s": 30, "long_s": 60, "factor": 1},
                {"name": "slow", "short_s": 60, "long_s": 3600,
                 "factor": 1e9},
            ],
            "slos": [_ratio_spec(window_s=60.0,
                                 labels={"candidate": "v2"})],
            "rules": [{"name": "fleet:custom", "expr":
                       "rate(route_requests)", "window_s": 60.0}],
        }

    def test_scrapes_feed_tsdb_rules_and_burn_alerts(self, tmp_path):
        run = str(tmp_path)
        slos, rules = load_slo_file(_write_json(
            tmp_path / "slo.json", self._slo_doc()))
        scraper = FleetScraper(run, thresholds=_quiet_thresholds(),
                               slo_spec=slos, slo_rules=rules)
        _write_route_snapshot(run, 100, 0)
        scraper.scrape_once()
        time.sleep(0.15)
        _write_route_snapshot(run, 200, 90)
        reg = scraper.scrape_once()

        # the tsdb saw both frames; rules recorded the unified rates
        st = scraper.tsdb.stats()
        assert st["frames"] == 2
        assert scraper.tsdb.query("fleet:req_rate") is not None
        assert scraper.tsdb.query("fleet:custom") is not None

        # burn alert: 90/100 bad over the window -> burn 9x >= 1
        fleet = scraper.fleet_json()
        burn = [a for a in fleet["alerts"]
                if a["name"] == "distlr_alert_slo_burn"]
        assert {a["labels"]["window"] for a in burn} == {"fast", "slow"}
        fast = next(a for a in burn if a["labels"]["window"] == "fast")
        assert fast["firing"] and fast["labels"]["candidate"] == "v2"
        assert not next(a for a in burn
                        if a["labels"]["window"] == "slow")["firing"]
        (s,) = fleet["slo"]
        assert s["budget_remaining"] < 0  # 9x burn: overspent

        # gauges + store health ride the same scrape
        text = reg.prometheus_text()
        assert 'distlr_slo_budget_remaining{slo="avail"}' in text
        assert 'distlr_slo_burn_rate{slo="avail",window="fast"}' in text
        assert "distlr_tsdb_series" in text
        assert "distlr_tsdb_frames_total 2" in text
        assert 'distlr_tsdb_points_dropped_total{tier="raw"} 0' in text

        # the burn edge dropped the flight-recorder trigger
        trig = os.path.join(run, "flightrec", "TRIGGER.json")
        assert os.path.exists(trig)
        with open(trig) as f:
            assert "distlr_alert_slo_burn" in json.load(f)["alert"]

        # `launch top` renders the budget line from the same doc
        assert "SLO avail" in render_fleet(fleet, color=False)

    def test_query_endpoint_and_http_400(self, tmp_path):
        run = str(tmp_path)
        scraper = FleetScraper(run, thresholds=_quiet_thresholds())
        _write_route_snapshot(run, 100, 0)
        scraper.scrape_once()
        time.sleep(0.15)
        _write_route_snapshot(run, 200, 0)
        scraper.scrape_once()

        doc = scraper.query_endpoint({"expr": "rate(route_requests)",
                                      "window": "60"})
        assert doc["value"] is not None and doc["value"] > 0
        assert doc["window_s"] == 60.0
        for bad in ({}, {"expr": "rate("}, {"expr": "x", "window": "0"}):
            with pytest.raises(ValueError):
                scraper.query_endpoint(bad)

        with MetricsServer(registry=scraper,
                           extra_query={"/query":
                                        scraper.query_endpoint}) as srv:
            url = f"http://{srv.host}:{srv.port}"
            with urllib.request.urlopen(
                    url + "/query?expr=rate(route_requests)&window=60",
                    timeout=5) as r:
                assert json.load(r)["value"] > 0
            try:
                urllib.request.urlopen(url + "/query?expr=rate(",
                                       timeout=5)
                raise AssertionError("expected HTTP 400")
            except urllib.error.HTTPError as e:
                assert e.code == 400
                assert "error" in json.load(e)

    def test_history_rotation_counts_into_drop_tier(self, tmp_path):
        run = str(tmp_path)
        scraper = FleetScraper(run, thresholds=_quiet_thresholds(),
                               history_max_lines=3)
        for _ in range(7):
            scraper.scrape_once()
            time.sleep(0.01)
        # 7 appends over max 3: two rotations; the second overwrote a
        # full .1 segment (3 lines) — counted, never silent
        assert os.path.exists(os.path.join(run, "history.jsonl.1"))
        assert scraper.tsdb.stats()["dropped"]["history"] == 3
        with pytest.raises(ValueError, match="history_max_lines"):
            FleetScraper(run, history_max_lines=0)


def _write_json(path, doc) -> str:
    with open(path, "w") as f:
        json.dump(doc, f)
    return str(path)


# ---------------------------------------------------------------------------
# rollout burn-rate gating (`launch rollout --slo`)
# ---------------------------------------------------------------------------

class _FakeFleet:
    """A /fleet.json stub whose alert list the test mutates live."""

    def __init__(self):
        self.alerts: list[dict] = []
        self.srv = MetricsServer(registry=MetricsRegistry(),
                                 extra_json={"/fleet.json": self._doc})

    def _doc(self):
        return {"updated": time.time(), "ranks": [], "alerts": self.alerts}

    def __enter__(self):
        self.srv.start()
        return self

    def __exit__(self, *exc):
        self.srv.stop()

    @property
    def url(self):
        return f"http://{self.srv.host}:{self.srv.port}"


def _burn_alert(slo: str, window: str, firing: bool, **labels) -> dict:
    return {"name": "distlr_alert_slo_burn",
            "labels": {"slo": slo, "window": window, **labels},
            "firing": firing, "value": 9.0, "threshold": 1.0}


class TestRolloutSLOGate:
    def test_scope_slo_filters_to_one_objective(self):
        from distlr_tpu.serve.rollout import fleet_alert_poller

        with _FakeFleet() as fleet:
            fleet.alerts = [
                _burn_alert("avail", "fast", True, candidate="v2"),
                _burn_alert("other", "fast", True, candidate="v2"),
                {"name": "distlr_alert_score_drift", "labels": {},
                 "firing": True, "value": 1.0, "threshold": 0.25},
            ]
            poll = fleet_alert_poller(fleet.url, scope_slo="avail")
            assert poll() == [
                "distlr_alert_slo_burn{candidate=v2,slo=avail,"
                "window=fast}"]
            # composed with candidate scoping: an unattributed burn
            # alert for the right SLO is still not the candidate's fault
            fleet.alerts = [_burn_alert("avail", "fast", True)]
            both = fleet_alert_poller(fleet.url, scope_model="v2",
                                      scope_slo="avail")
            assert both() == []
            fleet.alerts = [_burn_alert("avail", "fast", True,
                                        candidate="v2")]
            assert len(both()) == 1

    def test_unreachable_always_gates(self):
        from distlr_tpu.serve.rollout import fleet_alert_poller

        poll = fleet_alert_poller("http://127.0.0.1:1", scope_slo="avail",
                                  timeout_s=0.3)
        assert poll() == ["rollout_fleet_unreachable"]

    def test_ramp_rolls_back_on_fast_burn(self, tmp_path):
        """The satellite-2 contract end to end: a live two-version
        router mid-ramp, gated by `--slo`-scoped burn alerts — the fast
        window firing rolls the split back and clears the candidate."""
        from distlr_tpu.serve import ScoringEngine, ScoringRouter, \
            ScoringServer
        from distlr_tpu.serve.rollout import (
            RolloutController,
            RouterAdmin,
            fleet_alert_poller,
        )
        from distlr_tpu.serve.server import score_lines_over_tcp

        def _server(seed):
            from distlr_tpu.config import Config

            cfg = Config(num_feature_dim=8, model="sparse_lr", l2_c=0.0)
            eng = ScoringEngine(cfg)
            eng.set_weights(np.full(8, float(seed), np.float32))
            return ScoringServer(eng).start()

        s1, s2 = _server(0), _server(1)
        router = ScoringRouter(
            {"v1": [f"{s1.host}:{s1.port}"],
             "v2": [f"{s2.host}:{s2.port}"]}).start()
        try:
            with _FakeFleet() as fleet:
                # an unrelated firing alert must NOT break the ramp
                fleet.alerts = [
                    {"name": "distlr_alert_score_drift", "labels": {},
                     "firing": True, "value": 1.0, "threshold": 0.25},
                    _burn_alert("avail", "fast", False, candidate="v2"),
                ]
                timer = threading.Timer(0.6, lambda: fleet.alerts.append(
                    _burn_alert("avail", "fast", True, candidate="v2")))
                timer.start()
                ctrl = RolloutController(
                    RouterAdmin(router.host, router.port), "v1", "v2",
                    [(0.25, 30.0), (1.0, 30.0)],
                    alert_poll=fleet_alert_poller(
                        fleet.url, scope_model="v2", scope_slo="avail"),
                    poll_interval_s=0.05, journal_dir=str(tmp_path))
                out = ctrl.run()
                timer.cancel()
            assert out["outcome"] == "rolled_back", out
            assert out["alerts"] == [
                "distlr_alert_slo_burn{candidate=v2,slo=avail,"
                "window=fast}"]
            doc = json.loads(score_lines_over_tcp(
                router.host, router.port, ["MODELS"])[0])
            assert doc["splits"] == {}  # candidate traffic cleared
        finally:
            router.stop()
            s1.stop()
            s2.stop()


# ---------------------------------------------------------------------------
# `launch fleet-query` CLI
# ---------------------------------------------------------------------------

class TestFleetQueryCLI:
    def _run(self, *argv, timeout=60):
        return subprocess.run(
            [sys.executable, "-m", "distlr_tpu.launch", "fleet-query",
             *argv], capture_output=True, text=True, timeout=timeout,
            cwd=REPO)

    def test_value_nodata_and_bad_query_exit_codes(self, tmp_path):
        run = str(tmp_path)
        scraper = FleetScraper(run, thresholds=_quiet_thresholds())
        _write_route_snapshot(run, 100, 0)
        scraper.scrape_once()
        time.sleep(0.15)
        _write_route_snapshot(run, 250, 0)
        scraper.scrape_once()
        with MetricsServer(registry=scraper,
                           extra_query={"/query":
                                        scraper.query_endpoint}) as srv:
            url = f"http://{srv.host}:{srv.port}"
            r = self._run("increase(route_requests)", "--fleet", url,
                          "--window", "120")
            assert r.returncode == 0, r.stderr[-2000:]
            doc = json.loads(r.stdout)
            assert doc["value"] == pytest.approx(150.0)
            # no data in the window: exit 1, value null
            r = self._run("rate(ghost_series)", "--fleet", url)
            assert r.returncode == 1
            assert json.loads(r.stdout)["value"] is None
            # bad expression: the endpoint's 400 surfaces as exit 2
            r = self._run("rate(", "--fleet", url)
            assert r.returncode == 2
            assert "bad query syntax" in r.stderr

    def test_no_source_and_unreachable_exit_2(self, tmp_path):
        r = self._run("rate(x)")
        assert r.returncode == 2 and "--fleet" in r.stderr
        r = self._run("rate(x)", "--fleet", "http://127.0.0.1:1",
                      "--timeout", "0.3")
        assert r.returncode == 2
        r = self._run("rate(x)", "--obs-run-dir", str(tmp_path))
        assert r.returncode == 2 and "obs-agg" in r.stderr


# ---------------------------------------------------------------------------
# acceptance: budgets consume, fast fires before slow, one dump, and
# fleet-query agrees with the router's own STATS
# ---------------------------------------------------------------------------

class TestSLOAcceptance:
    def test_burn_fires_fast_first_with_one_dump_and_burst(
            self, tmp_path):
        """The ISSUE 17 acceptance e2e: a real serving tier (engine +
        router over TCP, its registry scraped through a real fleet
        endpoint) under a clean-then-saturated loadgen run with an SLO
        file — the error budget consumes monotonically through the
        chaos leg, the fast burn window fires while the slow one stays
        quiet, the burn EDGE triggers exactly one flight-recorder dump
        and one profiler burst, and `launch fleet-query` reproduces the
        route p99 the router's STATS reports."""
        from distlr_tpu.config import Config
        from distlr_tpu.obs import dtrace, profile
        from distlr_tpu.obs.registry import get_registry
        from distlr_tpu.serve import ScoringEngine, ScoringRouter, \
            ScoringServer
        from distlr_tpu.serve.rollout import RouterAdmin
        from distlr_tpu.serve.server import score_lines_over_tcp

        run = str(tmp_path)
        d_dim = 64
        cfg = Config(num_feature_dim=d_dim, model="sparse_lr", l2_c=0.0)
        eng = ScoringEngine(cfg)
        eng.set_weights(np.random.default_rng(3).standard_normal(
            d_dim).astype(np.float32))
        # the ~20ms microbatch floor + max_inflight=1 make the chaos
        # leg's offered load saturate and shed — the injected fault
        server = ScoringServer(eng, max_wait_ms=20.0).start()
        router = ScoringRouter([f"{server.host}:{server.port}"],
                               max_inflight=1).start()
        metrics_srv = MetricsServer(registry=get_registry()).start()
        slo_doc = {
            # short windows stay WELL above the ~0.35s scrape cadence
            # (incl. a flight-dump/burst stall): a short window that an
            # unlucky scrape gap can empty reads no-data -> not-firing
            # and the alert flaps, re-edging a second dump
            "burn_windows": [
                {"name": "fast", "short_s": 3.0, "long_s": 6.0,
                 "factor": 6.0},
                # the slow pair's factor sits above what the 12s chaos
                # leg can accumulate (bad:total can't reach 0.8 with
                # ~7s of pre-chaos good ticks in every window): "slow
                # stays quiet" holds for the WHOLE run, so the fast
                # pair's edge is the run's only alert edge — the
                # exactly-one-dump assertion tests incident
                # unification, not scrape-loop timing luck
                {"name": "slow", "short_s": 6.0, "long_s": 30.0,
                 "factor": 8.0},
            ],
            "slos": [{
                "name": "route_availability", "objective": 0.9,
                "window_s": 20.0,
                "sli": {"kind": "threshold",
                        "expr": "increase(route_shed) / "
                                "increase(route_requests)",
                        "op": "<=", "bound": 0.1},
            }],
        }
        slos, rules = load_slo_file(_write_json(
            tmp_path / "slo.json", slo_doc))
        scraper = FleetScraper(run, thresholds=_quiet_thresholds(),
                               slo_spec=slos, slo_rules=rules)
        agg_srv = MetricsServer(
            registry=scraper,
            extra_json={"/fleet.json": scraper.fleet_json},
            extra_query={"/query": scraper.query_endpoint}).start()
        try:
            write_endpoint(run, "route", 0, metrics_srv.host,
                           metrics_srv.port)
            warm = json.dumps({"rows": ["1:1 2:1"]})
            score_lines_over_tcp(server.host, server.port, [warm])
            router_addr = f"{router.host}:{router.port}"
            score_lines_over_tcp(router.host, router.port, [warm])

            # baseline scrapes BEFORE arming the recorders: any alert
            # pre-polluted global-registry state can fire establishes
            # its steady firing state here, so the only NEW edge left
            # in the watched window is the burn alert's
            scraper.scrape_once()
            time.sleep(0.1)
            scraper.scrape_once()
            dtrace.reset_for_tests()
            dtrace.configure(run, "route", 0, sample=0.0)
            prof = profile.SamplingProfiler(run, "route", 0, hz=15.0,
                                           burst_s=1.0).start()
            flight_dir = os.path.join(run, "flightrec")

            def dumps():
                return [n for n in os.listdir(flight_dir)
                        if n.startswith("route-0-")] \
                    if os.path.isdir(flight_dir) else []

            def bursts():
                return get_registry().get(
                    "distlr_prof_bursts_total").value

            dumps0, bursts0 = len(dumps()), bursts()

            legs = {"phase": "clean"}

            def _load():
                # ONE sequential clean-leg client: it can never exceed
                # the router's max_inflight=1, so clean-leg sheds are
                # impossible by construction (an open-loop worker pool
                # can burst 2 concurrent requests past admission and
                # fake a "burn" out of a 3-request denominator)
                run_load(router_addr, base_qps=6.0, peak_qps=6.0,
                         period_s=5.0, duration_s=5.0, dim=d_dim,
                         seed=7, workers=1)
                legs["phase"] = "chaos"
                legs["summary"] = run_load(
                    router_addr, base_qps=150.0, peak_qps=150.0,
                    period_s=12.0, duration_s=12.0, dim=d_dim, seed=8)
                legs["phase"] = "done"

            loader = threading.Thread(target=_load, daemon=True)
            loader.start()

            samples: list[dict] = []
            fast_fired_at = None
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                scraper.scrape_once()
                fleet = scraper.fleet_json()
                (s,) = fleet["slo"]
                samples.append({"phase": legs["phase"],
                                "budget": s["budget_remaining"],
                                "fast": s["burn"]["fast"]["firing"],
                                "slow": s["burn"]["slow"]["firing"]})
                if s["burn"]["fast"]["firing"] and fast_fired_at is None:
                    fast_fired_at = len(samples) - 1
                if fast_fired_at is not None:
                    break  # the edge is banked; stop driving scrapes
                if legs["phase"] == "done":
                    break
                time.sleep(0.35)
            loader.join(timeout=60)

            # the clean leg never false-positives: no burn window fires
            # and the budget reads untouched once traffic flows
            clean = [x for x in samples if x["phase"] == "clean"]
            assert clean, samples
            assert not any(x["fast"] or x["slow"] for x in clean), clean
            assert any(x["budget"] == pytest.approx(1.0)
                       for x in clean), clean

            # the chaos leg fired the FAST pair while slow stayed quiet
            assert fast_fired_at is not None, samples
            assert samples[fast_fired_at]["phase"] == "chaos", samples
            assert not samples[fast_fired_at]["slow"], samples
            assert legs["summary"]["shed"] > 0, legs

            # the budget consumed monotonically through the chaos leg
            chaos_budgets = [x["budget"] for x in samples
                             if x["phase"] == "chaos"
                             and x["budget"] is not None]
            assert len(chaos_budgets) >= 3, samples
            for a, b in zip(chaos_budgets, chaos_budgets[1:]):
                assert b <= a + 1e-9, chaos_budgets
            assert chaos_budgets[-1] < chaos_budgets[0] - 0.1

            # exactly ONE flight-recorder dump + profiler burst landed,
            # on the burn alert's edge
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and (
                    len(dumps()) - dumps0 < 1 or bursts() - bursts0 < 1):
                time.sleep(0.2)
            assert len(dumps()) - dumps0 == 1, dumps()
            assert bursts() - bursts0 == 1
            with open(os.path.join(flight_dir, dumps()[-1])) as f:
                assert "distlr_alert_slo_burn" in json.load(f)["reason"]
            trig = os.path.join(flight_dir, "TRIGGER.json")
            with open(trig) as f:
                assert "distlr_alert_slo_burn" in json.load(f)["alert"]

            # `launch fleet-query` reproduces the route p99 the
            # router's own STATS reports (same histogram ladder; the
            # tsdb answers from windowed bucket deltas)
            stats = json.loads(RouterAdmin(router.host,
                                           router.port).send("STATS"))
            r = subprocess.run(
                [sys.executable, "-m", "distlr_tpu.launch",
                 "fleet-query",
                 "histogram_quantile(0.99, distlr_route_request_seconds)",
                 "--fleet", f"http://{agg_srv.host}:{agg_srv.port}",
                 "--window", "120"],
                capture_output=True, text=True, timeout=60, cwd=REPO)
            assert r.returncode == 0, r.stderr[-2000:]
            q99_ms = json.loads(r.stdout)["value"] * 1e3
            p99_ms = stats["p99_ms"]
            assert q99_ms > 0 and p99_ms > 0
            assert abs(q99_ms - p99_ms) <= 0.6 * max(q99_ms, p99_ms) + 5.0, \
                (q99_ms, p99_ms)
        finally:
            try:
                prof.stop()
            except UnboundLocalError:
                pass
            from distlr_tpu.obs import dtrace as _dt
            _dt.reset_for_tests()
            agg_srv.stop()
            metrics_srv.stop()
            router.stop()
            server.stop()
