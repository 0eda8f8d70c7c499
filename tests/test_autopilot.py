"""Fleet autopilot (ISSUE 16): the closed-loop scaling daemon.

The tentpole contract under test:

* :class:`PolicyEngine` — the pure, clock-injected decision core:
  band triggers per actuator (up AND down), hysteresis (no action
  until N CONSECUTIVE breach ticks), per-actuator cooldowns,
  one-action-per-tick arbitration in ``ps`` -> ``engine`` -> ``worker``
  priority, bound clamping, fail-safe holds (unreachable aggregator,
  mid-migration PS group, unknown counts), rollback-on-alert (undo the
  youngest action exactly once while it is young enough to blame), and
  the determinism pin — the same input sequence yields byte-identical
  journal lines;
* :class:`AutopilotDaemon` — sensors to decisions: windowed rates from
  successive fleet polls (seeded from ``history.jsonl``), fetch /
  alert-poller failures degrading to holds not actions, the decision
  journal, and the ``distlr_autopilot_*`` metrics;
* the real actuator wires — ps-ctl ``RESIZE n wait=0`` + STATUS
  polling (the non-blocking resize satellite), router
  ADDREPLICA/DELREPLICA promote/demote over a standby pool, worker
  subprocess spawn/retire;
* the acceptance e2e: a real router + standby engine replicas under
  ``distlr_tpu/serve/loadgen.py``'s diurnal cycle — the autopilot breathes
  capacity up into the peak and back down, zero failed accepted
  requests, every action journaled, and fewer replica-seconds burned
  than static-peak provisioning.
"""

from __future__ import annotations

import http.server
import json
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from distlr_tpu.autopilot import (
    ACTUATORS,
    Action,
    ActuatorError,
    Actuators,
    AutopilotDaemon,
    EngineActuator,
    FleetSignals,
    PSActuator,
    PolicyConfig,
    PolicyEngine,
    WorkerActuator,
    fleet_fetcher,
)
from distlr_tpu.autopilot.daemon import _RateWindow
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.ps import (
    KVWorker,
    MembershipCoordinator,
    MembershipServer,
    ServerGroup,
)
from distlr_tpu.serve.loadgen import make_payloads, qps_at, run_load, schedule

D = 32

#: a worker command that parks until retired (SIGTERM's default
#: disposition kills it promptly — what `launch online` does explicitly)
SLEEPER = f"{sys.executable} -c 'import time; time.sleep(120)' {{worker_id}}"


def _counter_total(name: str) -> float:
    fam = get_registry().snapshot().get(name)
    if not fam:
        return 0.0
    return sum(s["value"] for s in fam.get("series", []))


def _gauge(name: str, **labels) -> float | None:
    fam = get_registry().snapshot().get(name)
    for s in (fam or {}).get("series", []):
        if all(s.get("labels", {}).get(k) == v for k, v in labels.items()):
            return s["value"]
    return None


def sig(**kw) -> FleetSignals:
    return FleetSignals(**kw)


def cur(ps=2, engine=2, worker=2, ps_busy=False) -> dict:
    return {"ps": ps, "engine": engine, "worker": worker,
            "ps_busy": ps_busy}


class _Clock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t


class _ScriptActuators:
    """Quacks like :class:`Actuators`; applies mutate the counts so the
    policy's next tick sees the fleet it just changed."""

    def __init__(self, **counts):
        self.counts = {"ps": None, "engine": None, "worker": None,
                       "ps_busy": False, **counts}
        self.applied: list[tuple[str, int]] = []
        self.closed = False
        self.fail = False

    def current(self) -> dict:
        return dict(self.counts)

    def apply(self, actuator: str, target: int) -> str:
        if self.fail:
            raise ActuatorError("scripted refusal")
        self.applied.append((actuator, target))
        self.counts[actuator] = target
        return f"set {actuator}={target}"

    def close(self) -> None:
        self.closed = True


# ---------------------------------------------------------------------------
# the pure policy core
# ---------------------------------------------------------------------------

class TestPolicyEngine:
    def test_steady_when_everything_is_in_band(self):
        p = PolicyEngine(PolicyConfig())
        s = sig(push_rate=100.0, shed_rate=0.0, req_rate=50.0,
                shard_lag=2.0, staleness_pushes_p99=10.0)
        for t in range(5):
            d = p.tick(s, cur(), float(t))
            assert d.rule == "steady" and d.action is None

    def test_hysteresis_delays_every_band(self):
        # hysteresis 2: the FIRST breach tick never acts, the second does
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=2))
        breach = sig(shed_rate=10.0)
        assert p.tick(breach, cur(), 0.0).rule == "steady"
        d = p.tick(breach, cur(), 1.0)
        assert d.rule == "engine_up"
        assert d.action == Action("engine", "up", 2, 3)

    def test_breach_counter_resets_on_a_clean_tick(self):
        # an in-band tick resets the consecutive counter: breaching
        # again still needs the full hysteresis
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=2))
        assert p.tick(sig(shard_lag=9.0), cur(), 0.0).rule == "steady"
        assert p.tick(sig(shard_lag=2.0), cur(), 1.0).rule == "steady"
        assert p.tick(sig(shard_lag=9.0), cur(), 2.0).rule == "steady"
        assert p.tick(sig(shard_lag=9.0), cur(), 3.0).rule == "worker_up"

    def test_every_band_fires_in_both_directions(self):
        c = PolicyConfig(hysteresis_ticks=1, cooldown_s=0.0)
        cases = [
            (sig(staleness_pushes_p99=999.0), "ps_up"),
            (sig(push_rate=999.0), "ps_up"),          # 999/2 > 200/server
            (sig(push_rate=1.0), "ps_down"),          # 0.5 < 20/server
            (sig(shed_rate=10.0), "engine_up"),
            (sig(route_p99_ms=10_000.0), "engine_up"),
            (sig(req_rate=1.0, shed_rate=0.0), "engine_down"),
            (sig(shard_lag=100.0), "worker_up"),
            (sig(shard_lag=0.0), "worker_down"),
        ]
        for s, rule in cases:
            d = PolicyEngine(c).tick(s, cur(), 0.0)
            assert d.rule == rule, (s, d.rule)

    def test_no_data_never_fires(self):
        # None signals must not breach in EITHER direction
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=1))
        for t in range(3):
            assert p.tick(sig(), cur(), float(t)).rule == "steady"

    def test_engine_down_requires_zero_sheds(self):
        # a shedding tier is not idle, however low the accepted rate
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=1))
        d = p.tick(sig(req_rate=1.0, shed_rate=0.3), cur(), 0.0)
        assert d.rule == "steady"

    def test_cooldown_holds_then_persistent_breach_fires_immediately(self):
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=1, cooldown_s=10.0))
        breach = sig(shed_rate=10.0)
        assert p.tick(breach, cur(engine=1), 0.0).rule == "engine_up"
        for t in (1.0, 5.0, 9.9):
            d = p.tick(breach, cur(engine=2), t)
            assert d.rule == "steady" and d.holding["engine"]
        # counters accumulated through the hold: fires the moment it clears
        d = p.tick(breach, cur(engine=2), 10.0)
        assert d.rule == "engine_up"
        assert d.action.to_count == 3
        # the journal line shows the cooldown the action itself started
        assert d.holding["engine"]

    def test_arbitration_ps_outranks_engine_outranks_worker(self):
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=1, cooldown_s=100.0))
        everything = sig(staleness_pushes_p99=999.0, shed_rate=10.0,
                         shard_lag=100.0)
        d = p.tick(everything, cur(), 0.0)
        assert d.rule == "ps_up"           # one action per tick, ps first
        # ps now cooling down; the OTHER bands kept arming and the next
        # tick falls through to the engine, then the worker
        d = p.tick(everything, cur(ps=3), 1.0)
        assert d.rule == "engine_up"
        d = p.tick(everything, cur(ps=3, engine=3), 2.0)
        assert d.rule == "worker_up"

    def test_bounds_clamp_to_steady(self):
        c = PolicyConfig(hysteresis_ticks=1, engine_min=1, engine_max=2)
        p = PolicyEngine(c)
        assert p.tick(sig(shed_rate=10.0),
                      cur(engine=2), 0.0).rule == "steady"   # at max
        assert p.tick(sig(req_rate=0.1, shed_rate=0.0),
                      cur(engine=1), 1.0).rule == "steady"   # at min

    def test_ps_busy_and_unknown_counts_hold(self):
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=1))
        d = p.tick(sig(staleness_pushes_p99=999.0),
                   cur(ps_busy=True), 0.0)
        assert d.rule == "steady"          # a migrating group never stacks
        d = p.tick(sig(shed_rate=10.0), cur(engine=None), 1.0)
        assert d.rule == "steady"          # unknown count: hold, don't guess

    def test_unreachable_holds_and_clears_hysteresis(self):
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=2))
        p.tick(sig(shard_lag=100.0), cur(), 0.0)
        d = p.tick(sig(reachable=False), cur(), 1.0)
        assert d.rule == "hold_unreachable" and d.action is None
        # the breach counter was cleared: full hysteresis required again
        assert p.tick(sig(shard_lag=100.0), cur(), 2.0).rule == "steady"
        assert p.tick(sig(shard_lag=100.0), cur(), 3.0).rule == "worker_up"

    def test_synthetic_unreachable_alert_holds_not_rolls_back(self):
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=1))
        assert p.tick(sig(shed_rate=10.0), cur(), 0.0).rule == "engine_up"
        d = p.tick(sig(alerts=("rollout_fleet_unreachable",)),
                   cur(engine=3), 1.0)
        assert d.rule == "hold_unreachable" and d.action is None

    def test_rollback_on_alert_exactly_once_inside_the_window(self):
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=1, cooldown_s=0.0,
                                      rollback_window_s=60.0))
        assert p.tick(sig(shed_rate=10.0),
                      cur(engine=1), 0.0).rule == "engine_up"
        d = p.tick(sig(alerts=("distlr_alert_route_p99{}",)),
                   cur(engine=2), 5.0)
        assert d.rule == "rollback_on_alert"
        assert d.action == Action("engine", "down", 2, 1)
        # the same alert again: already rolled back, just hold
        d = p.tick(sig(alerts=("distlr_alert_route_p99{}",)),
                   cur(engine=1), 6.0)
        assert d.rule == "hold_on_alert" and d.action is None

    def test_alert_outside_the_window_blames_nobody(self):
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=1, cooldown_s=0.0,
                                      rollback_window_s=10.0))
        p.tick(sig(shed_rate=10.0), cur(engine=1), 0.0)
        d = p.tick(sig(alerts=("distlr_alert_x{}",)), cur(engine=2), 50.0)
        assert d.rule == "hold_on_alert" and d.action is None

    def test_blamable_alert_freezes_every_actuator_for_a_cooldown(self):
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=1, cooldown_s=10.0))
        d = p.tick(sig(shed_rate=10.0), cur(), 0.0)
        assert d.rule == "engine_up"
        d = p.tick(sig(alerts=("distlr_alert_x{}",)), cur(engine=3), 1.0)
        assert d.rule == "rollback_on_alert"
        d = p.tick(sig(shed_rate=10.0), cur(), 2.0)
        assert d.rule == "steady"
        assert all(d.holding[a] for a in ACTUATORS)

    def test_unattributed_alert_still_allows_capacity_adds(self):
        # fleetsim slow_burn_slo: the SLO burn alert fires with no
        # recent action to blame.  The pre-fix policy froze every
        # actuator on EVERY alert tick — the engine add that would
        # clear the burn could never happen.  Capacity-only mode lets
        # the up-band fire; the add is not a rollback candidate.
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=1, cooldown_s=10.0))
        alert = ("distlr_alert_slo_burn{}",)
        d = p.tick(sig(alerts=alert, shed_rate=10.0), cur(), 0.0)
        assert d.rule == "engine_up"
        assert d.action.to_doc() == {"actuator": "engine",
                                     "direction": "up", "from": 2, "to": 3}
        d = p.tick(sig(alerts=alert, shed_rate=10.0), cur(engine=3), 1.0)
        assert d.rule == "hold_on_alert"   # never rolls back its own add
        assert d.action is None

    def test_unattributed_alert_suppresses_scale_down(self):
        # an alert with nobody to blame must not be answered by
        # REMOVING capacity, however idle the fleet looks
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=1, cooldown_s=0.0))
        alert = ("distlr_alert_x{}",)
        for t in range(3):
            d = p.tick(sig(alerts=alert, shed_rate=0.0, req_rate=1.0),
                       cur(), float(t))
            assert d.rule == "hold_on_alert"
            assert d.action is None
        # the moment the alert clears, the armed down-counter fires
        d = p.tick(sig(shed_rate=0.0, req_rate=1.0), cur(), 3.0)
        assert d.rule == "engine_down"

    def test_flap_reversal_escalates_the_cooldown(self):
        # fleetsim autopilot_resonance: load between the thresholds of
        # adjacent counts drives up/down/up at the cooldown cadence.
        # Each reversal doubles the next cooldown (2**streak, capped).
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=1, cooldown_s=10.0))
        d = p.tick(sig(shed_rate=10.0), cur(), 0.0)
        assert d.rule == "engine_up"
        assert p._cooldown_until["engine"] == 10.0       # streak 0
        d = p.tick(sig(shed_rate=0.0, req_rate=1.0), cur(engine=3), 10.0)
        assert d.rule == "engine_down"                   # reversal
        assert p._cooldown_until["engine"] == 30.0       # 10 + 10*2
        d = p.tick(sig(shed_rate=10.0), cur(), 30.0)
        assert d.rule == "engine_up"                     # reversal again
        assert p._cooldown_until["engine"] == 70.0       # 30 + 10*4

    def test_same_direction_ramp_never_pays_the_flap_penalty(self):
        p = PolicyEngine(PolicyConfig(hysteresis_ticks=1, cooldown_s=10.0))
        for i, t in enumerate((0.0, 10.0, 20.0)):
            d = p.tick(sig(shed_rate=10.0), cur(engine=2 + i), t)
            assert d.rule == "engine_up"
            assert p._cooldown_until["engine"] == t + 10.0

    def test_journal_schema_and_byte_identical_determinism(self):
        seq = [
            (sig(push_rate=100.0, shed_rate=0.0, req_rate=50.0), cur(), 0.0),
            (sig(shed_rate=10.0), cur(), 1.0),
            (sig(shed_rate=10.0), cur(), 2.0),
            (sig(reachable=False), cur(engine=3), 3.0),
            (sig(alerts=("distlr_alert_x{}",)), cur(engine=3), 4.0),
            (sig(shard_lag=0.25), cur(engine=2), 30.0),
            (sig(shard_lag=0.25), cur(engine=2), 31.0),
        ]

        def journal() -> list[str]:
            p = PolicyEngine(PolicyConfig())
            return [p.tick(s, c, t).to_json() for s, c, t in seq]

        a, b = journal(), journal()
        assert a == b                       # the determinism contract
        docs = [json.loads(line) for line in a]
        for doc in docs:
            assert sorted(doc) == ["action", "holding", "inputs",
                                   "outcome", "rule", "t", "tick"]
            assert sorted(doc["holding"]) == sorted(ACTUATORS)
            assert doc["outcome"] is None   # pure-policy run
        acts = [doc["action"] for doc in docs if doc["action"]]
        assert acts and all(sorted(actn) == ["actuator", "direction",
                                             "from", "to"] for actn in acts)
        # the t=4.0 alert lands inside the rollback window of the
        # t=2.0 engine_up, so it is rolled back, not merely held
        assert [doc["rule"] for doc in docs] == [
            "steady", "steady", "engine_up", "hold_unreachable",
            "rollback_on_alert", "steady", "worker_down"]


# ---------------------------------------------------------------------------
# windowed rates
# ---------------------------------------------------------------------------

class TestRateWindow:
    def test_rate_is_delta_over_dt(self):
        w = _RateWindow(10.0)
        w.push(0.0, {"pushes": 0.0})
        assert w.rate("pushes") is None     # one observation is no rate
        w.push(2.0, {"pushes": 100.0})
        assert w.rate("pushes") == 50.0
        assert w.rate("missing") is None

    def test_counter_reset_clamps_to_zero(self):
        w = _RateWindow(10.0)
        w.push(0.0, {"pushes": 1000.0})
        w.push(1.0, {"pushes": 0.0})        # a restarted process
        assert w.rate("pushes") == 0.0

    def test_old_observations_age_out(self):
        w = _RateWindow(5.0)
        w.push(0.0, {"pushes": 0.0})
        w.push(1.0, {"pushes": 10.0})
        w.push(20.0, {"pushes": 100.0})
        # the t=0 sample is far outside the horizon once t=1 is >= 5s old
        assert w.rate("pushes") == pytest.approx((100.0 - 10.0) / 19.0)


# ---------------------------------------------------------------------------
# the daemon: sensors -> policy -> actuators, fail-safe by construction
# ---------------------------------------------------------------------------

class TestDaemon:
    def test_scales_on_windowed_shed_rate(self):
        calls = [0]

        def fetch():
            calls[0] += 1
            return {"ranks": [{"role": "route", "rank": 0,
                               "route_shed": 50.0 * calls[0],
                               "route_requests": 100.0 * calls[0]}]}

        clock = _Clock()
        acts = _ScriptActuators(engine=1)
        d = AutopilotDaemon(PolicyEngine(PolicyConfig(hysteresis_ticks=2)),
                            acts, fetch=fetch, clock=clock)
        rules = []
        for _ in range(3):
            rules.append(d.tick_once().rule)
            clock.t += 1.0
        # tick 1 has no window yet; ticks 2 and 3 see shed_rate=50/s
        assert rules == ["steady", "steady", "engine_up"]
        assert acts.applied == [("engine", 2)]
        assert d.status()["actions"] == 1 and d.status()["errors"] == 0

    def test_unreachable_fetch_holds_and_exports_minus_one(self):
        def fetch():
            raise OSError("aggregator down")

        acts = _ScriptActuators(engine=2)
        d = AutopilotDaemon(PolicyEngine(), acts, fetch=fetch,
                            clock=_Clock())
        decision = d.tick_once()
        assert decision.rule == "hold_unreachable"
        assert acts.applied == []
        # engine count IS known (the actuator answered): exported as-is;
        # the unmanaged ps/worker actuators export the -1 sentinel
        assert _gauge("distlr_autopilot_current", actuator="engine") == 2.0
        assert _gauge("distlr_autopilot_current", actuator="ps") == -1.0

    def test_malformed_fleet_doc_holds(self):
        d = AutopilotDaemon(
            PolicyEngine(), _ScriptActuators(engine=2),
            fetch=lambda: (_ for _ in ()).throw(ValueError("bad json")),
            clock=_Clock())
        assert d.tick_once().rule == "hold_unreachable"

    def test_alert_poller_crash_degrades_to_hold(self):
        def poll():
            raise RuntimeError("poller bug")

        d = AutopilotDaemon(PolicyEngine(), _ScriptActuators(engine=2),
                            fetch=lambda: {"ranks": []}, alert_poll=poll,
                            clock=_Clock())
        decision = d.tick_once()
        assert decision.rule == "hold_on_alert"
        assert decision.inputs["alerts"] == [
            "autopilot_alert_poll_failed:RuntimeError"]

    def test_actuator_failure_is_journaled_not_fatal(self, tmp_path):
        acts = _ScriptActuators(worker=1)
        acts.fail = True
        clock = _Clock()
        errors0 = _counter_total("distlr_autopilot_errors_total")
        d = AutopilotDaemon(
            PolicyEngine(PolicyConfig(hysteresis_ticks=1)), acts,
            fetch=lambda: {"ranks": [{"shard_lag": 100.0}]},
            journal_dir=str(tmp_path), clock=clock)
        decision = d.tick_once()
        assert decision.rule == "worker_up"
        assert decision.outcome.startswith("error:")
        assert d.status()["errors"] == 1
        assert _counter_total("distlr_autopilot_errors_total") == errors0 + 1
        # and the failure is on the journal line, not swallowed
        doc = AutopilotDaemon.read_journal(
            str(tmp_path / "autopilot" / "decisions.jsonl"))[-1]
        assert doc["outcome"].startswith("error:")

    def test_journal_carries_every_tick_and_action(self, tmp_path):
        acts = _ScriptActuators(worker=1)
        clock = _Clock()
        d = AutopilotDaemon(
            PolicyEngine(PolicyConfig(hysteresis_ticks=2)), acts,
            fetch=lambda: {"ranks": [{"shard_lag": 100.0}]},
            journal_dir=str(tmp_path), clock=clock)
        for _ in range(3):
            d.tick_once()
            clock.t += 1.0
        path = tmp_path / "autopilot" / "decisions.jsonl"
        first = json.loads(path.read_text().splitlines()[0])
        assert first == {"schema": 1, "kind": "autopilot_decisions"}
        docs = AutopilotDaemon.read_journal(str(path))
        assert [doc["rule"] for doc in docs] == [
            "steady", "worker_up", "steady"]
        acted = [doc for doc in docs if doc["action"]]
        assert len(acted) == d.status()["actions"] == 1
        assert acted[0]["outcome"] == "set worker=2"

    def test_read_journal_rejects_headerless_and_unknown_schema(
            self, tmp_path):
        # the ISSUE-19 pin: a journal written by a pre-header build (or
        # a future schema) must fail LOUDLY, not misparse
        headerless = tmp_path / "old.jsonl"
        headerless.write_text(json.dumps({"rule": "steady"}) + "\n")
        with pytest.raises(ValueError, match="autopilot_decisions"):
            AutopilotDaemon.read_journal(str(headerless))
        future = tmp_path / "future.jsonl"
        future.write_text(json.dumps(
            {"schema": 99, "kind": "autopilot_decisions"}) + "\n")
        with pytest.raises(ValueError, match="schema 99"):
            AutopilotDaemon.read_journal(str(future))
        # a torn tail (live daemon mid-append) only truncates
        good = tmp_path / "good.jsonl"
        good.write_text(
            json.dumps({"schema": 1, "kind": "autopilot_decisions"}) + "\n"
            + json.dumps({"rule": "steady", "action": None}) + "\n"
            + '{"rule": "engi')
        assert [d["rule"] for d in
                AutopilotDaemon.read_journal(str(good))] == ["steady"]

    def test_seed_rates_from_history_primes_the_first_tick(self, tmp_path):
        with open(tmp_path / "history.jsonl", "w") as f:
            f.write(json.dumps({"t": 100.0,
                                "ranks": [{"pushes": 0.0}]}) + "\n")
            f.write("not json\n")
            f.write(json.dumps({"t": 105.0,
                                "ranks": [{"pushes": 500.0}]}) + "\n")
        clock = _Clock(50.0)
        d = AutopilotDaemon(PolicyEngine(), _ScriptActuators(),
                            fetch=lambda: {"ranks": [{"pushes": 600.0}]},
                            rate_window_s=10.0, clock=clock)
        assert d.seed_rates_from_history(str(tmp_path)) == 2
        clock.t = 51.0
        decision = d.tick_once()
        # (600 - 0) pushes over the rebased 6s span: live from tick one
        assert decision.inputs["push_rate"] == 100.0

    def test_seed_rates_missing_history_is_zero_not_fatal(self, tmp_path):
        d = AutopilotDaemon(PolicyEngine(), _ScriptActuators(),
                            fetch=lambda: {"ranks": []}, clock=_Clock())
        assert d.seed_rates_from_history(str(tmp_path)) == 0

    def test_start_stop_joins_and_closes_actuators(self):
        acts = _ScriptActuators()
        d = AutopilotDaemon(PolicyEngine(), acts,
                            fetch=lambda: {"ranks": []}, interval_s=0.01)
        with d:
            deadline = time.monotonic() + 10.0
            while d.status()["ticks"] < 3 and time.monotonic() < deadline:
                time.sleep(0.01)
        assert d.status()["ticks"] >= 3
        assert d._thread is None and acts.closed

    def test_run_forever_survives_a_crashing_tick(self):
        calls = [0]

        def fetch():
            calls[0] += 1
            if calls[0] == 1:
                raise KeyError("not an OSError: a genuine bug")
            return {"ranks": []}

        d = AutopilotDaemon(PolicyEngine(), _ScriptActuators(),
                            fetch=fetch, interval_s=0.01)
        with d:
            deadline = time.monotonic() + 10.0
            while d.status()["ticks"] < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
        assert d.status()["ticks"] >= 2   # the loop outlived the bug

    def test_fleet_fetcher_gets_fleet_json(self):
        doc = {"ranks": [{"role": "route", "rank": 0}]}

        class H(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                body = json.dumps(doc).encode()
                self.send_response(200 if self.path == "/fleet.json"
                                   else 404)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        srv = http.server.HTTPServer(("127.0.0.1", 0), H)
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        try:
            fetch = fleet_fetcher(f"http://127.0.0.1:{srv.server_port}")
            assert fetch() == doc
        finally:
            srv.shutdown()
            t.join()
        with pytest.raises(OSError):
            fleet_fetcher("http://127.0.0.1:1", timeout_s=0.3)()


# ---------------------------------------------------------------------------
# real actuator wires
# ---------------------------------------------------------------------------

class TestPSActuatorWire:
    def test_resize_nowait_accepts_then_status_polls_to_active(self):
        with ServerGroup(2, 1, D, sync=False) as g:
            coord = MembershipCoordinator(g)
            with KVWorker(g.hosts, D, sync_group=False) as s:
                s.push_init(np.arange(D, dtype=np.float32))
            with MembershipServer(coord) as ctl:
                act = PSActuator(f"127.0.0.1:{ctl.port}")
                assert act.current() == (2, False)
                out = act.scale(4)          # RESIZE 4 wait=0: returns NOW
                assert out.startswith("resize accepted")
                deadline = time.monotonic() + 60.0
                while time.monotonic() < deadline:
                    n, busy = act.current()
                    if n == 4 and not busy:
                        break
                    time.sleep(0.05)
                assert act.current() == (4, False)
                assert g.num_servers == 4
                # the reshard preserved every weight
                with KVWorker(g.hosts, D, sync_group=False) as kv:
                    np.testing.assert_array_equal(
                        kv.pull(), np.arange(D, dtype=np.float32))
                # resizing to the current size is an accepted noop
                assert act.scale(4).startswith("resize accepted")
                with pytest.raises(ActuatorError, match="refused"):
                    act.scale(0)

    def test_unreachable_ctl_reads_as_busy_hold(self):
        act = PSActuator("127.0.0.1:1", timeout_s=0.3)
        assert act.current() == (None, True)
        with pytest.raises(ActuatorError):
            act.scale(2)

    def test_ps_ctl_cli_no_wait_flag(self):
        # satellite 3 at the CLI layer: `launch ps-ctl resize N --no-wait`
        with ServerGroup(2, 1, D, sync=False) as g:
            coord = MembershipCoordinator(g)
            with MembershipServer(coord) as ctl:
                addr = f"127.0.0.1:{ctl.port}"
                r = subprocess.run(
                    [sys.executable, "-m", "distlr_tpu.launch", "ps-ctl",
                     "--ctl", addr, "resize", "4", "--no-wait"],
                    capture_output=True, text=True, timeout=120)
                assert r.returncode == 0, r.stderr[-2000:]
                doc = json.loads(r.stdout.split("PSCTL ", 1)[1])
                assert doc["ok"] and doc["accepted"] and doc["target"] == 4
                from distlr_tpu.ps.membership import ctl_request

                deadline = time.monotonic() + 60.0
                while time.monotonic() < deadline:
                    st = ctl_request(addr, "STATUS")
                    if st["status"] == "active" and st["num_servers"] == 4:
                        break
                    time.sleep(0.05)
                assert g.num_servers == 4


class TestEngineActuatorWire:
    def _tier(self, n):
        from distlr_tpu.config import Config
        from distlr_tpu.serve import (
            ScoringEngine,
            ScoringRouter,
            ScoringServer,
        )

        cfg = Config(num_feature_dim=8, model="sparse_lr", l2_c=0.0)
        servers = []
        for _ in range(n):
            eng = ScoringEngine(cfg)
            eng.set_weights(np.zeros(8, np.float32))
            servers.append(ScoringServer(eng).start())
        addrs = [f"{s.host}:{s.port}" for s in servers]
        router = ScoringRouter([addrs[0]], max_inflight=4).start()
        return servers, addrs, router

    def test_promote_demote_over_the_standby_pool(self):
        servers, addrs, router = self._tier(3)
        try:
            act = EngineActuator(f"{router.host}:{router.port}", addrs)
            assert act.current() == 1
            assert act.scale(2) == f"added {addrs[1]}"
            assert act.scale(3) == f"added {addrs[2]}"
            assert act.current() == 3
            with pytest.raises(ActuatorError, match="no standby"):
                act.scale(4)                # pool exhausted
            # demote retires the YOUNGEST pooled replica first
            assert act.scale(2) == f"removed {addrs[2]}"
            assert act.scale(1) == f"removed {addrs[1]}"
            assert act.current() == 1
            assert act.scale(1) == "noop"
        finally:
            router.stop()
            for s in servers:
                s.stop()

    def test_bad_router_address_and_unknown_model(self):
        with pytest.raises(ValueError, match="host:port"):
            EngineActuator("nonsense", [])
        servers, addrs, router = self._tier(1)
        try:
            ghost = EngineActuator(f"{router.host}:{router.port}", addrs,
                                   model="ghost")
            assert ghost.current() is None  # unknown count: policy holds
            with pytest.raises(ActuatorError):
                ghost.scale(2)
        finally:
            router.stop()
            for s in servers:
                s.stop()


class TestWorkerActuatorWire:
    def test_template_requires_worker_id_placeholder(self):
        with pytest.raises(ValueError, match="worker_id"):
            WorkerActuator(f"{sys.executable} -c pass")

    def test_spawn_retire_and_stop_all(self):
        act = WorkerActuator(SLEEPER, term_timeout_s=15.0)
        try:
            assert act.current() == 0
            assert act.scale(1).startswith("spawned worker 0")
            assert act.scale(2).startswith("spawned worker 1")
            assert act.current() == 2
            out = act.scale(1)              # SIGTERM retires the youngest
            assert out.startswith("retired worker 1")
            assert act.current() == 1
            # ids are never reused (the .claim protocol keys on them)
            assert act.scale(2).startswith("spawned worker 2")
        finally:
            act.stop_all()
        assert act.current() == 0

    def test_self_exited_worker_is_reaped(self):
        act = WorkerActuator(
            f"{sys.executable} -c 'pass' {{worker_id}}")
        act.scale(1)
        act.procs[0][1].wait(timeout=60)
        assert act.current() == 0           # reaped, not counted as live

    def test_spawn_failure_raises_actuator_error(self):
        act = WorkerActuator("/nonexistent-worker-binary {worker_id}")
        with pytest.raises(ActuatorError, match="spawn"):
            act.scale(1)
        assert act.current() == 0


# ---------------------------------------------------------------------------
# loadgen (the open-loop diurnal driver the acceptance + bench ride)
# ---------------------------------------------------------------------------

class TestLoadgen:
    def test_schedule_is_deterministic_and_tracks_the_curve(self):
        a = schedule(4.0, 10.0, 50.0, 4.0)
        assert a == schedule(4.0, 10.0, 50.0, 4.0)
        assert a == sorted(a) and a[0] >= 0.0 and a[-1] < 4.0
        # one period integrates to ~mean(base, peak) * duration
        assert len(a) == pytest.approx(0.5 * (10 + 50) * 4.0, rel=0.05)
        # more sends in the peak half-period than the valley halves
        mid = [t for t in a if 1.0 <= t < 3.0]
        assert len(mid) > len(a) - len(mid)

    def test_qps_at_endpoints(self):
        assert qps_at(0.0, 5.0, 60.0, 12.0) == pytest.approx(5.0)
        assert qps_at(6.0, 5.0, 60.0, 12.0) == pytest.approx(60.0)
        assert qps_at(12.0, 5.0, 60.0, 12.0) == pytest.approx(5.0)

    def test_payloads_are_seeded_valid_request_lines(self):
        a = make_payloads(8, 64, 4, 2, seed=7)
        assert a == make_payloads(8, 64, 4, 2, seed=7)
        assert a != make_payloads(8, 64, 4, 2, seed=8)
        doc = json.loads(a[0])
        assert len(doc["rows"]) == 2
        col = int(doc["rows"][0].split()[0].split(":")[0])
        assert 1 <= col <= 64               # the 1-based col:val contract


# ---------------------------------------------------------------------------
# acceptance: a real fleet breathes under a real diurnal cycle
# ---------------------------------------------------------------------------

class TestAutopilotAcceptance:
    def test_diurnal_cycle_breathes_up_then_down_and_holds_slo(
            self, tmp_path):
        """The ISSUE 16 acceptance e2e: router + standby engine replicas
        under one loadgen diurnal cycle, a live daemon promoting into
        the peak and demoting on the far side — zero failed accepted
        requests, every action journaled, and strictly fewer
        replica-seconds than static-peak provisioning."""
        from distlr_tpu.config import Config
        from distlr_tpu.serve import (
            ScoringEngine,
            ScoringRouter,
            ScoringServer,
        )
        from distlr_tpu.serve.rollout import RouterAdmin
        from distlr_tpu.serve.server import score_lines_over_tcp

        d_dim, replicas = 64, 2
        base, peak, period = 5.0, 60.0, 12.0
        cfg = Config(num_feature_dim=d_dim, model="sparse_lr", l2_c=0.0)
        w = np.random.default_rng(5).standard_normal(d_dim).astype(
            np.float32)
        servers = []
        for _ in range(replicas):
            eng = ScoringEngine(cfg)
            eng.set_weights(w)
            # the ~20ms microbatch floor makes the diurnal peak saturate
            # max_inflight=1 and shed — the signal the engine band
            # scales on
            servers.append(ScoringServer(eng, max_wait_ms=20.0).start())
        addrs = [f"{s.host}:{s.port}" for s in servers]
        router = ScoringRouter([addrs[0]], max_inflight=1).start()
        try:
            warm = json.dumps({"rows": ["1:1 2:1"]})
            for s in servers:
                score_lines_over_tcp(s.host, s.port, [warm])
            router_addr = f"{router.host}:{router.port}"
            admin = RouterAdmin(router.host, router.port)
            actuator = EngineActuator(router_addr, addrs)

            def fetch():
                st = json.loads(admin.send("STATS"))
                return {"ranks": [{"role": "route", "rank": 0,
                                   "route_requests": st["requests"],
                                   "route_shed": st["shed"],
                                   "route_p99_ms": st["p99_ms"]}]}

            policy = PolicyEngine(PolicyConfig(
                hysteresis_ticks=2, cooldown_s=period / 10.0,
                rollback_window_s=0.0,      # no alert gate in this harness
                engine_min=1, engine_max=replicas,
                shed_rate_high=0.2, req_rate_low=max(1.0, base / 2.0)))
            daemon = AutopilotDaemon(
                policy, Actuators(engine=actuator), fetch=fetch,
                interval_s=max(0.2, period / 60.0),
                rate_window_s=max(1.0, period / 10.0),
                journal_dir=str(tmp_path))

            rank_s = [0.0]
            last = [time.monotonic(), 1]

            def sample(count):
                now = time.monotonic()
                rank_s[0] += last[1] * (now - last[0])
                last[0] = now
                if count is not None:
                    last[1] = count

            actions0 = _counter_total("distlr_autopilot_actions_total")
            t0 = time.monotonic()
            with daemon:
                load = run_load(router_addr, base_qps=base, peak_qps=peak,
                                period_s=period, dim=d_dim, seed=11,
                                on_tick=lambda t, q: sample(
                                    actuator.current()))
                # the tail: let the controller breathe back down
                deadline = time.monotonic() + period / 2.0
                while time.monotonic() < deadline \
                        and (actuator.current() or 1) > 1:
                    sample(actuator.current())
                    time.sleep(daemon.interval_s)
            sample(None)
            elapsed = time.monotonic() - t0
            status = daemon.status()

            # SLO: zero failed accepted requests (sheds are explicit
            # admission control, not failures) and a live request path
            assert load["err"] == 0, load
            assert load["ok"] > 0 and load["shed"] > 0, load
            assert status["errors"] == 0, status

            # the controller breathed: up into the peak, down after it
            docs = AutopilotDaemon.read_journal(
                str(tmp_path / "autopilot" / "decisions.jsonl"))
            acted = [doc for doc in docs if doc["action"]]
            assert status["actions"] >= 2, status
            dirs = {a["action"]["direction"] for a in acted}
            assert dirs == {"up", "down"}, acted
            assert max(a["action"]["to"] for a in acted) == replicas
            assert actuator.current() == 1  # back at the valley size
            # no alert ever latched the controller mid-cycle
            assert not any(doc["rule"] in ("hold_on_alert",
                                           "rollback_on_alert")
                           for doc in docs), docs

            # every action is journaled (with its executed outcome) and
            # counted in the distlr_autopilot_actions_total delta
            assert len(acted) == status["actions"]
            assert all(a["outcome"] and not a["outcome"].startswith(
                "error") for a in acted), acted
            assert _counter_total("distlr_autopilot_actions_total") \
                == actions0 + status["actions"]

            # the headline: fewer replica-seconds than a static
            # peak-sized fleet burning `replicas` for the whole window
            assert rank_s[0] < 0.95 * replicas * elapsed, (
                rank_s[0], replicas * elapsed)
        finally:
            router.stop()
            for s in servers:
                s.stop()

    def test_ps_and_worker_legs_scale_real_endpoints(self, tmp_path):
        """The other two actuator legs through the REAL wires: one
        daemon drives a live elastic PS group (RESIZE wait=0) and real
        worker subprocesses from scripted sensor phases."""
        phase = {"staleness": 999.0, "lag": 10.0}

        def fetch():
            return {"ranks": [{"role": "online", "rank": 0,
                               "staleness_pushes_p99": phase["staleness"],
                               "shard_lag": phase["lag"],
                               "pushes": 0.0}]}

        with ServerGroup(1, 1, D, sync=False) as g:
            coord = MembershipCoordinator(g)
            with KVWorker(g.hosts, D, sync_group=False) as s:
                s.push_init(np.arange(D, dtype=np.float32))
            with MembershipServer(coord) as ctl:
                ps = PSActuator(f"127.0.0.1:{ctl.port}")
                worker = WorkerActuator(SLEEPER, term_timeout_s=15.0)
                clock = _Clock()
                daemon = AutopilotDaemon(
                    PolicyEngine(PolicyConfig(
                        hysteresis_ticks=1, cooldown_s=0.0,
                        ps_min=1, ps_max=2, worker_min=0, worker_max=2,
                        push_rate_low=0.0)),  # rates don't drive this leg
                    Actuators(ps=ps, worker=worker), fetch=fetch,
                    journal_dir=str(tmp_path), clock=clock)
                try:
                    # tick 1: both bands breached — ps wins arbitration
                    # and the REAL non-blocking reshard is accepted
                    assert daemon.tick_once().rule == "ps_up"
                    deadline = time.monotonic() + 60.0
                    while ps.current() != (2, False) \
                            and time.monotonic() < deadline:
                        time.sleep(0.05)
                    assert ps.current() == (2, False)
                    clock.t = 1.0
                    # tick 2: ps is at its bound; the worker leg spawns
                    assert daemon.tick_once().rule == "worker_up"
                    assert worker.current() == 1
                    # the quiet phase: the worker band breathes back down
                    phase.update(staleness=0.0, lag=0.0)
                    clock.t = 2.0
                    assert daemon.tick_once().rule == "worker_down"
                    assert worker.current() == 0
                    # the resize preserved the table across the ranks
                    with KVWorker(g.hosts, D, sync_group=False) as kv:
                        np.testing.assert_array_equal(
                            kv.pull(), np.arange(D, dtype=np.float32))
                    docs = AutopilotDaemon.read_journal(
                        str(tmp_path / "autopilot" / "decisions.jsonl"))
                    assert [doc["rule"] for doc in docs] == [
                        "ps_up", "worker_up", "worker_down"]
                    assert all(not doc["outcome"].startswith("error")
                               for doc in docs)
                finally:
                    worker.stop_all()
