"""schedcheck self-tests (ISSUE 15 tentpole).

Four kinds of coverage, per the acceptance criteria:

* the RUNTIME can catch what it claims: a seeded lock-order-inversion
  fixture deadlocks with the minimal wait-for cycle printed, schedule
  replay is byte-identical (same schedule id -> same failure report,
  twice), and the facade-drift detector fires on a class whose lock
  did not come through ``distlr_tpu.sync``;
* the ``sync`` facade's passthrough is ZERO-overhead-equivalent: the
  swappable names ARE the stdlib objects, and an uninstrumented
  MicroBatcher run behaves byte-identically to the pre-facade code;
* every real-module scenario's fast-tier DFS closes CLEAN in well
  under the 60 s budget, and both historical-race mutants (the PR-6
  joiner check-then-insert, the PR-13 ChaosLink.stop snapshot)
  rediscover as <= 20-step replayable counterexamples;
* the ShadowMirror mid-batch-shed accounting hole schedcheck's first
  run surfaced stays fixed, pinned by a replayed schedule against the
  reverted body.
"""

from __future__ import annotations

import queue as stdlib_queue
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from distlr_tpu import sync
from distlr_tpu.analysis import baseline
from distlr_tpu.analysis.__main__ import main as lint_main
from distlr_tpu.analysis.report import repo_root
from distlr_tpu.analysis.schedcheck import explore, lint, mutants, scenarios
from distlr_tpu.analysis.schedcheck.runtime import (
    InvariantViolation,
    RandomStrategy,
    Strategy,
    parse_schedule_id,
    run_controlled,
)

REPO = repo_root()


# ---------------------------------------------------------------------------
# facade passthrough — zero-overhead equivalence
# ---------------------------------------------------------------------------


class TestSyncFacade:
    def test_passthrough_is_the_stdlib(self):
        """Outside an install every swappable name IS the stdlib
        object — not a wrapper, so passthrough cost is one attribute
        lookup and behavior is definitionally identical."""
        assert sync.Lock is threading.Lock
        assert sync.RLock is threading.RLock
        assert sync.Condition is threading.Condition
        assert sync.Event is threading.Event
        assert sync.Semaphore is threading.Semaphore
        assert sync.BoundedSemaphore is threading.BoundedSemaphore
        assert sync.Thread is threading.Thread
        assert sync.Queue is stdlib_queue.Queue
        assert sync.Empty is stdlib_queue.Empty
        assert sync.Full is stdlib_queue.Full
        assert sync.monotonic is time.monotonic
        assert sync.wall is time.time
        assert sync.sleep is time.sleep
        assert not sync.instrumented()

    def test_install_restores_passthrough_after_a_run(self):
        res = run_controlled("noop", lambda rt: None, Strategy())
        assert res.failure is None
        assert sync.Lock is threading.Lock and not sync.instrumented()

    def test_double_install_refused(self):
        def scn(rt):
            with pytest.raises(RuntimeError, match="already instrumented"):
                sync.install({}, owner=object())
        assert run_controlled("dbl", scn, Strategy()).failure is None

    def test_uninstrumented_batcher_behaves_identically(self):
        """The existing-batcher-test equivalence leg: the facade'd
        MicroBatcher under plain threading produces exactly the
        pre-facade results — real stdlib primitives, real clock, same
        types, same scores, same stats schema."""
        from distlr_tpu.serve.batcher import MicroBatcher

        def score(merged):
            n = merged[0].shape[0]
            return (np.zeros(n, np.int32),
                    merged[0].reshape(n, -1).sum(axis=1).astype(np.float32))

        with MicroBatcher(score, max_batch_size=8, max_wait_ms=2.0) as b:
            assert isinstance(b._cv, threading.Condition)
            assert isinstance(b._thread, threading.Thread)
            futs = [b.submit((np.full((1, 2), v, np.float32),))
                    for v in (1.0, 2.0, 3.0)]
            got = [float(f.result(timeout=5.0)[1][0]) for f in futs]
        assert got == [2.0, 4.0, 6.0]
        assert b.requests == 3 and b.rows == 3


# ---------------------------------------------------------------------------
# runtime: deadlock fixture, replay determinism, drift detector
# ---------------------------------------------------------------------------


def _scn_lock_inversion(rt):
    """Seeded AB/BA lock-order inversion: the deadlock-detector
    fixture."""
    a, b = sync.Lock(), sync.Lock()

    def t_ab():
        with a:
            with b:
                pass

    def t_ba():
        with b:
            with a:
                pass

    t1 = sync.Thread(target=t_ab, name="ab")
    t2 = sync.Thread(target=t_ba, name="ba")
    t1.start()
    t2.start()
    t1.join()
    t2.join()


class TestRuntime:
    def test_deadlock_detector_finds_the_inversion(self):
        res = explore.dfs("inversion", _scn_lock_inversion,
                          preemption_bound=2, max_runs=500)
        assert res.failure is not None
        f = res.failure
        assert f.failure.kind == "deadlock"
        assert "wait-for cycle: ab -> ba -> ab" in f.failure.message \
            or "wait-for cycle: ba -> ab -> ba" in f.failure.message
        # the numbered schedule is part of the report
        assert "schedule (numbered lines" in f.render_failure()

    def test_deadlock_replay_is_byte_identical_twice(self):
        res = explore.dfs("inversion", _scn_lock_inversion,
                          preemption_bound=2, max_runs=500)
        choices = [d.chosen for d in res.failure.decisions]
        r1 = explore.replay("inversion", _scn_lock_inversion, choices)
        r2 = explore.replay("inversion", _scn_lock_inversion, choices)
        assert r1.failure is not None and r2.failure is not None
        assert r1.render_failure() == r2.render_failure()
        assert r1.render_failure() == res.failure.render_failure()

    def test_stale_schedule_reports_divergence(self):
        def scn(rt):
            lock = sync.Lock()
            with lock:
                pass
        res = explore.replay("one-task", scn, [7, 7, 7])
        assert res.failure is not None
        assert res.failure.kind == "divergence"

    def test_virtual_clock_fires_timeouts_deterministically(self):
        out = {}

        def scn(rt):
            ev = sync.Event()
            out["flag"] = ev.wait(5.0)
            out["clock"] = sync.monotonic()

        res = run_controlled("vclock", scn, Strategy())
        assert res.failure is None
        assert out == {"flag": False, "clock": 5.0}
        assert res.clock == 5.0

    def test_random_schedules_are_replayable(self):
        """A fuzz run's schedule id fully determines the run: replay
        by explicit choices matches the RandomStrategy run's trace."""
        s = scenarios.SCENARIOS["joiner_label_race"]
        rnd = run_controlled(s.name, s.fn, RandomStrategy(7),
                             max_steps=s.max_steps)
        assert rnd.failure is None
        rep = explore.replay(s.name, s.fn,
                             [d.chosen for d in rnd.decisions],
                             max_steps=s.max_steps)
        assert rep.failure is None
        assert [st.desc for st in rep.steps] == \
            [st.desc for st in rnd.steps]

    def test_facade_drift_detector_fires(self):
        """A class whose lint-registered lock is NOT an instrumented
        twin fails its scenario loudly — the raw-threading reversion
        guard.  (Outside an install the real joiner's lock is a plain
        stdlib lock, which is exactly the drifted shape.)"""
        import tempfile
        with tempfile.TemporaryDirectory() as wd:
            _spool, joiner = scenarios._mk_joiner(wd)
            with pytest.raises(InvariantViolation,
                               match="not an instrumented twin"):
                scenarios.assert_facade(
                    joiner, "distlr_tpu/feedback/join.py:LabelJoiner")

    def test_schedule_id_roundtrip(self):
        name, choices = parse_schedule_id("joiner_label_race:0.2.1")
        assert name == "joiner_label_race" and choices == [0, 2, 1]


# ---------------------------------------------------------------------------
# scenarios: the fast tier closes clean, fuzz stays clean
# ---------------------------------------------------------------------------


class TestScenarios:
    @pytest.mark.parametrize("name", sorted(scenarios.SCENARIOS))
    def test_fast_dfs_closes_clean(self, name):
        s = scenarios.SCENARIOS[name]
        t0 = time.monotonic()
        findings = lint.check_scenario(s)
        wall = time.monotonic() - t0
        assert findings == [], "\n".join(f.render() for f in findings)
        assert wall < 60.0, (
            f"{name}: fast tier took {wall:.1f}s — the <60s acceptance "
            "bound broke")

    def test_every_scenario_class_is_in_the_lint_registry(self):
        reg = scenarios._lint_registry()
        for s in scenarios.SCENARIOS.values():
            for label in s.classes:
                module, _, cls = label.partition(":")
                assert (module, cls) in reg, (s.name, label)


# ---------------------------------------------------------------------------
# mutants: both historical races rediscover, bounded and replayable
# ---------------------------------------------------------------------------


class TestMutants:
    @pytest.mark.parametrize("name", sorted(mutants.MUTANTS))
    def test_mutant_rediscovers_bounded_and_replayable(self, name):
        with lint.quiet_logs():
            problems = mutants.verify_mutant(name)
        assert problems == [], "\n".join(problems)

    @pytest.mark.parametrize("name", sorted(mutants.MUTANTS))
    def test_counterexample_is_short_and_names_the_bug(self, name):
        m = mutants.MUTANTS[name]
        with lint.quiet_logs():
            cex = m.rediscover()
        assert cex is not None, f"{name} not rediscovered"
        assert len(cex.decisions) <= mutants.MAX_SCHEDULE_STEPS
        assert m.expect_in_message in cex.failure.message
        # the pinned schedule replays byte-identically, twice
        choices = [d.chosen for d in cex.decisions]
        with lint.quiet_logs():
            r1, r2 = m.replay(choices), m.replay(choices)
        assert r1.render_failure() == cex.render_failure()
        assert r2.render_failure() == cex.render_failure()


# ---------------------------------------------------------------------------
# the first-run finding: ShadowMirror mid-batch shed accounting
# ---------------------------------------------------------------------------


def _prefix_shadow_run(self) -> None:
    """ShadowMirror._run BEFORE the schedcheck fix: a stop() landing
    mid-batch abandoned the dequeued mirrors uncounted."""
    from distlr_tpu.serve.tenant import _SHADOW_TOTAL, _ShadowPair
    from distlr_tpu.serve.tenant import extract_scores as _scores
    while not self._stop.is_set():
        with self._lock:
            batch, self._queue = self._queue, []
        if not batch:
            self._wake.wait(0.05)
            self._wake.clear()
            continue
        for tenant, candidate, line, primary in batch:
            if self._stop.is_set():
                return
            try:
                reply = self._exchange(candidate, line)
            except Exception:  # noqa: BLE001
                reply = None
            cand = _scores(reply) if reply is not None else None
            if cand is None:
                self.errors += 1
                _SHADOW_TOTAL.labels(tenant=tenant, candidate=candidate,
                                     outcome="error").inc()
                continue
            self.mirrored += 1
            _SHADOW_TOTAL.labels(tenant=tenant, candidate=candidate,
                                 outcome="scored").inc()
            key = (tenant, candidate)
            with self._lock:
                pair = self._pairs.get(key)
                if pair is None:
                    pair = self._pairs[key] = _ShadowPair(
                        tenant, candidate, block=self.block,
                        bins=self.bins)
            pair.observe(primary, cand)


class TestShadowMirrorShedRegression:
    """The real interleaving bug schedcheck's FIRST run surfaced
    (ISSUE-15 satellite): stop() mid-batch silently lost dequeued
    mirrors from the accounting (`submitted` could never reconcile
    with mirrored + errors + dropped + queued).  Fixed in
    serve/tenant.py; the counterexample schedule is re-derived against
    the reverted body and pinned by replay."""

    def _with_prefix_body(self):
        from distlr_tpu.serve.tenant import ShadowMirror
        return mutants.Mutant(
            name="shadow_mid_batch_shed",
            historical="ISSUE 15 first-run finding",
            target="distlr_tpu.serve.tenant:ShadowMirror._run",
            scenario_fn=scenarios.SCENARIOS["shadow_mirror_stop"].fn,
            buggy_fn=_prefix_shadow_run,
            expect_in_message="mirror accounting broke",
            dfs_runs=2000, max_steps=6000,
        )

    def test_reverted_body_loses_mirrors_and_replays(self):
        m = self._with_prefix_body()
        with lint.quiet_logs():
            cex = m.rediscover()
        assert cex is not None, \
            "pre-fix ShadowMirror._run no longer rediscovered"
        assert "mirror accounting broke" in cex.failure.message
        choices = [d.chosen for d in cex.decisions]
        with lint.quiet_logs():
            rep = m.replay(choices)
        assert rep.render_failure() == cex.render_failure()

    def test_fixed_body_is_schedule_proof(self):
        s = scenarios.SCENARIOS["shadow_mirror_stop"]
        with lint.quiet_logs():
            res = explore.dfs(s.name, s.fn, preemption_bound=s.dfs_bound,
                              max_runs=s.dfs_runs, max_steps=s.max_steps)
        assert res.failure is None and res.closed


# ---------------------------------------------------------------------------
# baseline cross-reference (the PR-13 staleness rule, extended)
# ---------------------------------------------------------------------------


class TestBaselineScenarioCrossref:
    def _load(self, tmp_path, body):
        p = tmp_path / "b.toml"
        p.write_text(body)
        return baseline.load_baseline(str(p))

    def test_entry_without_scenario_fails(self, tmp_path):
        _e, problems = self._load(tmp_path, (
            '[[suppress]]\nkey = "unlocked-read:x"\n'
            'justification = "why"\n'))
        assert any(f.key.startswith("baseline-no-scenario")
                   for f in problems)

    def test_unknown_scenario_name_fails(self, tmp_path):
        entries, problems = self._load(tmp_path, (
            '[[suppress]]\n'
            'key = "unlocked-read:distlr_tpu/serve/reload.py:'
            'HotReloader.*"\n'
            'justification = "why"\n'
            'schedcheck_scenario = "gone_scenario"\n'))
        assert problems == []
        fs = baseline.scenario_crossref(entries)
        assert any(f.key.startswith("baseline-stale-scenario")
                   for f in fs)

    def test_scenario_not_covering_the_class_fails(self, tmp_path):
        entries, _p = self._load(tmp_path, (
            '[[suppress]]\n'
            'key = "unlocked-read:distlr_tpu/serve/engine.py:'
            'ScoringEngine.*"\n'
            'justification = "why"\n'
            'schedcheck_scenario = "joiner_label_race"\n'))
        fs = baseline.scenario_crossref(entries)
        assert any(f.key.startswith("baseline-scenario-mismatch")
                   for f in fs)

    def test_dash_is_the_audited_opt_out(self, tmp_path):
        entries, problems = self._load(tmp_path, (
            '[[suppress]]\nkey = "unlocked-read:x"\n'
            'justification = "jax-holding class, cannot run here"\n'
            'schedcheck_scenario = "-"\n'))
        assert problems == []
        assert baseline.scenario_crossref(entries) == []

    def test_repo_baseline_crossrefs_are_live(self):
        entries, problems = baseline.load_baseline()
        assert problems == []
        assert baseline.scenario_crossref(entries) == []
        named = [e for e in entries if e.scenario != "-"]
        assert named, "no baseline entry names a schedcheck scenario"


# ---------------------------------------------------------------------------
# runner / make wiring
# ---------------------------------------------------------------------------


class TestRunnerWiring:
    def test_list_passes_includes_sched(self, capsys):
        assert lint_main(["--list-passes"]) == 0
        out = capsys.readouterr().out
        assert "sched:" in out and "protocol:" in out

    def test_only_alias_selects_one_pass(self, capsys):
        assert lint_main(["--only", "wire"]) == 0
        out = capsys.readouterr().out
        assert "clean (wire)" in out

    def test_schedcheck_cli_list_and_replay(self):
        from distlr_tpu.analysis.schedcheck.__main__ import main as sc_main
        assert sc_main(["--list"]) == 0
        m = mutants.MUTANTS["joiner_check_then_insert"]
        with lint.quiet_logs():
            cex = m.rediscover()
        sid = cex.schedule_id
        # replaying a mutant counterexample through the CLI re-applies
        # the mutation and exits non-zero with the report
        proc = subprocess.run(
            [sys.executable, "-m", "distlr_tpu.analysis.schedcheck",
             "--replay", sid],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "the label stranded" in proc.stdout

    def test_make_targets_exist(self):
        with open(f"{REPO}/Makefile") as f:
            mk = f.read()
        assert "verify-sched:" in mk and "verify-sched-full:" in mk


# ---------------------------------------------------------------------------
# deep tier (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestDeepTier:
    @pytest.mark.parametrize("name", ["joiner_label_race",
                                      "chaoslink_stop_accept",
                                      "shadow_mirror_stop"])
    def test_deep_dfs_closes_clean(self, name):
        s = scenarios.SCENARIOS[name]
        with lint.quiet_logs():
            res = explore.dfs(s.name, s.fn,
                              preemption_bound=s.deep_bound,
                              max_runs=s.deep_runs,
                              max_steps=s.max_steps)
        assert res.failure is None, res.failure.render_failure()
        assert res.closed

    def test_wide_fuzz_stays_clean(self):
        for s in scenarios.SCENARIOS.values():
            with lint.quiet_logs():
                fz = explore.fuzz(s.name, s.fn, seeds=150,
                                  max_steps=s.max_steps)
            assert fz.failure is None, \
                (s.name, fz.failure.render_failure())
