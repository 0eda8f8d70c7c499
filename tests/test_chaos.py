"""Network chaos layer + in-place client resilience (ISSUE 5).

The reference's only answer to ANY network fault is a poisoned
connection and (in sync mode) an eternal deadlock; PRs 1-4 only ever
injected SIGKILLs.  These tests pin the two-sided answer: a
deterministic fault-injection proxy (``distlr_tpu.chaos``) that can
inflict the faults that actually dominate production — delay, resets
mid-op, slow links, partitions — and a client ``RetryPolicy`` that
absorbs them in place: transient faults cost a retry, not a
checkpoint restore.
"""

import json
import os
import time

import numpy as np
import pytest

from distlr_tpu.chaos import (
    ChaosFabric,
    FaultPlanError,
    load_plan,
    parse_plan,
)
from distlr_tpu.ps import KVWorker, PSTimeoutError, RetryPolicy, ServerGroup


def _counter_total(name: str) -> float:
    from distlr_tpu.obs.registry import get_registry

    fam = get_registry().get(name)
    if fam is None:
        return 0.0
    return sum(child.value for _v, child in fam.children())


# ---------------------------------------------------------------------------
# plan validation (satellite: malformed plans rejected loudly at parse time)
# ---------------------------------------------------------------------------

class TestPlanValidation:
    def test_unknown_kind_named(self):
        with pytest.raises(FaultPlanError, match=r"fault\[0\].kind.*'flood'"):
            parse_plan({"faults": [{"kind": "flood"}]})

    def test_negative_delay_named(self):
        with pytest.raises(FaultPlanError, match=r"fault\[1\].delay_ms"):
            parse_plan({"faults": [
                {"kind": "delay", "delay_ms": 5},
                {"kind": "delay", "delay_ms": -1},
            ]})

    def test_unknown_key_named(self):
        with pytest.raises(FaultPlanError, match=r"fault\[0\].bytes_per_sec"):
            parse_plan({"faults": [
                {"kind": "delay", "delay_ms": 5, "bytes_per_sec": 10},
            ]})

    def test_overlapping_windows_rejected_with_indices(self):
        with pytest.raises(FaultPlanError,
                           match=r"fault\[0\].window overlaps fault\[1\]"):
            parse_plan({"faults": [
                {"kind": "delay", "delay_ms": 5, "window": [1.0, 3.0]},
                {"kind": "delay", "delay_ms": 9, "window": [2.0, 4.0]},
            ]})

    def test_disjoint_windows_and_links_allowed(self):
        plan = parse_plan({"faults": [
            {"kind": "delay", "delay_ms": 5, "window": [1.0, 2.0]},
            {"kind": "delay", "delay_ms": 9, "window": [2.0, 4.0]},
            {"kind": "partition", "links": [0], "window": [1.0, 2.0]},
            {"kind": "partition", "links": [1], "window": [1.5, 2.5]},
        ]})
        assert len(plan.faults) == 4

    def test_malformed_window_named(self):
        with pytest.raises(FaultPlanError, match=r"fault\[0\].window"):
            parse_plan({"faults": [
                {"kind": "partition", "window": [3.0, 1.0]}]})

    def test_partition_requires_window(self):
        with pytest.raises(FaultPlanError, match=r"fault\[0\].window"):
            parse_plan({"faults": [{"kind": "partition"}]})

    def test_reset_needs_exactly_one_offset(self):
        with pytest.raises(FaultPlanError, match=r"fault\[0\].after_ops"):
            parse_plan({"faults": [{"kind": "reset"}]})
        with pytest.raises(FaultPlanError, match=r"fault\[0\].after_ops"):
            parse_plan({"faults": [
                {"kind": "reset", "after_ops": 1, "after_bytes": 1}]})

    def test_reset_rejects_window(self):
        with pytest.raises(FaultPlanError, match=r"fault\[0\].window"):
            parse_plan({"faults": [
                {"kind": "reset", "after_ops": 3, "window": [0, 1]}]})

    def test_bad_links_named(self):
        with pytest.raises(FaultPlanError, match=r"fault\[0\].links"):
            parse_plan({"faults": [
                {"kind": "delay", "delay_ms": 1, "links": [0, 0]}]})
        with pytest.raises(FaultPlanError, match=r"fault\[0\].links"):
            parse_plan({"faults": [
                {"kind": "delay", "delay_ms": 1, "links": [-2]}]})

    def test_unknown_top_level_key_named(self):
        with pytest.raises(FaultPlanError, match="'fautls'"):
            parse_plan({"fautls": []})

    def test_jitter_cannot_exceed_delay(self):
        with pytest.raises(FaultPlanError, match=r"fault\[0\].jitter_ms"):
            parse_plan({"faults": [
                {"kind": "delay", "delay_ms": 2, "jitter_ms": 5}]})

    def test_load_plan_from_file_and_invalid_json(self, tmp_path):
        p = tmp_path / "plan.json"
        p.write_text(json.dumps(
            {"seed": 7, "faults": [{"kind": "delay", "delay_ms": 1}]}))
        plan = load_plan(str(p))
        assert plan.seed == 7 and plan.faults[0].kind == "delay"
        assert load_plan(str(p), seed=99).seed == 99  # explicit seed wins
        p.write_text("{nope")
        with pytest.raises(FaultPlanError, match="not valid JSON"):
            load_plan(str(p))

    def test_fabric_rejects_out_of_range_link(self):
        plan = parse_plan({"faults": [
            {"kind": "delay", "delay_ms": 1, "links": [3]}]})
        with ServerGroup(1, 1, dim=4, sync=False) as g:
            with pytest.raises(ValueError, match=r"fault\[0\].links"):
                ChaosFabric(g.direct_hosts, plan)


# ---------------------------------------------------------------------------
# determinism (satellite: same seed + same plan => identical fault-event log)
# ---------------------------------------------------------------------------

def _scripted_run(seed: int) -> list:
    """A fixed client op sequence through a fresh group + fabric: init,
    12 pushes, 1 pull — with a mid-stream reset absorbed by the retry
    layer, so the sequence completes identically every run."""
    plan = parse_plan({"faults": [
        {"kind": "delay", "links": "*", "delay_ms": 2, "jitter_ms": 1},
        {"kind": "reset", "links": [0], "after_ops": 6},
    ]})
    with ServerGroup(1, 1, dim=8, sync=False) as g:
        with ChaosFabric(g.direct_hosts, plan, seed=seed) as fab:
            kv = KVWorker(fab.hosts, 8, client_id=0, timeout_ms=2000,
                          sync_group=False,
                          retry=RetryPolicy(attempts=5, backoff_ms=10,
                                            seed=0))
            kv.push_init(np.zeros(8, np.float32))
            for _ in range(12):
                kv.push(np.ones(8, np.float32))
            kv.pull()
            kv.close()
            return fab.events()


class TestDeterminism:
    def test_same_seed_same_plan_identical_event_log(self):
        a = _scripted_run(seed=42)
        b = _scripted_run(seed=42)
        assert a, "plan injected nothing"
        assert a == b
        kinds = {e[1] for e in a}
        assert kinds == {"delay", "reset"}
        # the log is wall-clock-free: offsets and plan-quantized values
        # only (any float is a hash-derived delay, never a timestamp)
        reset = [e for e in a if e[1] == "reset"]
        assert reset == [(0, "reset", ("fault", 1), ("op", 6))]

    def test_different_seed_different_jitter(self):
        a = _scripted_run(seed=1)
        b = _scripted_run(seed=2)
        assert [e for e in a if e[1] == "delay"] != \
               [e for e in b if e[1] == "delay"]


# ---------------------------------------------------------------------------
# fault kinds through a live client
# ---------------------------------------------------------------------------

class TestFaultKinds:
    def test_delay_actually_delays(self):
        plan = parse_plan({"faults": [
            {"kind": "delay", "delay_ms": 60}]})
        with ServerGroup(1, 1, dim=4, sync=False) as g:
            with ChaosFabric(g.direct_hosts, plan) as fab:
                with KVWorker(fab.hosts, 4, timeout_ms=5000,
                              sync_group=False) as kv:
                    kv.push_init(np.zeros(4, np.float32))
                    t0 = time.perf_counter()
                    kv.pull()
                    assert time.perf_counter() - t0 >= 0.055

    def test_throttle_paces_bytes(self):
        # 2 KB/s over a ~2.1 KB pull (a 32 B request: header + the one
        # row key of a 512-value run; a 24 B + 512 * 4 B reply) must
        # take >= ~1 s; data integrity must hold
        plan = parse_plan({"faults": [
            {"kind": "throttle", "bytes_per_sec": 2048}]})
        with ServerGroup(1, 1, dim=512, sync=False) as g:
            with ChaosFabric(g.direct_hosts, plan) as fab:
                with KVWorker(fab.hosts, 512, timeout_ms=20_000,
                              sync_group=False) as kv:
                    kv.push_init(np.arange(512, dtype=np.float32))
                    t0 = time.perf_counter()
                    w = kv.pull()
                    assert time.perf_counter() - t0 > 0.8
                    np.testing.assert_array_equal(
                        w, np.arange(512, dtype=np.float32))

    def test_reset_after_bytes_drops_frame_without_apply(self):
        """A mid-frame cut: the server must NOT apply the half-delivered
        push (it sees an incomplete frame then EOF), and the client's
        next op rides a reconnect."""
        plan = parse_plan({"faults": [
            {"kind": "reset", "after_bytes": 1000}]})
        with ServerGroup(1, 1, dim=64, sync=False) as g:
            with ChaosFabric(g.direct_hosts, plan) as fab:
                kv = KVWorker(fab.hosts, 64, timeout_ms=2000,
                              sync_group=False,
                              retry=RetryPolicy(attempts=4, backoff_ms=10))
                kv.push_init(np.zeros(64, np.float32))  # 24+8+64*4 = 288 B
                issued = 0
                for _ in range(6):       # each push frame is 288 bytes
                    kv.push(np.ones(64, np.float32))
                    issued += 1
                w = kv.pull()
                kv.close()
            applied = g.health()[0]["total_pushes"] - 1  # minus init
            assert applied <= issued
            # the weights reflect exactly `applied` SGD steps
            np.testing.assert_allclose(
                w, -0.2 * applied * np.ones(64), rtol=1e-5)
            events = fab.events()
            assert any(e[1] == "reset" for e in events)

    def test_partition_window_blocks_then_heals(self):
        """During the window new connects are refused and ops stall past
        the client timeout; with a RetryPolicy the op survives the
        window in place — zero caller-visible failures."""
        plan = parse_plan({"faults": [
            {"kind": "partition", "links": [0], "window": [0.0, 1.2]}]})
        with ServerGroup(1, 1, dim=4, sync=False) as g:
            # seed BEFORE the fabric exists (windows start at fabric
            # construction): the partition covers the first pull attempt
            with KVWorker(g.direct_hosts, 4, timeout_ms=1000,
                          sync_group=False) as direct:
                direct.push_init(np.full(4, 3.0, np.float32))
            with ChaosFabric(g.direct_hosts, plan) as fab:
                kv = KVWorker(fab.hosts, 4, timeout_ms=500,
                              sync_group=False,
                              retry=RetryPolicy(attempts=8, backoff_ms=100,
                                                backoff_max_ms=400,
                                                deadline_s=20))
                t0 = time.perf_counter()
                w = kv.pull()   # stalls, times out, retries through heal
                took = time.perf_counter() - t0
                kv.close()
            np.testing.assert_array_equal(w, np.full(4, 3.0, np.float32))
            assert took >= 0.4  # the fault was actually felt
            assert any(e[1] == "partition" for e in fab.events())

    def test_partial_partition_spares_other_links(self):
        """Partition link 1 only: a client of a 2-server group keeps
        failing group ops (server 1 unreachable) while a 1-server client
        of link 0 sails through — the 'partial' in partial partition."""
        plan = parse_plan({"faults": [
            {"kind": "partition", "links": [1], "window": [0.0, 30.0]}]})
        with ServerGroup(2, 1, dim=8, sync=False) as g:
            with ChaosFabric(g.direct_hosts, plan) as fab:
                h0 = fab.hosts.split(",")[0]
                with KVWorker(h0, 4, client_id=7, timeout_ms=2000,
                              sync_group=False) as kv0:
                    kv0.push_init(np.zeros(4, np.float32))
                    assert kv0.pull().shape == (4,)   # link 0 unaffected
                kv = KVWorker(fab.hosts, 8, timeout_ms=400,
                              sync_group=False)
                with pytest.raises(OSError):
                    kv.push_init(np.zeros(8, np.float32))
                kv.close()


# ---------------------------------------------------------------------------
# client retry layer
# ---------------------------------------------------------------------------

class TestRetryPolicy:
    def test_policy_validation(self):
        with pytest.raises(ValueError):
            RetryPolicy(attempts=0)
        with pytest.raises(ValueError):
            RetryPolicy(backoff_ms=10, backoff_max_ms=5)
        with pytest.raises(ValueError):
            RetryPolicy(jitter=1.5)
        with pytest.raises(ValueError):
            RetryPolicy(deadline_s=0)

    def test_pull_retries_through_reset(self):
        plan = parse_plan({"faults": [
            {"kind": "reset", "after_ops": 2}]})
        before = _counter_total("distlr_ps_retries_total")
        with ServerGroup(1, 1, dim=4, sync=False) as g:
            with ChaosFabric(g.direct_hosts, plan) as fab:
                kv = KVWorker(fab.hosts, 4, timeout_ms=2000,
                              sync_group=False,
                              retry=RetryPolicy(attempts=4, backoff_ms=10))
                kv.push_init(np.full(4, 2.0, np.float32))
                kv.pull()           # op 2: delivered, reply severed -> retried
                w = kv.pull()       # clean, post-reconnect
                kv.close()
        np.testing.assert_array_equal(w, np.full(4, 2.0, np.float32))
        assert _counter_total("distlr_ps_retries_total") > before

    def test_no_policy_keeps_fail_fast(self):
        plan = parse_plan({"faults": [{"kind": "reset", "after_ops": 2}]})
        with ServerGroup(1, 1, dim=4, sync=False) as g:
            with ChaosFabric(g.direct_hosts, plan) as fab:
                with KVWorker(fab.hosts, 4, timeout_ms=2000,
                              sync_group=False) as kv:
                    kv.push_init(np.zeros(4, np.float32))
                    with pytest.raises(OSError):
                        kv.pull()

    def test_sync_push_stays_fail_fast_with_straggler_error(self):
        """The named straggler timeout must surface even with a policy
        attached: a BSP push cannot be retried without mixing rounds."""
        with ServerGroup(1, 2, dim=8, sync=True) as g:
            kv = KVWorker(g.hosts, 8, client_id=0, timeout_ms=300,
                          retry=RetryPolicy(attempts=5, backoff_ms=10))
            kv.push(np.zeros(8, np.float32))
            with pytest.raises(PSTimeoutError, match="straggler|BSP barrier"):
                kv.push(np.ones(8, np.float32))
            kv.close()

    def test_exhausted_policy_surfaces_failure(self):
        plan = parse_plan({"faults": [
            {"kind": "partition", "links": [0], "window": [0.0, 120.0]}]})
        with ServerGroup(1, 1, dim=4, sync=False) as g:
            with KVWorker(g.direct_hosts, 4, timeout_ms=1000,
                          sync_group=False) as direct:
                direct.push_init(np.zeros(4, np.float32))
            with ChaosFabric(g.direct_hosts, plan) as fab:
                kv = KVWorker(fab.hosts, 4, timeout_ms=200,
                              sync_group=False,
                              retry=RetryPolicy(attempts=2, backoff_ms=10,
                                                deadline_s=3))
                with pytest.raises(OSError):
                    kv.pull()
                kv.close()


class TestPushSafety:
    """Acceptance: under forced reset-after-push-send, applied pushes
    (the servers' monotonic push clock) never exceed issued pushes, and
    unknown outcomes are COUNTED, not guessed."""

    def test_no_silent_double_apply_and_unknowns_counted(self):
        plan = parse_plan({"faults": [
            {"kind": "reset", "links": [0], "after_ops": 4},
            {"kind": "reset", "links": [0], "after_bytes": 6000},
        ]})
        unknown_before = _counter_total(
            "distlr_ps_push_outcome_unknown_total")
        with ServerGroup(1, 1, dim=64, sync=False) as g:
            with ChaosFabric(g.direct_hosts, plan) as fab:
                kv = KVWorker(fab.hosts, 64, timeout_ms=2000,
                              sync_group=False,
                              retry=RetryPolicy(attempts=5, backoff_ms=10))
                kv.push_init(np.zeros(64, np.float32))
                issued = 0
                for _ in range(10):
                    kv.push(np.ones(64, np.float32))
                    issued += 1
                w = kv.pull()
                kv.close()
                assert any(e[1] == "reset" for e in fab.events())
            applied = g.health()[0]["total_pushes"] - 1  # minus init
        unknowns = (_counter_total("distlr_ps_push_outcome_unknown_total")
                    - unknown_before)
        assert applied <= issued, "double-apply: clock exceeds issues"
        # every losable push is accounted: lost ones were flagged unknown
        assert issued - applied <= unknowns
        assert unknowns >= 1  # the after_ops reset severed a push reply
        # the weights are an exact multiple of one mean update — partial
        # or duplicated application would break this
        np.testing.assert_allclose(
            w, -0.2 * applied * np.ones(64), rtol=1e-5)

    def test_global_pushes_clock_readable_after_chaos(self):
        with ServerGroup(2, 1, dim=8, sync=False) as g:
            with KVWorker(g.direct_hosts, 8, timeout_ms=2000,
                          sync_group=False) as kv:
                kv.push_init(np.zeros(8, np.float32))
                kv.push(np.ones(8, np.float32))
                assert g.global_pushes() == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# serving-tier resilience (LivePSWatcher + HotReloader satellites)
# ---------------------------------------------------------------------------

class _EngineStub:
    def __init__(self):
        self.weights = None
        self.sets = 0

    @property
    def has_weights(self):
        return self.weights is not None

    def set_weights(self, w):
        self.weights = np.asarray(w)
        self.sets += 1


class TestServeResilience:
    def test_watcher_reconnects_after_failed_poll(self):
        """One blip must not poison the serving pull path forever: the
        poll after a failure reconnects and succeeds (the pre-PR
        behavior was a permanently dead watcher on last-good weights)."""
        from distlr_tpu.serve.reload import LivePSWatcher

        plan = parse_plan({"faults": [{"kind": "reset", "after_ops": 3}]})
        with ServerGroup(1, 1, dim=16, sync=False) as g:
            with KVWorker(g.direct_hosts, 16, timeout_ms=2000,
                          sync_group=False) as kv:
                kv.push_init(np.arange(16, dtype=np.float32))
            with ChaosFabric(g.direct_hosts, plan) as fab:
                src = LivePSWatcher(fab.hosts, 16, timeout_ms=1500)
                got = src.poll()            # stats + pull: ops 1-2
                assert got is not None and got[0] == 1
                with pytest.raises(OSError):
                    src.poll()              # op 3 severed
                got = src.poll()            # reconnected in place
                assert got is not None
                np.testing.assert_array_equal(
                    got[1], np.arange(16, dtype=np.float32))
                src.close()

    def test_wait_for_weights_names_unreachable_ps(self):
        """A PS that dies after the watcher connected: the startup
        timeout must say 'PS unreachable', not just '30s of silence'."""
        from distlr_tpu.serve.reload import HotReloader, LivePSWatcher

        g = ServerGroup(1, 1, dim=4, sync=False).start()
        src = LivePSWatcher(g.direct_hosts, 4, timeout_ms=500)
        g.stop()  # servers gone; localhost connects now refuse fast
        eng = _EngineStub()
        r = HotReloader(eng, src, interval_s=0.05)
        with pytest.raises(TimeoutError, match="unreachable"):
            r.wait_for_weights(timeout_s=1.0)
        assert not eng.has_weights
        src.close()

    def test_wait_for_weights_names_uninitialized_ps(self):
        """Reachable-but-uninitialized must be NAMED in the startup
        timeout (and zeros must not be published as weights) — it used
        to read exactly like a dead PS."""
        from distlr_tpu.serve.reload import HotReloader, LivePSWatcher

        with ServerGroup(1, 1, dim=4, sync=False) as g:
            src = LivePSWatcher(g.direct_hosts, 4, timeout_ms=1000)
            eng = _EngineStub()
            r = HotReloader(eng, src, interval_s=0.1)
            with pytest.raises(TimeoutError, match="UNINITIALIZED"):
                r.wait_for_weights(timeout_s=0.8)
            assert not eng.has_weights  # zeros were never published
            # the trainer arrives: the next poll publishes real weights
            with KVWorker(g.direct_hosts, 4, timeout_ms=2000,
                          sync_group=False) as kv:
                kv.push_init(np.full(4, 5.0, np.float32))
            r.wait_for_weights(timeout_s=5)
            np.testing.assert_array_equal(
                eng.weights, np.full(4, 5.0, np.float32))
            r.source.close()

    def test_degraded_cycles_warn_rate_limited(self):
        """Every degraded poll cycle warns (rate-limited), and recovery
        logs once — the old behavior logged at errors 1/10/100 and was
        silent otherwise."""
        import logging

        from distlr_tpu.serve.reload import HotReloader

        class FlakySource:
            def __init__(self):
                self.fail = True

            def poll(self):
                if self.fail:
                    raise IOError("injected blip")
                return 1, np.zeros(2, np.float32)

            def close(self):
                pass

        records = []
        handler = logging.Handler()
        handler.emit = records.append  # the module logger doesn't propagate
        logger = logging.getLogger("distlr_tpu.serve.reload")
        logger.addHandler(handler)
        try:
            src = FlakySource()
            r = HotReloader(_EngineStub(), src, interval_s=0.01)
            for _ in range(5):
                r._poll_once()
            warns = [x for x in records if "DEGRADED" in x.getMessage()]
            assert len(warns) == 1  # rate-limited: one per warn_every_s
            r.warn_every_s = 0.0
            r._poll_once()
            r._poll_once()
            warns = [x for x in records if "DEGRADED" in x.getMessage()]
            assert len(warns) == 3  # un-throttled: every degraded cycle
            src.fail = False
            assert r._poll_once()
            assert any("recovered" in x.getMessage() for x in records)
        finally:
            logger.removeHandler(handler)


# ---------------------------------------------------------------------------
# launch wiring
# ---------------------------------------------------------------------------

class TestLaunchWiring:
    def test_chaos_cmd_rejects_malformed_plan(self, tmp_path, capsys):
        from distlr_tpu.launch import main

        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"faults": [{"kind": "flood"}]}))
        rc = main(["chaos", "--upstreams", "127.0.0.1:1",
                   "--plan", str(bad)])
        assert rc == 2
        assert "flood" in capsys.readouterr().err

    def test_ps_chaos_plan_requires_local_mode(self, tmp_path, capsys):
        from distlr_tpu.launch import main

        plan = tmp_path / "plan.json"
        plan.write_text(json.dumps(
            {"faults": [{"kind": "delay", "delay_ms": 1}]}))
        rc = main(["ps", "--hosts", "127.0.0.1:1",
                   "--chaos-plan", str(plan), "--data-dir", str(tmp_path)])
        assert rc == 2
        assert "launch chaos" in capsys.readouterr().err

    def test_ps_local_rejects_malformed_plan_before_spawning(self, tmp_path):
        from distlr_tpu.config import Config
        from distlr_tpu.chaos import FaultPlanError
        from distlr_tpu.train.ps_trainer import run_ps_local

        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps(
            {"faults": [{"kind": "delay", "delay_ms": -3}]}))
        cfg = Config(data_dir=str(tmp_path), num_feature_dim=8,
                     sync_mode=False, chaos_plan=str(bad))
        with pytest.raises(FaultPlanError, match=r"fault\[0\].delay_ms"):
            run_ps_local(cfg, save=False)

    def test_retry_flags_reach_config(self):
        from distlr_tpu.launch import _config_from_args, main  # noqa: F401
        import argparse

        ns = argparse.Namespace(
            ps_retry_attempts=5, ps_retry_backoff_ms=10.0,
            ps_retry_backoff_max_ms=100.0, ps_retry_deadline_s=9.0,
            chaos_seed=3)
        cfg = _config_from_args(ns)
        assert cfg.ps_retry_attempts == 5
        assert cfg.ps_retry_backoff_ms == 10.0
        assert cfg.ps_retry_backoff_max_ms == 100.0
        assert cfg.ps_retry_deadline_s == 9.0
        assert cfg.chaos_seed == 3

    def test_chaos_seed_defaults_to_plan_seed(self, tmp_path):
        """`launch ps --chaos-plan` without --chaos-seed must honor the
        plan file's own seed (Config.chaos_seed=None), matching `launch
        chaos` — not silently zero it."""
        from distlr_tpu.chaos import load_plan
        from distlr_tpu.config import Config

        assert Config().chaos_seed is None
        p = tmp_path / "plan.json"
        p.write_text(json.dumps(
            {"seed": 7, "faults": [{"kind": "delay", "delay_ms": 1}]}))
        cfg = Config(chaos_plan=str(p))
        assert load_plan(cfg.chaos_plan, seed=cfg.chaos_seed).seed == 7
        cfg = Config(chaos_plan=str(p), chaos_seed=9)
        assert load_plan(cfg.chaos_plan, seed=cfg.chaos_seed).seed == 9

    def test_retry_policy_from_config_async_only(self):
        from distlr_tpu.config import Config
        from distlr_tpu.train.ps_trainer import ps_retry_policy

        async_cfg = Config(sync_mode=False, ps_retry_attempts=3,
                           ps_retry_deadline_s=5)
        pol = ps_retry_policy(async_cfg)
        assert pol is not None and pol.attempts == 3
        assert ps_retry_policy(Config(sync_mode=True,
                                      ps_retry_attempts=3)) is None
        assert ps_retry_policy(Config(sync_mode=False)) is None


# ---------------------------------------------------------------------------
# the capstone soak: training through faults, zero restarts
# ---------------------------------------------------------------------------

def _write_soak_data(tmp_path, n, d=24):
    from distlr_tpu.data.synthetic import write_synthetic_shards

    data_dir = str(tmp_path / "data")
    write_synthetic_shards(data_dir, n, d, num_parts=2, seed=11, sparsity=0.0)
    return data_dir


def _accuracy(w, data_dir, d):
    from distlr_tpu.data import DataIter
    from distlr_tpu.data.sharding import part_name

    it = DataIter.from_file(os.path.join(data_dir, "test", part_name(0)),
                            d, -1)
    X, y, m = it.next_batch()
    z = np.asarray(X @ np.asarray(w), np.float64)
    m = np.asarray(m, np.float64)
    return float((((z > 0).astype(np.int64) == y) * m).sum()
                 / max(m.sum(), 1.0))


def _soak_cfg(data_dir, d, plan_path, *, epochs):
    from distlr_tpu.config import Config

    return Config(
        data_dir=data_dir, num_feature_dim=d, num_workers=2, num_servers=2,
        num_iteration=epochs, learning_rate=0.2, l2_c=0.0, batch_size=64,
        test_interval=0, sync_mode=False, ps_timeout_ms=1000,
        # Retry budget sized to outlast BOTH the longest plan window and
        # the worst worker finish-skew with ample margin.  Size on the
        # BACKOFF-SUM (~13 s for 20 attempts at 50..800 ms), not on
        # attempts x timeout: mid-partition the proxy refuses fresh
        # connects RST-style, so only the first stalled op costs a full
        # timeout — later attempts fail fast and burn only backoff.  The
        # skew matters because the EXIT barrier rides the same policy —
        # rank 0 finishes first and its barrier votes time out
        # (reconnect + re-vote, deduped server-side) until the
        # fault-delayed peer arrives; barrier waits DO cost a full
        # timeout per attempt, so the barrier budget is ~20 s of
        # timeouts on top.
        ps_retry_attempts=20, ps_retry_backoff_ms=50,
        ps_retry_backoff_max_ms=800, ps_retry_deadline_s=60,
        chaos_plan=plan_path,
    )


def _run_soak(tmp_path, plan: dict, *, epochs: int, samples: int = 2400):
    """Fault-free run vs chaos run on the same data/seed; returns
    (acc_clean, acc_chaos, counter deltas)."""
    from distlr_tpu.train import ps_trainer
    from distlr_tpu.train.ps_trainer import run_ps_local

    d = 24
    data_dir = _write_soak_data(tmp_path, samples, d=d)
    plan_path = str(tmp_path / "plan.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)

    clean_cfg = _soak_cfg(data_dir, d, None, epochs=epochs).replace(
        chaos_plan=None)
    clean = run_ps_local(clean_cfg, save=False)
    acc_clean = _accuracy(clean[0], data_dir, d)

    before = {
        "restarts": _counter_total("distlr_ps_worker_restarts_total"),
        "retries": _counter_total("distlr_ps_retries_total"),
        "reconnects": _counter_total("distlr_ps_reconnects_total"),
        "chaos": _counter_total("distlr_chaos_faults_total"),
    }
    chaos_cfg = _soak_cfg(data_dir, d, plan_path, epochs=epochs)
    chaos = run_ps_local(chaos_cfg, save=False)
    acc_chaos = _accuracy(chaos[0], data_dir, d)
    deltas = {
        k: _counter_total(name) - before[k]
        for k, name in [
            ("restarts", "distlr_ps_worker_restarts_total"),
            ("retries", "distlr_ps_retries_total"),
            ("reconnects", "distlr_ps_reconnects_total"),
            ("chaos", "distlr_chaos_faults_total"),
        ]
    }
    return acc_clean, acc_chaos, deltas


def _assert_scrape_shows_fault_accounting():
    """One scrape (the process /metrics surface) must show the injected
    faults NEXT TO what they cost: non-zero distlr_chaos_* alongside
    matching distlr_ps_retries_total / distlr_ps_reconnects_total."""
    from distlr_tpu.obs.registry import get_registry

    text = get_registry().prometheus_text()
    for needle in ("distlr_chaos_faults_total", "distlr_ps_retries_total",
                   "distlr_ps_reconnects_total"):
        assert needle in text, f"{needle} missing from the scrape"


class TestChaosSoak:
    """Tier-1-safe short soak (<60 s): one reset + one delay window."""

    def test_short_soak_converges_with_zero_restarts(self, tmp_path):
        plan = {"faults": [
            # always-on 2 ms on link 1: stretches the run so the window
            # faults are guaranteed to overlap live traffic
            {"kind": "delay", "links": [1], "delay_ms": 2},
            # 1.3 s > the 1 s op timeout: every op entering the window
            # TIMES OUT and must survive via reconnect + re-issue — the
            # guaranteed retry/reconnect source (ops start flowing well
            # inside [0, 2.0): init push + barrier land at ~0.1-0.3 s)
            {"kind": "delay", "links": [0], "delay_ms": 1300,
             "window": [0.0, 2.0]},
            {"kind": "reset", "links": [0], "after_ops": 120},
        ]}
        # 4,800 samples as the full soak has them: on the default 2,400
        # (a 480-row test split) two Hogwild runs of the same data differ
        # by 1 pt or more about once in fifteen under a loaded host, with
        # no fault injected at all (60 runs a side, PR 43: 4 of 60 at the
        # parent commit and at the change alike); on 960 rows none of 60
        # did, and the largest difference was 0.73 pt
        acc_clean, acc_chaos, deltas = _run_soak(tmp_path, plan, epochs=12,
                                                 samples=4800)
        assert deltas["restarts"] == 0, "faults escalated to a restart"
        assert deltas["chaos"] > 0, "no fault was injected"
        assert deltas["reconnects"] >= 1
        assert deltas["retries"] >= 1
        assert abs(acc_clean - acc_chaos) < 0.01, (
            f"chaos cost accuracy: clean={acc_clean:.4f} "
            f"chaos={acc_chaos:.4f}")
        _assert_scrape_shows_fault_accounting()


@pytest.mark.slow
class TestChaosSoakFull:
    """The full acceptance soak: >=1 reset mid-op, >=1 delay window,
    >=1 timed partition — converges within 1 pt of the fault-free run
    on the same data/seed with ZERO process restarts."""

    def test_full_soak(self, tmp_path):
        plan = {"faults": [
            # always-on 4 ms on link 0 stretches the run past the
            # partition window; the windowed faults ride link 1
            {"kind": "delay", "links": [0], "delay_ms": 4},
            {"kind": "delay", "links": [1], "delay_ms": 50,
             "window": [0.5, 2.5]},
            {"kind": "reset", "links": [0], "after_ops": 150},
            {"kind": "reset", "links": [1], "after_bytes": 200_000},
            # 2.5 s partial partition — longer than TWO 1 s op-timeout
            # cycles, so the retry counter is structurally guaranteed to
            # tick: the first stalled op times out (outcome-unknown push
            # -> reconnect), and the follow-up pull must also time out
            # and be re-issued before the window can heal it
            {"kind": "partition", "links": [1], "window": [3.0, 5.5]},
        ]}
        unknown_before = _counter_total(
            "distlr_ps_push_outcome_unknown_total")
        # 2x the short soak's data: the 1 pt acceptance margin needs a
        # test split large enough that async run-to-run noise (both runs
        # are Hogwild) stays well inside it; epochs sized so training
        # outlives the 4.6 s fault schedule with a fault-free tail
        acc_clean, acc_chaos, deltas = _run_soak(tmp_path, plan, epochs=40,
                                                 samples=4800)
        assert deltas["restarts"] == 0, "faults escalated to a restart"
        assert deltas["chaos"] > 0
        assert deltas["reconnects"] >= 1
        assert deltas["retries"] >= 1
        assert abs(acc_clean - acc_chaos) < 0.01, (
            f"chaos cost accuracy: clean={acc_clean:.4f} "
            f"chaos={acc_chaos:.4f}")
        _assert_scrape_shows_fault_accounting()
        # every potentially-lost push is accounted, never re-issued
        assert (_counter_total("distlr_ps_push_outcome_unknown_total")
                >= unknown_before)


# ---------------------------------------------------------------------------
# adaptive retry backoff (ISSUE 7 satellite)
# ---------------------------------------------------------------------------

class TestAdaptiveBackoff:
    def test_fault_rate_tracker_scales_and_decays(self):
        from distlr_tpu.ps import FaultRateTracker

        tr = FaultRateTracker(window_s=10.0, max_scale=8.0)
        assert tr.scale(now=0.0) == 1.0
        for t in (1.0, 2.0, 3.0, 4.0):
            tr.record(now=t)
        # 1 + 0.5 * faults-in-window
        assert tr.scale(now=5.0) == 3.0
        # saturates at max_scale under a storm
        for t in np.linspace(5.0, 6.0, 30):
            tr.record(now=float(t))
        assert tr.scale(now=6.0) == 8.0
        # quiet window: old faults age out, scale decays to the base
        assert tr.scale(now=17.0) == 1.0

    def test_fault_rate_tracker_validation(self):
        from distlr_tpu.ps import FaultRateTracker

        with pytest.raises(ValueError, match="window_s"):
            FaultRateTracker(window_s=0)
        with pytest.raises(ValueError, match="max_scale"):
            FaultRateTracker(max_scale=0.5)

    def test_backoff_scale_multiplies_base_under_cap(self):
        import random

        pol = RetryPolicy(attempts=5, backoff_ms=100, backoff_max_ms=400,
                          jitter=0.0)
        rng = random.Random(0)
        assert pol.backoff_s(0, rng) == pytest.approx(0.1)
        assert pol.backoff_s(0, rng, scale=2.0) == pytest.approx(0.2)
        # the cap applies AFTER scaling: adaptivity saturates, never
        # exceeds the configured ceiling
        assert pol.backoff_s(1, rng, scale=8.0) == pytest.approx(0.4)
        with pytest.raises(ValueError, match="adaptive_window_s"):
            RetryPolicy(adaptive_window_s=0)
        with pytest.raises(ValueError, match="adaptive_max_scale"):
            RetryPolicy(adaptive_max_scale=0.9)

    def test_from_config_plumbs_adaptive_flag(self):
        from distlr_tpu.config import Config

        pol = RetryPolicy.from_config(Config(ps_retry_attempts=3,
                                             ps_retry_adaptive=True))
        assert pol is not None and pol.adaptive is True
        pol = RetryPolicy.from_config(Config(ps_retry_attempts=3))
        assert pol is not None and pol.adaptive is False
        assert RetryPolicy.from_config(Config(ps_retry_attempts=0)) is None

    def test_adaptive_worker_records_faults_through_chaos(self):
        """An adaptive worker crossing injected resets records its
        faults (the scale input) while still recovering in place."""
        plan = parse_plan({"faults": [
            {"kind": "reset", "after_ops": 3},
        ]})
        with ServerGroup(1, 1, dim=32, sync=False) as g:
            with ChaosFabric(g.direct_hosts, plan) as fab:
                kv = KVWorker(fab.hosts, 32, timeout_ms=2000,
                              sync_group=False,
                              retry=RetryPolicy(attempts=6, backoff_ms=10,
                                                adaptive=True))
                assert kv._fault_rate is not None
                kv.push_init(np.zeros(32, np.float32))
                for _ in range(4):
                    kv.pull()
                w = kv.pull()
                kv.close()
            np.testing.assert_array_equal(w, np.zeros(32))
            assert len(kv._fault_rate._faults) >= 1
