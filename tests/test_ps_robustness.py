"""Failure detection for the PS mode (SURVEY.md §5.3).

The reference has no failure handling at all: a dead worker in sync mode
deadlocks the BSP barrier forever (``src/main.cc:67-78`` waits for
exactly ``NumWorkers()`` pushes).  These tests pin the framework's
answer: client-side op timeouts that raise a *named* straggler error,
and a stats probe that stays answerable while the barrier is wedged.
"""

import time

import numpy as np
import pytest

from distlr_tpu.ps import KVWorker, PSTimeoutError, ServerGroup


def _wait_pending_zero(group, *, deadline_s: float = 5.0) -> int:
    """Poll server 0 until its deferred-push count drops to 0 (the
    disconnect rollback runs on the server's reader thread, which races
    a freshly-connected stats probe)."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        pending = group.health()[0]["pending_sync_pushes"]
        if pending == 0:
            return 0
        time.sleep(0.02)
    return pending


@pytest.fixture()
def sync_group_of_two():
    """Sync server expecting 2 workers — one never shows up."""
    with ServerGroup(1, 2, dim=8, sync=True, learning_rate=0.5) as group:
        yield group


class TestStragglerTimeout:
    def test_sync_push_times_out_with_named_straggler_error(self, sync_group_of_two):
        with KVWorker(sync_group_of_two.hosts, 8, client_id=0, timeout_ms=300) as kv:
            kv.push(np.zeros(8, np.float32))  # first push = init, replies at once
            t0 = time.monotonic()
            with pytest.raises(PSTimeoutError, match="straggler|BSP barrier"):
                kv.push(np.ones(8, np.float32))  # deferred: needs 2 workers
            assert time.monotonic() - t0 < 5.0  # timed out, not deadlocked

    def test_barrier_times_out_when_peer_missing(self, sync_group_of_two):
        with KVWorker(sync_group_of_two.hosts, 8, client_id=0, timeout_ms=300) as kv:
            with pytest.raises(PSTimeoutError):
                kv.barrier()

    def test_zero_timeout_means_blocking(self, sync_group_of_two):
        # timeout_ms=0 must not set a timeout: a pull (never deferred)
        # still completes after an arbitrary client-side pause.
        with KVWorker(sync_group_of_two.hosts, 8, client_id=0, timeout_ms=0) as kv:
            kv.push(np.zeros(8, np.float32))
            time.sleep(0.4)
            assert kv.pull().shape == (8,)


class TestStatsProbe:
    def test_stats_reflect_progress_and_survive_wedged_barrier(self):
        with ServerGroup(2, 2, dim=10, sync=True) as group:
            with KVWorker(group.hosts, 10, client_id=0, timeout_ms=500) as kv:
                kv.push(np.zeros(10, np.float32))  # init both servers
                kv.pull()
                with pytest.raises(PSTimeoutError):
                    kv.push(np.ones(10, np.float32))  # wedges the barrier
                # probe on a FRESH connection while the wedged push is
                # still pending (the timed-out client is alive, just
                # poisoned client-side)
                health = group.health(timeout_ms=1000)
                assert len(health) == 2
                for h, dim in zip(health, (5, 5)):
                    assert h["dim"] == dim
                    assert h["initialized"] == 1
                    assert h["pending_sync_pushes"] == 1  # the wedged push
                    assert h["total_pushes"] == 2
                    assert h["total_pulls"] == 1
            # once the wedged client disconnects, its deferred push is
            # rolled back (see TestWorkerRestartRecovery)
            assert _wait_pending_zero(group) == 0

    def test_alive_tracks_processes(self):
        group = ServerGroup(1, 1, dim=4, sync=False).start()
        assert group.alive() == [True]
        group.stop()
        assert group.alive() == []


class TestWorkerRestartRecovery:
    def test_reconnected_worker_is_not_double_counted(self, sync_group_of_two):
        """A worker that times out, reconnects, and re-pushes must count
        once: the server rolls the dead connection's deferred push out of
        the merge buffer (no rollback -> the barrier would release early
        with a duplicated gradient)."""
        hosts = sync_group_of_two.hosts
        with KVWorker(hosts, 8, client_id=0, timeout_ms=300) as kv:
            kv.push(np.zeros(8, np.float32))  # init
            with pytest.raises(PSTimeoutError):
                kv.push(np.ones(8, np.float32))  # deferred, then timeout
        # old connection closed -> server must have rolled its push back
        assert _wait_pending_zero(sync_group_of_two) == 0

        # restart: reconnect and train with BOTH workers present
        kv0 = KVWorker(hosts, 8, client_id=0, timeout_ms=3000)
        kv1 = KVWorker(hosts, 8, client_id=1, timeout_ms=3000)
        import threading

        g0 = np.full(8, 1.0, np.float32)
        g1 = np.full(8, 3.0, np.float32)
        t = threading.Thread(target=lambda: kv1.push(g1))
        t.start()
        kv0.push(g0)  # releases once both arrive
        t.join()
        w = kv0.pull()
        kv0.close()
        kv1.close()
        # exactly one mean update: -lr * (1+3)/2 = -0.5 * 2 = -1
        np.testing.assert_allclose(w, -1.0 * np.ones(8), rtol=1e-6)

    def test_poisoned_connection_fails_fast_after_timeout(self, sync_group_of_two):
        with KVWorker(sync_group_of_two.hosts, 8, client_id=0, timeout_ms=300) as kv:
            kv.push(np.zeros(8, np.float32))
            with pytest.raises(PSTimeoutError):
                kv.push(np.ones(8, np.float32))
            with pytest.raises(IOError, match="poisoned"):
                kv.pull()

    def test_reconnect_recovers_poisoned_connection_in_place(self, sync_group_of_two):
        """The poisoned-connection dead end, fixed: reconnect() rebuilds
        the native handle on the SAME object (dim/timeout/group-mode
        preserved) and the next op completes — callers running their own
        retry loop no longer have to recreate the KVWorker."""
        with KVWorker(sync_group_of_two.hosts, 8, client_id=0, timeout_ms=300) as kv:
            kv.push(np.zeros(8, np.float32))
            with pytest.raises(PSTimeoutError):
                kv.push(np.ones(8, np.float32))  # wedged barrier -> poisoned
            with pytest.raises(IOError, match="poisoned"):
                kv.pull()
            kv.reconnect()
            # a pull (never deferred) completes on the rebuilt handle
            np.testing.assert_allclose(kv.pull(), np.zeros(8), rtol=1e-6)
            # the old connection's deferred push is rolled back on the
            # server's reader thread: on a loaded host the next push can
            # beat it and release the round with it (the driver's run of
            # PR 32 saw this test fail that way)
            assert _wait_pending_zero(sync_group_of_two) == 0
            # the receive timeout survived the rebuild: a second wedged
            # push still times out fast instead of blocking forever
            t0 = time.monotonic()
            with pytest.raises(PSTimeoutError):
                kv.push(np.ones(8, np.float32))
            assert time.monotonic() - t0 < 5.0


class TestAsyncUnaffected:
    def test_async_single_worker_never_needs_peers(self):
        with ServerGroup(1, 4, dim=6, sync=False) as group:
            with KVWorker(group.hosts, 6, client_id=0, timeout_ms=1000) as kv:
                kv.push(np.zeros(6, np.float32))  # init
                kv.push(np.full(6, 2.0, np.float32))  # applied immediately
                w = kv.pull()
                np.testing.assert_allclose(w, -0.2 * 2.0 * np.ones(6), rtol=1e-6)


class TestAsyncWorkerRestart:
    """run_ps_workers(max_restarts=N): async workers are rebuilt in place
    after a failure and rejoin the group (Hogwild tolerates arbitrary
    rejoin; the server's disconnect rollback cleared any partial state).
    The reference's only outcome for ANY worker failure is a hang."""

    def test_failed_async_worker_restarts_and_run_completes(self, tmp_path, monkeypatch):
        from distlr_tpu.config import Config
        from distlr_tpu.data.synthetic import write_synthetic_shards
        from distlr_tpu.train import ps_trainer
        from distlr_tpu.train.ps_trainer import PSWorker, run_ps_local

        d = str(tmp_path / "data")
        write_synthetic_shards(d, 1200, 16, num_parts=2, seed=9, sparsity=0.0)

        # Rank 1's first load blows up (simulating a worker crash at
        # startup); the restarted instance succeeds.
        real_load = PSWorker._load_train_iter
        failures = {"left": 1}

        def flaky_load(self):
            if self.rank == 1 and failures["left"] > 0:
                failures["left"] -= 1
                raise RuntimeError("injected worker crash")
            return real_load(self)

        monkeypatch.setattr(PSWorker, "_load_train_iter", flaky_load)
        cfg = Config(
            data_dir=d, num_feature_dim=16, num_workers=2, num_servers=1,
            num_iteration=10, learning_rate=0.2, l2_c=0.0, batch_size=100,
            test_interval=0, sync_mode=False,
        )
        results = run_ps_local(cfg, save=False, max_restarts=2)
        assert failures["left"] == 0  # the injected crash actually fired
        assert all(r is not None for r in results)

    def test_async_failure_without_restarts_still_raises(self, tmp_path, monkeypatch):
        from distlr_tpu.config import Config
        from distlr_tpu.data.synthetic import write_synthetic_shards
        from distlr_tpu.train.ps_trainer import PSWorker, run_ps_local

        d = str(tmp_path / "data")
        write_synthetic_shards(d, 600, 16, num_parts=2, seed=9, sparsity=0.0)
        monkeypatch.setattr(
            PSWorker, "_load_train_iter",
            lambda self: (_ for _ in ()).throw(RuntimeError("boom")),
        )
        cfg = Config(
            data_dir=d, num_feature_dim=16, num_workers=2, num_servers=1,
            num_iteration=3, sync_mode=False, test_interval=0, batch_size=100,
        )
        with pytest.raises(RuntimeError):
            run_ps_local(cfg, save=False)

    def test_sync_mode_never_restarts_in_place(self, tmp_path, monkeypatch):
        """BSP rounds are counted per worker: sync recovery is job-level
        checkpoint+resume, so max_restarts must not mask a sync failure."""
        from distlr_tpu.config import Config
        from distlr_tpu.data.synthetic import write_synthetic_shards
        from distlr_tpu.train.ps_trainer import PSWorker, run_ps_local

        d = str(tmp_path / "data")
        write_synthetic_shards(d, 600, 16, num_parts=2, seed=9, sparsity=0.0)
        calls = {"n": 0}
        def always_fail(self):
            calls["n"] += 1
            raise RuntimeError("boom")
        monkeypatch.setattr(PSWorker, "_load_train_iter", always_fail)
        cfg = Config(
            data_dir=d, num_feature_dim=16, num_workers=2, num_servers=1,
            num_iteration=3, sync_mode=True, test_interval=0, batch_size=-1,
        )
        with pytest.raises(RuntimeError):
            run_ps_local(cfg, save=False, max_restarts=5)
        assert calls["n"] <= 2  # one attempt per rank, no retries


class TestMidTrainingRestart:
    def test_async_worker_crash_mid_training_recovers(self, tmp_path, monkeypatch):
        """The advertised case: a worker dies AFTER the startup barrier
        (mid-epoch), restarts, re-sends its idempotent init, re-votes the
        released generation-0 barrier (instant), and rejoins — while
        rank 0's exit vote (generation 1) can never pair with it."""
        from distlr_tpu.config import Config
        from distlr_tpu.data.synthetic import write_synthetic_shards
        from distlr_tpu.models import host_math
        from distlr_tpu.train.ps_trainer import run_ps_local

        d = str(tmp_path / "data")
        write_synthetic_shards(d, 1200, 16, num_parts=2, seed=9, sparsity=0.0)

        # inject at the dense hot path (the numpy fast-path grad — tiny
        # D=16 steps route there, not through _place)
        real_grad = host_math.dense_grad
        state = {"calls": 0, "crashed": False}

        def flaky_grad(*args, **kw):
            # rank-agnostic but only one crash: trip after a few batches
            state["calls"] += 1
            if not state["crashed"] and state["calls"] == 5:
                state["crashed"] = True
                raise RuntimeError("injected mid-training crash")
            return real_grad(*args, **kw)

        monkeypatch.setattr(host_math, "dense_grad", flaky_grad)
        cfg = Config(
            data_dir=d, num_feature_dim=16, num_workers=2, num_servers=2,
            num_iteration=8, learning_rate=0.2, l2_c=0.0, batch_size=100,
            test_interval=0, sync_mode=False,
        )
        results = run_ps_local(cfg, save=False, max_restarts=2)
        assert state["crashed"]
        assert all(r is not None for r in results)
        # weights stayed sane (a re-applied init-as-gradient would shift
        # every weight by -lr*[0,1) — catch gross corruption)
        assert np.isfinite(results[0]).all()


class TestInitIdempotence:
    def test_force_init_overwrites_surviving_group(self):
        """Checkpoint resume against servers that survived a worker-job
        crash: the restored weights must REPLACE the stale live ones
        (plain idempotent init would no-op and silently resume wrong)."""
        from distlr_tpu.ps import KVWorker, ServerGroup

        with ServerGroup(1, 1, dim=4, learning_rate=1.0, sync=False) as sg:
            with KVWorker(sg.hosts, 4, timeout_ms=20_000) as kv:
                kv.wait(kv.push_init(np.arange(4, dtype=np.float32)))
                kv.wait(kv.push(np.ones(4, np.float32)))  # live training drift
                restored = np.full(4, 7.0, np.float32)
                kv.wait(kv.push_init(restored, force=True))
                np.testing.assert_allclose(kv.pull(), restored)
                kv.shutdown_servers()

    def test_barrier_id_range_checked(self):
        from distlr_tpu.ps import KVWorker, ServerGroup

        with ServerGroup(1, 1, dim=2, sync=False) as sg:
            with KVWorker(sg.hosts, 2, timeout_ms=20_000) as kv:
                with pytest.raises(ValueError, match="uint16"):
                    kv.barrier(1 << 16)
                kv.shutdown_servers()

    def test_push_init_noops_after_initialization(self):
        from distlr_tpu.ps import KVWorker, ServerGroup

        with ServerGroup(1, 1, dim=4, learning_rate=1.0, sync=False) as sg:
            with KVWorker(sg.hosts, 4, timeout_ms=20_000) as kv:
                kv.wait(kv.push_init(np.arange(4, dtype=np.float32)))
                # second init (a restarted rank 0) must not touch weights
                kv.wait(kv.push_init(np.full(4, 99.0, np.float32)))
                np.testing.assert_allclose(kv.pull(), np.arange(4))
                kv.shutdown_servers()

    def test_barrier_revote_same_client_never_double_counts(self):
        """One vote per CLIENT per generation, not per connection: a
        worker that times out and re-votes (reconnect path) must not
        hold two live votes.  Nothing orders the re-vote after the old
        connection's DropConnection rollback (separate server reader
        threads), so without client_id dedup the exit barrier could
        release with a peer absent — and rank 0 would shut the servers
        down under a still-training worker (found by the chaos soak)."""
        import threading

        with ServerGroup(1, 2, dim=8, sync=False) as g:
            kv1 = KVWorker(g.hosts, 8, client_id=0, timeout_ms=400)
            with pytest.raises(PSTimeoutError):
                kv1.barrier(3)  # 1 of 2 votes: wedged
            # same client re-votes on a SECOND live connection (the
            # reconnect race shape: old vote not yet rolled back)
            kv2 = KVWorker(g.hosts, 8, client_id=0, timeout_ms=400)
            with pytest.raises(PSTimeoutError):
                kv2.barrier(3)  # must still be 1 effective vote
            # the real second worker arrives: NOW it releases, and the
            # rank-0 reply routes to the replacement (live) connection
            kv3 = KVWorker(g.hosts, 8, client_id=1, timeout_ms=5000)
            t = threading.Thread(target=kv3.barrier, args=(3,))
            t.start()
            t.join(timeout=5)
            assert not t.is_alive(), "barrier never released"
            for kv in (kv1, kv2, kv3):
                kv.close()

    def test_released_barrier_generation_passes_late_votes(self):
        from distlr_tpu.ps import KVWorker, ServerGroup

        with ServerGroup(1, 1, dim=2, sync=False) as sg:
            with KVWorker(sg.hosts, 2, timeout_ms=20_000) as kv:
                kv.barrier(0)   # 1 worker: releases immediately
                kv.barrier(0)   # late re-vote: must return, not hang
                kv.barrier(1)   # next generation independent
                kv.shutdown_servers()


class TestSurvivingGroupResume:
    """Job-level resume against a server group that SURVIVED the worker
    crash (ADVICE r1): the group already released the crashed run's
    startup barrier generation, so the resumed run must rendezvous on a
    FRESH generation pair (sidecar attempt counter, bumped once per
    resume by the launcher) — otherwise peers sail through barrier(0)
    and pull stale crash-time weights before rank 0's forced init."""

    def test_resume_against_surviving_group(self, tmp_path, monkeypatch):
        import json
        import os
        import shutil

        from distlr_tpu.config import Config
        from distlr_tpu.data.synthetic import write_synthetic_shards
        from distlr_tpu.train.ps_trainer import (
            PSWorker, ps_param_dim, run_ps_local, run_ps_workers,
        )

        d = str(tmp_path / "data")
        write_synthetic_shards(d, 600, 16, num_parts=2, seed=9, sparsity=0.0)
        ck = str(tmp_path / "ck")
        cfg = Config(
            data_dir=d, num_feature_dim=16, num_workers=2, num_servers=2,
            num_iteration=4, learning_rate=0.5, l2_c=0.0, batch_size=-1,
            test_interval=0, sync_mode=True, checkpoint_dir=ck,
            checkpoint_interval=2, ps_timeout_ms=4000,
        )

        # Rank 0 dies right after writing the epoch-2 checkpoint; rank 1
        # then times out on the next BSP round.  No on_error: servers live.
        real_ckpt = PSWorker._checkpoint
        state = {"crashed": False}

        def crashing_ckpt(self, ckpt, epoch):
            real_ckpt(self, ckpt, epoch)
            if epoch == 2 and not state["crashed"]:
                state["crashed"] = True
                raise RuntimeError("injected crash after checkpoint")

        monkeypatch.setattr(PSWorker, "_checkpoint", crashing_ckpt)
        group = ServerGroup(2, 2, ps_param_dim(cfg), learning_rate=0.5, sync=True)
        with group:
            with pytest.raises(Exception):
                run_ps_workers(cfg, group.hosts, range(2), save=False)
            assert state["crashed"]
            with open(os.path.join(ck, "ps_latest.json")) as f:
                sc = json.load(f)
            assert sc == {"epoch": 2, "attempt": 0}

            # Deterministic oracle: the same resume on a FRESH group from
            # a copy of the checkpoint (sync full-batch is deterministic).
            ck2 = str(tmp_path / "ck2")
            shutil.copytree(ck, ck2)

            resumed = run_ps_workers(
                cfg, group.hosts, range(2), save=False, resume=True,
            )
        with open(os.path.join(ck, "ps_latest.json")) as f:
            sc = json.load(f)
        assert sc["attempt"] == 1, "resume must advance the barrier epoch"
        assert sc["epoch"] == 4

        ref = run_ps_local(
            cfg.replace(checkpoint_dir=ck2), save=False, resume=True,
        )
        np.testing.assert_allclose(resumed[0], ref[0], rtol=1e-5, atol=1e-6)

    def test_bump_resume_attempt_preserves_epoch_and_creates_missing_sidecar(self, tmp_path):
        import json
        import os

        from distlr_tpu.config import Config
        from distlr_tpu.train.ps_trainer import bump_resume_attempt

        cfg = Config(checkpoint_dir=str(tmp_path / "ck"), num_feature_dim=4)
        # No sidecar (crash predated the first checkpoint): the resume must
        # still get a fresh barrier generation, so the sidecar is CREATED
        # at epoch 0 (ADVICE r2 — a no-op here reused released barrier 0).
        bump_resume_attempt(cfg)
        sidecar = os.path.join(cfg.checkpoint_dir, "ps_latest.json")
        with open(sidecar) as f:
            assert json.load(f) == {"epoch": 0, "attempt": 1}

        with open(sidecar, "w") as f:
            json.dump({"epoch": 6}, f)  # legacy sidecar without attempt
        bump_resume_attempt(cfg)
        bump_resume_attempt(cfg)
        with open(sidecar) as f:
            assert json.load(f) == {"epoch": 6, "attempt": 2}

    def test_resume_before_first_checkpoint_reinitializes(self, tmp_path, monkeypatch):
        """Workers crash BEFORE any checkpoint exists; the surviving server
        group holds stale crash-time weights and has already released
        barrier generation 0.  The resume must (a) rendezvous on a fresh
        generation and (b) force a fresh epoch-0 init over the stale
        weights — equaling a from-scratch run on a fresh group."""
        import json
        import os

        from distlr_tpu.config import Config
        from distlr_tpu.data.synthetic import write_synthetic_shards
        from distlr_tpu.train.ps_trainer import (
            PSWorker, ps_param_dim, run_ps_local, run_ps_workers,
        )

        d = str(tmp_path / "data")
        write_synthetic_shards(d, 600, 16, num_parts=2, seed=9, sparsity=0.0)
        ck = str(tmp_path / "ck")
        cfg = Config(
            data_dir=d, num_feature_dim=16, num_workers=2, num_servers=2,
            num_iteration=3, learning_rate=0.5, l2_c=0.0, batch_size=-1,
            test_interval=0, sync_mode=True, checkpoint_dir=ck,
            checkpoint_interval=0, ps_timeout_ms=4000,
        )

        from distlr_tpu.models import host_math

        real_grad = host_math.dense_grad
        state = {"calls": 0, "crashed": False}

        def flaky_grad(*args, **kw):
            state["calls"] += 1
            if not state["crashed"] and state["calls"] == 3:
                state["crashed"] = True
                raise RuntimeError("injected crash before first checkpoint")
            return real_grad(*args, **kw)

        monkeypatch.setattr(host_math, "dense_grad", flaky_grad)
        group = ServerGroup(2, 2, ps_param_dim(cfg), learning_rate=0.5, sync=True)
        with group:
            with pytest.raises(Exception):
                run_ps_workers(cfg, group.hosts, range(2), save=False)
            assert state["crashed"]
            sidecar = os.path.join(ck, "ps_latest.json")
            assert not os.path.exists(sidecar)  # crash predates any ckpt

            monkeypatch.setattr(host_math, "dense_grad", real_grad)
            resumed = run_ps_workers(
                cfg, group.hosts, range(2), save=False, resume=True,
            )
        with open(sidecar) as f:
            sc = json.load(f)
        assert sc["attempt"] == 1
        assert sc["epoch"] == 3  # final checkpoint of the resumed run

        # Oracle: from-scratch run, fresh group, fresh checkpoint dir
        # (sync full-batch is deterministic; same Q2 deterministic init).
        ref = run_ps_local(
            cfg.replace(checkpoint_dir=str(tmp_path / "ck_ref")), save=False,
        )
        np.testing.assert_allclose(resumed[0], ref[0], rtol=1e-5, atol=1e-6)


class TestServerSupervisor:
    """Server-side crash recovery (VERDICT r2 #3): ServerSupervisor
    respawns SIGKILLed server ranks on their original ports and re-seeds
    the slice from a rolling snapshot — the complement of the
    worker-crash tests above.  The reference's outcome for a dead server
    is (like everything else) an eternal hang."""

    def test_sync_group_refused(self):
        from distlr_tpu.ps import ServerSupervisor

        with ServerGroup(1, 1, dim=4, sync=True) as g:
            with pytest.raises(ValueError, match="async"):
                ServerSupervisor(g)

    def _wait_event(self, sup, rank, event, deadline_s=10.0):
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            if any(r == rank and ev == event for _, r, ev in sup.events):
                return True
            time.sleep(0.05)
        return False

    def test_sigkill_respawn_reseeds_slice_from_snapshot(self):
        from distlr_tpu.ps import ServerSupervisor

        with ServerGroup(2, 1, dim=8, sync=False, learning_rate=1.0) as g:
            ports_before = list(g.ports)
            sup = ServerSupervisor(g, poll_interval=0.05, snapshot_interval=0.05)
            with KVWorker(g.hosts, 8, timeout_ms=5000, sync_group=False) as kv:
                kv.wait(kv.push_init(np.arange(8, dtype=np.float32)))
            with sup:
                time.sleep(0.4)  # a post-init snapshot lands
                g.procs[1].kill()  # SIGKILL rank 1 (keys 4..8)
                assert self._wait_event(sup, 1, "respawned")
                assert self._wait_event(sup, 1, "reseeded")
            assert g.ports == ports_before  # hosts string still valid
            assert all(g.alive())
            with KVWorker(g.hosts, 8, timeout_ms=5000, sync_group=False) as kv2:
                np.testing.assert_allclose(kv2.pull(), np.arange(8))
                kv2.shutdown_servers()

    def test_snapshot_skips_untouched_ranges(self):
        """Keyed snapshots (VERDICT r3 #6): a rank whose total_pushes
        counter hasn't moved since its last capture must NOT be re-pulled
        every interval — snapshot cost scales with write traffic, not
        key-space size.  Observed via the servers' total_pulls counters:
        after the first capture, idle cycles add zero pulls; pushing to
        one rank's range makes only THAT rank's pulls advance."""
        from distlr_tpu.ps import ServerSupervisor

        with ServerGroup(2, 1, dim=8, sync=False, learning_rate=1.0) as g:
            sup = ServerSupervisor(g, poll_interval=0.05,
                                   snapshot_interval=0.05)
            with KVWorker(g.hosts, 8, timeout_ms=5000, sync_group=False) as kv:
                kv.wait(kv.push_init(np.zeros(8, np.float32)))
                with sup:
                    # first capture lands, then several idle cycles
                    t0 = time.monotonic()
                    while not all(sup._snap_valid):
                        assert time.monotonic() - t0 < 10.0, "no snapshot"
                        time.sleep(0.02)
                    time.sleep(0.5)  # ~10 idle snapshot intervals
                    pulls_idle = [kv.stats(r)["total_pulls"] for r in (0, 1)]
                    time.sleep(0.5)
                    pulls_idle2 = [kv.stats(r)["total_pulls"] for r in (0, 1)]
                    assert pulls_idle2 == pulls_idle, (
                        "idle ranges were re-pulled every interval")
                    # touch ONLY rank 0's range (keys 0..4)
                    kv.wait(kv.push(np.ones(4, np.float32),
                                    keys=np.arange(4, dtype=np.uint64)))
                    time.sleep(0.5)
                    pulls_after = [kv.stats(r)["total_pulls"] for r in (0, 1)]
                    assert pulls_after[0] > pulls_idle2[0], (
                        "touched range was never re-captured")
                    assert pulls_after[1] == pulls_idle2[1], (
                        "untouched range was re-pulled")
                    kv.shutdown_servers()

    def test_snapshot_captures_healthy_ranks_while_one_is_down(self):
        """Per-rank capture isolation (r4 review finding): one dead rank
        must not fail the whole snapshot cycle — that would silently
        freeze the HEALTHY ranks' slices and unbound the
        snapshot_interval loss guarantee (e.g. after a rank exhausts
        max_respawns and is left down for hours)."""
        from distlr_tpu.ps import ServerSupervisor

        with ServerGroup(2, 1, dim=8, sync=False, learning_rate=1.0) as g:
            sup = ServerSupervisor(g)  # not started: drive captures directly
            with KVWorker(g.hosts, 8, timeout_ms=5000, sync_group=False) as kv:
                kv.wait(kv.push_init(np.arange(8, dtype=np.float32)))
            g.procs[1].kill()
            g.procs[1].wait(timeout=5)
            sup._try_snapshot()
            assert sup._snap_valid[0] and not sup._snap_valid[1]
            np.testing.assert_allclose(sup._snapshot[:4], np.arange(4))
            # rank 0 keeps absorbing updates; its slice must keep moving
            with KVWorker(f"127.0.0.1:{g.ports[0]}", 4, timeout_ms=5000,
                          sync_group=False) as kv0:
                kv0.wait(kv0.push(np.ones(4, np.float32)))  # w -= lr*1
            sup._try_snapshot()
            np.testing.assert_allclose(sup._snapshot[:4],
                                       np.arange(4) - 1.0)

    def test_sigkill_recovery_loses_at_most_snapshot_window(self):
        """The loss bound (VERDICT r3 #6): a SIGKILL-recovered rank loses
        at most the updates applied after its last snapshot capture.
        Deterministic accounting: lr=1 and unit gradients on key 0 make
        weight[0] = -(number of applied updates), so the recovered value
        must land in [-(n1+n2+n3), -(n1+n3)] — phase-A updates (snapshot
        confirmed to postdate them) and phase-C updates (post-recovery)
        can never be lost; only the n2 pushed inside the final snapshot
        window may be."""
        from distlr_tpu.ps import ServerSupervisor

        n1, n2, n3 = 5, 3, 4
        g_unit = np.array([1, 0, 0, 0], np.float32)  # key 0 -> rank 0
        with ServerGroup(2, 1, dim=4, sync=False, learning_rate=1.0) as g:
            sup = ServerSupervisor(g, poll_interval=0.05,
                                   snapshot_interval=0.05)
            with sup:
                with KVWorker(g.hosts, 4, timeout_ms=5000,
                              sync_group=False) as kv:
                    kv.wait(kv.push_init(np.zeros(4, np.float32)))
                    for _ in range(n1):  # phase A: blocking => applied
                        kv.wait(kv.push(g_unit))
                    t_a = time.monotonic()
                    # wait until rank 0's snapshot capture postdates
                    # phase A — those n1 updates are now unlosable
                    while sup._snap_at[0] <= t_a:
                        assert time.monotonic() - t_a < 10.0, "no snapshot"
                        time.sleep(0.02)
                    for _ in range(n2):  # phase B: inside the loss window
                        kv.wait(kv.push(g_unit))
                    g.procs[0].kill()
                assert self._wait_event(sup, 0, "respawned")
                assert self._wait_event(sup, 0, "reseeded")  # not zeros
                with KVWorker(g.hosts, 4, timeout_ms=5000,
                              sync_group=False) as kv2:
                    for _ in range(n3):  # phase C: post-recovery
                        kv2.wait(kv2.push(g_unit))
                    w0 = float(kv2.pull()[0])
                    kv2.shutdown_servers()
        applied = -w0
        assert n1 + n3 <= applied <= n1 + n2 + n3, (
            f"applied={applied}, bound=[{n1 + n3}, {n1 + n2 + n3}] "
            f"(events: {sup.events})")

    def _async_run_with_killer(self, tmp_path, kill_policy, *,
                               num_iteration, max_restarts,
                               max_respawns=3):
        """Shared scaffold for the SIGKILL recovery tests: synthetic
        data, a 2-worker/2-server async run with the supervisor
        attached, and a killer thread driving ``kill_policy(group,
        stop)`` until it returns or training ends.  Returns
        ``(results, evals, sup)``."""
        import threading

        from distlr_tpu.config import Config
        from distlr_tpu.data.synthetic import write_synthetic_shards
        from distlr_tpu.ps import ServerSupervisor
        from distlr_tpu.train.ps_trainer import ps_param_dim, run_ps_workers

        d = str(tmp_path / "data")
        write_synthetic_shards(d, 2400, 16, num_parts=2, seed=9, sparsity=0.0)
        evals = []
        cfg = Config(
            data_dir=d, num_feature_dim=16, num_workers=2, num_servers=2,
            num_iteration=num_iteration, learning_rate=0.2, l2_c=0.0,
            batch_size=100, test_interval=num_iteration, sync_mode=False,
            ps_timeout_ms=20_000,
        )
        group = ServerGroup(2, 2, ps_param_dim(cfg), learning_rate=0.2,
                            sync=False)
        stop = threading.Event()
        killer = threading.Thread(target=kill_policy, args=(group, stop))
        with group, ServerSupervisor(group, poll_interval=0.05,
                                     snapshot_interval=0.05,
                                     max_respawns=max_respawns) as sup:
            killer.start()
            try:
                results = run_ps_workers(
                    cfg, group.hosts, range(2), save=False,
                    max_restarts=max_restarts,
                    eval_fn=lambda ep, acc: evals.append((ep, acc)),
                )
            finally:
                stop.set()
                killer.join()
        assert all(r is not None for r in results.values())
        assert np.isfinite(results[0]).all()
        # trained, not reset-to-zero/corrupt: the dense synthetic config
        # reaches ~0.9+ by these epoch counts (cf. async convergence bands)
        assert evals and evals[-1][1] >= 0.75, evals
        return results, evals, sup

    def test_async_training_survives_server_sigkill(self, tmp_path):
        """End to end: SIGKILL a server mid-async-run with the supervisor
        attached; training completes with trained (not reset, not
        corrupt) weights."""
        killed = {"at_pushes": None}

        def kill_rank1_once(group, stop):
            # deterministic mid-run kill: wait for real training progress
            # (stats probe), then SIGKILL rank 1
            while not stop.is_set():
                try:
                    pushes = group.health(timeout_ms=1000)[1]["total_pushes"]
                except Exception:
                    pushes = 0
                if pushes >= 20:
                    killed["at_pushes"] = pushes
                    group.procs[1].kill()
                    return
                time.sleep(0.02)

        _, _, sup = self._async_run_with_killer(
            tmp_path, kill_rank1_once, num_iteration=40, max_restarts=5)
        assert killed["at_pushes"] is not None, "kill never fired (run too fast?)"
        assert any(ev == "respawned" for _, r, ev in sup.events), sup.events

    def test_repeated_kills_across_ranks_all_recover(self, tmp_path):
        """Chaos variant: three kills alternating across ranks during
        one async run.  Each death exercises a fresh respawn + keyed
        re-seed cycle; the run must still finish trained (respawn
        budget, rollback, and per-rank snapshots compose across
        repeated failures, not just one)."""
        kills = []

        def killer_loop(group, stop):
            # kill rank (k % 2) each time total pushes advance another
            # ~25 past the previous kill; exactly 3 kills
            next_at = 25
            while not stop.is_set() and len(kills) < 3:
                rank = len(kills) % 2
                try:
                    pushes = sum(
                        h["total_pushes"]
                        for h in group.health(timeout_ms=1000))
                except Exception:
                    pushes = 0
                if pushes >= next_at and group.procs[rank].poll() is None:
                    kills.append((rank, pushes))
                    group.procs[rank].kill()
                    next_at = pushes + 25
                time.sleep(0.05)

        _, _, sup = self._async_run_with_killer(
            tmp_path, killer_loop, num_iteration=60, max_restarts=8,
            max_respawns=5)
        assert len(kills) == 3, f"chaos never fired fully: {kills}"
        respawns = [r for _, r, ev in sup.events if ev == "respawned"]
        # A kill landing in the final poll window before the run ends may
        # be torn down with the group instead of respawned — tolerate
        # exactly one such tail race, never more.
        assert len(respawns) >= len(kills) - 1, (kills, sup.events)


class TestSupervisorEdgeCases:
    def test_double_sigkill_reseeds_both_via_retry(self):
        """Both ranks die within one poll window: each respawned rank
        must end up re-seeded from the snapshot, never left alive-but-
        uninitialized (which would install the next gradient push AS the
        weights).  Re-seeds are per-rank connections, so neither rank's
        recovery may depend on the other being up; a re-seed that does
        fail (e.g. the respawned process not yet accepting) is retried
        via _needs_reseed, not dropped."""
        from distlr_tpu.ps import ServerSupervisor

        with ServerGroup(2, 1, dim=8, sync=False, learning_rate=1.0) as g:
            sup = ServerSupervisor(g, poll_interval=0.05, snapshot_interval=0.05)
            with KVWorker(g.hosts, 8, timeout_ms=5000, sync_group=False) as kv:
                kv.wait(kv.push_init(np.arange(8, dtype=np.float32)))
            with sup:
                time.sleep(0.4)
                g.procs[0].kill()
                g.procs[1].kill()
                t0 = time.monotonic()
                while time.monotonic() - t0 < 10.0:
                    seeded = {r for _, r, ev in sup.events if ev == "reseeded"}
                    if seeded == {0, 1}:
                        break
                    time.sleep(0.05)
                assert seeded == {0, 1}, sup.events
            with KVWorker(g.hosts, 8, timeout_ms=5000, sync_group=False) as kv2:
                np.testing.assert_allclose(kv2.pull(), np.arange(8))
                kv2.shutdown_servers()

    def test_voluntary_shutdown_is_not_a_crash(self):
        """rank 0's shutdown_servers at the end of a clean run exits every
        server with code 0; the supervisor must not misread that as a
        group-wide crash and respawn uninitialized servers."""
        from distlr_tpu.ps import ServerSupervisor

        with ServerGroup(2, 1, dim=4, sync=False) as g:
            with ServerSupervisor(g, poll_interval=0.05,
                                  snapshot_interval=0.05) as sup:
                with KVWorker(g.hosts, 4, timeout_ms=5000,
                              sync_group=False) as kv:
                    kv.wait(kv.push_init(np.zeros(4, np.float32)))
                    kv.shutdown_servers()
                for p in g.procs:
                    p.wait(timeout=5)
                time.sleep(0.3)  # several poll cycles after retirement
                assert sup.events == [], sup.events
                assert all(p.poll() == 0 for p in g.procs)


class TestWireCorruption:
    """Wire values size allocations on the server; garbage must drop the
    connection, never kill the group member (a bad_alloc from
    resize(2^50) would take down the whole rank and trigger a pointless
    supervisor respawn)."""

    HEADER = "<IBBHIIQ"  # kv_protocol.h MsgHeader, 24 bytes packed
    MAGIC = 0xD157C0DE

    def _frame(self, op, num_keys):
        import struct
        return struct.pack(self.HEADER, self.MAGIC, op, 0, 0, 99, 1, num_keys)

    def test_huge_num_keys_drops_connection_not_server(self):
        import socket
        import struct

        with ServerGroup(1, 1, dim=8, sync=False) as g:
            port = g.ports[0]
            with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
                s.sendall(self._frame(op=1, num_keys=1 << 50))  # kPush
                # server must close on us, not crash
                assert s.recv(1) == b""
            with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
                # key id past the elasticity cap: same outcome
                s.sendall(self._frame(op=2, num_keys=1))  # kPull
                s.sendall(struct.pack("<Q", 1 << 60))
                assert s.recv(1) == b""
            assert all(g.alive())
            # and the server still serves real clients afterwards
            with KVWorker(g.hosts, 8, timeout_ms=5000, sync_group=False) as kv:
                assert kv.stats(0)["dim"] == 8
                kv.shutdown_servers()

    def test_unsorted_push_frame_grows_to_max_key(self):
        """Regression (r4 review): capacity used to grow to keys.back(),
        which assumes sorted keys — an unsorted frame like [100, 3] on a
        dim-8 server would write weights_[100] out of bounds.  The wire
        does not promise ordering, so the server must size by the
        frame's MAX key and apply both updates."""
        import socket
        import struct

        with ServerGroup(1, 1, dim=8, sync=False, learning_rate=1.0) as g:
            with socket.create_connection(("127.0.0.1", g.ports[0]),
                                          timeout=5) as s:
                # async push, keys [100, 3] (unsorted), grads [2.0, 5.0]
                s.sendall(self._frame(op=1, num_keys=2))
                s.sendall(struct.pack("<QQ", 100, 3))
                s.sendall(struct.pack("<ff", 2.0, 5.0))
                # first-ever push takes the init branch: seeds weights
                resp = s.recv(24)
                assert len(resp) == 24
            assert all(g.alive())
            with KVWorker(g.hosts, 101, timeout_ms=5000,
                          sync_group=False) as kv:
                w = kv.pull()
                assert w[100] == 2.0 and w[3] == 5.0  # init semantics
                kv.shutdown_servers()

    def test_alloc_failure_drops_connection_not_server(self):
        """A key just UNDER the elasticity cap passes every guard but
        demands a huge EnsureCapacity resize; the bad_alloc must drop
        the connection, not std::terminate the rank.  Deterministic via
        an address-space rlimit on a directly-spawned server."""
        import shlex
        import socket
        import struct
        import subprocess

        from distlr_tpu.ps.build import server_binary

        # ulimit via a shell wrapper, NOT preexec_fn: preexec_fn forces
        # a raw os.fork() in this (JAX-)multithreaded test process —
        # a documented deadlock risk — while a plain argv spawn uses
        # posix_spawn.
        cmd = (f"ulimit -v {1 << 20}; exec "  # 1 GiB of address space
               f"{shlex.quote(server_binary())} --port=0 --num_workers=1 "
               f"--dim=8 --sync=0")
        proc = subprocess.Popen(
            ["/bin/sh", "-c", cmd],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        try:
            line = proc.stdout.readline().strip()
            assert line.startswith("PORT "), line
            port = int(line.split()[1])
            with socket.create_connection(("127.0.0.1", port), timeout=10) as s:
                # pull of key 2^31-1: under the default cap, but the
                # resize to ~16 GiB cannot fit in a 1 GiB address space
                s.sendall(self._frame(op=2, num_keys=1))
                s.sendall(struct.pack("<Q", (1 << 31) - 1))
                assert s.recv(1) == b""  # dropped, not served
            assert proc.poll() is None  # rank still alive
            # still serves real clients afterwards
            with KVWorker(f"127.0.0.1:{port}", 8, timeout_ms=5000,
                          sync_group=False) as kv:
                assert kv.stats(0)["dim"] == 8
                kv.shutdown_servers()
            proc.wait(timeout=10)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()


class TestConnectTimeout:
    def test_unresponsive_host_fails_fast(self, monkeypatch):
        """kv_connect to a host that drops SYNs must fail within the
        bounded connect timeout, not the kernel's minutes-long SYN-retry
        window (a DCN partition would otherwise freeze supervisor probes
        and worker restarts mid-op).  Reproduced locally by saturating a
        backlog-0 listener's accept queue: the kernel then silently
        drops further SYNs — exactly the partitioned-host picture."""
        import socket

        from distlr_tpu.ps.build import build_native

        build_native()  # keep a cold-start compile out of the timing window
        monkeypatch.setenv("DISTLR_CONNECT_TIMEOUT_MS", "400")
        lst = socket.socket()
        try:
            lst.bind(("127.0.0.1", 0))
            lst.listen(0)
            host, port = lst.getsockname()
            saturate = socket.create_connection((host, port))
            try:
                t0 = time.monotonic()
                with pytest.raises(ConnectionError):
                    KVWorker(f"{host}:{port}", 8, timeout_ms=1000,
                             sync_group=False)
                assert time.monotonic() - t0 < 5.0
            finally:
                saturate.close()
        finally:
            lst.close()
