"""Tests for the native parameter-server stack (C++ server + ctypes client).

These run real server subprocesses on localhost ports — the same
multi-process-on-one-machine strategy the reference uses for cluster
testing (SURVEY.md §4), minus the env-var role faking.
"""

import threading
import time

import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.ps import KVWorker, ServerGroup
from distlr_tpu.train.ps_trainer import run_ps_local
from distlr_tpu.data.synthetic import write_synthetic_shards


@pytest.fixture(scope="module")
def ps_data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("psdata")
    write_synthetic_shards(str(d), 1200, 16, num_parts=2, seed=4, sparsity=0.0)
    return str(d)


class TestKVBasics:
    def test_init_pull_roundtrip(self):
        with ServerGroup(1, 1, dim=8) as sg, KVWorker(sg.hosts, 8) as kv:
            init = np.arange(8, dtype=np.float32)
            kv.wait(kv.push(init))
            np.testing.assert_array_equal(kv.pull(), init)

    def test_range_sharding_uneven(self):
        # dim=10 over 3 servers -> ranges [0,3) [3,6) [6,10)
        with ServerGroup(3, 1, dim=10) as sg, KVWorker(sg.hosts, 10) as kv:
            init = np.linspace(0, 9, 10).astype(np.float32)
            kv.push(init)
            np.testing.assert_allclose(kv.pull(), init)
            # partial pull crossing a range boundary
            keys = np.array([2, 3, 4, 7], dtype=np.uint64)
            np.testing.assert_allclose(kv.pull(keys), init[[2, 3, 4, 7]])

    def test_async_applies_immediately(self):
        with ServerGroup(1, 2, dim=4, sync=False, learning_rate=1.0) as sg:
            kv = KVWorker(sg.hosts, 4)
            kv.push(np.zeros(4, np.float32))  # init
            kv.push(np.ones(4, np.float32))   # w -= 1*g
            np.testing.assert_allclose(kv.pull(), -np.ones(4))
            kv.close()

    def test_sync_push_blocks_until_all_workers(self):
        """The deferred reply is the BSP barrier: one worker's push must
        not return until the other worker pushes too."""
        with ServerGroup(1, 2, dim=4, sync=True, learning_rate=0.5) as sg:
            kv0 = KVWorker(sg.hosts, 4, client_id=0)
            kv1 = KVWorker(sg.hosts, 4, client_id=1)
            kv0.push(np.zeros(4, np.float32))  # init (responds immediately)

            t_done = []

            def push0():
                kv0.push(np.full(4, 2.0, np.float32))
                t_done.append(time.monotonic())

            th = threading.Thread(target=push0)
            th.start()
            time.sleep(0.3)
            assert not t_done, "sync push returned before all workers pushed"
            t_release = time.monotonic()
            kv1.push(np.full(4, 4.0, np.float32))
            th.join(timeout=5)
            assert t_done and t_done[0] >= t_release - 0.05
            # correct-mean update: w -= lr * (g0+g1)/2 = -0.5*3
            np.testing.assert_allclose(kv0.pull(), np.full(4, -1.5))
            kv0.close()
            kv1.close()

    def test_q1_last_gradient_mode(self):
        with ServerGroup(1, 2, dim=4, sync=True, learning_rate=1.0, last_gradient=True) as sg:
            kv0 = KVWorker(sg.hosts, 4, client_id=0)
            kv1 = KVWorker(sg.hosts, 4, client_id=1)
            kv0.push(np.zeros(4, np.float32))
            th = threading.Thread(target=lambda: kv0.push(np.full(4, 2.0, np.float32)))
            th.start()
            time.sleep(0.2)  # ensure kv0's push arrives first
            kv1.push(np.full(4, 4.0, np.float32))  # last arrival
            th.join(timeout=5)
            # Q1: w -= lr * g_last / W = -4/2 = -2 (NOT the mean -3)
            np.testing.assert_allclose(kv0.pull(), np.full(4, -2.0))
            kv0.close()
            kv1.close()

    def test_worker_group_barrier(self):
        with ServerGroup(1, 2, dim=2) as sg:
            kv0 = KVWorker(sg.hosts, 2, client_id=0)
            kv1 = KVWorker(sg.hosts, 2, client_id=1)
            released = []

            def b0():
                kv0.barrier()
                released.append(0)

            th = threading.Thread(target=b0)
            th.start()
            time.sleep(0.2)
            assert not released
            kv1.barrier()
            th.join(timeout=5)
            assert released == [0]
            kv0.close()
            kv1.close()

    def test_connect_failure_raises(self):
        with pytest.raises(ConnectionError):
            KVWorker("127.0.0.1:1", 4)

    def test_invalid_keys_rejected(self):
        with ServerGroup(2, 1, dim=8) as sg, KVWorker(sg.hosts, 8) as kv:
            kv.push(np.zeros(8, np.float32))
            with pytest.raises(ValueError, match="ascending"):
                kv.pull(np.array([5, 2], dtype=np.uint64))
            with pytest.raises(ValueError, match="out of range"):
                kv.pull(np.array([3, 8], dtype=np.uint64))

    def test_shutdown_with_multiple_workers_connected(self):
        """Shutdown must terminate the server even while other workers
        hold open connections (their reads are unblocked)."""
        with ServerGroup(1, 2, dim=4) as sg:
            kv0 = KVWorker(sg.hosts, 4, client_id=0)
            kv1 = KVWorker(sg.hosts, 4, client_id=1)  # idle second connection
            kv0.push(np.zeros(4, np.float32))
            kv0.shutdown_servers()
            sg.procs[0].wait(timeout=5)  # server process actually exits
            assert sg.procs[0].returncode == 0
            kv0.close()
            kv1.close()

    def test_worker_failure_does_not_hang_peers(self, ps_data_dir, tmp_path):
        """A worker that dies (missing shard) must fail the run, not
        deadlock the surviving workers at the sync barrier."""
        import shutil

        broken = tmp_path / "broken"
        shutil.copytree(ps_data_dir, broken)
        (broken / "train" / "part-002").unlink()  # worker 1's shard gone
        cfg = Config(
            data_dir=str(broken), num_feature_dim=16, num_workers=2,
            num_servers=1, num_iteration=5, sync_mode=True, test_interval=0,
        )
        with pytest.raises(Exception):
            run_ps_local(cfg)


class TestPSTraining:
    def test_sync_ps_converges(self, ps_data_dir):
        cfg = Config(
            data_dir=ps_data_dir, num_feature_dim=16, num_workers=2, num_servers=2,
            num_iteration=40, learning_rate=0.5, l2_c=0.0, batch_size=-1,
            test_interval=20, sync_mode=True,
        )
        evals = []
        results = run_ps_local(cfg, eval_fn=lambda ep, acc: evals.append((ep, acc)))
        assert all(r is not None for r in results)
        # sync: every worker ends with identical weights
        np.testing.assert_allclose(results[0], results[1], atol=1e-5)
        assert evals[-1][1] > 0.8, f"sync PS accuracy {evals}"

    def test_async_ps_converges(self, ps_data_dir):
        cfg = Config(
            data_dir=ps_data_dir, num_feature_dim=16, num_workers=2, num_servers=1,
            num_iteration=40, learning_rate=0.2, l2_c=0.0, batch_size=100,
            test_interval=40, sync_mode=False,
        )
        evals = []
        results = run_ps_local(cfg, eval_fn=lambda ep, acc: evals.append((ep, acc)))
        assert all(r is not None for r in results)
        assert evals[-1][1] > 0.8, f"async PS accuracy {evals}"

    def test_ps_matches_spmd_sync_result(self, ps_data_dir):
        """PS sync mode and the SPMD psum path implement the same math:
        full-batch runs from the same init must track each other."""
        from distlr_tpu.parallel import make_mesh
        from distlr_tpu.train import Trainer

        common = dict(
            data_dir=ps_data_dir, num_feature_dim=16, num_iteration=10,
            learning_rate=0.3, l2_c=0.0, batch_size=-1, test_interval=0,
            compat_mode="reference",  # identical deterministic init (Q2)
        )
        # correct-mean sync in both paths
        cfg_ps = Config(num_workers=2, num_servers=1, sync_mode=True,
                        sync_last_gradient=False, **common)
        ps_w = run_ps_local(cfg_ps)[0]

        cfg_spmd = Config(sync_last_gradient=False, **common)
        tr = Trainer(cfg_spmd, mesh=make_mesh({"data": 2})).load_data()
        spmd_w = np.asarray(tr.fit())
        np.testing.assert_allclose(ps_w, spmd_w, atol=5e-2)


class TestMultiHostSurface:
    def test_ps_workers_join_external_group(self, ps_data_dir):
        """Two `run_ps_workers` calls with disjoint rank subsets (the
        multi-host deployment shape: each host runs its ranks against a
        shared `launch ps-server` group) train one model together, and
        rank 0's Finalize-parity exit retires the server processes."""
        from distlr_tpu.train.ps_trainer import run_ps_workers

        cfg = Config(
            data_dir=ps_data_dir, num_feature_dim=16, num_workers=2,
            num_servers=2, num_iteration=20, learning_rate=0.5, l2_c=0.0,
            batch_size=-1, test_interval=0, sync_mode=True,
        )
        group = ServerGroup(2, 2, dim=16, learning_rate=0.5, sync=True)
        with group:
            out = {}

            def host(ranks):
                out.update(run_ps_workers(cfg, group.hosts, ranks))

            hosts = [threading.Thread(target=host, args=([r],)) for r in (0, 1)]
            for t in hosts:
                t.start()
            for t in hosts:
                t.join()
            assert set(out) == {0, 1}
            np.testing.assert_allclose(out[0], out[1], atol=1e-5)
            # rank 0 shut the group down at the exit barrier
            for p in group.procs:
                p.wait(timeout=5)
            assert not any(group.alive())


class TestPSComputeDevice:
    """PS workers pick the step device by workload size (dispatch-latency
    avoidance for tiny reference-scale models)."""

    def test_a_size_between_the_thresholds_picks_the_host_cpu(
            self, monkeypatch, ps_steps_on):
        import jax

        from distlr_tpu.train.ps_trainer import ps_compute_device

        cfg = Config(num_feature_dim=16, batch_size=64)
        with ps_steps_on("device"):
            assert ps_compute_device(cfg, rows=64) is None
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with ps_steps_on("cpu"):
            dev = ps_compute_device(cfg, rows=64)
        assert dev is not None and dev.platform == "cpu"

    def test_auto_thresholds(self, monkeypatch, ps_steps_on):
        import jax

        from distlr_tpu.train import ps_trainer

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        # tiny step -> plain numpy (jit dispatch itself dominates)
        small = Config(num_feature_dim=123, batch_size=256)
        assert ps_trainer.ps_compute_device(small) == "numpy"
        # mid-size step -> jitted host CPU backend
        mid = Config(num_feature_dim=20_000, batch_size=256)
        assert ps_trainer.ps_compute_device(mid).platform == "cpu"
        # big step -> default (accelerator) backend
        big = Config(num_feature_dim=1_000_000, batch_size=4096)
        assert ps_trainer.ps_compute_device(big) is None
        # full-shard batch (-1) with unknown size assumed big
        full = Config(num_feature_dim=1_000_000, batch_size=-1)
        assert ps_trainer.ps_compute_device(full) is None
        # ...but the actual row count decides when known: a small shard
        # stays on host, a huge eval set goes to the accelerator
        assert ps_trainer.ps_compute_device(small.replace(batch_size=-1), rows=2000) == "numpy"
        assert ps_trainer.ps_compute_device(mid.replace(batch_size=-1), rows=1000).platform == "cpu"
        assert ps_trainer.ps_compute_device(small, rows=5_000_000) is None
        # under a threshold that is over it, the big step is numpy's too
        with ps_steps_on("numpy"):
            assert ps_trainer.ps_compute_device(big) == "numpy"

    def test_auto_on_cpu_platform_is_default(self):
        # Under the test conftest the default backend IS cpu: auto must
        # not commit arrays (None = uncommitted default placement).
        from distlr_tpu.train.ps_trainer import ps_compute_device

        assert ps_compute_device(Config(num_feature_dim=123, batch_size=256)) is None

    def test_no_option_overrides_the_choice(self):
        with pytest.raises(TypeError, match="ps_compute_backend"):
            Config(ps_compute_backend="numpy")


class TestKeyedOps:
    """Keyed (subset) Push/Pull — the ps-lite sliced-key capability the
    reference app never exercises (its key set is always dense 0..D-1)."""

    def test_keyed_push_pull_across_ranges(self):
        dim = 10
        group = ServerGroup(2, 1, dim, learning_rate=1.0, sync=False)
        with group:
            with KVWorker(group.hosts, dim, timeout_ms=20_000) as kv:
                kv.wait(kv.push(np.zeros(dim, np.float32)))  # init
                # touched keys straddle the two server ranges [0,5) and [5,10)
                keys = np.array([1, 4, 5, 9], np.uint64)
                kv.wait(kv.push(np.array([1, 2, 3, 4], np.float32), keys=keys))
                w = kv.pull()
                expect = np.zeros(dim, np.float32)
                expect[[1, 4, 5, 9]] = [-1, -2, -3, -4]  # async applies w -= lr*g
                np.testing.assert_allclose(w, expect)
                # keyed pull of a different subset
                np.testing.assert_allclose(
                    kv.pull(keys=np.array([0, 4, 9], np.uint64)), [0, -2, -4]
                )
                kv.shutdown_servers()

    def test_sync_keyed_push_skipping_a_range_keeps_barrier(self):
        """BSP regression: a keyed push whose slice for some server is
        EMPTY must still count toward that server's barrier (the client
        sends an empty 'present' vote), or peers that did touch the range
        deadlock waiting for the round to fill."""
        dim = 10  # ranges [0,5) and [5,10)
        group = ServerGroup(2, 2, dim, learning_rate=1.0, sync=True)
        with group:
            kv0 = KVWorker(group.hosts, dim, client_id=0, timeout_ms=20_000)
            kv1 = KVWorker(group.hosts, dim, client_id=1, timeout_ms=20_000)
            kv0.wait(kv0.push(np.zeros(dim, np.float32)))  # init (full)
            done = []

            def push0():  # touches ONLY server 0's range
                kv0.wait(kv0.push(np.array([2.0], np.float32),
                                  keys=np.array([1], np.uint64)))
                done.append(0)

            th = threading.Thread(target=push0, daemon=True)
            th.start()
            # touches ONLY server 1's range — without empty votes, server 0
            # would wait forever for this worker and kv0 would hang
            kv1.wait(kv1.push(np.array([4.0], np.float32),
                              keys=np.array([7], np.uint64)))
            th.join(timeout=15)
            assert done == [0], "sync keyed push deadlocked across ranges"
            # correct-mean round: w -= lr * g/2 on each touched key
            w = kv0.pull()
            expect = np.zeros(dim, np.float32)
            expect[1], expect[7] = -1.0, -2.0
            np.testing.assert_allclose(w, expect)
            kv0.close()
            kv1.close()


class TestPSSparse:
    """sparse_lr over the PS: keyed pulls/pushes of only the touched
    columns per batch."""

    def _cfg(self, d, **kw):
        return Config(
            data_dir=d, num_feature_dim=128, model="sparse_lr",
            num_iteration=40, learning_rate=1.0, l2_c=0.0, test_interval=20,
            batch_size=100, num_workers=2, num_servers=2, **kw,
        )

    @pytest.mark.parametrize("sync", [True, False], ids=["sync", "async"])
    def test_sparse_ps_converges(self, tmp_path, sync):
        from distlr_tpu.data.hashing import write_ctr_shards
        from distlr_tpu.train.ps_trainer import run_ps_local

        d = str(tmp_path / "ctr")
        write_ctr_shards(d, 1200, 6, 200, 128, num_parts=2, seed=5)
        accs = []
        run_ps_local(self._cfg(d, sync_mode=sync),
                     eval_fn=lambda _e, a: accs.append(a))
        # oracle (true hashed weights) scores ~0.81 on this config
        assert accs[-1] > 0.70, f"sparse PS accuracy {accs[-1]}"

    def test_sparse_ps_matches_trainer_math(self, tmp_path):
        """One sync full-batch step over the PS equals SparseBinaryLR.grad
        applied directly (same mean-of-worker-gradients update)."""
        from distlr_tpu.data.hashing import write_ctr_shards
        from distlr_tpu.data.iterator import SparseDataIter
        from distlr_tpu.models import SparseBinaryLR
        from distlr_tpu.train.ps_trainer import run_ps_local

        d = str(tmp_path / "ctr")
        write_ctr_shards(d, 300, 6, 100, 64, num_parts=2, seed=3)
        cfg = Config(
            data_dir=d, num_feature_dim=64, model="sparse_lr",
            num_iteration=1, learning_rate=0.5, l2_c=0.0, test_interval=0,
            batch_size=-1, num_workers=2, num_servers=2, sync_mode=True,
        )
        ws = run_ps_local(cfg, save=False)

        model = SparseBinaryLR(64)
        w = np.asarray(model.init(cfg)).reshape(-1)
        import os as _os

        grads = []
        for rank in range(2):
            it = SparseDataIter.from_file(
                _os.path.join(d, "train", f"part-{rank + 1:03d}"), 64, -1
            )
            cols, vals, y, mask = it.next_batch()
            g = model.grad(
                np.asarray(w), (cols, vals, y.astype(np.int32), mask.astype(np.float32)), cfg
            )
            grads.append(np.asarray(g))
        expect = w - 0.5 * (grads[0] + grads[1]) / 2
        np.testing.assert_allclose(ws[0], expect, rtol=1e-5, atol=1e-6)


class TestSparseDataIter:
    def test_roundtrip_from_libsvm(self, tmp_path):
        from distlr_tpu.data.hashing import write_ctr_shards
        from distlr_tpu.data.iterator import SparseDataIter

        d = str(tmp_path / "ctr")
        man = write_ctr_shards(d, 50, 4, 30, 32, num_parts=1, seed=2)
        it = SparseDataIter.from_file(man["train_parts"][0], 32, batch_size=16)
        cols, vals, y, mask = it.next_batch()
        assert cols.shape == vals.shape == (16, cols.shape[1])
        assert cols.shape[1] <= 4  # one-hot rows: at most F entries
        assert mask.all()
        n = 16
        for cols, vals, y, mask in it:
            n += int(mask.sum())
        assert n == it.num_samples


class TestKeyedOpsModes:
    def test_async_client_skips_untouched_servers(self):
        """sync_group=False: a keyed push whose slice for a server is
        empty skips it entirely (no barrier to vote in) — observable via
        that server's push counter."""
        dim = 10
        group = ServerGroup(2, 1, dim, learning_rate=1.0, sync=False)
        with group:
            with KVWorker(group.hosts, dim, timeout_ms=20_000, sync_group=False) as kv:
                kv.wait(kv.push(np.zeros(dim, np.float32)))  # init: both servers
                kv.wait(kv.push(np.array([1.0], np.float32),
                                keys=np.array([2], np.uint64)))  # server 0 only
                s0, s1 = kv.stats(0), kv.stats(1)
                assert s0["total_pushes"] == 2
                assert s1["total_pushes"] == 1, "async empty vote was sent anyway"
                kv.shutdown_servers()

    def test_sparse_q1_compat_rejected(self, tmp_path):
        """Q1 (last-gradient) is a dense parity quirk; sparse PS must
        refuse it rather than nondeterministically drop rounds."""
        from distlr_tpu.train.ps_trainer import PSWorker

        cfg = Config(
            data_dir=str(tmp_path), num_feature_dim=32, model="sparse_lr",
            compat_mode="reference", num_workers=1, num_servers=1,
        )
        with pytest.raises(ValueError, match="sync_last_gradient"):
            PSWorker(cfg, 0, "127.0.0.1:1")


class TestPSCheckpointResume:
    """PS-mode durable checkpoint + resume (SURVEY.md §5.4 — the
    reference can only text-dump final weights, no load path at all)."""

    def test_resume_matches_straight_run(self, ps_data_dir, tmp_path):
        """Sync full-batch PS is deterministic: 4 epochs + resume(4 more)
        must equal a straight 8-epoch run."""
        base = Config(
            data_dir=ps_data_dir, num_feature_dim=16, num_workers=2,
            num_servers=2, learning_rate=0.5, l2_c=0.0, batch_size=-1,
            test_interval=0, sync_mode=True,
        )
        straight = run_ps_local(base.replace(num_iteration=8), save=False)

        ck = str(tmp_path / "ck")
        cfg = base.replace(checkpoint_dir=ck, checkpoint_interval=2)
        run_ps_local(cfg.replace(num_iteration=4), save=False)
        import os
        assert os.path.exists(os.path.join(ck, "ps_latest.json"))
        resumed = run_ps_local(cfg.replace(num_iteration=8), save=False, resume=True)
        np.testing.assert_allclose(resumed[0], straight[0], rtol=1e-5, atol=1e-6)

    def test_resume_without_checkpoint_starts_fresh(self, ps_data_dir, tmp_path):
        cfg = Config(
            data_dir=ps_data_dir, num_feature_dim=16, num_workers=2,
            num_servers=1, num_iteration=3, learning_rate=0.5, l2_c=0.0,
            batch_size=-1, test_interval=0, sync_mode=True,
            checkpoint_dir=str(tmp_path / "empty"), checkpoint_interval=2,
        )
        results = run_ps_local(cfg, save=False, resume=True)
        assert all(r is not None for r in results)

    def test_async_checkpoints_written(self, ps_data_dir, tmp_path):
        from distlr_tpu.train.checkpoint import Checkpointer

        ck = str(tmp_path / "ck")
        cfg = Config(
            data_dir=ps_data_dir, num_feature_dim=16, num_workers=2,
            num_servers=1, num_iteration=5, learning_rate=0.2, l2_c=0.0,
            batch_size=200, test_interval=0, sync_mode=False,
            checkpoint_dir=ck, checkpoint_interval=2,
        )
        run_ps_local(cfg, save=False)
        with Checkpointer(ck) as c:
            steps = c.all_steps()
        assert 5 in steps, f"final checkpoint missing: {steps}"


class TestPSSoftmax:
    def test_softmax_ps_converges(self, tmp_path):
        d = str(tmp_path / "mc")
        write_synthetic_shards(d, 1500, 12, num_parts=2, seed=7,
                               num_classes=4, sparsity=0.0)
        cfg = Config(
            data_dir=d, num_feature_dim=12, model="softmax", num_classes=4,
            num_workers=2, num_servers=2, num_iteration=60,
            learning_rate=0.5, l2_c=0.0, batch_size=-1, test_interval=30,
            sync_mode=True,
        )
        accs = []
        run_ps_local(cfg, eval_fn=lambda _e, a: accs.append(a), save=False)
        assert accs[-1] > 0.6, f"softmax PS accuracy {accs}"


class TestFusedPushPull:
    """kPushPull: one round trip per batch replaces the reference's two
    (src/lr.cc:116-132).  Sync: the deferred reply carries the post-round
    weights = bit-identical to the pull that would have followed."""

    def test_async_applies_and_returns_fresh_weights(self):
        with ServerGroup(2, 1, dim=8, sync=False, learning_rate=1.0) as g:
            with KVWorker(g.hosts, 8, timeout_ms=20_000, sync_group=False) as kv:
                kv.wait(kv.push_init(np.arange(8, dtype=np.float32)))
                w = kv.push_pull(np.ones(8, np.float32))
                np.testing.assert_allclose(w, np.arange(8) - 1.0)
                # and the state is durable (a plain pull agrees)
                np.testing.assert_allclose(kv.pull(), w)
                kv.shutdown_servers()

    def test_sync_defers_and_returns_post_round_weights(self):
        import threading

        with ServerGroup(2, 2, dim=8, sync=True, learning_rate=0.5) as g:
            kv0 = KVWorker(g.hosts, 8, client_id=0, timeout_ms=20_000)
            kv1 = KVWorker(g.hosts, 8, client_id=1, timeout_ms=20_000)
            kv0.wait(kv0.push_init(np.zeros(8, np.float32)))
            out = {}

            def other():
                out[1] = kv1.push_pull(np.full(8, 3.0, np.float32))

            t = threading.Thread(target=other)
            t.start()
            out[0] = kv0.push_pull(np.full(8, 1.0, np.float32))
            t.join()
            # one mean BSP update: -0.5 * (1+3)/2 = -1; both workers see it
            np.testing.assert_allclose(out[0], -np.ones(8), rtol=1e-6)
            np.testing.assert_array_equal(out[0], out[1])
            kv0.shutdown_servers()
            kv0.close()
            kv1.close()

    def test_fused_sync_trajectory_equals_serialized(self, ps_data_dir):
        """ps_pipeline=True must not change sync results at all — same
        shards, same init, bitwise-equal final weights."""
        common = dict(
            data_dir=ps_data_dir, num_feature_dim=16, num_iteration=6,
            learning_rate=0.3, l2_c=0.0, batch_size=100, test_interval=0,
            compat_mode="reference", sync_last_gradient=False,
            num_workers=2, num_servers=2, sync_mode=True,
        )
        w_fused = run_ps_local(Config(ps_pipeline=True, **common))[0]
        w_serial = run_ps_local(Config(ps_pipeline=False, **common))[0]
        np.testing.assert_array_equal(w_fused, w_serial)

    def test_pipelined_async_converges(self, ps_data_dir):
        """Double-buffered Hogwild (staleness <= 1 in-flight push) still
        converges on the standard shards."""
        evals = []
        cfg = Config(
            data_dir=ps_data_dir, num_feature_dim=16, num_iteration=20,
            learning_rate=0.1, l2_c=0.0, batch_size=100, test_interval=10,
            sync_mode=False, num_workers=2, num_servers=2, ps_pipeline=True,
        )
        run_ps_local(cfg, eval_fn=lambda ep, a: evals.append((ep, a)))
        assert evals and evals[-1][1] >= 0.80, evals


class TestProtocolModelBased:
    """Randomized (seeded) op sequences against a numpy reference state
    machine: async mode, keyed subsets, fused push_pull, interleaved
    stats probes.  The targeted tests pin each mechanism alone; this
    sweeps their interactions."""

    @pytest.mark.parametrize("seed,num_servers", [(0, 1), (1, 2), (2, 3)])
    def test_random_keyed_ops_track_reference_state(self, seed, num_servers):
        dim, lr, n_ops = 32, 1.0, 60
        rng = np.random.default_rng(seed)
        with ServerGroup(num_servers, 1, dim=dim, sync=False,
                         learning_rate=lr) as g:
            with KVWorker(g.hosts, dim, timeout_ms=10_000,
                          sync_group=False) as kv:
                ref = rng.standard_normal(dim).astype(np.float32)
                kv.wait(kv.push_init(ref.copy()))
                pushes = pulls = 0
                for _ in range(n_ops):
                    op = rng.choice(["push", "pull", "push_pull", "stats",
                                     "push_vpk", "pull_vpk"])
                    k = np.sort(rng.choice(
                        dim, size=int(rng.integers(1, dim + 1)),
                        replace=False)).astype(np.uint64)
                    v = rng.standard_normal(k.size).astype(np.float32)
                    if op in ("push_vpk", "pull_vpk"):
                        # multi-val row keys (vals_per_key): exercised
                        # only where the group's ranges align (S=1/2 at
                        # dim=32); elsewhere the op maps to the expanded
                        # encoding — the same fallback decision the
                        # blocked trainer makes
                        vpk = int(rng.choice([4, 8]))
                        space = dim // vpk
                        rows = np.sort(rng.choice(
                            space, size=int(rng.integers(1, space + 1)),
                            replace=False)).astype(np.uint64)
                        flat = (rows[:, None] * vpk
                                + np.arange(vpk, dtype=np.uint64)).reshape(-1)
                        use_vpk = kv.supports_vals_per_key(vpk)
                        if op == "push_vpk":
                            g_v = rng.standard_normal(
                                flat.size).astype(np.float32)
                            if use_vpk:
                                kv.wait(kv.push(g_v, keys=rows,
                                                vals_per_key=vpk))
                            else:
                                kv.wait(kv.push(g_v, keys=flat))
                            ref[flat] -= lr * g_v
                            pushes += 1
                        else:
                            got = (kv.pull(keys=rows, vals_per_key=vpk)
                                   if use_vpk else kv.pull(keys=flat))
                            np.testing.assert_allclose(
                                got, ref[flat], rtol=1e-5, atol=1e-5)
                            pulls += 1
                    elif op == "push":
                        kv.wait(kv.push(v, keys=k))
                        ref[k] -= lr * v
                        pushes += 1
                    elif op == "pull":
                        np.testing.assert_allclose(
                            kv.pull(keys=k), ref[k], rtol=1e-5, atol=1e-5)
                        pulls += 1
                    elif op == "push_pull":
                        got = kv.push_pull(v, keys=k)
                        ref[k] -= lr * v
                        np.testing.assert_allclose(
                            got, ref[k], rtol=1e-5, atol=1e-5)
                        pushes += 1
                        pulls += 1
                    else:
                        total = sum(
                            kv.stats(r)["total_pushes"]
                            for r in range(num_servers))
                        # async keyed pushes skip empty-slice servers, so
                        # the per-server sum counts only visited ranges —
                        # it can exceed the op count (push_init visits
                        # all) but never fall below the pushes that
                        # touched at least one key
                        assert total >= pushes or num_servers > 1
                # final full-vector agreement
                np.testing.assert_allclose(kv.pull(), ref,
                                           rtol=1e-5, atol=1e-5)
                kv.shutdown_servers()


class TestValsPerKey:
    """vals_per_key wire encoding (ps-lite KVPairs.lens, uniform): one
    u64 row id addresses R consecutive flat slots.  Semantics must be
    bit-identical to expanded per-lane keys — the server expands at the
    parsing layer onto the same handlers."""

    def test_pull_matches_expanded(self):
        # dim=64 over 2 servers -> ranges [0,32) [32,64), R=8-aligned
        with ServerGroup(2, 1, dim=64) as sg, KVWorker(sg.hosts, 64) as kv:
            init = np.arange(64, dtype=np.float32)
            kv.push(init)
            rows = np.array([0, 3, 4, 7], dtype=np.uint64)  # crosses boundary
            expanded = (rows[:, None] * 8 + np.arange(8, dtype=np.uint64)
                        ).reshape(-1)
            np.testing.assert_array_equal(
                kv.pull(keys=rows, vals_per_key=8), kv.pull(keys=expanded))

    def test_push_matches_expanded(self):
        def run(use_vpk):
            with ServerGroup(1, 1, dim=64, sync=False,
                             learning_rate=1.0) as sg, \
                    KVWorker(sg.hosts, 64) as kv:
                kv.push(np.zeros(64, np.float32))  # init
                rows = np.array([1, 5], dtype=np.uint64)
                g = np.arange(16, dtype=np.float32)
                if use_vpk:
                    kv.push(g, keys=rows, vals_per_key=8)
                else:
                    expanded = (rows[:, None] * 8
                                + np.arange(8, dtype=np.uint64)).reshape(-1)
                    kv.push(g, keys=expanded)
                return kv.pull()

        np.testing.assert_array_equal(run(True), run(False))

    def test_push_pull_fused_vpk(self):
        with ServerGroup(1, 1, dim=32, sync=False, learning_rate=1.0) as sg, \
                KVWorker(sg.hosts, 32) as kv:
            kv.push(np.zeros(32, np.float32))  # init
            rows = np.array([2], dtype=np.uint64)
            g = np.ones(8, np.float32)
            out = kv.push_pull(g, keys=rows, vals_per_key=8)
            np.testing.assert_allclose(out, -np.ones(8))  # w -= 1*g
            full = kv.pull()
            np.testing.assert_allclose(full[16:24], -np.ones(8))
            assert np.all(full[:16] == 0) and np.all(full[24:] == 0)

    def test_sync_merge_mixes_vpk_and_expanded(self):
        """Two workers of one BSP round, one pushing row keys, one
        pushing expanded keys for the SAME slots: the merge must treat
        them identically (server-side expansion feeds one merge path)."""
        with ServerGroup(1, 2, dim=32, sync=True, learning_rate=1.0) as sg:
            kv0 = KVWorker(sg.hosts, 32, client_id=0)
            kv1 = KVWorker(sg.hosts, 32, client_id=1)
            kv0.push(np.zeros(32, np.float32))  # init
            rows = np.array([1], dtype=np.uint64)
            expanded = np.arange(8, 16, dtype=np.uint64)
            done = []

            def w0():
                kv0.push(np.full(8, 2.0, np.float32), keys=rows,
                         vals_per_key=8)
                done.append(0)

            th = threading.Thread(target=w0)
            th.start()
            kv1.push(np.full(8, 4.0, np.float32), keys=expanded)
            th.join(timeout=10)
            assert done
            # mean update on slots 8..16: w -= 1 * (2+4)/2
            np.testing.assert_allclose(kv0.pull()[8:16], np.full(8, -3.0))
            kv0.close()
            kv1.close()

    def test_supports_vals_per_key_alignment(self):
        # dim=96 over 2 servers -> boundary 48: aligned for R=8, not R=32
        with ServerGroup(2, 1, dim=96) as sg, KVWorker(sg.hosts, 96) as kv:
            assert kv.supports_vals_per_key(8)
            assert not kv.supports_vals_per_key(32)
            assert kv.supports_vals_per_key(1)
            # the client refuses an unaligned vpk op with a named error
            kv.push(np.zeros(96, np.float32))
            with pytest.raises(IOError, match="aligned|expanded"):
                kv.pull(keys=np.array([0], dtype=np.uint64), vals_per_key=32)

    def test_dense_default_keys_reject_vpk(self):
        """keys=None is the FLAT dense key set; combining it with
        vals_per_key > 1 must raise instead of silently reinterpreting
        flat ids as row ids (r5 review finding)."""
        with ServerGroup(1, 1, dim=64) as sg, KVWorker(sg.hosts, 64) as kv:
            kv.push(np.zeros(64, np.float32))
            with pytest.raises(ValueError, match="row keys"):
                kv.pull(vals_per_key=8)
            with pytest.raises(ValueError, match="row keys"):
                kv.push(np.zeros(64, np.float32), vals_per_key=8)

    def test_row_key_range_validation(self):
        with ServerGroup(1, 1, dim=64) as sg, KVWorker(sg.hosts, 64) as kv:
            kv.push(np.zeros(64, np.float32))
            with pytest.raises(ValueError, match="out of range"):
                kv.pull(keys=np.array([8], dtype=np.uint64), vals_per_key=8)

    def test_corrupt_vals_per_key_drops_connection_server_survives(self):
        """A frame claiming a huge vals_per_key must drop that
        connection (allocation guard), leaving the server serving other
        clients — same never-kill-the-rank contract as the other
        corruption guards."""
        import socket
        import struct

        with ServerGroup(1, 1, dim=32) as sg:
            kv = KVWorker(sg.hosts, 32)
            kv.push(np.zeros(32, np.float32))
            host, port = sg.hosts.split(":")
            s = socket.create_connection((host, int(port)), timeout=5)
            # header: magic, op=kPull, flags=0, aux=65535 (> kMaxValsPerKey),
            # client_id, ts, num_keys=1
            s.sendall(struct.pack("<IBBHII Q".replace(" ", ""),
                                  0xD157C0DE, 2, 0, 65535, 99, 0, 1))
            s.sendall(struct.pack("<Q", 0))
            # server must close this connection without replying — as a
            # clean FIN (recv -> b"") or an RST (reset error) depending
            # on whether our key bytes were still unread at close time
            s.settimeout(5)
            try:
                assert s.recv(1) == b""
            except ConnectionResetError:
                pass
            s.close()
            # and keep serving the legitimate client
            np.testing.assert_array_equal(kv.pull(), np.zeros(32))
            kv.close()
