import os

import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.data.synthetic import make_synthetic_dataset, write_synthetic_shards
from distlr_tpu.parallel import make_mesh
from distlr_tpu.train import GlobalShardedData, Trainer, load_model_text, save_model_text
from distlr_tpu.utils.logging import log_eval_line


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("synth")
    write_synthetic_shards(str(d), 1600, 24, num_parts=8, seed=0, sparsity=0.0)
    return str(d)


class TestGlobalShardedData:
    def test_padding_and_lockstep_batches(self):
        shards = [
            (np.ones((5, 2), np.float32) * i, np.full(5, i % 2, np.int32)) for i in range(3)
        ]
        shards[2] = (np.ones((3, 2), np.float32) * 2, np.full(3, 0, np.int32))
        g = GlobalShardedData(shards)
        assert g.num_samples == 13 and g.n_pad == 5
        X, y, mask = next(iter(g.batches(2)))
        assert X.shape == (6, 2)  # 3 shards x per-worker batch 2
        assert mask.sum() == 6
        batches = list(g.batches(2))
        assert len(batches) == 3
        last_mask = batches[-1][2].reshape(3, -1)
        assert last_mask[2].sum() == 0  # short shard's padding is masked

    def test_empty_shards_rejected(self):
        with pytest.raises(ValueError, match="no training data"):
            GlobalShardedData([(np.zeros((0, 2), np.float32), np.zeros(0, np.int32))])

    def test_full_shard_batch(self):
        shards = [(np.zeros((4, 2), np.float32), np.zeros(4, np.int32))] * 2
        g = GlobalShardedData(shards)
        batches = list(g.batches(-1))
        assert len(batches) == 1 and batches[0][0].shape == (8, 2)

    def test_wrap_batches_match_dataiter_q5(self):
        """batches(wrap=True) must reproduce the reference Q5 wraparound
        exactly as DataIter(wrap_compat=True) does (the PS-path parity
        oracle): the short final batch re-serves leading shard samples."""
        from distlr_tpu.data import DataIter

        rng = np.random.default_rng(3)
        X = rng.normal(size=(10, 2)).astype(np.float32)
        y = (rng.random(10) < 0.5).astype(np.int32)
        g = GlobalShardedData([(X, y)])
        it = DataIter(X, y, batch_size=4, wrap_compat=True)
        got = list(g.batches(4, wrap=True))
        want = list(it)
        assert len(got) == len(want) == 3
        for (Xg, yg, mg), (Xw, yw, mw) in zip(got, want):
            np.testing.assert_array_equal(Xg, Xw)
            np.testing.assert_array_equal(yg, yw)
            assert mg.all() and mw.all()  # wrapped rows are REAL samples
        # last batch holds the tail (8, 9) then wraps to the head (0, 1)
        np.testing.assert_array_equal(got[-1][0], X[[8, 9, 0, 1]])

    def test_wrap_rejects_unequal_shards(self):
        shards = [
            (np.ones((5, 2), np.float32), np.zeros(5, np.int32)),
            (np.ones((3, 2), np.float32), np.zeros(3, np.int32)),
        ]
        g = GlobalShardedData(shards)
        with pytest.raises(ValueError, match="wrap_final_batch"):
            list(g.batches(2, wrap=True))
        # the SHORT shard needs the wrap too (5 % 5 == 0 but 3 % 5 != 0) —
        # keying the check on n_pad alone would silently serve padding here
        with pytest.raises(ValueError, match="wrap_final_batch"):
            list(g.batches(5, wrap=True))
        # batch=-1 is one whole-shard batch: no wrap in the reference either
        assert len(list(g.batches(-1, wrap=True))) == 1

    def test_wrap_triggers_on_real_shard_sizes_not_padded(self):
        """Sizes [8, 7] with b=4: n_pad % b == 0, but the short shard DOES
        wrap in the reference — silent padded fall-through is the bug the
        loud rejection exists to prevent."""
        shards = [
            (np.ones((8, 2), np.float32), np.zeros(8, np.int32)),
            (np.ones((7, 2), np.float32), np.zeros(7, np.int32)),
        ]
        g = GlobalShardedData(shards)
        with pytest.raises(ValueError, match="wrap_final_batch"):
            list(g.batches(4, wrap=True))

    def test_wrap_batch_larger_than_shard_cycles(self):
        """b=5 over a 3-sample shard: the reference serves ONE 5-row batch
        cycling the shard ([0,1,2,0,1]) — not a clamped 3-row batch."""
        from distlr_tpu.data import DataIter

        X = np.arange(6, dtype=np.float32).reshape(3, 2)
        y = np.array([1, 0, 1], np.int32)
        g = GlobalShardedData([(X, y)])
        got = list(g.batches(5, wrap=True))
        want = list(DataIter(X, y, batch_size=5, wrap_compat=True))
        assert len(got) == len(want) == 1
        np.testing.assert_array_equal(got[0][0], want[0][0])
        np.testing.assert_array_equal(got[0][0], X[[0, 1, 2, 0, 1]])
        assert got[0][2].all()

    def test_trainer_reference_mode_uses_wrap(self, tmp_path):
        """compat_mode='reference' must thread Q5 into the sync Trainer's
        batching (ADVICE r2: the flag was silently ignored here)."""
        d = str(tmp_path / "wrapdata")
        # 300 samples over 1 shard, batch 64 -> short final batch
        write_synthetic_shards(d, 300, 8, num_parts=1, seed=4, sparsity=0.0)
        mesh = make_mesh({"data": 1})
        base = dict(
            data_dir=d, num_feature_dim=8, num_iteration=4, batch_size=64,
            learning_rate=0.3, test_interval=0,
        )
        w_ref = Trainer(Config(compat_mode="reference", **base), mesh=mesh).fit()
        w_cor = Trainer(Config(compat_mode="correct", sync_last_gradient=False,
                               l2_scale_by_batch=True, reference_rng_init=True,
                               **base), mesh=mesh).fit()
        # identical except Q5: wrapped duplicates shift the final-batch
        # gradient, so the trajectories must DIVERGE (teeth check)
        assert not np.allclose(np.asarray(w_ref), np.asarray(w_cor))

    def test_from_data_dir_resharding(self, data_dir):
        g = GlobalShardedData.from_data_dir(data_dir, "train", 4, 24)
        assert g.num_shards == 4
        g8 = GlobalShardedData.from_data_dir(data_dir, "train", 8, 24)
        assert g8.num_shards == 8
        assert g.num_samples == g8.num_samples


class TestTrainerEndToEnd:
    def test_converges_on_synthetic(self, data_dir):
        cfg = Config(
            data_dir=data_dir,
            num_feature_dim=24,
            num_iteration=60,
            learning_rate=0.5,
            l2_c=0.0,
            batch_size=-1,
            test_interval=30,
        )
        mesh = make_mesh({"data": 8})
        tr = Trainer(cfg, mesh=mesh).load_data()
        evals = []
        tr.fit(eval_fn=lambda ep, acc: evals.append((ep, acc)))
        assert [ep for ep, _ in evals] == [30, 60]
        final_acc = tr.evaluate()
        assert final_acc > 0.8, f"final accuracy {final_acc}"
        # accuracy improved over training
        assert evals[-1][1] >= evals[0][1] - 0.02

    def test_reference_compat_mode_runs(self, data_dir):
        cfg = Config(
            data_dir=data_dir,
            num_feature_dim=24,
            num_iteration=5,
            compat_mode="reference",
            test_interval=5,
        )
        tr = Trainer(cfg, mesh=make_mesh({"data": 8})).load_data()
        w = tr.fit()
        assert np.isfinite(np.asarray(w)).all()

    def test_save_model_reference_format(self, data_dir, tmp_path):
        cfg = Config(data_dir=data_dir, num_feature_dim=24, num_iteration=1, test_interval=10)
        tr = Trainer(cfg, mesh=make_mesh({"data": 8})).load_data()
        tr.fit(epochs=1)
        path = tr.save_model()
        assert path.endswith(os.path.join("models", "part-001"))
        lines = open(path).read().splitlines()
        assert lines[0] == "24"
        w = load_model_text(path)
        np.testing.assert_allclose(w, np.asarray(tr.weights), rtol=1e-4)

    def test_minibatch_training(self, data_dir):
        cfg = Config(
            data_dir=data_dir, num_feature_dim=24, num_iteration=10,
            batch_size=32, learning_rate=0.3, l2_c=0.0, test_interval=10,
        )
        tr = Trainer(cfg, mesh=make_mesh({"data": 8})).load_data()
        tr.fit()
        assert tr.evaluate() > 0.75
        assert tr.timer.samples > 0 and tr.timer.samples_per_sec > 0


class TestPrefetch:
    """Host->device double-buffered streaming (cfg.prefetch; VERDICT r3
    item 3).  The prefetched trajectory must be IDENTICAL to the serial
    one — only the host work moves off the critical path."""

    def test_trajectory_identical_to_serial(self, data_dir):
        ws = {}
        for pf in (1, 2, 4):
            cfg = Config(
                data_dir=data_dir, num_feature_dim=24, num_iteration=8,
                batch_size=32, learning_rate=0.3, l2_c=0.0,
                test_interval=0, prefetch=pf,
            )
            tr = Trainer(cfg, mesh=make_mesh({"data": 8})).load_data()
            ws[pf] = np.asarray(tr.fit())
        np.testing.assert_array_equal(ws[1], ws[2])
        np.testing.assert_array_equal(ws[1], ws[4])

    def test_producer_exception_propagates(self):
        """An error raised while slicing batches in the background thread
        must surface in fit(), not hang the queue (unequal shards + Q5
        wrap is such an error)."""
        rng = np.random.default_rng(0)
        shards = [
            (rng.normal(size=(10, 4)).astype(np.float32),
             rng.integers(0, 2, 10).astype(np.int32)),
            (rng.normal(size=(7, 4)).astype(np.float32),
             rng.integers(0, 2, 7).astype(np.int32)),
        ]
        data = GlobalShardedData(shards)
        cfg = Config(
            num_feature_dim=4, num_iteration=2, batch_size=4,
            learning_rate=0.3, test_interval=0, compat_mode="reference",
            prefetch=2,
        )
        mesh = make_mesh({"data": 2})
        tr = Trainer(cfg, mesh=mesh)
        tr._train_data, tr._test_data = data, None
        with pytest.raises(ValueError, match="equal-size shards"):
            tr.fit()

    def test_early_consumer_exit_does_not_hang(self, data_dir):
        """A consumer-side failure mid-epoch must unblock the producer
        thread (fit raises, the generator's finally releases the queue)."""
        import threading

        cfg = Config(
            data_dir=data_dir, num_feature_dim=24, num_iteration=1,
            batch_size=16, learning_rate=0.3, test_interval=0, prefetch=3,
        )
        tr = Trainer(cfg, mesh=make_mesh({"data": 8})).load_data()
        calls = []

        def boom(w, batch):
            calls.append(1)
            raise RuntimeError("step failed")

        tr.init_weights()
        tr.train_step = boom
        with pytest.raises(RuntimeError, match="step failed"):
            tr.fit()
        # the daemon producer must wind down, not sit blocked on put()
        for _ in range(50):
            alive = [t for t in threading.enumerate()
                     if t.name == "distlr-prefetch" and t.is_alive()]
            if not alive:
                break
            import time
            time.sleep(0.05)
        assert not alive, "prefetch producer thread still blocked"

    def test_invalid_prefetch_rejected(self):
        with pytest.raises(ValueError, match="prefetch"):
            Config(prefetch=0)


@pytest.fixture
def producers(monkeypatch):
    """Every ``distlr-prefetch`` thread started while the test runs."""
    import threading

    started, start = [], threading.Thread.start

    def counting(thread):
        if thread.name == "distlr-prefetch":
            started.append(thread)
        start(thread)

    monkeypatch.setattr(threading.Thread, "start", counting)
    return started


def _span_counts():
    from distlr_tpu.obs.tracing import get_tracer

    return {k: v["count"] for k, v in get_tracer().breakdown().items()}


class TestOneProducerAFit:
    """ISSUE 42: the producer thread lives as long as its ``fit``, over
    every epoch of the call, and never past it."""

    STEPS = 4  # 1,280 train rows over 8 shards, 40 rows a shard a batch

    def _trainer(self, data_dir, **kw):
        kw = {"prefetch": 2, "test_interval": 1, **kw}
        cfg = Config(data_dir=data_dir, num_feature_dim=24, batch_size=40,
                     learning_rate=0.3, l2_c=0.0, **kw)
        return Trainer(cfg, mesh=make_mesh({"data": 8})).load_data()

    @pytest.mark.parametrize("prefetch", [2, 3, 5])
    def test_one_thread_a_fit_of_several_epochs(self, data_dir, producers,
                                                prefetch):
        from distlr_tpu.obs.tracing import get_tracer

        tr = self._trainer(data_dir, prefetch=prefetch)
        get_tracer().reset()
        tr.fit(epochs=4, eval_fn=lambda *a: None)
        assert len(producers) == 1 and not producers[0].is_alive()
        # it made the call's batches and not one more
        counts = _span_counts()
        assert counts["batch_slice"] == counts["h2d"] == 4 * self.STEPS
        assert counts["compute"] == tr.batches_taken == 4 * self.STEPS
        events = get_tracer().chrome_trace()["traceEvents"]
        assert len({e["tid"] for e in events if e["name"] == "h2d"}) == 1
        # a second fit starts its own, and ends it
        tr.fit(epochs=2, eval_fn=lambda *a: None)
        assert len(producers) == 2 and not producers[1].is_alive()
        assert tr.batches_taken == 6 * self.STEPS

    def test_the_serial_shape_starts_no_thread(self, data_dir, producers):
        tr = self._trainer(data_dir, prefetch=1)
        tr.fit(epochs=3, eval_fn=lambda *a: None)
        assert producers == []
        assert tr.batches_taken == 3 * self.STEPS

    @pytest.mark.parametrize("fails_at", [0, 3, 7])
    def test_a_step_that_raises_leaves_no_producer_behind(
            self, data_dir, producers, fails_at):
        """``fails_at`` 7 is in the second epoch: the producer has by
        then gone over an epoch's end."""
        tr = self._trainer(data_dir, prefetch=3)
        step, seen = tr.train_step, []

        def failing(w, batch):
            if len(seen) == fails_at:
                raise RuntimeError("step failed")
            seen.append(1)
            return step(w, batch)

        tr.train_step = failing
        with pytest.raises(RuntimeError, match="step failed"):
            tr.fit(epochs=3, eval_fn=lambda *a: None)
        # closed before fit returned: no polling, nothing to wait for
        assert len(producers) == 1 and not producers[0].is_alive()
        # a retried fit stacks none on top
        tr.train_step = step
        tr.fit(epochs=1, eval_fn=lambda *a: None)
        assert len(producers) == 2
        assert not any(t.is_alive() for t in producers)

    def test_a_resumed_fit_produces_the_epochs_that_are_left(
            self, data_dir, producers, tmp_path):
        from distlr_tpu.obs.tracing import get_tracer

        kw = dict(checkpoint_dir=str(tmp_path), checkpoint_interval=1)
        first = self._trainer(data_dir, **kw)
        first.fit(epochs=2, eval_fn=lambda *a: None)
        whole = self._trainer(data_dir)
        whole.fit(epochs=5, eval_fn=lambda *a: None)
        resumed = self._trainer(data_dir, **kw)
        get_tracer().reset()
        resumed.fit(epochs=5, resume=True, eval_fn=lambda *a: None)
        assert _span_counts()["batch_slice"] == 3 * self.STEPS
        assert resumed.batches_taken == 3 * self.STEPS
        assert len(producers) == 3
        assert not any(t.is_alive() for t in producers)
        np.testing.assert_array_equal(np.asarray(resumed.weights),
                                      np.asarray(whole.weights))
        # nothing left to run: a thread that ends at once, no batch made
        get_tracer().reset()
        resumed.fit(epochs=5, resume=True, eval_fn=lambda *a: None)
        assert "batch_slice" not in _span_counts()
        assert not any(t.is_alive() for t in producers)


def _write_family(family, d):
    from distlr_tpu.data.hashing import write_ctr_shards, write_raw_ctr_shards

    if family in ("binary_lr", "feature_sharded"):
        write_synthetic_shards(d, 600, 24, num_parts=2, seed=1, sparsity=0.0)
        return dict(num_feature_dim=24)
    if family == "softmax":
        write_synthetic_shards(d, 600, 32, num_parts=2, seed=1, num_classes=4)
        return dict(model="softmax", num_feature_dim=32, num_classes=4)
    if family == "blocked_lr":
        write_raw_ctr_shards(d, 600, 6, 40, 2, seed=9)
        return dict(model="blocked_lr", num_feature_dim=4096, block_size=4)
    write_ctr_shards(d, 600, 5, 50, 64, 2, seed=3)
    kw = dict(model=family, num_feature_dim=64)
    return {**kw, "num_classes": 2} if family == "sparse_softmax" else kw


FAMILIES = ("binary_lr", "softmax", "sparse_lr", "sparse_softmax",
            "blocked_lr", "feature_sharded")


class TestProducerKeepsTheTrajectory:
    """ISSUE 42: whatever the producer is ahead by, over epochs' ends,
    evals and checkpoints, the rows' order and so every weight are the
    serial shape's, bit for bit, in every family the sync trainer runs."""

    @pytest.fixture(scope="class")
    def runs(self, tmp_path_factory):
        """``run(family, wrap, prefetch)`` -> weights after 3 epochs, the
        accuracies of the three evals, the checkpoint after epoch 2;
        each computed once."""
        dirs, done = {}, {}

        def run(family, wrap, prefetch):
            key = (family, wrap, prefetch)
            if key in done:
                return done[key]
            if family not in dirs:
                d = str(tmp_path_factory.mktemp(family))
                dirs[family] = (d, _write_family(family, d))
            d, kw = dirs[family]
            ck = str(tmp_path_factory.mktemp("ck"))
            shape = ({"data": 2, "model": 2} if family == "feature_sharded"
                     else {"data": 2})
            # 240 train rows a shard in batches of 64: a short fourth batch
            cfg = Config(data_dir=d, mesh_shape=shape, batch_size=64,
                         learning_rate=0.3, l2_c=0.0, test_interval=1,
                         checkpoint_dir=ck, checkpoint_interval=2,
                         wrap_final_batch=int(wrap), prefetch=prefetch, **kw)
            tr = Trainer(cfg).load_data()
            accs = []
            w = np.asarray(tr.fit(epochs=3,
                                  eval_fn=lambda _e, acc: accs.append(acc)))
            assert tr.timer.steps == 12
            from distlr_tpu.train.checkpoint import Checkpointer

            with Checkpointer(ck) as ckpt:
                saved = np.asarray(ckpt.restore(2)["weights"])
                assert ckpt.latest_step() == 3
            done[key] = (w, accs, saved)
            return done[key]

        return run

    @pytest.mark.parametrize("prefetch", [2, 3])
    @pytest.mark.parametrize("wrap", [False, True], ids=["padded", "wrapped"])
    @pytest.mark.parametrize("family", FAMILIES)
    def test_weights_are_the_serial_shapes_bit_for_bit(self, runs, family,
                                                       wrap, prefetch):
        w1, accs1, saved1 = runs(family, wrap, 1)
        w, accs, saved = runs(family, wrap, prefetch)
        assert np.any(w1 != np.asarray(saved1).reshape(w1.shape))
        np.testing.assert_array_equal(w, w1)
        assert accs == accs1 and len(accs) == 3
        np.testing.assert_array_equal(saved, saved1)


class TestFeatureShardedTrainer:
    def test_2d_mesh_end_to_end(self, data_dir):
        cfg = Config(
            data_dir=data_dir, num_feature_dim=24, num_iteration=40,
            learning_rate=0.5, l2_c=0.0, test_interval=40,
            mesh_shape={"data": 4, "model": 2},
        )
        tr = Trainer(cfg).load_data()
        assert tr.feature_sharded
        tr.fit()
        acc = tr.evaluate()
        assert acc > 0.8, f"2D-sharded accuracy {acc}"
        # weights stay model-sharded on device but export flattens fine
        path = tr.save_model()
        w = load_model_text(path)
        assert w.shape == (24,)


class TestExport:
    def test_text_roundtrip(self, tmp_path):
        w = np.random.default_rng(0).standard_normal(17).astype(np.float32)
        p = str(tmp_path / "m")
        save_model_text(p, w)
        w2 = load_model_text(p)
        np.testing.assert_allclose(w, w2, rtol=1e-5)

    def test_eval_line_format(self, capsys):
        line = log_eval_line(10, 0.8472)
        out = capsys.readouterr().out.strip()
        assert out == line
        import re
        assert re.fullmatch(r"\d{2}:\d{2}:\d{2} Iteration 10, accuracy: 0\.8472", line)


class TestEvalLogloss:
    """Test logloss is the driver's parity metric (BASELINE.json
    epochs-to-logloss): both trainers must log it at every eval, and it
    must equal the offline definition (mean softplus(z) - y*z, no L2)."""

    def _offline_ll(self, data_dir, w, d):
        import os

        from distlr_tpu.data import parse_libsvm_file

        X, y = parse_libsvm_file(os.path.join(data_dir, "test", "part-001"), d)
        z = X @ np.asarray(w, np.float64).reshape(-1)
        return float(np.mean(np.logaddexp(0.0, z) - y * z))

    def test_sync_trainer_logs_test_logloss(self, data_dir):
        cfg = Config(data_dir=data_dir, num_feature_dim=32, num_iteration=4,
                     learning_rate=0.5, l2_c=0.1, batch_size=-1,
                     test_interval=2)
        tr = Trainer(cfg).load_data()
        w = tr.fit(eval_fn=lambda *_: None)
        lls = [r["test_logloss"] for r in tr.metrics.records
               if "test_logloss" in r]
        assert len(lls) == 2  # epochs 2 and 4
        # final record matches the offline definition on the final weights
        # (bf16 matmul in the jitted eval vs float64 offline: loose tol)
        assert lls[-1] == pytest.approx(self._offline_ll(data_dir, w, 32),
                                        rel=2e-2)
        em = tr.evaluate_metrics()
        assert set(em) == {"accuracy", "logloss"}
        assert em["logloss"] == pytest.approx(lls[-1], rel=2e-2)

    def test_ps_worker_logs_test_logloss(self, data_dir):
        from distlr_tpu.train.ps_trainer import run_ps_local

        cfg = Config(data_dir=data_dir, num_feature_dim=32, num_iteration=4,
                     learning_rate=0.5, l2_c=0.0, batch_size=-1,
                     test_interval=2, num_workers=1, num_servers=1,
                     sync_mode=True)
        lls = []
        # eval_fn keeps its (epoch, acc) signature; logloss rides the
        # metrics records — grab it via a tiny shim around MetricsLogger
        from distlr_tpu.train import ps_trainer as pt

        orig = pt.MetricsLogger.log

        def spy(self, **rec):
            if "test_logloss" in rec:
                lls.append(rec["test_logloss"])
            return orig(self, **rec)

        pt.MetricsLogger.log = spy
        try:
            ws = run_ps_local(cfg, save=False)
        finally:
            pt.MetricsLogger.log = orig
        assert len(lls) == 2
        assert lls[-1] == pytest.approx(self._offline_ll(data_dir, ws[0], 32),
                                        rel=2e-2)


class TestGoldenModelFormat:
    """Byte-level cross-validation of the text model format against a
    REFERENCE-WRITTEN file (VERDICT r2 #8): the oracle binary reproduces
    ``LR::SaveModel``'s exact ofstream layout (reference src/lr.cc:73-82),
    and the framework must round-trip those bytes — load the file, then
    re-serialize to the identical byte string."""

    def test_roundtrip_reference_written_file(self, tmp_path):
        import subprocess

        src = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "oracle", "reference_oracle.cc")
        # build into tmp_path: never touch the tracked binary in-place,
        # and a missing compiler skips instead of erroring
        oracle = str(tmp_path / "reference_oracle")
        try:
            r = subprocess.run(
                ["g++", "-O2", "-std=c++17", "-o", oracle, src],
                capture_output=True, text=True,
            )
        except OSError as e:
            pytest.skip(f"no C++ compiler: {e}")
        if r.returncode != 0 or not os.path.exists(oracle):
            pytest.skip(f"cannot build reference_oracle: {r.stderr[-300:]}")

        from distlr_tpu.data.synthetic import write_synthetic_shards
        from distlr_tpu.train.export import load_model_text, save_model_text

        d = str(tmp_path / "data")
        write_synthetic_shards(d, 400, 24, num_parts=1, seed=3, sparsity=0.0)
        golden = str(tmp_path / "ref_model.txt")
        out = subprocess.run(
            [oracle, f"--data_dir={d}", "--dim=24", "--iters=8",
             "--batch=100", "--lr=0.3", "--C=1", "--test_interval=0",
             f"--save_model={golden}"],
            capture_output=True, text=True, check=True,
        ).stdout
        golden_bytes = open(golden, "rb").read()
        # layout: line 1 = dim, line 2 = weights + trailing space
        lines = golden_bytes.decode().split("\n")
        assert lines[0] == "24" and lines[1].endswith(" ")

        # framework load: values match the oracle's full-precision stdout
        # within the file format's 6-significant-digit text precision
        w = load_model_text(golden)
        stdout_w = np.array(
            [float(v) for ln in out.splitlines() if ln.startswith("WEIGHTS")
             for v in ln.split()[1:]], dtype=np.float32)
        assert w.shape == (24,)
        np.testing.assert_allclose(w, stdout_w, rtol=1e-5)

        # framework save: BYTE-identical re-serialization (%g == default
        # ostream precision; 6 sig digits round-trip through float32)
        ours = str(tmp_path / "ours.txt")
        save_model_text(ours, w)
        assert open(ours, "rb").read() == golden_bytes

    def test_trainer_export_is_reference_loadable_layout(self, data_dir):
        """Trainer.save_model output obeys the same two-line contract the
        reference reader-side (and the golden file) pin."""
        cfg = Config(data_dir=data_dir, num_feature_dim=32, num_iteration=2,
                     learning_rate=0.5, l2_c=0.0, test_interval=0)
        tr = Trainer(cfg).load_data()
        tr.fit(eval_fn=lambda *_: None)
        path = tr.save_model()
        raw = open(path).read().split("\n")
        assert raw[0] == "32" and raw[1].endswith(" ")


class TestEvalSubcommand:
    def test_eval_reproduces_training_eval(self, tmp_path):
        """launch eval scores a saved text model identically to the
        trainer's own final evaluate() — the model-file round trip
        (reference SaveModel format) loses nothing."""
        import contextlib
        import io

        from distlr_tpu import launch

        d = str(tmp_path / "data")
        assert launch.main([
            "gen-data", "--data-dir", d, "--num-samples", "1500",
            "--num-feature-dim", "24", "--num-parts", "1", "--seed", "3",
        ]) == 0
        assert launch.main([
            "sync", "--data-dir", d, "--num-feature-dim", "24",
            "--num-iteration", "15", "--test-interval", "0",
            "--learning-rate", "0.5", "--l2-c", "0",
        ]) == 0
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            assert launch.main([
                "eval", "--data-dir", d, "--num-feature-dim", "24",
                "--model-file", f"{d}/models/part-001",
            ]) == 0
        line = out.getvalue().strip()
        assert line.startswith("accuracy: ") and "test_logloss: " in line
        # compare against an in-process evaluate of the same weights
        import numpy as np

        from distlr_tpu import Config
        from distlr_tpu.train import Trainer
        from distlr_tpu.train.export import load_model_text

        cfg = Config(data_dir=d, num_feature_dim=24, test_interval=0)
        tr = Trainer(cfg).load_data()
        tr.weights = tr._shard_weights(load_model_text(f"{d}/models/part-001"))
        want = tr.evaluate_metrics()
        acc = float(line.split()[1])
        assert abs(acc - want["accuracy"]) < 1e-4

    def test_eval_softmax_shape(self, tmp_path):
        from distlr_tpu import launch

        d = str(tmp_path / "mc")
        assert launch.main([
            "gen-data", "--data-dir", d, "--num-samples", "1500",
            "--num-feature-dim", "24", "--num-classes", "4",
            "--num-parts", "1", "--seed", "4",
        ]) == 0
        assert launch.main([
            "sync", "--data-dir", d, "--model", "softmax",
            "--num-classes", "4", "--num-feature-dim", "24",
            "--num-iteration", "10", "--test-interval", "0",
            "--learning-rate", "0.3", "--l2-c", "0",
        ]) == 0
        assert launch.main([
            "eval", "--data-dir", d, "--model", "softmax",
            "--num-classes", "4", "--num-feature-dim", "24",
            "--model-file", f"{d}/models/part-001",
        ]) == 0

    def test_eval_blocked_family(self, tmp_path):
        """eval round-trips the blocked table ((rows, R) via param_shape)
        from raw-CTR shards."""
        from distlr_tpu import launch

        d = str(tmp_path / "bl")
        assert launch.main([
            "gen-data", "--data-dir", d, "--num-samples", "2000",
            "--ctr-fields", "6", "--ctr-vocab", "4", "--ctr-raw",
            "--num-parts", "1", "--seed", "6",
        ]) == 0
        common = ["--data-dir", d, "--model", "blocked_lr",
                  "--num-feature-dim", "1024", "--block-size", "4"]
        assert launch.main([
            "sync", *common, "--num-iteration", "8", "--test-interval", "0",
            "--learning-rate", "0.5", "--l2-c", "0",
        ]) == 0
        assert launch.main([
            "eval", *common, "--model-file", f"{d}/models/part-001",
        ]) == 0

    def test_eval_blocked_respects_block_groups(self, tmp_path, capsys):
        """A model trained under an explicit --block-groups must be
        evaluated under the same grouping: eval re-hashes the test
        split at load time, so a grouping mismatch silently scores a
        differently-hashed feature space (r5 review scenario).  The
        matched eval must beat the mismatched one by a wide margin."""
        from distlr_tpu import launch

        d = str(tmp_path / "blg")
        assert launch.main([
            "gen-data", "--data-dir", d, "--num-samples", "6000",
            "--ctr-fields", "12", "--ctr-vocab", "3", "--ctr-raw",
            "--ctr-tuples", "48", "--num-parts", "1", "--seed", "6",
        ]) == 0
        common = ["--data-dir", d, "--model", "blocked_lr",
                  "--num-feature-dim", "4096", "--block-size", "8"]
        assert launch.main([
            "sync", *common, "--block-groups", "3", "--num-iteration", "30",
            "--test-interval", "0", "--learning-rate", "0.5", "--l2-c", "0",
        ]) == 0
        capsys.readouterr()

        def eval_metrics(extra):
            assert launch.main([
                "eval", *common, *extra,
                "--model-file", f"{d}/models/part-001",
            ]) == 0
            out = capsys.readouterr().out
            return (float(out.split("accuracy:")[1].split()[0]),
                    float(out.split("test_logloss:")[1].split()[0]))

        matched, matched_ll = eval_metrics(["--block-groups", "3"])
        mismatched, mismatched_ll = eval_metrics([])  # default = 2 groups
        assert matched > 0.6, matched
        # logloss carries the robust signal: the generator's uncentered
        # labels skew the class marginal, so a garbage model still gets
        # majority-class accuracy (measured 0.88 vs 0.83) while its
        # logloss degrades decisively (measured 0.37 vs 0.55)
        assert matched > mismatched + 0.03, (matched, mismatched)
        assert matched_ll < mismatched_ll - 0.1, (matched_ll, mismatched_ll)


class TestSparseSoftmaxEndToEnd:
    """sparse_softmax (r5): the multiclass member of the CTR encoding
    family, trained through the real surfaces — sync CLI (+ eval
    subcommand) and the keyed PS plane (where (D, K) rows ride the
    vals_per_key=K wire encoding)."""

    def _gen(self, d, launch):
        assert launch.main([
            "gen-data", "--data-dir", d, "--num-samples", "4000",
            "--num-feature-dim", "200", "--num-parts", "2", "--seed", "9",
            "--num-classes", "5", "--sparsity", "0.9",
        ]) == 0

    def test_sync_cli_and_eval(self, tmp_path, capsys):
        from distlr_tpu import launch

        d = str(tmp_path / "ssm")
        self._gen(d, launch)
        common = ["--data-dir", d, "--model", "sparse_softmax",
                  "--num-feature-dim", "200", "--num-classes", "5"]
        assert launch.main([
            "sync", *common, "--num-iteration", "40", "--batch-size", "-1",
            "--learning-rate", "0.5", "--l2-c", "0", "--test-interval", "40",
        ]) == 0
        capsys.readouterr()
        assert launch.main([
            "eval", *common, "--model-file", f"{d}/models/part-001",
        ]) == 0
        out = capsys.readouterr().out
        acc = float(out.split("accuracy:")[1].split()[0])
        # 5 balanced classes: marginal ~0.2.  The fixture's Gumbel label
        # noise caps achievable accuracy at ~0.375 (the DENSE softmax
        # measures the same ceiling on this data) — assert clear learning
        # with headroom below that ceiling
        assert acc > 0.33, out

    def test_keyed_ps_run_uses_vpk_and_converges(self, tmp_path, capfd):
        from distlr_tpu import Config
        from distlr_tpu import launch
        from distlr_tpu.train.ps_trainer import run_ps_local

        d = str(tmp_path / "ssm_ps")
        self._gen(d, launch)
        cfg = Config(
            data_dir=d, num_feature_dim=200, model="sparse_softmax",
            num_classes=5, num_iteration=30, learning_rate=0.5, l2_c=0.0,
            batch_size=200, test_interval=30, sync_mode=True,
            num_workers=2, num_servers=2, ps_timeout_ms=30_000,
        )
        evals = []
        capfd.readouterr()
        run_ps_local(cfg, eval_fn=lambda ep, a: evals.append(a))
        # (D*K) = 1000 over 2 servers -> boundary 500 % 5 == 0: the
        # keyed rounds must ride the vals_per_key=5 encoding.  The
        # trainer logs the chosen encoding to stderr (fd-level capture:
        # the package logger neither propagates nor rebinds sys.stderr).
        err = capfd.readouterr().err
        assert "keyed wire encoding: vals_per_key=5" in err, err[-2000:]
        # same noise-capped fixture ceiling (~0.375) as the sync test
        assert evals and evals[-1] > 0.33, evals
