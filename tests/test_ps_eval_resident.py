"""Rank 0's eval keeps the test split on the eval's device: placed once by
the first eval, read in place by every later one, one forward pass and
one compile; the fall-back where the split does not fit; the lock-step
trajectory untouched by ``test_interval``; and a pull answered at once,
with the weights before the round, while a round is open."""

import threading
import time

import numpy as np
import pytest

from chipbench.families import dense_ps_bsp_eval
from distlr_tpu.config import Config
from distlr_tpu.data.iterator import DataIter
from distlr_tpu.data.synthetic import write_synthetic_shards
from distlr_tpu.models import host_math
from distlr_tpu.obs.registry import family_total, get_registry
from distlr_tpu.obs.tracing import get_tracer
from distlr_tpu.ps import KVWorker, ServerGroup
from distlr_tpu.train import ps_trainer
from distlr_tpu.train.ps_trainer import (
    PSWorker,
    ps_param_dim,
    run_ps_local,
)

DIM, ROWS, TEST_ROWS = 300, 96, 24
H2D = "distlr_h2d_bytes_total"


pytestmark = pytest.mark.usefixtures("ps_steps_on_device")


def _cfg(tmp_path, workers=1, **kw):
    d = str(tmp_path / f"job-{workers}")
    # a quarter as many rows to test on as to train on
    write_synthetic_shards(d, ROWS * workers * 5 // 4, DIM, num_parts=workers,
                           seed=6, sparsity=0.0)
    base = dict(data_dir=d, num_feature_dim=DIM, model="binary_lr",
                num_workers=workers, num_servers=2, sync_mode=True,
                batch_size=-1, num_iteration=30, learning_rate=0.2, l2_c=0.0,
                test_interval=10, compute_dtype="float32")
    return Config(**{**base, **kw})


def _group(cfg):
    return ServerGroup(cfg.num_servers, cfg.num_workers, ps_param_dim(cfg),
                       learning_rate=cfg.learning_rate, sync=cfg.sync_mode)


def _gauge(name, rank="0"):
    return get_registry().get(name).labels(rank=rank).value


def _seeded_weights(seed=11):
    return (np.random.default_rng(seed).standard_normal(DIM) * 0.3).astype(
        np.float32)


def _as_coo(X):
    """Dense rows as the family takes them: every column, its value."""
    cols = np.tile(np.arange(X.shape[1], dtype=np.int32), (X.shape[0], 1))
    return cols, np.asarray(X, np.float32)


@pytest.fixture
def lone_worker(tmp_path):
    cfg = _cfg(tmp_path)
    with _group(cfg) as group:
        w = PSWorker(cfg, 0, group.hosts)
        try:
            w.load_data()
            w.start()
            yield w
        finally:
            w.close()


@pytest.mark.parametrize("held", ["default-layout", "row-major-padded"])
def test_the_resident_eval_is_the_references_and_numpys(tmp_path, monkeypatch,
                                                        held):
    if held == "row-major-padded":
        # the selection as a TPU makes it
        monkeypatch.setattr(ps_trainer, "_ONE_PASS_PLATFORMS", ("tpu", "cpu"))
    cfg = _cfg(tmp_path)
    with _group(cfg) as group:
        w = PSWorker(cfg, 0, group.hosts)
        try:
            w.load_data()
            X, y, mask = w._test.whole_shard()
            for seed in (11, 12):
                weights = _seeded_weights(seed)
                acc, ll = w.evaluate(weights)
                ref_acc, ref_ll, z = dense_ps_bsp_eval.evaluate(
                    weights, *_as_coo(X), y)
                assert abs(ll - ref_ll) <= 2e-6 * ref_ll
                np_acc, np_ll = host_math.dense_eval(weights, X, y,
                                               mask.astype(np.float32))
                assert abs(ll - np_ll) <= 2e-6 * np_ll
                # every row's class, but one whose logit is a rounding from 0
                sure = np.abs(z) > 1e-4
                assert abs(round(acc * len(y)) - round(ref_acc * len(y))) <= (
                    ~sure).sum()
                assert abs(round(acc * len(y)) - round(np_acc * len(y))) <= (
                    ~sure).sum()
            placed, plan, _rows = w._test_resident
            if held == "row-major-padded":
                assert placed[0].shape == (len(y), plan.dim_padded)
                assert not np.asarray(placed[0][:, DIM:]).any()
            else:
                assert plan is None and placed[0].shape == (len(y), DIM)
        finally:
            w.close()


def test_the_split_crosses_once_and_the_eval_compiles_once(lone_worker):
    w = lone_worker
    split_bytes = sum(a.nbytes for a in w._test.whole_shard())
    tracer = get_tracer()
    tracer.reset()
    crossed = family_total(H2D)
    evals = _gauge("distlr_ps_evals_total")
    rows = _gauge("distlr_ps_eval_rows_total")
    w.fit(epochs=10)
    assert family_total(H2D) - crossed == split_bytes
    assert _gauge("distlr_ps_test_resident_bytes") == split_bytes
    compiled = w._acc_fn._cache_size()
    w.fit(epochs=20)
    assert w.epochs_done == 30
    # three evals over 30 rounds: nothing crossed again, nothing compiled
    assert family_total(H2D) - crossed == split_bytes
    assert w._acc_fn._cache_size() == compiled
    assert _gauge("distlr_ps_evals_total") - evals == 3
    assert _gauge("distlr_ps_eval_rows_total") - rows == 3 * TEST_ROWS
    spans = tracer.breakdown()
    assert spans["test_put"]["count"] == 1
    for name in ("eval", "eval_pull", "eval_w_put", "eval_compute",
                 "eval_d2h"):
        assert spans[name]["count"] == 3, name
    assert "h2d" not in spans
    rows_logged = [r for r in w.metrics.records if "accuracy" in r]
    assert [r["epoch"] for r in rows_logged] == [10, 20, 30]
    assert all({"accuracy", "test_logloss", "samples_per_sec"} <= set(r)
               for r in rows_logged)


def test_one_compile_over_a_whole_run_by_the_probes_count(tmp_path):
    """``JitCacheProbe`` site ``train.ps.eval`` stays at one compile over
    a run of 30 rounds with three evals."""
    # a width no other test's eval has: the process's one compiled
    # function has not seen this shape
    cfg = _cfg(tmp_path, num_feature_dim=DIM + 7)
    write_synthetic_shards(cfg.data_dir, ROWS * 5 // 4, DIM + 7, num_parts=1,
                           seed=8, sparsity=0.0)

    def compiles():
        fam = get_registry().get("distlr_jax_compiles_total")
        return sum(c.value for labels, c in (fam.children() if fam else [])
                   if "train.ps.eval" in labels)

    before = compiles()
    run_ps_local(cfg, save=False)
    assert compiles() - before == 1


def test_a_split_that_does_not_fit_is_streamed_at_every_eval(lone_worker,
                                                             monkeypatch):
    w = lone_worker
    split_bytes = sum(a.nbytes for a in w._test.whole_shard())
    weights = _seeded_weights()
    seen = []

    def free(device):
        seen.append(device)
        return ps_trainer._PLACE_HEADROOM * split_bytes - 1

    monkeypatch.setattr(ps_trainer, "_device_free_bytes", free)
    tracer = get_tracer()
    tracer.reset()
    streamed = [w.evaluate(weights) for _ in range(2)]
    assert w._test_resident is None and len(seen) == 2
    assert _gauge("distlr_ps_test_resident_bytes") == 0
    spans = tracer.breakdown()
    assert spans["h2d"]["count"] == 2 and "test_put" not in spans
    # room enough by one byte: placed, and the same two numbers
    monkeypatch.setattr(
        ps_trainer, "_device_free_bytes",
        lambda device: ps_trainer._PLACE_HEADROOM * split_bytes)
    resident = w.evaluate(weights)
    assert w._test_resident is not None
    assert _gauge("distlr_ps_test_resident_bytes") == split_bytes
    assert streamed[0] == streamed[1] == resident
    assert tracer.breakdown()["test_put"]["count"] == 1


def test_a_backend_that_keeps_no_count_of_its_memory_is_the_hosts(monkeypatch):
    class Counted:
        def memory_stats(self):
            return {"bytes_limit": 1000, "bytes_in_use": 400,
                    "peak_bytes_in_use": 900}

    class Silent:
        def memory_stats(self):
            return None

    assert ps_trainer._device_free_bytes(Counted()) == 600
    assert ps_trainer._device_free_bytes(Silent()) is None


@pytest.mark.parametrize("eval_dev", ["numpy", "jax"])
def test_an_eval_gathers_no_row_where_the_batch_is_the_iterators_arrays(
        tmp_path, monkeypatch, eval_dev, ps_steps_on):
    cfg = _cfg(tmp_path)
    with ps_steps_on("numpy" if eval_dev == "numpy" else "device"), \
            _group(cfg) as group:
        w = PSWorker(cfg, 0, group.hosts)
        try:
            w.load_data()

            def refuse(self):
                raise AssertionError("gathered the split row by row")

            monkeypatch.setattr(DataIter, "next_batch", refuse)
            weights = _seeded_weights()
            first = w.evaluate(weights)
            assert w.evaluate(weights) == first
            assert (w._test_resident is None) == (eval_dev == "numpy")
        finally:
            w.close()


def test_the_lock_step_trajectory_does_not_know_of_the_eval(tmp_path):
    """Two workers (a + b is b + a: the servers' float32 merge does not
    depend on who arrived first): 30 rounds with an eval after every 10th
    end on the bits 30 rounds without one end on."""
    with_evals = _cfg(tmp_path, workers=2, test_interval=10)
    without = Config(**{**with_evals.__dict__, "test_interval": 0})
    lines = []
    a = run_ps_local(with_evals, save=False,
                     eval_fn=lambda epoch, acc: lines.append(epoch))
    b = run_ps_local(without, save=False,
                     eval_fn=lambda epoch, acc: lines.append(-epoch))
    assert lines == [10, 20, 30]
    for rank in range(2):
        assert np.array_equal(np.asarray(a[rank]).view(np.uint32),
                              np.asarray(b[rank]).view(np.uint32))


def test_an_evals_numbers_are_of_the_weights_after_its_round(tmp_path):
    """What rank 0 reports after round k is the eval of the weights its
    round-k push-pull returned: the recorder round the compiled program
    sees those bits, and the reference at those bits agrees."""
    cfg = _cfg(tmp_path, workers=2)
    seen = []
    with _group(cfg) as group:
        workers = [PSWorker(cfg, r, group.hosts) for r in range(2)]
        try:
            for w in workers:
                w.load_data()
            real = workers[0]._acc_fn

            def recorder(wd, *batch, **how):
                got = real(wd, *batch, **how)
                seen.append((workers[0].rounds, np.array(wd),
                             np.array(workers[0]._w_cache), float(got[1])))
                return got

            workers[0]._acc_fn = recorder
            threads = [threading.Thread(
                target=lambda w=w: (w.start(), w.fit(epochs=20)))
                for w in workers]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
            X, y, _mask = workers[0]._test.whole_shard()
        finally:
            for w in workers:
                w.close()
    assert [r for r, *_ in seen] == [10, 20]
    for _round, ran_on, after_round, ll in seen:
        assert np.array_equal(ran_on.view(np.uint32),
                              after_round.view(np.uint32))
        _acc, ref_ll, _z = dense_ps_bsp_eval.evaluate(ran_on, *_as_coo(X), y)
        assert abs(ll - ref_ll) <= 2e-6 * ref_ll


# -- the servers: a pull while a round is open --------------------------------
def test_a_pull_in_an_open_round_is_answered_at_once_with_the_weights_before():
    dim, workers, lr = 64, 4, 0.5
    w0 = np.linspace(-1.0, 1.0, dim).astype(np.float32)
    g = np.full(dim, 0.25, np.float32)
    with ServerGroup(2, workers, dim, learning_rate=lr, sync=True) as group:
        with KVWorker(group.hosts, dim, client_id=0xFC00) as probe:
            probe.wait(probe.push_init(w0))
            clients = [KVWorker(group.hosts, dim, client_id=r,
                                sync_group=True) for r in range(workers)]
            replies = {}

            def push(r):
                replies[r] = clients[r].push_pull(g * (r + 1))

            early = [threading.Thread(target=push, args=(r,))
                     for r in (1, 2, 3)]
            for t in early:
                t.start()
            deadline = time.monotonic() + 30
            while (min(probe.stats(s)["pending_sync_pushes"]
                       for s in range(2)) < 3):
                assert time.monotonic() < deadline, "three pushes never merged"
                time.sleep(0.005)
            # the round is open: three merged and withheld.  Rank 0 pulls,
            # as its eval does, on the connection its push will use
            t = time.perf_counter()
            pulled = clients[0].pull()
            waited = time.perf_counter() - t
            assert all(th.is_alive() for th in early)  # still withheld
            assert np.array_equal(pulled, w0)
            assert waited < 5.0  # at once: the barrier's timeout is minutes
            assert [probe.stats(s)["pending_sync_pushes"]
                    for s in range(2)] == [3, 3]
            push(0)
            for t in early:
                t.join(timeout=30)
            want = w0 - lr * (g * (1 + 2 + 3 + 4)) / workers
            for r in range(workers):
                np.testing.assert_allclose(replies[r], want, rtol=1e-6)
            assert np.array_equal(clients[0].pull(), replies[0])
            for c in clients:
                c.close()
