"""A dense PS worker keeps its shard on the step's device: placed once,
the same gradients as a streamed batch bit for bit, and a second ``fit``
that loads, places and compiles nothing.  A whole-shard worker's batch is
the resident rows, a minibatch worker's a window of them; what still
streams is a shuffled or Q5-wrapping iterator, a keyed model, and a shard
the device has no room for."""

import threading

import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.data.synthetic import write_synthetic_shards
from distlr_tpu.obs.registry import family_total, get_registry
from distlr_tpu.ps import ServerGroup
from distlr_tpu.train.ps_trainer import PSWorker, ps_param_dim

DIM, CLASSES = 24, 3
H2D = "distlr_h2d_bytes_total"
ROUNDS = "distlr_ps_grad_rounds_total"


pytestmark = pytest.mark.usefixtures("ps_steps_on_device")


def _job(tmp_path, model, num_workers, **kw):
    d = str(tmp_path / f"{model}-{num_workers}")
    write_synthetic_shards(d, 100 * num_workers, DIM, num_parts=num_workers,
                           seed=5, sparsity=0.0,
                           num_classes=CLASSES if model == "softmax" else 2)
    base = dict(
        data_dir=d, num_feature_dim=DIM, model=model,
        num_classes=CLASSES if model == "softmax" else 2,
        num_workers=num_workers, num_servers=2, sync_mode=False,
        batch_size=-1, num_iteration=5, learning_rate=0.2, l2_c=0.0,
        test_interval=0)
    return Config(**{**base, **kw})


def _group(cfg):
    return ServerGroup(cfg.num_servers, cfg.num_workers, ps_param_dim(cfg),
                       learning_rate=cfg.learning_rate, sync=cfg.sync_mode)


def _in_threads(workers, call):
    errors = []

    def one(w):
        try:
            call(w)
        except Exception as e:  # surfaced below
            errors.append(e)

    threads = [threading.Thread(target=one, args=(w,)) for w in workers]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors


def _shard_bytes(worker):
    it = worker._train
    it.reset()
    batch = it.next_batch()
    it.reset()
    return sum(a.nbytes for a in batch)


class _Recorder:
    def __init__(self, step, keep=3):
        self.step, self.keep, self.seen = step, keep, []

    def __call__(self, wf, batch):
        g = self.step(wf, batch)
        if len(self.seen) < self.keep:
            self.seen.append((np.array(wf), np.array(g)))
        return g


@pytest.mark.parametrize("model", ["binary_lr", "softmax"])
@pytest.mark.parametrize("num_workers", [1, 4])
def test_whole_shard_is_placed_once_and_gives_the_streamed_gradients(
        tmp_path, monkeypatch, model, num_workers):
    cfg = _job(tmp_path, model, num_workers)
    before = family_total(H2D)
    with _group(cfg) as group:
        workers = [PSWorker(cfg, r, group.hosts) for r in range(num_workers)]
        with monkeypatch.context() as m:
            m.setattr(PSWorker, "_place_shard", lambda self, train, dev: None)
            twins = [PSWorker(cfg, r, group.hosts) for r in range(num_workers)]
            for t in twins:
                t.load_data()
        try:
            assert family_total(H2D) == before  # a streaming worker places nothing ahead
            for w in workers:
                w.load_data()
            shards = sum(_shard_bytes(w) for w in workers)
            assert family_total(H2D) - before == shards
            gauge = get_registry().get("distlr_ps_resident_bytes")
            for w, t in zip(workers, twins):
                assert w._resident is not None and t._resident is None
                assert (gauge.labels(rank=str(w.rank)).value
                        == _shard_bytes(w))
                w.grad_step = _Recorder(w.grad_step)
            _in_threads(workers, lambda w: w.run(save=False))
            # five iterations later the one placement is all that crossed
            assert family_total(H2D) - before == shards
            for w, t in zip(workers, twins):
                assert w.rounds == cfg.num_iteration == w.timer.steps
                assert len(w.grad_step.seen) == 3
                for weights, pushed in w.grad_step.seen:
                    t._train.reset()
                    streamed = t.grad_step(weights, t._train.next_batch())
                    assert pushed.dtype == streamed.dtype == np.float32
                    assert np.array_equal(pushed, streamed)
                    assert np.count_nonzero(pushed)
        finally:
            for w in (*workers, *twins):
                w.close()


@pytest.mark.parametrize("why", ["shuffled", "wrap_compat", "no_room"])
def test_a_minibatch_worker_still_streams(tmp_path, monkeypatch, why):
    """What a window cannot serve: rows in another order than they are
    held, a last batch that Q5 wraps, a shard the device has no room
    for."""
    from distlr_tpu.data.iterator import DataIter
    from distlr_tpu.train import ps_trainer

    # 80 training rows: the last batch of 24 is short
    cfg = _job(tmp_path, "binary_lr", 1, batch_size=24, num_iteration=2,
               wrap_final_batch=why == "wrap_compat")
    train = None
    if why == "shuffled":
        from distlr_tpu.data.sharding import part_name

        train = DataIter.from_file(f"{cfg.data_dir}/train/{part_name(0)}",
                                   DIM, 24, shuffle=True, seed=3)
    if why == "no_room":
        monkeypatch.setattr(ps_trainer, "_device_free_bytes",
                            lambda device: 100 * DIM * 4)
    before = family_total(H2D)
    with _group(cfg) as group:
        w = PSWorker(cfg, 0, group.hosts, train_iter=train)
        try:
            w.load_data()
            assert w._resident is None
            final = w.run(save=False)
        finally:
            w.close()
    assert family_total(H2D) == before
    assert w.rounds == 2 * w._train.num_batches > 2
    assert np.isfinite(final).all() and np.count_nonzero(final)


@pytest.mark.parametrize("where", ["size", "device"])
def test_a_keyed_model_places_nothing_where_its_step_is_numpys(
        tmp_path, ps_steps_on, where):
    """By its size a 16-row sparse step is numpy's, over the batch's
    unique rows: no program, nothing placed.  Where the rule sends it to
    the device the worker keeps its localised shard there
    (``tests/test_ps_keyed_device.py``)."""
    cfg = _job(tmp_path, "sparse_lr", 1, batch_size=16, num_iteration=1)
    before = family_total(H2D)
    with ps_steps_on(where), _group(cfg) as group:
        w = PSWorker(cfg, 0, group.hosts)
        try:
            w.load_data()
            assert w._grad_fn is None and w.grad_step is not None
            assert (w._resident is None) == (where == "size")
            w.run(save=False)
        finally:
            w.close()
    assert (family_total(H2D) == before) == (where == "size")
    assert w.rounds == w._train.num_batches


# -- a minibatch worker's batch is a window of its resident shard -----------
WINDOW_ROUNDS = "distlr_ps_window_rounds_total"
WINDOW_ROWS = "distlr_ps_window_rows_total"


def _count(name, rank=0, **labels):
    return get_registry().get(name).labels(rank=str(rank), **labels).value


def _h2d_spans():
    from distlr_tpu.obs.tracing import get_tracer

    return get_tracer().breakdown().get("h2d", {"count": 0})["count"]


@pytest.fixture
def one_pass_on_the_cpu(monkeypatch):
    """The selection as a TPU makes it, the kernel interpreted."""
    from distlr_tpu.train import ps_trainer

    monkeypatch.setattr(ps_trainer, "_ONE_PASS_PLATFORMS", ("tpu", "cpu"))


@pytest.mark.parametrize("step", ["xla", "one_pass"])
@pytest.mark.parametrize("sync", [False, True], ids=["async", "bsp"])
@pytest.mark.parametrize("batch", [32, 40], ids=["B-divides-R", "B-leaves-16"])
def test_a_windowed_workers_gradients_are_a_streamed_workers(
        tmp_path, monkeypatch, request, batch, sync, step):
    """Round by round over two epochs of 96 rows: the windows
    ``[k B, k B + B)`` of the resident shard give what the iterator's
    gathered batches give, the short last batch's pad rows masked out."""
    from distlr_tpu.train import ps_trainer

    if step == "one_pass":
        request.getfixturevalue("one_pass_on_the_cpu")
    cfg = _job(tmp_path, "binary_lr", 1, num_feature_dim=300,
               compute_dtype="float32", l2_c=0.3, batch_size=batch,
               sync_mode=sync, num_iteration=2)
    write_synthetic_shards(cfg.data_dir, 120, 300, num_parts=1, seed=6,
                           sparsity=0.0)
    rounds = 2 * -(-96 // batch)
    was = (_count(WINDOW_ROUNDS), _count(WINDOW_ROWS),
           _count(ROUNDS, path=step if step == "one_pass" else "two_pass"))
    with _group(cfg) as group:
        w = PSWorker(cfg, 0, group.hosts)
        with monkeypatch.context() as m:
            m.setattr(PSWorker, "_place_shard", lambda self, train, dev: None)
            m.setattr(ps_trainer, "_ONE_PASS_PLATFORMS", ("tpu",))
            twin = PSWorker(cfg, 0, group.hosts)
            twin.load_data()
        try:
            w.load_data()
            X, y, mask = w._resident
            assert twin._resident is None and w._windowed
            assert (w._panels is not None) == (step == "one_pass")
            assert len(y) == len(mask) == X.shape[0] == rounds // 2 * batch
            assert int(mask.sum()) == 96 and not np.asarray(X[96:]).any()
            w.grad_step = _Recorder(w.grad_step, keep=rounds)
            placed, spans = family_total(H2D), _h2d_spans()
            w.run(save=False)
            assert w.rounds == rounds == len(w.grad_step.seen)
            assert family_total(H2D) == placed and _h2d_spans() == spans
            assert _count(WINDOW_ROUNDS) - was[0] == rounds
            assert _count(WINDOW_ROWS) - was[1] == 2 * 96
            assert _count(ROUNDS, path=step if step == "one_pass"
                          else "two_pass") - was[2] == rounds
            twin._train.reset()
            for k, (weights, pushed) in enumerate(w.grad_step.seen):
                if k == rounds // 2:
                    twin._train.reset()
                streamed = twin.grad_step(weights, twin._train.next_batch())
                assert pushed.dtype == streamed.dtype == np.float32
                # not bit for bit: XLA sums a slice fused into the product
                # in another order than a batch handed in whole
                assert (np.linalg.norm(pushed - streamed)
                        <= 1e-6 * np.linalg.norm(streamed)), k
                assert np.count_nonzero(pushed)
        finally:
            w.close()
            twin.close()


@pytest.mark.parametrize("step", ["xla", "one_pass"])
def test_windows_place_once_and_run_one_executable(tmp_path, request, step):
    """Two ``fit``s of a windowed worker: one placement, no ``h2d`` span,
    and the jit cache of the step as the first round left it."""
    if step == "one_pass":
        request.getfixturevalue("one_pass_on_the_cpu")
    cfg = _job(tmp_path, "binary_lr", 1, num_feature_dim=300,
               compute_dtype="float32", batch_size=24, num_iteration=9)
    write_synthetic_shards(cfg.data_dir, 120, 300, num_parts=1, seed=6,
                           sparsity=0.0)
    before, spans = family_total(H2D), _h2d_spans()
    with _group(cfg) as group:
        w = PSWorker(cfg, 0, group.hosts)
        try:
            w.load_data()
            placed = family_total(H2D)
            shard = sum(a.nbytes for a in w._train.held_rows())
            assert shard > 96 * 300 * 4
            assert placed - before == shard
            assert _count("distlr_ps_resident_bytes") == shard
            w.start()
            w.grad_step = _Recorder(w.grad_step, keep=1)
            w.fit(epochs=1)
            compiled = w._grad_fn._cache_size()
            w.fit(epochs=2)
            w.fit(epochs=1)
            assert (w.epochs_done, w.rounds) == (4, 16)
            assert w._grad_fn._cache_size() == compiled
            assert family_total(H2D) == placed and _h2d_spans() == spans
            w.finish(save=False)
        finally:
            w.close()


def test_numpy_steps_place_nothing(tmp_path, ps_steps_on):
    """Its size takes a step this small to numpy: no device, no shard."""
    cfg = _job(tmp_path, "binary_lr", 1)
    before = family_total(H2D)
    with ps_steps_on("size"), _group(cfg) as group:
        w = PSWorker(cfg, 0, group.hosts)
        try:
            w.load_data()
            assert w._resident is None
            w.run(save=False)
        finally:
            w.close()
    assert family_total(H2D) == before


def test_a_second_fit_loads_places_and_compiles_nothing(tmp_path, monkeypatch):
    cfg = _job(tmp_path, "binary_lr", 1)
    with _group(cfg) as group:
        w = PSWorker(cfg, 0, group.hosts)
        try:
            w.load_data()
            w.start()
            w.fit(epochs=2)
            assert (w.epochs_done, w.rounds, w._barrier_base) == (2, 2, 0)
            placed = family_total(H2D)
            compiled = w._grad_fn._cache_size()
            held = w.kv.pull()

            def refuse(*a, **kw):
                raise AssertionError("loaded or placed again")

            monkeypatch.setattr(PSWorker, "_load_train_iter", refuse)
            monkeypatch.setattr(PSWorker, "_place_shard", refuse)
            w.load_data()
            w.start()  # again: the next pair of barrier generations,
            assert np.array_equal(w.kv.pull(), held)  # the init a no-op
            w.fit(epochs=3)
            assert (w.epochs_done, w.rounds, w._barrier_base) == (5, 5, 2)
            assert w.timer.steps == 5
            assert family_total(H2D) == placed
            assert w._grad_fn._cache_size() == compiled
            w.fit()  # nothing is left of num_iteration
            assert w.rounds == 5
            final = w.finish(save=False)
        finally:
            w.close()
    assert np.isfinite(final).all()


def test_whole_shard_hands_over_the_arrays_an_iterator_holds():
    from distlr_tpu.data.iterator import DataIter

    X = np.arange(12, dtype=np.float32).reshape(6, 2)
    y = np.arange(6) % 2
    got = DataIter(X, y, -1).whole_shard()
    assert got[0] is not None and np.shares_memory(got[0], X)
    assert np.shares_memory(got[1], y) and got[2].all() and len(got[2]) == 6
    # and the batch next_batch would gather, row for row
    want = DataIter(X, y, -1).next_batch()
    assert all(np.array_equal(a, b) for a, b in zip(got, want))


@pytest.mark.parametrize("kw", [{"batch_size": 4}, {"shuffle": True, "seed": 3}])
def test_whole_shard_is_nothing_where_a_batch_is_anything_else(kw):
    from distlr_tpu.data.iterator import DataIter

    X = np.arange(12, dtype=np.float32).reshape(6, 2)
    assert DataIter(X, np.zeros(6), **{"batch_size": -1, **kw}).whole_shard() is None


# -- the one-pass step of a resident shard (ops/pallas_lr.py) ---------------

def _rounds(rank, path):
    return _count(ROUNDS, rank, path=path)


def _one_pass_job(tmp_path, model="binary_lr", **kw):
    # 96 rows a worker (whole sublane groups), a D that is no multiple of
    # 128, float32 matmuls: XLA's program on the CPU rounds to bfloat16
    # otherwise, and the comparison below is at 1e-6
    return _job(tmp_path, model, 1, num_feature_dim=300,
                compute_dtype="float32", l2_c=0.3,
                **{"num_iteration": 4, **kw})


def _write_rows(cfg, rows, **kw):
    """``rows`` training rows (and a quarter as many to test on)."""
    write_synthetic_shards(cfg.data_dir, rows * 5 // 4, cfg.num_feature_dim,
                           num_parts=1, seed=6, sparsity=0.0, **kw)


def test_a_resident_shard_takes_the_one_pass_step(tmp_path, monkeypatch,
                                                  one_pass_on_the_cpu):
    from distlr_tpu.train import ps_trainer

    cfg = _one_pass_job(tmp_path)
    _write_rows(cfg, 96)
    before = family_total(H2D)
    with _group(cfg) as group:
        w = PSWorker(cfg, 0, group.hosts)
        with monkeypatch.context() as m:
            m.setattr(ps_trainer, "_ONE_PASS_PLATFORMS", ("tpu",))
            parent = PSWorker(cfg, 0, group.hosts)
            parent.load_data()
        try:
            w.load_data()
            plan = w._panels
            assert parent._panels is None and plan is not None
            assert (plan.rows, plan.dim, plan.held_share) == (96, 300, 1.0)
            # a whole second bank of slots: the next panel fetched ahead
            assert plan.ahead == plan.chunks and plan.slots == 2 * plan.chunks
            X = w._resident[0]
            assert X.shape == (96, plan.dim_padded) and X.dtype == np.float32
            assert not np.asarray(X[:, 300:]).any()
            assert np.array_equal(np.asarray(X[:, :300]),
                                  np.asarray(parent._resident[0]))
            # what crossed and what is counted as held: the shard's bytes
            assert family_total(H2D) - before == 2 * _shard_bytes(w)
            reg = get_registry()
            assert (reg.get("distlr_ps_resident_bytes").labels(rank="0").value
                    == _shard_bytes(w))
            assert reg.get("distlr_ps_grad_panel_held").labels(
                rank="0").value == 1.0
            assert reg.get("distlr_ps_grad_panel_ahead").labels(
                rank="0").value == plan.ahead_share == 1.0
            was = _rounds(0, "one_pass"), _rounds(0, "two_pass")
            w.grad_step = _Recorder(w.grad_step)
            w.run(save=False)
            assert w.rounds == cfg.num_iteration
            assert _rounds(0, "one_pass") - was[0] == cfg.num_iteration
            for weights, pushed in w.grad_step.seen:
                want = parent.grad_step(weights, parent._resident)
                assert pushed.dtype == want.dtype == np.float32
                assert pushed.shape == want.shape == (300,)
                assert (np.linalg.norm(pushed - want)
                        <= 1e-6 * np.linalg.norm(want))
            assert _rounds(0, "two_pass") - was[1] == len(w.grad_step.seen)
        finally:
            w.close()
            parent.close()


@pytest.mark.parametrize("refusal", ["streamed", "softmax", "rows", "window"])
def test_the_selection_keeps_the_xla_step(tmp_path, one_pass_on_the_cpu,
                                          refusal):
    """A streamed batch (the rows of a Q5-wrapping iterator), a softmax
    model, rows that are no whole sublane groups and windows that are
    none each keep ``model.grad`` under XLA, counted so."""
    kw = {"streamed": dict(batch_size=36, wrap_final_batch=True),
          "softmax": dict(model="softmax"),
          "rows": {},
          "window": dict(batch_size=36)}[refusal]
    cfg = _one_pass_job(tmp_path, num_iteration=2, **kw)
    _write_rows(cfg, 100 if refusal == "rows" else 96,
                num_classes=cfg.num_classes)
    was = _rounds(0, "one_pass"), _rounds(0, "two_pass")
    with _group(cfg) as group:
        w = PSWorker(cfg, 0, group.hosts)
        try:
            w.load_data()
            assert w._panels is None
            assert (w._resident is None) == (refusal == "streamed")
            assert w._windowed == (refusal == "window")
            if w._resident is not None:
                # the default layout, three windows of 36 where 96 rows
                # are read in windows
                assert w._resident[0].shape == (
                    108 if refusal == "window" else w._train.num_samples, 300)
            final = w.run(save=False)
        finally:
            w.close()
    assert np.isfinite(final).all() and np.count_nonzero(final)
    assert _rounds(0, "one_pass") == was[0]
    assert _rounds(0, "two_pass") - was[1] == w.rounds > 0
    for share in ("held", "ahead"):
        assert get_registry().get(f"distlr_ps_grad_panel_{share}").labels(
            rank="0").value == 0.0


def test_the_selection_reads_the_model_the_device_and_the_shape():
    import jax

    from distlr_tpu.models.linear import BinaryLR, SoftmaxRegression
    from distlr_tpu.train import ps_trainer

    _one_pass_plan = ps_trainer._one_pass_plan

    tpu = type("Device", (), {"platform": "tpu"})()
    plan = _one_pass_plan(BinaryLR(1_000_000), 384, 1_000_000, tpu)
    assert plan.held_share == 1.0 and plan.dim_padded % 128 == 0
    assert _one_pass_plan(BinaryLR(1_000_000, int8_dot=True),
                          384, 1_000_000, tpu) is None
    assert _one_pass_plan(SoftmaxRegression(1_000_000, 3),
                          384, 1_000_000, tpu) is None
    assert _one_pass_plan(BinaryLR(1_000_000), 380, 1_000_000, tpu) is None
    assert _one_pass_plan(BinaryLR(4_000_000), 384, 4_000_000, tpu) is None
    # the CPU never: the kernel runs there only interpreted, in tests
    assert _one_pass_plan(BinaryLR(1_000_000), 384, 1_000_000,
                          jax.devices()[0]) is None
