"""``PSWorker.fit`` is ONE loop over an exchange: what each variant of the
exchange puts on the wire, in order, with which keys and which
``vals_per_key``; what the worker holds (``_w_cache``) after each round;
the pipelined epoch's drain; a keyed span's empty vote.  Over a recording
connection in the servers' place: two epochs of three rounds a case."""

import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.data.iterator import DataIter, SparseDataIter
from distlr_tpu.models import host_math
from distlr_tpu.obs.tracing import get_tracer
from distlr_tpu.train import ps_trainer

DIM, CLASSES, ROWS, BATCH, NNZ, LR = 12, 3, 48, 16, 3, 0.5
EPOCHS, ROUNDS = 2, ROWS // BATCH


class Recording:
    """A connection whose servers are one array under plain SGD, and
    which notes every keyed operation: ``(op, keys, vals_per_key)``."""

    aligned = True   # the group's ranges align to any row width

    def __init__(self, hosts, dim, **kw):
        self.dim, self.calls = dim, []
        self.table = np.random.default_rng(3).standard_normal(dim).astype(
            np.float32) * 0.1

    def supports_vals_per_key(self, vpk):
        return vpk <= 1 or Recording.aligned

    def _slots(self, keys, vpk):
        if keys is None:
            return np.arange(self.dim)
        return (np.asarray(keys, np.int64)[:, None] * vpk
                + np.arange(vpk)).reshape(-1)

    def _note(self, op, keys, vpk):
        self.calls.append(
            (op, None if keys is None else tuple(int(k) for k in keys), vpk))

    def pull(self, keys=None, *, vals_per_key=1):
        self._note("pull", keys, vals_per_key)
        return self.table[self._slots(keys, vals_per_key)].copy()

    def push(self, vals, keys=None, *, vals_per_key=1):
        self._note("push", keys, vals_per_key)
        self.table[self._slots(keys, vals_per_key)] -= LR * np.asarray(vals)
        return 0

    def push_pull(self, vals, keys=None, *, vals_per_key=1):
        self._note("push_pull", keys, vals_per_key)
        self.table[self._slots(keys, vals_per_key)] -= LR * np.asarray(vals)
        return self.table.copy()

    def wait(self, ts):
        pass

    def global_pushes(self):
        return 0.0

    def close(self):
        pass


class Rounds:
    """In the step timer's place: what the worker held when each round
    ended; round the gradient call: the weights and keys each round saw."""

    def __init__(self, worker, step=None):
        self.worker, self.step = worker, step or worker.grad_step
        self.held, self.ran_on, self.grads, self.loaded = [], [], [], []
        worker.timer, worker.grad_step = self, self

    def start(self):
        # how many batches the loop had fetched when the timer started
        self.loaded.append(len(_spans("data_load")))

    def stop(self, n):
        held = self.worker._w_cache
        self.held.append(None if held is None else held.copy())

    def __call__(self, w, batch):
        self.ran_on.append(w.copy())
        self.grads.append(np.array(self.step(w, batch)))
        return self.grads[-1]


def _worker(monkeypatch, model, *, aligned=True, **kw):
    monkeypatch.setattr(ps_trainer, "KVWorker", Recording)
    monkeypatch.setattr(Recording, "aligned", aligned)
    rng = np.random.default_rng(11)
    y = rng.integers(0, CLASSES if "softmax" in model else 2, ROWS)
    if model.startswith("sparse"):
        train = SparseDataIter(rng.integers(0, DIM, (ROWS, NNZ)),
                               np.ones((ROWS, NNZ), np.float32), y, BATCH)
    else:
        train = DataIter(rng.standard_normal((ROWS, DIM)).astype(np.float32),
                         y, BATCH)
    cfg = Config(model=model, num_feature_dim=DIM, num_classes=CLASSES,
                 batch_size=BATCH, learning_rate=LR, l2_c=0.0,
                 test_interval=0, num_workers=2, **kw)
    # rank 1: no test split to load, no eval; the loop is every rank's
    worker = ps_trainer.PSWorker(cfg, 1, "nowhere:0", train_iter=train)
    worker.load_data()
    return worker, worker.kv, train


def _fit(worker, step=None):
    rounds = Rounds(worker, step)
    get_tracer().reset()
    worker.fit(epochs=EPOCHS)
    worker.close()
    assert worker.rounds == EPOCHS * ROUNDS == len(rounds.held)
    return rounds


def _spans(name):
    return [e["args"] for e in get_tracer().chrome_trace()["traceEvents"]
            if e["name"] == name]


def _same(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert (a is None and b is None) or np.array_equal(a, b)


def _batch_rows(train):
    """The unique rows each round of an epoch touches."""
    train.reset()
    return [np.unique(b[0]) for b in train]


# -- serialized: pull, then push and wait -----------------------------------
@pytest.mark.parametrize("sync", [True, False], ids=["sync", "async"])
def test_serialized_dense_pulls_and_pushes_every_round(monkeypatch, sync):
    worker, kv, _ = _worker(monkeypatch, "binary_lr", sync_mode=sync,
                            ps_pipeline=False)
    rounds = _fit(worker)
    assert kv.calls == [("pull", None, 1), ("push", None, 1)] * 6
    # every round runs on its own pull, and the loop holds none of them
    assert not np.array_equal(rounds.ran_on[0], rounds.ran_on[1])
    assert rounds.held == [None] * 6 and worker._w_cache is None
    # a dense batch is fetched before the step timer starts
    assert rounds.loaded == [1, 2, 3, 4, 5, 6]


@pytest.mark.parametrize("model,aligned,vpk", [
    ("sparse_lr", True, 1),
    ("sparse_softmax", True, CLASSES),     # one key a row
    ("sparse_softmax", False, 1),          # a row's lanes, key by key
], ids=["one-value-rows", "vals-per-key", "expanded-keys"])
@pytest.mark.parametrize("sync", [True, False], ids=["sync", "async"])
def test_serialized_keyed_addresses_its_rows_as_the_group_allows(
        monkeypatch, model, aligned, vpk, sync):
    worker, kv, train = _worker(monkeypatch, model, aligned=aligned,
                                sync_mode=sync)
    assert worker._rows.vpk == vpk
    rounds = _fit(worker)
    want = []
    for rows in _batch_rows(train) * EPOCHS:
        keys = rows if vpk > 1 or model == "sparse_lr" else (
            host_math.expand_block_keys(rows, CLASSES))
        keys = tuple(int(k) for k in keys)
        want += [("pull", keys, vpk), ("push", keys, vpk)]
    assert kv.calls == want
    # a keyed loop holds no flat weights
    assert rounds.held == [None] * 6 and worker._w_cache is None
    # naming a keyed batch's unique rows is part of the timed step
    assert rounds.loaded == [0, 1, 2, 3, 4, 5]


# -- fused: BSP, a blocking push_pull ---------------------------------------
def test_fused_pulls_once_and_holds_every_reply(monkeypatch):
    worker, kv, _ = _worker(monkeypatch, "binary_lr", sync_mode=True)
    opening = kv.table.copy()
    rounds = _fit(worker)
    assert kv.calls == [("pull", None, 1)] + [("push_pull", None, 1)] * 6
    # a round runs on the reply before it; the last reply is held
    _same(rounds.ran_on, [opening, *rounds.held[:-1]])
    assert np.array_equal(worker._w_cache, kv.table)
    assert not any("drain" in a for a in _spans("push"))
    # the one pull comes before the first round, not inside it
    assert [a["step"] for a in _spans("pull")] == [0]
    # a second fit goes on from what is held: no pull
    worker2, kv2, _ = _worker(monkeypatch, "binary_lr", sync_mode=True)
    worker2.fit(epochs=1)
    worker2.fit(epochs=1)
    assert [c[0] for c in kv2.calls] == ["pull"] + ["push_pull"] * 6


# -- pipelined: async, one push_pull in flight, drained at an epoch's end ----
def test_pipelined_keeps_one_in_flight_and_drains_each_epoch(monkeypatch):
    worker, kv, _ = _worker(monkeypatch, "binary_lr", sync_mode=False)
    opening = kv.table.copy()
    replies = []
    real = kv.push_pull

    def noted(*a, **kw):   # on the instance, as the benchmark's tap is
        replies.append(real(*a, **kw))
        return replies[-1]

    kv.push_pull = noted
    rounds = _fit(worker)
    assert kv.calls == [("pull", None, 1)] + [("push_pull", None, 1)] * 6
    r = replies
    # a round's push is waited for after the NEXT gradient: the worker
    # holds the reply before last when a round ends ...
    _same(rounds.held, [opening, r[0], r[1], r[2], r[3], r[4]])
    # ... and a round runs on what it held, but an epoch's first, which
    # the drain has handed the epoch's last reply (r[1] is never run on)
    _same(rounds.ran_on, [opening, opening, r[0], r[2], r[2], r[3]])
    assert np.array_equal(worker._w_cache, r[5])
    assert [a["step"] for a in _spans("pull")] == [0]
    pushes = _spans("push")
    assert [a["step"] for a in pushes if a.get("drain") == 1] == [3, 6]
    assert sorted(a["step"] for a in pushes if "drain" not in a) == [
        2, 3, 5, 6]


# -- accumulated: a span's mean, round the serialized exchange ---------------
ACCUM = dict(ps_accum_start=2, ps_accum_max=2)


@pytest.mark.parametrize("sync", [True, False], ids=["sync", "async"])
def test_accumulated_dense_pulls_a_span_and_pushes_its_mean(monkeypatch,
                                                            sync):
    worker, kv, _ = _worker(monkeypatch, "binary_lr", sync_mode=sync, **ACCUM)
    pushed = []
    real = kv.push
    kv.push = lambda vals, **kw: (pushed.append(np.array(vals)),
                                  real(vals, **kw))[1]
    rounds = _fit(worker)
    # three rounds an epoch in spans of two: a whole span, then the
    # epoch's end flushes the partial one
    assert kv.calls == [("pull", None, 1), ("push", None, 1)] * 4
    g, held = rounds.grads, rounds.held
    _same(pushed, [(g[0] + g[1]) / 2, g[2], (g[3] + g[4]) / 2, g[5]])
    # a span's rounds run on the span's one pull
    _same(rounds.ran_on, held)
    assert np.array_equal(held[0], held[1])
    assert not np.array_equal(held[1], held[2])


@pytest.mark.parametrize("model,aligned,vpk", [
    ("sparse_lr", True, 1),
    ("sparse_softmax", True, CLASSES),
    ("sparse_softmax", False, 1),
], ids=["one-value-rows", "vals-per-key", "expanded-keys"])
@pytest.mark.parametrize("sync", [True, False], ids=["sync", "async"])
def test_accumulated_keyed_pulls_every_round_and_pushes_the_spans_union(
        monkeypatch, model, aligned, vpk, sync):
    worker, kv, train = _worker(monkeypatch, model, aligned=aligned,
                                sync_mode=sync, **ACCUM)
    rounds = _fit(worker)
    width = CLASSES if model == "sparse_softmax" else 1

    def keys_of(rows):
        keys = rows if vpk == width else host_math.expand_block_keys(
            rows, width)
        return tuple(int(k) for k in keys)

    def union(span):
        """The keys of a span's mean that are not all zero."""
        total = np.zeros(DIM * width, np.float32)
        for rows, g in span:
            total[np.asarray(keys_of(rows))[:, None] * vpk
                  + np.arange(vpk)] += g.reshape(-1, vpk)
        rows = np.flatnonzero((total.reshape(-1, vpk) != 0).any(axis=1))
        return tuple(int(k) for k in rows)

    per_round = list(zip(_batch_rows(train) * EPOCHS, rounds.grads))
    want = []
    for e in range(EPOCHS):
        a, b, c = per_round[e * ROUNDS:(e + 1) * ROUNDS]
        want += [("pull", keys_of(a[0]), vpk), ("pull", keys_of(b[0]), vpk),
                 ("push", union([a, b]), vpk),
                 ("pull", keys_of(c[0]), vpk), ("push", union([c]), vpk)]
    assert kv.calls == want
    assert rounds.held == [None] * 6


@pytest.mark.parametrize("sync,votes", [(True, 4), (False, 0)],
                         ids=["sync", "async"])
def test_a_keyed_span_of_zeros_still_votes_in_lock_step(monkeypatch, sync,
                                                        votes):
    """BSP peers' deferred replies wait on every worker's push: a span
    whose mean is all zeros sends its EMPTY frame; Hogwild sends none."""
    worker, kv, _ = _worker(monkeypatch, "sparse_lr", sync_mode=sync, **ACCUM)
    _fit(worker, step=lambda w, batch: np.zeros_like(w))
    pushes = [c for c in kv.calls if c[0] == "push"]
    assert pushes == [("push", (), 1)] * votes
    assert sum(c[0] == "pull" for c in kv.calls) == 6


@pytest.mark.parametrize("kw,sets", [
    (dict(sync_mode=True, ps_pipeline=False), 0),
    (dict(sync_mode=True), 0),
    (dict(sync_mode=True, **ACCUM), 0),
    (dict(sync_mode=False, ps_pipeline=False), 6),
    (dict(sync_mode=False), 6),
    (dict(sync_mode=False, **ACCUM), 4),
], ids=["bsp-serialized", "bsp-fused", "bsp-span",
        "hogwild-serialized", "hogwild-pipelined", "hogwild-span"])
def test_only_a_hogwild_worker_has_a_weight_age(monkeypatch, kw, sets):
    """In lock step the age of the weights is the round: a BSP worker
    neither sets the staleness gauge nor makes its series; a Hogwild
    worker sets it once a gradient that leaves."""
    class Gauge:
        made, ages = 0, []

        def labels(self, **kw):
            Gauge.made += 1
            return self

        def set(self, v):
            Gauge.ages.append(v)

    monkeypatch.setattr(ps_trainer, "_STALENESS", Gauge())
    worker, _, _ = _worker(monkeypatch, "binary_lr", **kw)
    _fit(worker)
    assert Gauge.made == (1 if sets else 0)
    assert len(Gauge.ages) == sets and all(a >= 0 for a in Gauge.ages)


def test_the_variant_is_chosen_from_the_config_and_the_model(monkeypatch):
    def chosen(model, **kw):
        worker, _, _ = _worker(monkeypatch, model, **kw)
        return type(worker._exchange())

    assert chosen("binary_lr", sync_mode=True) is ps_trainer._Fused
    assert chosen("binary_lr", sync_mode=False) is ps_trainer._Pipelined
    assert chosen("softmax", sync_mode=True,
                  ps_pipeline=False) is ps_trainer._Serialized
    assert chosen("sparse_lr", sync_mode=False) is ps_trainer._Serialized
    assert chosen("sparse_softmax", sync_mode=True) is ps_trainer._Serialized
    for sync in (True, False):
        assert chosen("binary_lr", sync_mode=sync,
                      **ACCUM) is ps_trainer._DenseSpan
        assert chosen("sparse_lr", sync_mode=sync,
                      **ACCUM) is ps_trainer._KeyedSpan
