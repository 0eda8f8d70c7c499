"""A keyed ``sparse_lr`` PS worker keeps its shard on its step's device,
localised at load, and runs its step there as one compiled program: the
localisation, the program against numpy's step and against the plain
reference, four workers and two native servers end to end, and what
stays as it was (the other keyed families, a shuffled or wrapped shard, a
small step, every dense model's program)."""

import hashlib
import logging
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import datagen
from chipbench.drivers.ps_keyed_epochs import shard_bytes
from chipbench.families import sparse_ps_keyed
from distlr_tpu.config import Config
from distlr_tpu.data.hashing import make_uniform_blocked_batch
from distlr_tpu.data.iterator import (
    BlockedDataIter,
    SparseDataIter,
    Window,
)
from distlr_tpu.models import get_model, host_math
from distlr_tpu.obs.registry import family_total, get_registry
from distlr_tpu.obs.tracing import get_tracer
from distlr_tpu.ops.pallas_keyed import (
    CHUNK_LINES,
    TABLE_ROWS,
    chunk_base,
    keyed_plan,
)
from distlr_tpu.ps import KVWorker, ServerGroup
from distlr_tpu.train import ps_trainer

DIM, BATCH, SLOTS, LR = 4096, 256, 39, 0.2
ROWS = 2 * BATCH + 200          # the last window is short
WINDOWS = 3
LINES = 128     # 9,984 entries are 78 lines; two whole grid steps hold them
ROUNDS = "distlr_ps_grad_rounds_total"
# sha256[:16] of the keyed step's lowered text over this file's shard
PINNED_KEYED = "5f0039e7368b4e32"


@pytest.fixture(scope="module")
def rows():
    return datagen.make_rows(51, "train", 4 * ROWS, fields="criteo-kaggle",
                             num_buckets=DIM, label_scale=0.5,
                             label_bias=-1.0)


class Table:
    """A connection whose servers are one array under plain SGD."""

    def __init__(self, hosts, dim, **kw):
        self.dim, self.pulled, self.pushed, self.held = dim, [], [], []
        self.table = (np.random.default_rng(3).standard_normal(dim)
                      .astype(np.float32) * 0.1)

    def supports_vals_per_key(self, vpk):
        return True

    def _slots(self, keys, vpk):
        return (np.asarray(keys, np.int64)[:, None] * vpk
                + np.arange(vpk)).reshape(-1)

    def hold(self, keys, vals_per_key=1):
        frame = np.array(keys, np.uint64)
        frame.flags.writeable = False
        self.held.append(frame)
        return frame

    def pull(self, keys=None, *, vals_per_key=1, out=None):
        self.pulled.append(np.array(keys))
        got = self.table[self._slots(keys, vals_per_key)]
        if out is None:
            return got.copy()
        out[:len(got)] = got
        return out[:len(got)]

    def push(self, vals, keys=None, *, vals_per_key=1):
        self.pushed.append((np.array(keys), np.array(vals)))
        self.table[self._slots(keys, vals_per_key)] -= LR * np.asarray(vals)
        return 0

    def wait(self, ts):
        pass

    def global_pushes(self):
        return 0.0

    def close(self):
        pass


def _cfg(model="sparse_lr", **kw):
    base = dict(model=model, num_feature_dim=DIM, batch_size=BATCH,
                learning_rate=LR, l2_c=0.0, test_interval=0, num_workers=2,
                sync_mode=False)
    return Config(**{**base, **kw})


def _worker(monkeypatch, train, cfg=None):
    monkeypatch.setattr(ps_trainer, "KVWorker", Table)
    # rank 1: no test split to load, no eval; the loop is every rank's
    worker = ps_trainer.PSWorker(cfg or _cfg(), 1, "nowhere:0",
                                 train_iter=train)
    get_tracer().reset()
    worker.load_data()
    return worker


def _shard(rows, rank=0):
    return tuple(a[rank * ROWS:(rank + 1) * ROWS] for a in rows)


def _count(name, rank=1, **labels):
    return get_registry().get(name).labels(rank=str(rank), **labels).value


# -- the localisation ---------------------------------------------------------
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("shape", [(256, 39), (1, 5), (700, 3)])
def test_localise_is_numpys_unique_without_the_sort(dtype, shape):
    cols = np.random.default_rng(shape[0]).integers(0, 5000, shape).astype(
        dtype)
    keys, places = host_math.localise(cols, 5000)
    want, inverse = np.unique(cols, return_inverse=True)
    assert np.array_equal(keys, want)
    assert places.dtype == np.int32 and places.shape == cols.shape
    assert np.array_equal(places, inverse.reshape(cols.shape))


def _leaves(w):
    """A placed worker's leaves on the host (packed, values, labels,
    mask, bases), and its row bits."""
    return ([np.asarray(a) for a in (*w._resident, w._keyed_bases)],
            w._keyed_row_bits)


def _entries(keys, packed, values, bits):
    """A window's entries as ``(column, row, value)`` in one order."""
    col = keys[packed >> bits].astype(np.int64)
    row = packed & ((1 << bits) - 1)
    order = np.lexsort((values, row, col))
    return col[order], row[order], values[order]


def test_the_shard_is_localised_once_and_held_lane_dense(
        monkeypatch, rows, ps_steps_on_device):
    cols, vals, y = _shard(rows)
    train = SparseDataIter(cols, vals, y, BATCH)
    w = _worker(monkeypatch, train)
    assert w._windowed and len(w._window_keys) == WINDOWS
    (packed, values, labels, mask, bases), bits = _leaves(w)
    # lane-dense: a window's entries in whole lines of 128 (whole grid
    # steps of the kernel), nothing a [rows, 39] array's lanes would be
    # padded with; a base row for every chunk of eight lines
    assert packed.shape == values.shape == (WINDOWS * LINES, 128)
    assert packed.dtype == np.int32 and values.dtype == np.float32
    assert bases.shape == (WINDOWS, LINES // CHUNK_LINES)
    assert bases.dtype == np.int32 and 1 << bits >= BATCH
    assert labels.shape == mask.shape == (WINDOWS * BATCH,)
    assert mask.sum() == ROWS and not mask[ROWS:].any()
    for j, keys in enumerate(w._window_keys):
        at = slice(j * BATCH, min(j * BATCH + BATCH, ROWS))
        assert keys.dtype == np.uint64
        assert np.array_equal(keys, np.unique(cols[at]))
        p = packed[j * LINES:(j + 1) * LINES].reshape(-1)
        v = values[j * LINES:(j + 1) * LINES].reshape(-1)
        # sorted by place, so that a chunk's entries name places next to
        # one another: 16 rows of the weights' table from its base
        place = p >> bits
        assert (np.diff(place) >= 0).all()
        chunks = place.reshape(-1, CHUNK_LINES * 128)
        assert np.array_equal(bases[j], chunk_base(chunks[:, 0]))
        assert (chunks < (bases[j][:, None] + TABLE_ROWS) * 128).all()
        # a permutation of the host's entries, each with its row; what is
        # put behind them (place 0, value 0; the row a short window's
        # masked one, or row 0 behind the last row's) names no key of its
        # own and adds nothing
        n = cols[at].size
        behind = np.arange(n, LINES * 128)
        behind = np.where(behind < BATCH * SLOTS, behind // SLOTS, 0)
        row = np.broadcast_to(np.arange(at.stop - at.start)[:, None],
                              cols[at].shape)
        want = _entries(
            keys, np.concatenate([behind.astype(np.int32), (
                np.searchsorted(keys, cols[at]).astype(np.int32) << bits
                | row).reshape(-1)]),
            np.concatenate([np.zeros(len(behind), np.float32),
                            vals[at].reshape(-1)]), bits)
        for got, held in zip(_entries(keys, p, v, bits), want):
            assert np.array_equal(got, held)
    # lines the kernel takes in whole grid steps (on a TPU; here the
    # step is XLA's over the same leaves)
    assert keyed_plan(BATCH, LINES, w._keyed_key_count, bits) is not None
    assert w._keyed_plan(train) is None and w._keyed_program == "xla"
    # one key count for the whole shard: no window compiles
    most = max(len(k) for k in w._window_keys)
    assert w._keyed_key_count % ps_trainer._KEYED_KEY_QUANTUM == 0
    assert most <= w._keyed_key_count < most + ps_trainer._KEYED_KEY_QUANTUM
    # never under what the benchmark holds a worker to: 8 B an entry in
    # whole lines, 8 B a row
    assert _count("distlr_ps_resident_bytes") == sum(
        a.nbytes for a in (packed, values, bases, labels, mask))
    assert _count("distlr_ps_resident_bytes") >= shard_bytes(
        ROWS, BATCH, SLOTS)
    spans = get_tracer().breakdown()
    assert spans["localise"]["count"] == spans["shard_put"]["count"] == 1
    # the host's copy is let go; the iterator serves windows
    assert train.X is None and train.vals is None
    train.reset()
    assert [train.next_window() for _ in range(WINDOWS)] == [
        Window(0, BATCH), Window(BATCH, BATCH), Window(2 * BATCH, 200)]


def test_whole_windows_of_whole_lines_are_the_hosts_own_values(
        monkeypatch, ps_steps_on_device):
    """Where the windows are whole and their entries whole grid steps of
    lines, nothing is put behind them: a window's values are the host's,
    in the places' order, and the step is numpy's."""
    quantum = ps_trainer._KEYED_LINE_QUANTUM
    batch = quantum * 128               # x 39 slots: 39 whole quanta of lines
    rng = np.random.default_rng(8)
    cols = rng.integers(0, DIM, (2 * batch, SLOTS))
    vals = rng.standard_normal(cols.shape).astype(np.float32)
    y = rng.integers(0, 2, 2 * batch).astype(np.int32)
    w = _worker(monkeypatch, SparseDataIter(cols, vals, y, batch),
                _cfg(batch_size=batch))
    (packed, values, _labels, mask, bases), bits = _leaves(w)
    assert packed.shape == (2 * SLOTS * quantum, 128) and mask.all()
    assert _count("distlr_ps_resident_bytes") - bases.nbytes == shard_bytes(
        2 * batch, batch, SLOTS)
    for j in range(2):
        at = slice(j * batch, (j + 1) * batch)
        lines = slice(j * SLOTS * quantum, (j + 1) * SLOTS * quantum)
        assert np.array_equal(np.sort(values[lines].reshape(-1)),
                              np.sort(vals[at].reshape(-1)))
        keys, places = np.unique(cols[at], return_inverse=True)
        p = packed[lines].reshape(-1)
        place, row = p >> bits, p & ((1 << bits) - 1)
        # every entry names a column its row has
        assert (cols[at][row] == keys[place][:, None]).any(axis=1).all()
        w_u = (rng.standard_normal(len(keys)) * 0.1).astype(np.float32)
        want = host_math.sparse_batch_grad(
            w_u, places.reshape(cols[at].shape), vals[at], y[at],
            np.ones(batch, bool), 0.0, False)
        got = w.grad_step(w_u, Window(at.start, batch))
        assert np.linalg.norm(got - want) <= 2e-6 * np.linalg.norm(want)


# -- the compiled step --------------------------------------------------------
@pytest.mark.parametrize("l2", [(0.0, False), (0.5, False), (0.5, True)],
                         ids=["no-l2", "l2", "l2-by-batch"])
@pytest.mark.parametrize("j", [0, 1, 2], ids=["first", "second", "short"])
def test_the_compiled_step_is_numpys_and_the_references(
        monkeypatch, rows, ps_steps_on_device, j, l2):
    cols, vals, y = _shard(rows)
    cfg = _cfg(l2_c=l2[0], l2_scale_by_batch=l2[1])
    w = _worker(monkeypatch, SparseDataIter(cols, vals, y, BATCH), cfg)
    at = slice(j * BATCH, min(j * BATCH + BATCH, ROWS))
    keys, places = np.unique(cols[at], return_inverse=True)
    w_u = (np.random.default_rng(j).standard_normal(len(keys)) * 0.1).astype(
        np.float32)
    real = at.stop - at.start
    got = w.grad_step(w_u, Window(at.start, real))
    assert got.shape == w_u.shape and got.dtype == np.float32
    want = host_math.sparse_batch_grad(
        w_u, places.reshape(cols[at].shape), vals[at], y[at],
        np.ones(real, bool), *l2)
    scale = np.linalg.norm(want)
    assert np.linalg.norm(got - want) <= 2e-6 * scale
    if not l2[0]:
        ref = sparse_ps_keyed.gradient(w_u, cols[at], vals[at], y[at])
        assert np.linalg.norm(got - ref) <= 2e-6 * scale
    assert _count(ROUNDS, path="keyed_device") >= 1


def test_every_window_and_every_epoch_runs_the_one_executable(
        monkeypatch, rows, ps_steps_on_device):
    cols, vals, y = _shard(rows)
    fn = ps_trainer._compiled_keyed_fns(0.0, False)
    w = _worker(monkeypatch, SparseDataIter(cols, vals, y, BATCH))
    before = _count(ROUNDS, path="keyed_device")
    keys0, rows0 = (_count("distlr_ps_keyed_keys_total"),
                    _count("distlr_ps_keyed_rows_total"))
    get_tracer().reset()
    w.fit(epochs=1)
    compiled = fn._cache_size()
    w.fit(epochs=2)
    assert fn._cache_size() == compiled
    assert _count(ROUNDS, path="keyed_device") - before == 3 * WINDOWS
    # a round pulls and pushes exactly its window's keys
    kv = w.kv
    want = [np.unique(cols[j * BATCH:(j + 1) * BATCH]) for j in range(WINDOWS)]
    assert len(kv.pulled) == len(kv.pushed) == 3 * WINDOWS
    for i, (pulled, (pushed, g)) in enumerate(zip(kv.pulled, kv.pushed)):
        assert np.array_equal(pulled, want[i % WINDOWS])
        assert np.array_equal(pushed, pulled) and len(g) == len(pushed)
    assert _count("distlr_ps_keyed_rows_total") - rows0 == 3 * ROWS
    assert _count("distlr_ps_keyed_keys_total") - keys0 == 3 * sum(
        len(k) for k in want)
    # nothing is placed or localised again, and the spans say the keys
    spans = get_tracer().breakdown()
    assert "localise" not in spans and "shard_put" not in spans
    assert "h2d" not in spans
    events = get_tracer().chrome_trace()["traceEvents"]
    for name in ("pull", "push", "w_put", "compute", "grad_d2h"):
        said = [e["args"]["keys"] for e in events if e["name"] == name]
        assert said == [len(want[i % WINDOWS]) for i in range(3 * WINDOWS)]
    w.close()


def test_the_device_rounds_are_the_host_rounds_to_rounding(
        monkeypatch, rows, ps_steps_on):
    """Two epochs over one table: the device step's trajectory beside
    numpy's, the same keys on the wire."""
    cols, vals, y = _shard(rows)
    tables = {}
    for where in ("device", "numpy"):
        with ps_steps_on(where):
            w = _worker(monkeypatch, SparseDataIter(cols, vals, y, BATCH))
            assert (w._resident is not None) == (where == "device")
            w.fit(epochs=2)
            tables[where] = (w.kv.table.copy(), w.kv.pulled)
            w.close()
    moved = np.linalg.norm(tables["numpy"][0] - Table(None, DIM).table)
    assert moved > 0
    assert np.linalg.norm(tables["device"][0] - tables["numpy"][0]) <= (
        1e-5 * moved)
    for a, b in zip(tables["device"][1], tables["numpy"][1]):
        # numpy's short batch is padded with copies of row 0, whose keys
        # it pulls too; the resident window's pads name no key
        assert set(a.tolist()) <= set(b.tolist())
    assert all(np.array_equal(a, b) for a, b in list(zip(
        tables["device"][1], tables["numpy"][1]))[:2])


# -- four workers, two native servers -----------------------------------------
def test_four_workers_and_two_servers_conserve_what_was_pushed(
        rows, ps_steps_on_device):
    cfg = _cfg(num_workers=4, num_servers=2)
    keyed0 = (family_total("distlr_ps_keyed_keys_total"),
              family_total("distlr_ps_keyed_rows_total"))

    def path(p):
        fam = get_registry().get(ROUNDS)
        return sum(c.value for labels, c in fam.children() if labels[-1] == p)

    device0, host0 = path("keyed_device"), path("keyed_host")
    with ServerGroup(2, 4, DIM, learning_rate=LR, sync=False) as group:
        probe = KVWorker(group.hosts, DIM, client_id=0xFC00)
        w0 = (np.random.default_rng(9).standard_normal(DIM) * 0.05).astype(
            np.float32)
        probe.wait(probe.push_init(w0))
        workers = [ps_trainer.PSWorker(
            cfg, r, group.hosts,
            train_iter=SparseDataIter(*_shard(rows, r), BATCH),
            test_iter=SparseDataIter(*_shard(rows, 0), -1))
            for r in range(4)]
        total = np.zeros(DIM, np.float64)
        first_pulls, moved_keys, lock = {}, [0], threading.Lock()
        together = threading.Barrier(4)
        try:
            for w in workers:
                w.load_data()
                assert w._resident is not None
                pull, push = w.kv.pull, w.kv.push

                def tapped_pull(keys=None, *, _pull=pull, _w=w, **kw):
                    got = _pull(keys=keys, **kw)
                    if _w.rank not in first_pulls:
                        first_pulls[_w.rank] = (np.array(keys), np.array(got))
                        together.wait(timeout=60)
                    return got

                def tapped_push(vals, keys=None, *, _push=push, **kw):
                    with lock:
                        total[np.asarray(keys).astype(np.int64)] += vals
                        moved_keys[0] += len(keys)
                    return _push(vals, keys=keys, **kw)

                w.kv.pull, w.kv.push = tapped_pull, tapped_push
            w_before = probe.pull()
            assert np.array_equal(w_before, w0)
            errors = []

            def run(w):
                try:
                    w.start()
                    w.fit(epochs=2)
                except Exception as e:  # surfaced below
                    errors.append(e)

            threads = [threading.Thread(target=run, args=(w,))
                       for w in workers]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not errors and not any(t.is_alive() for t in threads)
            w_after = probe.pull()
        finally:
            for w in workers:
                w.close()
            probe.close()
    # a pull returns the weights of exactly the keys asked: every
    # worker's first one, taken before any push, is the seed at its keys
    for rank, (keys, got) in first_pulls.items():
        want = np.unique(_shard(rows, rank)[0][:BATCH])
        assert np.array_equal(keys, want)
        assert np.array_equal(got.view(np.uint32),
                              w0[want].view(np.uint32))
    pushed = LR * total
    moved = w_after.astype(np.float64) - w_before
    assert np.linalg.norm(moved) > 0.5 * np.linalg.norm(pushed) > 0
    assert np.linalg.norm(moved + pushed) <= 2e-5 * np.linalg.norm(pushed)
    rounds = 4 * 2 * WINDOWS
    assert path("keyed_device") - device0 == rounds
    assert path("keyed_host") == host0
    assert family_total("distlr_ps_keyed_rows_total") - keyed0[1] == 8 * ROWS
    assert family_total("distlr_ps_keyed_keys_total") - keyed0[0] == (
        moved_keys[0])


# -- a round's frame is made at load ------------------------------------------
FRAMES = "distlr_ps_client_key_frames_total"


def _frames():
    return {labels: child.value
            for labels, child in get_registry().get(FRAMES).children()}


class _Tap:
    """Round a worker's connection as the benchmark's ``WireTap`` stands
    (``chipbench/drivers/ps_keyed_epochs.py``): ``_pull(keys=None, **kw)``
    and ``_push(vals, keys=None, **kw)``, every other keyword passed on,
    copies of what it sees taken with ``np.array`` as the round goes."""

    def __init__(self, worker):
        self.worker, self.pulls, self.pushes = worker, [], []
        self.calls = {name: getattr(worker.kv, name)
                      for name in ("pull", "push")}
        worker.kv.pull, worker.kv.push = self._pull, self._push

    def _pull(self, keys=None, **kw):
        got = self.calls["pull"](keys=keys, **kw)
        self.pulls.append((np.array(keys), np.array(got), len(keys)))
        return got

    def _push(self, vals, keys=None, **kw):
        self.pushes.append((np.array(keys), np.array(vals)))
        assert np.array_equal(keys, self.pulls[-1][0])
        return self.calls["push"](vals, keys=keys, **kw)

    def remove(self):
        for name in self.calls:
            delattr(self.worker.kv, name)  # the class's own again


def test_two_epochs_after_load_check_no_key_and_count_only_held_frames(
        rows, ps_steps_on_device):
    """A worker against two native servers: what ``load_data`` held is
    what every round's pull and push are handed, so ``_validate_keys``
    runs zero times in ``fit`` and the counter moves under ``held``
    alone; a tap in the benchmark's shape sees each window's keys, the
    servers' weights at them and the gradient pushed."""
    cfg = _cfg(num_workers=1, num_servers=2)
    w0 = (np.random.default_rng(9).standard_normal(DIM) * 0.05).astype(
        np.float32)
    cols = _shard(rows)[0]
    want_keys = [np.unique(cols[j * BATCH:(j + 1) * BATCH])
                 for j in range(WINDOWS)]
    with ServerGroup(2, 1, DIM, learning_rate=LR, sync=False) as group:
        w = ps_trainer.PSWorker(
            cfg, 0, group.hosts,
            train_iter=SparseDataIter(*_shard(rows), BATCH),
            test_iter=SparseDataIter(*_shard(rows, 1), -1))
        try:
            checked = []
            real = w.kv._validate_keys
            w.kv._validate_keys = lambda keys, vpk=1: (
                checked.append(len(keys)), real(keys, vpk))[1]
            w.load_data()
            # the check was made at load, once a window (on the pool's
            # threads, in their order)
            assert sorted(checked) == sorted(len(k) for k in want_keys)
            for frame, want in zip(w._window_keys, want_keys):
                assert type(frame) is np.ndarray and frame.dtype == np.uint64
                assert not frame.flags.writeable
                assert np.array_equal(frame, want)
                assert w.kv._resolve_keys(frame, 1)[2] == "held"
            del checked[:]
            w.kv.wait(w.kv.push_init(w0))
            tap = _Tap(w)
            before = _frames()
            w.fit(epochs=2)
            tap.remove()
            assert checked == []
            moved = {k: v - before.get(k, 0) for k, v in _frames().items()
                     if v != before.get(k, 0)}
            assert moved == {("pull", "held"): 2 * WINDOWS,
                             ("push", "held"): 2 * WINDOWS}
            table = w.kv.pull()
        finally:
            w.close()
    # one worker: the servers' table is replayed from what the tap saw
    sim = w0.copy()
    assert len(tap.pulls) == len(tap.pushes) == 2 * WINDOWS
    for i, ((keys, got, n), (pushed, g)) in enumerate(
            zip(tap.pulls, tap.pushes)):
        assert np.array_equal(keys, want_keys[i % WINDOWS])
        assert n == len(keys) == len(got) == len(g)
        assert np.array_equal(pushed, keys)
        at = keys.astype(np.int64)
        assert np.array_equal(got.view(np.uint32), sim[at].view(np.uint32))
        assert np.count_nonzero(g)
        sim[at] -= np.float32(LR) * g
    assert np.array_equal(table.view(np.uint32), sim.view(np.uint32))


def test_the_vector_the_step_is_handed_is_zeros_behind_the_pulled_weights(
        monkeypatch, rows, ps_steps_on_device):
    """A window of fewer keys after one of more, and the other way round
    (the short last window, then the first again): what reaches
    ``device_put`` is ``np.zeros(padded)`` with the pulled weights at its
    head, as bits, every round; and the same for weights that are an
    array of the caller's own."""
    cols, vals, y = _shard(rows)
    w = _worker(monkeypatch, SparseDataIter(cols, vals, y, BATCH))
    padded = w._keyed_key_count
    counts = [len(k) for k in w._window_keys]
    assert counts[2] < counts[1] and counts[2] < counts[0] <= padded
    # no zero among the table's values: a stale tail would show
    assert np.count_nonzero(w.kv.table) == DIM
    put, handed = [], []
    real_put, step = jax.device_put, w.grad_step

    def device_put(x, *a, **kw):
        if isinstance(x, np.ndarray) and x.shape == (padded,):
            put.append(np.array(x))
        return real_put(x, *a, **kw)

    def grad_step(w_u, window):
        handed.append((np.array(w_u), w_u))
        return step(w_u, window)

    monkeypatch.setattr(jax, "device_put", device_put)
    w.grad_step = grad_step
    w.fit(epochs=2)
    assert len(put) == len(handed) == 2 * WINDOWS
    for i, (vector, (bits, w_u)) in enumerate(zip(put, handed)):
        n = counts[i % WINDOWS]
        assert len(bits) == n and w_u.base is w._keyed_vector.buf
        want = np.zeros(padded, np.float32)
        want[:n] = bits
        assert np.array_equal(vector.view(np.uint32), want.view(np.uint32))
    # the pulled weights were the table's at the window's keys
    for (bits, _), keys in zip(handed, w.kv.pulled):
        assert len(bits) == len(keys)
    # a caller's own array after the longest window: staged, zeros behind
    del put[:]
    own = np.full(counts[2] - 5, 0.25, np.float32)
    got = step(own, Window(2 * BATCH, ROWS - 2 * BATCH))
    want = np.zeros(padded, np.float32)
    want[:len(own)] = own
    assert len(put) == 1 and np.array_equal(put[0].view(np.uint32),
                                            want.view(np.uint32))
    assert got.shape == own.shape
    w.close()


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the pinned text is jax 0.9.0's")
def test_the_keyed_steps_lowered_program_is_the_parents(
        monkeypatch, rows, ps_steps_on_device):
    """The text ``_compiled_keyed_fns`` lowers to for this file's shard,
    hashed on the commit before the held frames (PR 55) and here: where
    the pulled vector is made moved, what the device runs did not."""
    cols, vals, y = _shard(rows)
    w = _worker(monkeypatch, SparseDataIter(cols, vals, y, BATCH))
    fn = ps_trainer._compiled_keyed_fns(0.0, False)
    sd = jax.ShapeDtypeStruct
    leaves = [sd(a.shape, a.dtype) for a in (*w._resident, w._keyed_bases)]
    text = fn.lower(
        sd((w._keyed_key_count,), jnp.float32), *leaves, sd((), jnp.int32),
        rows=BATCH, row_bits=w._keyed_row_bits, plan=None).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PINNED_KEYED
    w.close()


def test_numpys_step_in_the_device_steps_place_is_counted_as_the_hosts(
        capsys, monkeypatch):
    """The benchmark's rehearsal whole, with numpy's step over a copy of
    the resident sorted leaves where the device step stood and counted as
    the host's: not ``correct``, by ``host_steps`` alone (the gradients
    are sound, of the right keys; the shard stays where it was put).
    What ``tests/chipbench/test_sparse_ps_keyed.py``'s ``numpy-step``
    case drove over PR 51's row-major places (``tests/conftest.py``)."""
    import json

    from chipbench import run

    real = ps_trainer.PSWorker._keyed_device_step

    def on_the_host(self, train):
        real(self, train)
        B, bits = train.batch_size, self._keyed_row_bits
        packed, v, y, mask = (np.asarray(a) for a in self._resident)
        lines = len(packed) // len(self._window_keys)
        counted = ps_trainer._GRAD_ROUNDS.labels(rank=str(self.rank),
                                                 path="keyed_host")

        def grad_step(w_u, window):
            j = window.first // B
            at, rows = slice(j * lines, (j + 1) * lines), slice(j * B, j * B + B)
            p, vals = packed[at].reshape(-1), v[at].reshape(-1)
            place, row = p >> bits, p & ((1 << bits) - 1)
            with self._span("compute", marks_step=True):
                z = np.bincount(row, weights=w_u[place] * vals, minlength=B)
                resid = (1 / (1 + np.exp(-z)) - y[rows]) * mask[rows]
                g = np.bincount(place, weights=resid[row] * vals,
                                minlength=len(w_u)) / max(mask[rows].sum(), 1)
            counted.inc()
            return g.astype(np.float32)
        return grad_step

    monkeypatch.setattr(ps_trainer.PSWorker, "_keyed_device_step", on_the_host)
    rc = run.main(["--workload", "sparse-ps-async-keyed-1chip", "--seed",
                   "3100000052", "--seconds", "0.2", "--trace", "0",
                   "--rehearse"])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert rc == 0 and last.startswith("REHEARSAL ")
    doc = json.loads(last[len("REHEARSAL "):])
    assert doc["correct"] is False
    assert {r["name"] for r in doc["compared"] if not r["ok"]} == {
        "host_steps"}


# -- what keeps the host path, bit for bit ------------------------------------
def _parents_rounds(train, grad, width, table, epochs, l2=(0.0, False)):
    """The keyed loop as the parent of the device step ran it: an
    ``np.unique`` of the batch's ids, a pull of those rows, numpy's
    gradient, a push."""
    table = table.copy()
    for _ in range(epochs):
        train.reset()
        for batch in train:
            ids = batch[0]
            ub, pos = np.unique(ids, return_inverse=True)
            slots = (ub[:, None] * width + np.arange(width)).reshape(-1)
            w_u = table[slots].copy()
            if width > 1:
                w_u = w_u.reshape(-1, width)
            g = grad(w_u, pos.reshape(ids.shape), *batch[1:], *l2)
            table[slots] -= LR * g.reshape(-1)
    return table


def _host_case(name, rows):
    cols, vals, y = (a[:ROWS] for a in rows)
    rng = np.random.default_rng(4)
    if name == "shuffled":
        return (_cfg(), SparseDataIter(cols, vals, y, BATCH, shuffle=True,
                                       seed=7), 1,
                host_math.sparse_batch_grad)
    if name == "wrapped-short-batch":
        return (_cfg(wrap_final_batch=True),
                SparseDataIter(cols, vals, y, BATCH, wrap_compat=True), 1,
                host_math.sparse_batch_grad)
    if name == "sparse_softmax":
        return (_cfg("sparse_softmax", num_classes=4),
                SparseDataIter(cols, vals, rng.integers(0, 4, ROWS), BATCH),
                4, host_math.sparse_softmax_batch_grad)
    blocks, lane_vals = make_uniform_blocked_batch(rng, ROWS, 6, DIM // 4, 4)
    return (_cfg("blocked_lr", block_size=4),
            BlockedDataIter(blocks, lane_vals, y, BATCH), 4,
            host_math.blocked_batch_grad)


@pytest.mark.parametrize("name", ["shuffled", "wrapped-short-batch",
                                  "sparse_softmax", "blocked_lr"])
def test_what_keeps_the_host_path_runs_as_it_did(
        monkeypatch, rows, ps_steps_on_device, name):
    cfg, train, width, grad = _host_case(name, rows)
    before = _count(ROUNDS, path="keyed_host")
    w = _worker(monkeypatch, train, cfg)
    assert w._resident is None and w._window_keys is None
    assert "shard_put" not in get_tracer().breakdown()
    opening = w.kv.table.copy()
    w.fit(epochs=2)
    w.close()
    assert _count(ROUNDS, path="keyed_host") - before == 2 * WINDOWS
    want = _parents_rounds(train, grad, width, opening, 2)
    assert np.array_equal(w.kv.table.view(np.uint32), want.view(np.uint32))


def test_places_and_rows_that_do_not_share_an_int32_keep_the_host_path(
        monkeypatch, ps_steps_on_device):
    """65,536 rows take 16 bits, which leaves a place 15: a window that
    touches more than 32,768 keys is not packed, and says so."""
    batch, dim = 1 << 16, 1 << 17
    rng = np.random.default_rng(6)
    cols = rng.integers(0, dim, (batch, 2))
    vals = np.ones(cols.shape, np.float32)
    y = rng.integers(0, 2, batch).astype(np.int32)
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    logger = logging.getLogger(ps_trainer.__name__)
    logger.addHandler(handler)
    try:
        w = _worker(monkeypatch, SparseDataIter(cols, vals, y, batch),
                    _cfg(batch_size=batch, num_feature_dim=dim))
    finally:
        logger.removeHandler(handler)
    assert w._resident is None and w._window_keys is None
    assert any("do not share an int32" in line for line in said)
    assert "shard_put" not in get_tracer().breakdown()
    w.close()


def test_a_small_keyed_step_stays_numpys_by_its_size(monkeypatch, rows):
    cols, vals, y = _shard(rows)
    w = _worker(monkeypatch, SparseDataIter(cols, vals, y, BATCH))
    assert w._resident is None          # 256 x 39 entries: under the rule
    opening = w.kv.table.copy()
    w.fit(epochs=1)
    w.close()
    want = _parents_rounds(SparseDataIter(cols, vals, y, BATCH),
                           host_math.sparse_batch_grad, 1, opening, 1)
    assert np.array_equal(w.kv.table.view(np.uint32), want.view(np.uint32))


@pytest.mark.parametrize("rows_,nnz,want", [
    (256, 39, "numpy"),             # 9,984 entries x 64 < 2^20
    (512, 39, "device"),            # the benchmark's rehearsal
    (16384, 39, "device"),          # the benchmark's cell
    (16384, None, "device"),        # a dense step of D x rows elements
    (8, None, "numpy"),
])
def test_the_rule_counts_a_keyed_steps_entries(rows_, nnz, want):
    cfg = _cfg()
    device = jax.devices()[0]
    got = ps_trainer.ps_compute_device(cfg, rows_, device, nnz=nnz)
    assert got == ("numpy" if want == "numpy" else device)
    # on an accelerator the cell's step passes the accelerator's threshold
    assert 16384 * 39 * ps_trainer._PS_KEYED_ENTRY_WORK >= (
        ps_trainer._PS_AUTO_CPU_THRESHOLD)


# -- the iterator -------------------------------------------------------------
@pytest.mark.parametrize("kw,held", [
    ({}, True), ({"shuffle": True, "seed": 3}, False),
    ({"wrap_compat": True}, False),
], ids=["in-order", "shuffled", "wrapped"])
def test_a_sparse_iterator_says_which_rows_it_holds(rows, kw, held):
    cols, vals, y = _shard(rows)
    it = SparseDataIter(cols, vals, y, BATCH, **kw)
    got = it.held_rows()
    if not held:
        assert got is None
        return
    assert got[0] is it.X and got[1] is it.vals and got[2] is it.y
    assert got[3].all() and len(got[3]) == ROWS
    # rows that are already arrays are taken as they are
    assert np.shares_memory(it.X, cols) and np.shares_memory(it.vals, vals)


# -- every dense model's program is the one it was ----------------------------
@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the pinned text is jax 0.9.0's")
@pytest.mark.parametrize("model,classes,whole,window", [
    ("binary_lr", 2, "63ff194a793476ec", "80b3f02e55f33b37"),
    ("softmax", 3, "2fbca4555862fd25", "97cc494aba0b0cb0"),
])
def test_a_dense_models_lowered_step_is_the_parents(model, classes, whole,
                                                    window):
    """The text ``_compiled_fns`` lowers to, hashed on the commit before
    the keyed device step (PR 50) and here."""
    cfg = Config(model=model, num_feature_dim=64, num_classes=classes,
                 l2_c=0.5)
    fn = ps_trainer._compiled_fns(get_model(cfg), 0.5, False)
    sd = jax.ShapeDtypeStruct
    args = (sd((64 * (classes if model == "softmax" else 1),), jnp.float32),
            sd((32, 64), jnp.float32), sd((32,), jnp.int32),
            sd((32,), jnp.bool_))
    texts = (fn.lower(*args).as_text(),
             fn.lower(*args, first=sd((), jnp.int32), window=8).as_text())
    assert [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts] == [
        whole, window]
