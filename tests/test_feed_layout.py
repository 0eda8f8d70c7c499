"""The sync feed hands a large dense matrix to the runtime as the bytes
the host holds and restores it on the device (ISSUE 25,
``distlr_tpu/parallel/feed.py``).

On the CPU backend the device's default layout is the host's own, so the
mechanism is bypassed; the ``engaged`` fixture steers it in the test: it
makes the layout probe answer as a TPU does and lowers the size
constants, and the restore kernel then runs in Pallas's interpreter.
Whatever way a leaf went, what ``_shard_batch`` returns is a plain
``device_put``'s values, shape, dtype and sharding, and
``distlr_h2d_bytes_total`` says which way it went.
"""

import gc
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distlr_tpu import Config
from distlr_tpu.data.hashing import write_ctr_shards, write_raw_ctr_shards
from distlr_tpu.obs import jaxrt
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.obs.tracing import get_tracer
from distlr_tpu.parallel import feed
from distlr_tpu.train import Trainer
from distlr_tpu.train.trainer import GlobalShardedData

#: 576 columns: rows of 576, 288 and 144 32-bit words for float32,
#: bfloat16 and int8, none a multiple of the 128 lanes (groups of 2, 4, 8)
DIM, ROWS = 576, 128
DTYPES = ("float32", "bfloat16", "int8")


def _handed() -> dict:
    fam = get_registry().snapshot().get("distlr_h2d_bytes_total", {})
    got = {s["labels"]["layout"]: s["value"] for s in fam.get("series", [])}
    return {"as_held": got.get("as_held", 0.0),
            "default": got.get("default", 0.0)}


def _since(before: dict) -> dict:
    return {k: v - before[k] for k, v in _handed().items()}


@pytest.fixture
def tpu_layouts(monkeypatch):
    """The layout probe answers as the v5e does for a dense batch: the
    device wants the row index in the lanes."""
    monkeypatch.setattr(feed, "_default_is_row_major", lambda *a: False)


@pytest.fixture
def engaged(monkeypatch, tpu_layouts):
    monkeypatch.setattr(feed, "AS_HELD_MIN_BYTES", 1)
    monkeypatch.setattr(feed, "_PIECE_BYTES", 1 << 15)  # several pieces


def _dense_trainer(n_dev, dtype, rows_per_dev, *, wrap=False, seed=0, **kw):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=DIM)

    def split(n):
        shards = []
        for _ in range(n_dev):
            X = rng.normal(size=(n, DIM)).astype(np.float32)
            X[rng.random(X.shape) < 0.5] = 0.0
            shards.append((X, (X @ w_true > 0).astype(np.int32)))
        return GlobalShardedData(shards)

    cfg = Config(num_feature_dim=DIM, feature_dtype=dtype,
                 mesh_shape={"data": n_dev}, batch_size=ROWS, l2_c=0.0,
                 learning_rate=0.1, test_interval=0,
                 wrap_final_batch=int(wrap), **kw)
    return Trainer(cfg).load_data(train=split(rows_per_dev), test=split(ROWS))


def _same_as_plain_put(tr, placed, host_batch):
    plain = NamedSharding(tr.mesh, P("data"))
    for got, host in zip(placed, host_batch, strict=True):
        want = jax.device_put(host, plain)
        assert got.shape == want.shape and got.dtype == want.dtype
        assert got.sharding.is_equivalent_to(want.sharding, got.ndim)
        assert got.sharding.spec == want.sharding.spec
        assert bool(jnp.array_equal(got, want))


@pytest.mark.parametrize("way", ["bypassed", "engaged"])
@pytest.mark.parametrize("final", ["padded", "wrapped"])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n_dev", [1, 4, 8])
def test_a_placed_batch_is_a_plain_device_put(request, n_dev, dtype, final,
                                              way):
    if way == "engaged":
        request.getfixturevalue("engaged")
    # a full batch and a short final one: padded, or wrapped to the head
    tr = _dense_trainer(n_dev, dtype, ROWS + 40, wrap=final == "wrapped")
    batches = list(tr._train_data.batches(
        ROWS, wrap=bool(tr.cfg.wrap_final_batch)))
    assert len(batches) == 2 and batches[0][0].shape == (n_dev * ROWS, DIM)
    for hb in (*batches, tr._test_data.full_batch()):
        before = _handed()
        placed = tr._shard_batch(hb)
        _same_as_plain_put(tr, placed, hb)
        rest = sum(leaf.nbytes for leaf in hb[1:])
        assert _since(before) == (
            {"as_held": hb[0].nbytes, "default": rest} if way == "engaged"
            else {"as_held": 0, "default": hb[0].nbytes + rest})


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n_dev", [1, 4])
def test_engaged_trains_to_the_bypass_weights_bit_for_bit(request, n_dev,
                                                          dtype):
    def three_steps():
        tr = _dense_trainer(n_dev, dtype, 3 * ROWS, seed=5)
        before = _handed()
        w = np.asarray(tr.fit(epochs=1))
        assert tr.timer.steps == 3
        return w, _since(before)

    w_plain, handed = three_steps()
    assert handed["as_held"] == 0
    request.getfixturevalue("engaged")
    w_held, handed = three_steps()
    itemsize = {"float32": 4, "bfloat16": 2, "int8": 1}[dtype]
    # three train batches and the test split's one, features only
    assert handed["as_held"] == 4 * n_dev * ROWS * DIM * itemsize
    assert np.array_equal(w_plain, w_held)
    assert np.any(w_plain != 0)


@pytest.mark.parametrize("model", ["sparse_lr", "sparse_softmax",
                                   "blocked_lr"])
def test_sparse_and_blocked_leaves_take_the_plain_put(tmp_path, tpu_layouts,
                                                      model):
    """With layouts as on the TPU, where the default layout of a
    ``(65536, 39)`` leaf is not row-major either: the leaves are far
    under the size that pays for a program, and go as they always did."""
    d = str(tmp_path)
    if model == "blocked_lr":
        write_raw_ctr_shards(d, 600, 6, 40, 2, seed=9)
        cfg = Config(model=model, num_feature_dim=4096, block_size=4,
                     data_dir=d, batch_size=ROWS, mesh_shape={"data": 1})
    else:
        write_ctr_shards(d, 600, 5, 50, 64, 2, seed=3)
        cfg = Config(model=model, num_feature_dim=64, data_dir=d,
                     num_classes=2, batch_size=ROWS, mesh_shape={"data": 1})
    tr = Trainer(cfg).load_data()
    hb = next(iter(tr._train_data.batches(ROWS)))
    before = _handed()
    _same_as_plain_put(tr, tr._shard_batch(hb), hb)
    assert _since(before) == {"as_held": 0,
                              "default": sum(a.nbytes for a in hb)}


def test_the_size_that_engages_is_tens_of_megabytes(tpu_layouts):
    """The constants as they ship: a 34 MB matrix goes as held, the same
    rows at half the width go the plain way."""
    mesh = jax.make_mesh((1,), ("data",))
    rng = np.random.default_rng(0)
    for dim, way in ((65600, "as_held"), (32800, "default")):
        x = rng.integers(-3, 4, (ROWS, dim)).astype(np.float32)
        assert (x.nbytes >= feed.AS_HELD_MIN_BYTES) == (way == "as_held")
        before = _handed()
        got = feed.place(x, mesh)
        assert _since(before)[way] == x.nbytes
        assert bool(jnp.array_equal(got, x))


@pytest.mark.parametrize("why,make", [
    ("a device array", lambda x: jnp.asarray(x)),
    ("rows not in the host's order", lambda x: np.asfortranarray(x)),
    ("a vector", lambda x: x[:, 0].copy()),
    ("rows that do not fill the lanes", lambda x: x[:ROWS - 8].copy()),
    ("a row of odd bytes", lambda x: x.astype(np.int8)[:, :DIM - 1].copy()),
    ("an integer matrix", lambda x: x.astype(np.int32)),
])
def test_what_the_word_view_cannot_carry_is_bypassed(engaged, why, make):
    mesh = jax.make_mesh((1,), ("data",))
    x = make(np.random.default_rng(1).normal(size=(ROWS, DIM))
             .astype(np.float32))
    before = _handed()
    got = feed.place(x, mesh)
    assert _since(before) == {"as_held": 0, "default": x.nbytes}, why
    assert bool(jnp.array_equal(got, jnp.asarray(x)))


def test_a_second_fit_compiles_nothing(engaged):
    tr = _dense_trainer(1, "bfloat16", 2 * ROWS + 40, prefetch=2)
    tr.fit(epochs=1)  # the step, the eval put and both batches' restores
    programs = feed._restore_program.cache_info().currsize
    restores = [feed._restore_program(feed._plan(hb[0], tr.mesh))
                for hb in (next(iter(tr._train_data.batches(ROWS))),
                           tr._test_data.full_batch())]
    probes = [jaxrt.JitCacheProbe(fn, f"test.{i}") for i, fn in enumerate(
        (tr.train_step, tr.eval_step, *restores))]
    before = _handed()
    tr.fit(epochs=2)
    assert _since(before)["as_held"] > 0
    assert [p.tick() for p in probes] == [0, 0, 0, 0]
    assert feed._restore_program.cache_info().currsize == programs


def test_the_restore_program_is_not_taken_for_a_step(engaged):
    """The benchmark finds the train step's runs in a trace by ``step``
    in the program's name."""
    tr = _dense_trainer(1, "bfloat16", ROWS)
    x = tr._test_data.full_batch()[0]
    plan = feed._plan(x, tr.mesh)
    piece = jax.ShapeDtypeStruct(
        (1, x.nbytes // 4 // 128 // plan.pieces, 128), jnp.uint32)
    text = feed._restore_program(plan).lower(*[piece] * plan.pieces).as_text()
    assert "module @jit_feed_restore" in text


# -- the producer's pacer (ISSUE 42) ---------------------------------------

def _paced() -> dict:
    fam = get_registry().snapshot().get("distlr_h2d_paced_pieces_total", {})
    got = {s["labels"]["waited"]: s["value"] for s in fam.get("series", [])}
    return {"yes": got.get("yes", 0.0), "no": got.get("no", 0.0)}


def _paced_since(before: dict) -> dict:
    return {k: v - before[k] for k, v in _paced().items()}


class _Piece:
    """What a ``device_put`` returns, landing when the test says."""

    def __init__(self, landed, asked):
        self._landed, self._asked = landed, asked

    def is_ready(self):
        return self._landed.is_set()

    def block_until_ready(self):
        self._asked.set()
        assert self._landed.wait(10), "the test never let the piece land"
        return self


class _Link:
    """Stands in ``jax.device_put`` for a :class:`feed.Pacer`: keeps, a
    piece put, the event that lands it, the event that says the pacer
    waits for it, and a weak reference to the piece itself."""

    def __init__(self):
        self.landed, self.asked, self.refs = [], [], []

    def __call__(self, piece, sharding):
        self.landed.append(threading.Event())
        self.asked.append(threading.Event())
        out = _Piece(self.landed[-1], self.asked[-1])
        self.refs.append(weakref.ref(out))
        return out


def _produce(pacer, batches, pieces, done):
    """A producer's puts: ``batches`` leaves of ``pieces`` pieces each."""
    try:
        for n in range(batches):
            pacer.step = 100 + n
            for k in range(pieces):
                pacer.put((n, k), None)
    except feed.Stopped:
        done.append("stopped")
    else:
        done.append("all put")


@pytest.mark.parametrize("batches,pieces", [(2, 4), (3, 1), (1, 6), (2, 3)])
def test_the_pacer_keeps_ahead_pieces_between_the_producer_and_the_link(
        batches, pieces):
    """Piece *k* is put when piece *k - AHEAD* has landed, whichever
    batch that one belongs to; a landed piece is let go at once."""
    link, done, total = _Link(), [], batches * pieces
    pacer = feed.Pacer(threading.Event(), device_put=link)
    before = _paced()
    get_tracer().reset()
    t = threading.Thread(target=_produce, args=(pacer, batches, pieces, done))
    t.start()
    for i in range(max(total - feed.AHEAD, 0)):
        assert link.asked[i].wait(10), f"no wait for piece {i}"
        # the producer stands at piece i: AHEAD are put beyond the landed
        # ones, this batch's or the next's, and not one more
        assert len(link.landed) == i + feed.AHEAD
        gc.collect()
        assert [r() is None for r in link.refs[:i]] == [True] * i
        link.landed[i].set()
    t.join(10)
    assert not t.is_alive() and done == ["all put"]
    assert len(link.landed) == total
    waits = max(total - feed.AHEAD, 0)
    assert _paced_since(before) == {"yes": waits, "no": total - waits}
    # every wait is a span, under the id of the batch whose piece waits
    spans = [e for e in get_tracer().chrome_trace()["traceEvents"]
             if e["name"] == "h2d_pace"]
    assert [e["args"]["step"] for e in spans] == [
        100 + (i + feed.AHEAD) // pieces for i in range(waits)]
    # what is still held is the last AHEAD pieces, until the pacer goes
    gc.collect()
    held = min(feed.AHEAD, total)
    assert [r() is None for r in link.refs] == (
        [True] * (total - held) + [False] * held)
    del pacer
    gc.collect()
    assert all(r() is None for r in link.refs)


def test_a_piece_that_has_landed_is_not_waited_for():
    link, done = _Link(), []

    def landing(piece, sharding):
        out = link(piece, sharding)
        link.landed[-1].set()
        return out

    pacer = feed.Pacer(threading.Event(), device_put=landing)
    before = _paced()
    get_tracer().reset()
    _produce(pacer, 2, 5, done)
    assert done == ["all put"]
    assert _paced_since(before) == {"yes": 0, "no": 10}
    assert not any(a.is_set() for a in link.asked)
    assert "h2d_pace" not in get_tracer().breakdown()


@pytest.mark.parametrize("when", ["while it waits", "before a put"])
def test_the_pacer_gives_the_batch_up_when_its_owner_stops(when):
    link, done, stop = _Link(), [], threading.Event()
    pacer = feed.Pacer(stop, device_put=link)
    if when == "before a put":
        stop.set()
        _produce(pacer, 1, 4, done)
        assert done == ["stopped"] and link.landed == []
        return
    t = threading.Thread(target=_produce, args=(pacer, 2, 4, done))
    t.start()
    assert link.asked[0].wait(10)
    stop.set()
    link.landed[0].set()
    t.join(10)
    assert not t.is_alive() and done == ["stopped"]
    assert len(link.landed) == feed.AHEAD  # nothing was put after the stop


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n_dev", [1, 4, 8])
def test_a_paced_place_is_a_plain_device_put(engaged, n_dev, dtype):
    """Through the trainer's ``_shard_batch`` with a pacer, as the
    producer calls it: two batches and the test split through ONE pacer
    come back as plain puts, and every piece went through it."""
    tr = _dense_trainer(n_dev, dtype, ROWS + 40)
    batches = [*tr._train_data.batches(ROWS), tr._test_data.full_batch()]
    pacer = feed.Pacer(threading.Event())
    for hb in batches:
        pieces = feed._plan(hb[0], tr.mesh).pieces
        assert pieces >= 2  # int8: two tiles a shard, so two pieces
        before, handed = _paced(), _handed()
        placed = tr._shard_batch(hb, pacer)
        _same_as_plain_put(tr, placed, hb)
        assert sum(_paced_since(before).values()) == pieces
        assert _since(handed) == {
            "as_held": hb[0].nbytes,
            "default": sum(leaf.nbytes for leaf in hb[1:])}


def test_a_pacer_does_nothing_for_a_plain_put(tpu_layouts):
    """Where the plan does not engage (here: a leaf under the size that
    pays for a program) the pacer is not asked."""
    mesh = jax.make_mesh((1,), ("data",))
    x = np.ones((ROWS, DIM), np.float32)

    def refuse(piece, sharding):
        raise AssertionError("a plain put went through the pacer")

    before = _paced()
    got = feed.place(x, mesh, feed.Pacer(threading.Event(), refuse))
    assert bool(jnp.array_equal(got, x))
    assert _paced_since(before) == {"yes": 0, "no": 0}


def test_place_without_a_pacer_puts_its_pieces_at_once(engaged, monkeypatch):
    """The PS workers' shards and test splits: the calls ``place`` made
    before there was a pacer, in their order, and nothing of the pacer's."""
    mesh = jax.make_mesh((1,), ("data",))
    x = np.random.default_rng(2).normal(size=(ROWS, DIM)).astype(np.float32)
    plan = feed._plan(x, mesh)
    calls, real = [], jax.device_put

    def recording(piece, sharding):
        calls.append((piece, sharding))
        return real(piece, sharding)

    monkeypatch.setattr(jax, "device_put", recording)
    before = _paced()
    get_tracer().reset()
    got = feed.place(x, mesh)
    monkeypatch.undo()
    assert bool(jnp.array_equal(got, x))
    assert len(calls) == plan.pieces > 1
    words = x.reshape(-1).view(np.uint32)
    per = words.size // plan.pieces
    for k, (piece, sharding) in enumerate(calls):
        # the k-th run of the host's own buffer: a view, not a copy
        assert np.shares_memory(piece, x)
        assert np.array_equal(piece.reshape(-1), words[k * per:(k + 1) * per])
        assert sharding.spec == P("data", None, None)
    assert _paced_since(before) == {"yes": 0, "no": 0}
    assert "h2d_pace" not in get_tracer().breakdown()
