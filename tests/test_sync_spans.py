"""The sync trainer's spans, each where its work happens (ISSUE 24).

A step's wait for its batch is recorded under ``data_load`` (``queue_wait``
until the producer hands the batch over, ``h2d_wait`` until its copy has
landed) and ``compute`` starts with the step's batch resident; every span
carries its parent and the step it belongs to; the load says where its
time goes; and the same spans ride the profiler's trace.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distlr_tpu import Config
from distlr_tpu.data.hashing import write_ctr_shards
from distlr_tpu.data.synthetic import write_synthetic_shards
from distlr_tpu.obs import jaxrt
from distlr_tpu.obs.tracing import get_tracer
from distlr_tpu.train import Trainer

DIM, BATCH, TRAIN_PARTS = 24, 160, 2
COPY_S = 0.05


@pytest.fixture(scope="module")
def dense_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("spans_dense")
    write_synthetic_shards(str(d), 800, DIM, num_parts=TRAIN_PARTS, seed=3,
                           sparsity=0.0)
    return str(d)


@pytest.fixture(scope="module")
def sparse_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("spans_sparse")
    write_ctr_shards(str(d), 800, 5, 50, 64, TRAIN_PARTS, seed=3)
    return str(d)


def _trainer(data_dir, **kw):
    kw = {"num_feature_dim": DIM, "test_interval": 0, **kw}
    cfg = Config(data_dir=data_dir, mesh_shape={"data": 1}, batch_size=BATCH,
                 l2_c=0.0, **kw)
    return Trainer(cfg).load_data()


def _events():
    return get_tracer().chrome_trace()["traceEvents"]


class _Late:
    """A leaf that is handed over at once and becomes ready later, as a
    ``device_put`` result does on the chip."""

    def __init__(self, value, ready_at):
        self.value, self.ready_at = value, ready_at

    def block_until_ready(self):
        time.sleep(max(0.0, self.ready_at - time.perf_counter()))
        return self


class _CopyChannel:
    """Stands in ``Trainer._shard_batch``: each batch's copy takes
    ``COPY_S`` on one channel, so copies queue behind each other however
    far ahead the producer runs."""

    def __init__(self, place):
        self.place, self.free_at, self.lock = place, 0.0, threading.Lock()

    def __call__(self, host_batch, pacer=None):
        with self.lock:
            self.free_at = max(self.free_at, time.perf_counter()) + COPY_S
            ready_at = self.free_at
        return tuple(_Late(leaf, ready_at) for leaf in self.place(host_batch))


@pytest.mark.parametrize("prefetch", [1, 2])
def test_a_late_copy_is_waited_for_in_h2d_wait_not_in_compute(dense_dir,
                                                              prefetch):
    tr = _trainer(dense_dir, prefetch=prefetch)
    tr.fit(epochs=1)  # compiles the step
    step = tr.train_step
    tr._shard_batch = _CopyChannel(tr._shard_batch)
    tr.train_step = lambda w, batch: step(w, tuple(b.value for b in batch))
    tr._test_data = None  # no eval_put: every placed batch is a step's
    tracer = get_tracer()
    tracer.reset()
    tr.fit(epochs=3)
    spans = tracer.breakdown()
    steps = spans["compute"]["count"]
    assert steps == 3 * (640 // BATCH)
    assert spans["h2d_wait"]["count"] == steps
    # the copies are the pace: nearly all of them is waited for, in h2d_wait
    assert spans["h2d_wait"]["seconds"] > 0.8 * steps * COPY_S
    assert spans["data_load"]["seconds"] >= spans["h2d_wait"]["seconds"]
    assert spans["compute"]["seconds"] / steps < COPY_S
    # ... and under data_load: every h2d_wait's parent is a data_load span
    events = _events()
    loads = {e["args"]["id"] for e in events if e["name"] == "data_load"}
    waits = [e for e in events if e["name"] == "h2d_wait"]
    assert len(waits) == steps
    assert all(e["args"]["parent"] in loads for e in waits)
    assert all(e["dur"] < COPY_S * 1e6 for e in events
               if e["name"] == "compute")


@pytest.mark.parametrize("prefetch", [1, 2])
def test_spans_carry_their_parent_and_the_step_they_belong_to(dense_dir,
                                                              prefetch):
    tr = _trainer(dense_dir, prefetch=prefetch)
    tracer = get_tracer()
    tracer.reset()
    tr.fit(epochs=2)
    tr.fit(epochs=1)  # ids go on where the first call stopped
    events = _events()
    computes = [e for e in events if e["name"] == "compute"]
    ids = [e["args"]["step"] for e in computes]
    assert ids == list(range(3 * (640 // BATCH)))
    assert tr.batches_taken == len(ids)
    by_step, seen = {}, set()
    for e in events:
        if "step" in e["args"]:
            by_step.setdefault(e["args"]["step"], []).append(e)
            # a step's id is used once a span name: no pull to find the
            # epoch over borrows the next step's
            assert (e["name"], e["args"]["step"]) not in seen
            seen.add((e["name"], e["args"]["step"]))
    assert set(by_step) == set(ids)
    for n in ids:
        names = {e["name"] for e in by_step[n]}
        # the producer's spans for batch n and the consumer's for step n
        assert {"batch_slice", "h2d", "data_load", "queue_wait", "h2d_wait",
                "compute"} <= names
        if prefetch > 1:
            threads = {e["name"]: e["tid"] for e in by_step[n]}
            assert threads["h2d"] != threads["compute"]
            assert threads["queue_wait"] == threads["compute"]
    by_id = {e["args"]["id"]: e for e in events}
    for e in events:
        if e["name"] in ("queue_wait", "h2d_wait"):
            assert by_id[e["args"]["parent"]]["name"] == "data_load"
        if e["name"] in ("data_load", "compute", "eval_put"):
            assert "parent" not in e["args"]
        if prefetch == 1 and e["name"] in ("batch_slice", "h2d"):
            # the serial path: the slice and the dispatch are the wait
            assert by_id[e["args"]["parent"]]["name"] == "queue_wait"
    spans = tracer.breakdown()
    # data_load is its two children: nothing left to count twice
    assert spans["data_load"]["self_seconds"] < max(
        0.05 * spans["data_load"]["seconds"],
        2e-4 * spans["data_load"]["count"])
    assert spans["compute"]["self_seconds"] == pytest.approx(
        spans["compute"]["seconds"], abs=1e-5)
    assert spans["eval_put"]["count"] == 2  # once a fit


@pytest.mark.parametrize("kind,feature_dtype", [
    ("dense", "float32"), ("dense", "bfloat16"), ("sparse", "float32")])
def test_load_data_says_where_its_time_goes(dense_dir, sparse_dir, kind,
                                            feature_dtype):
    tracer = get_tracer()
    tracer.reset()
    if kind == "dense":
        _trainer(dense_dir, feature_dtype=feature_dtype)
    else:
        _trainer(sparse_dir, model="sparse_lr", num_feature_dim=64)
    spans = tracer.breakdown()
    files = TRAIN_PARTS + 1  # the train parts and the one test part
    assert spans["load_data"]["count"] == 1
    assert spans["load_parse"]["count"] == files
    assert spans["load_pack"]["count"] == 2  # once a split
    per_file = "load_densify" if kind == "dense" else "load_coo"
    other = "load_coo" if kind == "dense" else "load_densify"
    assert spans[per_file]["count"] == files and other not in spans
    assert ("load_cast" in spans) == (feature_dtype != "float32")
    if feature_dtype != "float32":
        assert spans["load_cast"]["count"] == 1
    events = _events()
    (load,) = [e for e in events if e["name"] == "load_data"]
    inner = [e for e in events if e["name"].startswith("load_")
             and e["name"] != "load_data"]
    assert all(e["args"]["parent"] == load["args"]["id"] for e in inner)
    assert spans["load_data"]["self_seconds"] <= spans["load_data"]["seconds"]


def test_a_profiler_trace_holds_the_loops_spans(dense_dir, tmp_path):
    """While a ``jax.profiler`` trace is taken the loop's spans lie on the
    host lines of the same file as the device operations."""
    from jax.profiler import ProfileData

    from chipbench import trace_reduce

    tr = _trainer(dense_dir, prefetch=2)
    tr.fit(epochs=1)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        tr.fit(epochs=1)
    found = {}
    path = trace_reduce.find_xplane(str(tmp_path))
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("data_load", "queue_wait", "h2d_wait", "h2d",
                               "batch_slice", "compute", "eval_put"):
                    found.setdefault(ev.name, []).append(
                        {k: v for k, v in ev.stats})
    steps = 640 // BATCH
    for name in ("data_load", "h2d_wait", "compute"):
        assert len(found.get(name, [])) >= steps, sorted(found)
    assert {"batch_slice", "h2d", "queue_wait", "eval_put"} <= set(found)
    # the step id rides along: step_num on the step marker, step elsewhere
    assert {int(s["step_num"]) for s in found["compute"]} == set(
        range(steps, 2 * steps))
    assert {int(s["step"]) for s in found["h2d_wait"]} == set(
        range(steps, 2 * steps))


@pytest.mark.parametrize("rows,batch,wrap", [
    (800, 160, False), (800, 300, False), (800, -1, False),
    (800, 300, True), (800, 1000, True)])
def test_an_epochs_batches_are_counted_ahead(rows, batch, wrap):
    """The loop takes ``num_batches`` batches an epoch and never pulls
    once more to find the epoch over, so the count has to be the
    iterator's own."""
    from distlr_tpu.train.trainer import GlobalShardedData

    X = np.zeros((rows, 3), np.float32)
    data = GlobalShardedData([(X, np.zeros(rows, np.int32))])
    assert data.num_batches(batch) == len(list(data.batches(batch, wrap=wrap)))


def test_self_seconds_count_no_interval_twice():
    tracer = get_tracer()
    tracer.reset()
    t = time.perf_counter()
    with tracer.phase("outer"):
        time.sleep(0.01)
        with tracer.phase("inner"):
            time.sleep(0.02)
        with tracer.phase("inner"):
            time.sleep(0.02)
    wall = time.perf_counter() - t
    spans = tracer.breakdown()
    assert spans["outer"]["seconds"] == pytest.approx(wall, abs=2e-3)
    assert spans["outer"]["self_seconds"] == pytest.approx(
        wall - spans["inner"]["seconds"], abs=2e-3)
    assert sum(s["self_seconds"] for s in spans.values()) == pytest.approx(
        wall, abs=2e-3)


def test_compile_seconds_and_cache_outcomes_reach_the_registry():
    before = jaxrt.compile_totals()
    jax.jit(lambda x: x * 3 + before["seconds"])(jnp.arange(7.0))
    after = jaxrt.compile_totals()
    assert after["seconds"] > before["seconds"]
    jaxrt._on_event("/jax/compilation_cache/cache_hits")
    jaxrt._on_event("/jax/compilation_cache/cache_misses")
    jaxrt._on_event("/jax/compilation_cache/some_other_event")
    last = jaxrt.compile_totals()
    assert (last["hits"], last["misses"]) == (after["hits"] + 1,
                                              after["misses"] + 1)


def test_fit_reports_the_rate_of_the_run_beside_the_rate_inside_steps(
        dense_dir):
    tr = _trainer(dense_dir, prefetch=2, test_interval=1)
    t = time.perf_counter()
    tr.fit(epochs=2, eval_fn=lambda epoch, acc: None)
    wall = time.perf_counter() - t
    rec = tr.metrics.records[-1]
    # rows over the wall of fit, never above the rate inside steps alone
    assert 0 < rec["samples_per_sec"] <= rec["step_samples_per_sec"]
    assert rec["step_samples_per_sec"] == pytest.approx(
        tr.timer.samples_per_sec)
    assert tr.fit_samples_per_sec == pytest.approx(2 * 640 / wall, rel=0.2)
    assert np.isfinite(tr.fit_samples_per_sec)


# -- the producer's waits for its turn at the link (ISSUE 42) --------------

PACED_DIM, PACED_ROWS = 576, 128


class _LatePiece:
    """A piece that is put at once and lands ``COPY_S / 10`` after the
    piece before it: one link, one DMA at a time."""

    def __init__(self, value, ready_at):
        self.value, self.ready_at = value, ready_at

    def is_ready(self):
        return time.perf_counter() >= self.ready_at

    def block_until_ready(self):
        time.sleep(max(0.0, self.ready_at - time.perf_counter()))
        return self


@pytest.fixture
def paced_feed(monkeypatch):
    """The feed engaged on the CPU as ``tests/test_feed_layout.py`` does
    it, with the pacer's pieces landing late: the producer has to wait
    for its turn at the link, as on the chip."""
    from distlr_tpu.parallel import feed

    monkeypatch.setattr(feed, "_default_is_row_major", lambda *a: False)
    monkeypatch.setattr(feed, "AS_HELD_MIN_BYTES", 1)
    monkeypatch.setattr(feed, "_PIECE_BYTES", 1 << 15)
    free_at = [0.0]

    def late_put(piece, sharding):
        free_at[0] = max(free_at[0], time.perf_counter()) + COPY_S / 10
        return _LatePiece(jax.device_put(piece, sharding), free_at[0])

    pacer, restore = feed.Pacer, feed._restore_program
    monkeypatch.setattr(
        feed, "Pacer", lambda stop: pacer(stop, device_put=late_put))
    monkeypatch.setattr(
        feed, "_restore_program",
        lambda plan: lambda *pieces: restore(plan)(
            *[getattr(p, "value", p) for p in pieces]))
    return feed


def _paced_counts():
    from distlr_tpu.obs.registry import get_registry

    fam = get_registry().snapshot().get("distlr_h2d_paced_pieces_total", {})
    got = {s["labels"]["waited"]: s["value"] for s in fam.get("series", [])}
    return got.get("yes", 0.0), got.get("no", 0.0)


@pytest.mark.parametrize("prefetch", [2, 3])
def test_the_producers_waits_are_spans_under_h2d(paced_feed, prefetch):
    from distlr_tpu.train.trainer import GlobalShardedData

    rng = np.random.default_rng(0)
    X = rng.normal(size=(3 * PACED_ROWS, PACED_DIM)).astype(np.float32)
    data = GlobalShardedData([(X, (X[:, 0] > 0).astype(np.int32))])
    cfg = Config(num_feature_dim=PACED_DIM, mesh_shape={"data": 1},
                 batch_size=PACED_ROWS, l2_c=0.0, test_interval=0,
                 prefetch=prefetch)
    tr = Trainer(cfg).load_data(train=data, test=data)
    tr._test_data = None  # no eval_put: every piece is a train batch's
    pieces = paced_feed._plan(X[:PACED_ROWS], tr.mesh).pieces
    assert pieces == 8
    tracer = get_tracer()
    tracer.reset()
    yes0, no0 = _paced_counts()
    tr.fit(epochs=2)
    tr.fit(epochs=1)
    yes, no = (a - b for a, b in zip(_paced_counts(), (yes0, no0)))
    events = _events()
    by_id = {e["args"]["id"]: e for e in events}
    h2d = [e for e in events if e["name"] == "h2d"]
    paces = [e for e in events if e["name"] == "h2d_pace"]
    batches = 9
    # h2d stays one span a batch, its id used once, over epochs and fits
    assert sorted(e["args"]["step"] for e in h2d) == list(range(batches))
    # six pieces a batch through the pacer (eight here), by whether the
    # producer waited; every wait is a span
    assert yes + no == batches * pieces
    assert len(paces) == yes > no
    for e in paces:
        parent = by_id[e["args"]["parent"]]
        assert parent["name"] == "h2d"
        assert parent["args"]["step"] == e["args"]["step"]
        assert parent["tid"] == e["tid"]
        assert parent["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1
    # ... also a batch's first pieces, which wait for the batch before:
    # the pacer is carried across batches and epochs (not across fits)
    firsts = {e["args"]["step"] for e in paces}
    assert {1, 2, 3, 4, 5, 7, 8} <= firsts
    spans = tracer.breakdown()
    assert spans["h2d_pace"]["seconds"] <= spans["h2d"]["seconds"]
    assert spans["h2d"]["self_seconds"] <= (
        spans["h2d"]["seconds"] - spans["h2d_pace"]["seconds"] + 1e-4)
    # the other spans' ids are still used once a name
    seen = set()
    for e in events:
        if "step" in e["args"] and e["name"] != "h2d_pace":
            assert (e["name"], e["args"]["step"]) not in seen
            seen.add((e["name"], e["args"]["step"]))
    assert tr.batches_taken == batches
