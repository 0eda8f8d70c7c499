"""A PS worker's round hands its device the chain whole (weights in, the
gradient program, the gradient out) and waits once: the same gradient as
the blocking form bit for bit, the same three spans in the same order, a
counter of how the dispatch found the weights' copy, and the fence on the
weights' host array that makes it safe.  What goes in and comes out is the
flat vector the wire carries, whatever the model's shape: a gauge says
where that shape is restored."""

import collections
import threading

import jax
import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.data.iterator import Window
from distlr_tpu.data.synthetic import write_synthetic_shards
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.obs.tracing import get_tracer
from distlr_tpu.ps import ServerGroup
from distlr_tpu.train.ps_trainer import PSWorker, ps_param_dim
from test_ps_resident import _group, _in_threads  # a group for a job; a thread a worker

DIM, CLASSES, WORKERS, ITERATIONS = 24, 3, 2, 3
CHAIN = ("w_put", "compute", "grad_d2h")

MODELS = ("binary_lr", "softmax")
# 80 rows a worker: whole; in windows of 32 of the resident shard; and
# streamed, as Q5's wrapped last batch still is
BATCHES = {"resident": dict(batch_size=-1),
           "windowed": dict(batch_size=32),
           "streamed": dict(batch_size=32, wrap_final_batch=True)}
LOOPS = {"fused-bsp": dict(sync_mode=True),
         "pipelined-async": dict(sync_mode=False)}
CASES = [(m, b, l) for m in MODELS for b in BATCHES for l in LOOPS]
ROUNDS, DISPATCHES = ("distlr_ps_grad_rounds_total",
                      "distlr_ps_grad_dispatches_total")
SHAPED = "distlr_ps_step_params_shaped"
SERIES = {ROUNDS: ("path", ("one_pass", "two_pass")),
          DISPATCHES: ("weights", ("in_flight", "landed"))}


pytestmark = pytest.mark.usefixtures("ps_steps_on_device")


def _cfg(d, model, batch, loop, **kw):
    classes = CLASSES if model == "softmax" else 2
    write_synthetic_shards(d, 100 * WORKERS, DIM, num_parts=WORKERS, seed=11,
                           sparsity=0.0, num_classes=classes)
    base = dict(
        data_dir=d, num_feature_dim=DIM, model=model, num_classes=classes,
        num_workers=WORKERS, num_servers=2, **BATCHES[batch],
        num_iteration=ITERATIONS, learning_rate=0.2, l2_c=0.0,
        test_interval=0)
    return Config(**{**base, **LOOPS[loop], **kw})


class _Recorder:
    """Round ``grad_step``: the very arrays the loop handed in (kept, so
    that none is freed and its address used again), a copy of the weights'
    bits and of the gradient that came back."""

    def __init__(self, step):
        self.step, self.seen = step, []

    def __call__(self, wf, batch):
        bits = np.array(wf)
        g = self.step(wf, batch)
        self.seen.append((wf, bits, batch, np.array(g)))
        return g


def _blocking(worker, wf, batch):
    """The round's chain as it stood before: a wait after every link."""
    how = {}
    if isinstance(batch, Window):
        how = dict(first=np.int32(batch.first), window=32)
        batch = worker._resident
    elif batch is not worker._resident:
        batch = jax.block_until_ready(
            tuple(jax.device_put(a) for a in batch))
    w = jax.block_until_ready(jax.device_put(wf))
    g = jax.block_until_ready(worker._grad_fn(w, *batch, **how))
    return np.asarray(g)


def _counts():
    """The two families' children, by family, rank and label value."""
    out = collections.Counter()
    for name, (label, values) in SERIES.items():
        fam = get_registry().get(name)
        for rank in range(WORKERS):
            for value in values:
                out[name, rank, value] = fam.labels(
                    rank=str(rank), **{label: value}).value
    return out


def _shaped_at(rank):
    """The series of ``distlr_ps_step_params_shaped`` that read 1 for
    ``rank``, and the sum over all of its series."""
    fam = get_registry().get(SHAPED)
    got = {where: fam.labels(rank=str(rank), where=where).value
           for where in ("none", "host", "device")}
    return [where for where, v in got.items() if v == 1], sum(got.values())


def _run(tmp_path_factory, model, batch, loop, **kw):
    d = str(tmp_path_factory.mktemp(f"chain-{model}-{batch}-{loop}"))
    cfg = _cfg(d, model, batch, loop, **kw)
    tracer = get_tracer()
    with _group(cfg) as group:
        workers = [PSWorker(cfg, r, group.hosts) for r in range(WORKERS)]
        try:
            for w in workers:
                w.load_data()
                assert (w._resident is not None) == (batch != "streamed")
                assert w._windowed == (batch == "windowed")
                assert w._panels is None  # the CPU keeps the XLA step
                w.grad_step = _Recorder(w.grad_step)
            shaped = {w.rank: _shaped_at(w.rank) for w in workers}
            before = _counts()
            tracer.reset()
            _in_threads(workers, lambda w: w.run(save=False))
            events = tracer.chrome_trace()["traceEvents"]
            counted = _counts() - before
            seen = {w.rank: w.grad_step.seen for w in workers}
            want = {w.rank: [_blocking(w, bits, b)
                             for _, bits, b, _ in w.grad_step.seen]
                    for w in workers}
            # the fence, from outside: the weights' array written over
            # once the step has returned
            w0 = workers[0]
            wf, _, b, _ = seen[0][0]
            wf = np.array(wf)
            got = w0.grad_step.step(wf, b)
            kept = np.array(got)
            wf[:] = np.nan
            return dict(
                cfg=cfg, events=events, seen=seen, want=want,
                rounds={w.rank: w.rounds for w in workers},
                counted=counted, shaped=shaped,
                overwritten=(got, kept, want[0][0]))
        finally:
            for w in workers:
                w.close()


@pytest.fixture(scope="module", params=CASES, ids="-".join)
def run(request, tmp_path_factory, ps_steps_on):
    # the jitted step on the default backend: by their size these tiny
    # steps would go to numpy, which has no device and no chain
    with ps_steps_on("device"):
        return _run(tmp_path_factory, *request.param)


def test_the_gradient_is_the_blocking_forms_bit_for_bit(run):
    for rank, seen in run["seen"].items():
        assert len(seen) == run["rounds"][rank] >= ITERATIONS
        for (_, _, _, got), want in zip(seen, run["want"][rank]):
            assert got.dtype == want.dtype == np.float32
            assert got.shape == want.shape == (ps_param_dim(run["cfg"]),)
            assert np.array_equal(got, want)
            assert np.count_nonzero(got)


def test_a_round_records_its_three_spans_once_each_in_that_order(run):
    spans = collections.defaultdict(list)
    for e in run["events"]:
        if e["name"] in CHAIN:
            spans[e["args"]["rank"], e["args"]["step"]].append(e)
    for rank, rounds in run["rounds"].items():
        for step in range(1, rounds + 1):
            got = sorted(spans.pop((rank, step)), key=lambda e: e["ts"])
            assert [e["name"] for e in got] == list(CHAIN), (rank, step)
            assert len({e["tid"] for e in got}) == 1
            for a, b in zip(got, got[1:]):
                # one after the other on the loop's thread, none inside
                # another (the tracer's clock is in whole microseconds)
                assert a["ts"] + a["dur"] <= b["ts"] + 1
    assert not spans  # and no span of the chain under any other step


def test_every_round_counts_one_dispatch_beside_its_program(run):
    for rank, rounds in run["rounds"].items():
        programs, dispatches = (
            sum(n for (name, r, _), n in run["counted"].items()
                if name == family and r == rank)
            for family in (ROUNDS, DISPATCHES))
        # the fixture's one step more, on rank 0, is outside both reads
        assert dispatches == programs == rounds


def test_the_gauge_says_where_the_parameters_take_their_shape(run):
    """By the rank of the model's ``param_shape`` alone: a class axis is
    restored inside the jitted program, a rank-1 vector nowhere."""
    want = "device" if run["cfg"].model == "softmax" else "none"
    for rank in run["rounds"]:
        assert run["shaped"][rank] == ([want], 1)


@pytest.mark.parametrize("model", MODELS)
def test_a_numpy_step_shapes_its_parameters_on_the_host(
        tmp_path_factory, ps_steps_on, model):
    d = str(tmp_path_factory.mktemp(f"chain-numpy-{model}"))
    cfg = _cfg(d, model, "resident", "pipelined-async")
    with _group(cfg) as group, ps_steps_on("numpy"):
        worker = PSWorker(cfg, 0, group.hosts)
        try:
            worker.load_data()
            assert worker._resident is None  # numpy places nothing
            want = "host" if model == "softmax" else "none"
            assert _shaped_at(0) == ([want], 1)
            g = worker.grad_step(
                np.full(ps_param_dim(cfg), 0.01, np.float32),
                worker._train.next_batch())
            assert g.shape == (ps_param_dim(cfg),) and np.count_nonzero(g)
        finally:
            worker.close()


def test_the_weights_array_written_over_afterwards_changes_nothing(run):
    got, kept, want = run["overwritten"]
    assert np.array_equal(got, kept)
    assert np.array_equal(got, want)
    assert np.isfinite(got).all()


def test_no_reply_lands_in_an_array_a_round_was_handed(run):
    """The fence the step's comment states: every reply of ``push_pull``
    (and the first ``pull``) is a fresh array and no loop writes one in
    place, so nothing writes the weights a chain in flight may still be
    reading.  (The pipelined loop computes an epoch's first two rounds on
    one array, with no exchange in flight before the first: the same
    object, still never written.)"""
    fused = run["cfg"].sync_mode
    for seen in run["seen"].values():
        for wf, bits, *_ in seen:
            assert np.array_equal(wf, bits)  # as it was when handed in
        arrays = [wf for wf, *_ in seen]
        for a, b in zip(arrays, arrays[1:]):
            if a is b:
                assert not fused
            else:
                assert not np.shares_memory(a, b)
        if run["cfg"].batch_size > 0 or not fused:
            continue
        # whole-shard lock-step rounds: a round an exchange, so the
        # weights moved
        for (_, a, *_), (_, b, *_) in zip(seen, seen[1:]):
            assert not np.array_equal(a, b)


@pytest.mark.parametrize("model", MODELS)
def test_the_serialized_loops_pull_is_a_fresh_array_too(tmp_path_factory,
                                                        model):
    got = _run(tmp_path_factory, model, "resident", "pipelined-async",
               ps_pipeline=False)
    names = {e["name"] for e in got["events"]}
    assert "pull" in names and "wire" not in names
    for rank, seen in got["seen"].items():
        assert len(seen) == ITERATIONS
        arrays = [wf for wf, *_ in seen]
        for a, b in zip(arrays, arrays[1:]):
            assert a is not b and not np.shares_memory(a, b)
        for (_, _, _, g), want in zip(seen, got["want"][rank]):
            assert np.array_equal(g, want)


@pytest.mark.parametrize("op", ["pull", "push_pull"])
def test_the_client_returns_a_new_array_every_call(op):
    from distlr_tpu.ps import KVWorker

    with ServerGroup(2, 1, DIM, learning_rate=0.1, sync=False) as group:
        kv = KVWorker(group.hosts, DIM)
        try:
            kv.wait(kv.push_init(np.ones(DIM, np.float32)))
            g = np.full(DIM, 0.5, np.float32)
            call = kv.pull if op == "pull" else (lambda: kv.push_pull(g))
            first = call()
            bits = np.array(first)
            second = call()
            assert first is not second
            assert not np.shares_memory(first, second)
            assert np.array_equal(first, bits)  # the later reply left it be
        finally:
            kv.close()


# -- the keyed chain: a vector that is kept ----------------------------------
K_DIM, K_BATCH, K_ROWS, K_WORKERS, K_EPOCHS = 2048, 128, 2 * 128 + 70, 2, 3


def _keyed_job():
    """Two ``sparse_lr`` workers over resident localised shards of three
    windows (the last short), against two native servers."""
    from distlr_tpu.data.iterator import SparseDataIter

    rng = np.random.default_rng(17)
    cfg = Config(model="sparse_lr", num_feature_dim=K_DIM, batch_size=K_BATCH,
                 learning_rate=0.2, l2_c=0.0, test_interval=0,
                 num_workers=K_WORKERS, num_servers=2, sync_mode=False)

    def shard():
        cols = rng.integers(0, K_DIM, (K_ROWS, 9))
        vals = rng.standard_normal(cols.shape).astype(np.float32)
        y = rng.integers(0, 2, K_ROWS).astype(np.int32)
        return cols, vals, y

    def workers(hosts):
        return [PSWorker(cfg, r, hosts,
                         train_iter=SparseDataIter(*shard(), K_BATCH),
                         test_iter=SparseDataIter(*shard(), -1))
                for r in range(K_WORKERS)]

    return cfg, workers


class _Fence:
    """What touches a worker's pulled vector, in order, with the vector's
    bits where it is handed over and where the round's wait returns."""

    def __init__(self, worker):
        self.worker, self.vector = worker, worker._keyed_vector
        self.events, self.thread = [], None
        room, pull, step = (self.vector.room, worker.kv.pull,
                            worker.grad_step)

        def tapped_room(count):
            self.events.append(("room", count))
            return room(count)

        def tapped_pull(keys=None, **kw):
            self.events.append(("pull", kw.get("out") is self.vector.buf))
            got = pull(keys=keys, **kw)
            self.events.append(("pulled", got.base is self.vector.buf))
            return got

        def tapped_step(w_u, window):
            self.thread = threading.get_ident()
            self.events.append(("step", self.vector.holds(w_u)))
            g = step(w_u, window)
            self.events.append(("stepped", self.bits()))
            return g

        self.vector.room, worker.kv.pull = tapped_room, tapped_pull
        worker.grad_step = tapped_step

    def bits(self):
        return self.vector.buf.tobytes()


def test_nothing_writes_the_pulled_vector_while_a_round_may_read_it(
        monkeypatch):
    """The fence of the dense chain, for a buffer that is reused: between
    the ``device_put`` of a worker's vector and the return of that round's
    ``block_until_ready`` nothing writes it (its bits are the same at
    both and at the step's return), and what does write it (the room made
    for the next reply, the reply) comes after, in that order, every
    round."""
    cfg, make = _keyed_job()
    with _group(cfg) as group:
        workers = make(group.hosts)
        try:
            for w in workers:
                w.load_data()
                assert w._keyed_vector is not None and w._windowed
            fences = {id(w._keyed_vector.buf): _Fence(w) for w in workers}

            def by_thread():
                return next((f for f in fences.values()
                             if f.thread == threading.get_ident()), None)

            real_put, real_ready = jax.device_put, jax.block_until_ready

            def device_put(x, *a, **kw):
                fence = fences.get(id(x))
                if fence is not None:
                    fence.events.append(("put", fence.bits()))
                return real_put(x, *a, **kw)

            def block_until_ready(x):
                got = real_ready(x)
                fence = by_thread()
                if fence is not None:
                    fence.events.append(("ready", fence.bits()))
                return got

            monkeypatch.setattr(jax, "device_put", device_put)
            monkeypatch.setattr(jax, "block_until_ready", block_until_ready)
            _in_threads(workers, lambda w: (w.start(), w.fit(epochs=K_EPOCHS)))
            monkeypatch.undo()
        finally:
            for w in workers:
                w.close()
    windows = -(-K_ROWS // K_BATCH)
    for fence in fences.values():
        names = [name for name, _ in fence.events]
        round_ = ["room", "pull", "pulled", "step", "put", "ready", "stepped"]
        assert names == round_ * (K_EPOCHS * windows)
        rounds = [fence.events[i:i + 7] for i in range(0, len(names), 7)]
        counts = []
        for (_, n), (_, into), (_, view), (_, held), (_, put), (_, ready), (
                _, stepped) in rounds:
            # the reply landed in the vector, and the step took it there
            assert into and view and held
            assert put == ready == stepped
            vector = np.frombuffer(put, np.float32)
            assert not vector[n:].any()
            counts.append(n)
        # (the model starts at zero weights: the later rounds' are not)
        assert np.count_nonzero(np.frombuffer(rounds[-1][4][1], np.float32))
        # windows of fewer keys after more, and of more after fewer
        assert any(a > b for a, b in zip(counts, counts[1:]))
        assert any(a < b for a, b in zip(counts, counts[1:]))


def test_the_pulled_vector_written_over_afterwards_changes_nothing(
        monkeypatch):
    """The fence from outside: once the step has returned the vector is
    free, and writing it over moves neither the gradient that came back
    nor the one the same weights give from an array of their own."""
    cfg, make = _keyed_job()
    with _group(cfg) as group:
        w, _ = workers = make(group.hosts)
        try:
            w.load_data()
            keys = w._window_keys[0]
            w.kv.wait(w.kv.push_init(
                (np.random.default_rng(3).standard_normal(K_DIM) * 0.1
                 ).astype(np.float32)))
            vector = w._keyed_vector
            w_u = w.kv.pull(keys=keys, out=vector.room(len(keys)))
            assert vector.holds(w_u)
            own = np.array(w_u)
            got = w.grad_step(w_u, Window(0, K_BATCH))
            kept = np.array(got)
            vector.buf[:] = np.nan
            assert np.array_equal(got, kept) and np.isfinite(got).all()
            # the staged form (an array of the caller's own): the same bits
            again = w.grad_step(own, Window(0, K_BATCH))
            assert np.array_equal(again.view(np.uint32), kept.view(np.uint32))
            assert np.count_nonzero(kept)
        finally:
            for w in workers:
                w.close()
