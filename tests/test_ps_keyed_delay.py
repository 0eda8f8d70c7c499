"""Bounded delay, tau = 1 (``ps_max_delay``), on the asynchronous keyed
``sparse_lr`` job (``_KeyedDelayed``): one worker alone against the
plain reference's trajectory bit for bit, the order of operations on a
worker's connection (across an epoch's end, round an eval and a
checkpoint, at ``finish``, over two ``fit`` calls), four workers' pulls
each exactly one own push behind, the fence of the ring of two pulled
vectors, what ``Config`` and ``load_data`` refuse, and the spans and the
counter a traced run keeps.  ``ps_max_delay=0`` is the accepted tests'
to hold (``test_ps_round_chain.py``, ``test_ps_keyed_device.py``)."""

import threading

import jax
import numpy as np
import pytest

from chipbench.families import sparse_ps_keyed_delay as family
from distlr_tpu.config import Config
from distlr_tpu.data.iterator import SparseDataIter
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.obs.tracing import get_tracer
from distlr_tpu.ps import KVWorker
from distlr_tpu.train import ps_trainer
from distlr_tpu.train.ps_trainer import PSWorker
from test_ps_resident import _in_threads

DIM, BATCH, SLOTS, WINDOWS = 4096, 512, 39, 3
ROWS = WINDOWS * BATCH
RULE = dict(alpha=0.1, beta=1.0, l1=1e-3, l2=0.0)
LINEAGE = "distlr_ps_keyed_pull_lineage_total"


def _cfg(workers=1, **kw):
    return Config(**{**dict(
        model="sparse_lr", num_feature_dim=DIM, batch_size=BATCH,
        learning_rate=0.2, l2_c=0.0, test_interval=0, num_workers=workers,
        num_servers=2, sync_mode=False, ps_optimizer="ftrl",
        ftrl_alpha=RULE["alpha"], ftrl_beta=RULE["beta"], ftrl_l1=RULE["l1"],
        ftrl_l2=RULE["l2"], ps_max_delay=1), **kw})


def _shard(seed, rows=ROWS):
    rng = np.random.default_rng(seed)
    # a few hot columns and many cold ones: windows share keys
    cols = np.where(rng.random((rows, SLOTS)) < 0.3,
                    rng.integers(0, 64, (rows, SLOTS)),
                    rng.integers(0, DIM, (rows, SLOTS)))
    vals = rng.standard_normal(cols.shape).astype(np.float32)
    y = rng.integers(0, 2, rows).astype(np.int32)
    return cols, vals, y


class _Job:
    """A server group under ``cfg``'s rule and its loaded, started
    workers (rank r over ``_shard(seed + r)``)."""

    def __init__(self, cfg, seed=5, w0=None):
        self.cfg, self.seed, self.w0 = cfg, seed, w0

    def __enter__(self):
        cfg = self.cfg
        self.group = ps_trainer.server_group(cfg).start()
        self.workers = []
        try:
            if self.w0 is not None:
                with KVWorker(self.group.hosts, DIM, client_id=0xFC00) as kv:
                    kv.wait(kv.push_init(self.w0))
            self.shards = [_shard(self.seed + r)
                           for r in range(cfg.num_workers)]
            test = _shard(99, 256)
            self.workers = [
                PSWorker(cfg, r, self.group.hosts,
                         train_iter=SparseDataIter(*shard, BATCH),
                         test_iter=SparseDataIter(*test, -1))
                for r, shard in enumerate(self.shards)]
            for w in self.workers:
                w.load_data()
            _in_threads(self.workers, lambda w: w.start())
        except BaseException:
            self.__exit__()
            raise
        return self

    def __exit__(self, *exc):
        for w in self.workers:
            w.close()
        self.group.stop()

    def state(self):
        """The servers' w and, under FTRL, z and n, nothing in flight."""
        with KVWorker(self.group.hosts, DIM, client_id=0xFC01) as kv:
            w = np.array(kv.pull())
        if self.cfg.ps_optimizer != "ftrl":
            return w, None, None
        z, n = [], []
        for r in range(self.cfg.num_servers):
            lo, hi = self.group.key_range(r)
            with KVWorker(f"127.0.0.1:{self.group.ports[r]}", hi - lo,
                          client_id=0xFC02 + r, sync_group=False) as one:
                zr, nr = one.pull_opt_state()
            z.append(zr)
            n.append(nr)
        return w, np.concatenate(z), np.concatenate(n)


class _Tap:
    """Stands round one worker's connection: every operation in the order
    it was issued, as ``("L", k)`` (the k-th keyed pull of a window's
    held frame), ``("P", k)`` (the k-th keyed push), ``("E",)`` (another
    keyed pull: rank 0's eval) or ``("C",)`` (a dense pull: the
    checkpoint's), beside the pushes acknowledged and what the worker
    says is in flight at that instant; and each pull's reply and each
    push's gradient as arrays."""

    def __init__(self, worker):
        self.worker = worker
        self.ops, self.seen, self.threads = [], [], set()
        self.pulled, self.pushed = [], []
        self.acked = 0
        self.frames = {id(k) for k in worker._window_keys}
        pull, push = worker.kv.pull, worker.kv.push

        def tapped_pull(keys=None, **kw):
            mine = keys is not None and id(keys) in self.frames
            self.ops.append(("C",) if keys is None else ("L", len(
                self.pulled)) if mine else ("E",))
            self.seen.append((self.acked, worker.in_flight))
            self.threads.add(threading.current_thread().name)
            got = pull(keys=keys, **kw)
            if mine:
                self.pulled.append((keys, np.array(got)))
            return got

        def tapped_push(vals, keys=None, **kw):
            self.ops.append(("P", len(self.pushed)))
            self.seen.append((self.acked, worker.in_flight))
            self.threads.add(threading.current_thread().name)
            self.pushed.append((keys, np.array(vals)))
            ts = push(vals, keys=keys, **kw)
            self.acked += 1
            return ts

        worker.kv.pull, worker.kv.push = tapped_pull, tapped_push


def _the_rule(rounds):
    """L_0, L_1, P_0, L_2, P_1, ..., P_{R-3}, L_{R-1}, P_{R-2}, P_{R-1}."""
    ops = [("L", k) for k in range(min(2, rounds))]
    for k in range(rounds):
        ops.append(("P", k))
        if k + 2 < rounds:
            ops.append(("L", k + 2))
    return ops


def _bits(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return a.shape == b.shape and np.array_equal(a.view(np.uint32),
                                                 b.view(np.uint32))


def _lineage(rank=None):
    fam = get_registry().get(LINEAGE)
    out = {}
    for (r, behind), child in fam.children():
        if rank is None or r == str(rank):
            out[behind] = out.get(behind, 0) + int(child.value)
    return out


def _rise(before, after):
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in set(before) | set(after) if after.get(k, 0)
            != before.get(k, 0)}


# -- (a) one worker alone: a trajectory ---------------------------------------
@pytest.mark.parametrize("optimizer", ["ftrl", "sgd"])
def test_a_worker_alone_follows_the_references_trajectory(optimizer):
    """Three epochs of three windows, one ``fit``: every ``v_k`` is the
    state with the worker's own pushes through round k - 2 applied, bit
    for bit; every ``g_k`` is the reference's gradient at ``v_k`` within
    the sibling cell's limits; and the tables the servers end on are the
    ones ``solo`` ends on with the same pushes, bit for bit."""
    rounds = 3 * WINDOWS
    w0 = (np.random.default_rng(3).standard_normal(DIM) * 0.05).astype(
        np.float32) if optimizer == "sgd" else np.zeros(DIM, np.float32)
    rule = RULE if optimizer == "ftrl" else None
    with _Job(_cfg(ps_optimizer=optimizer), w0=w0) as job:
        (worker,) = job.workers
        tap = _Tap(worker)
        before = _lineage()
        worker.fit(epochs=3)
        assert worker.in_flight == 0
        w, z, n = job.state()
        counted = _rise(before, _lineage())
    assert tap.ops == _the_rule(rounds)
    assert counted == {"0": 1, "1": rounds - 1}
    shard = job.shards[0]
    pushes = [g for _keys, g in tap.pushed]
    # the program's own pushes where the reference's stood: bit for bit
    vs, gs, (w_r, z_r, n_r) = family.solo(
        w0, shard, rounds, batch=BATCH, rule=rule, lr=0.2, pushes=pushes)
    for k, ((keys, v), (pkeys, g)) in enumerate(zip(tap.pulled, tap.pushed)):
        at = family.window(k, ROWS, BATCH)
        assert np.array_equal(keys, family.keys(shard[0][at]))
        assert np.array_equal(pkeys, keys)
        assert _bits(v, vs[k]), k
        ref = np.linalg.norm(gs[k])
        assert abs(np.linalg.norm(g) - ref) <= 1e-5 * ref
        assert np.linalg.norm(g - gs[k]) <= 4e-5 * ref
    assert _bits(w, w_r)
    if rule is not None:
        assert _bits(z, z_r) and _bits(n, n_r)
        # computed_on says the same of every round, push by push
        zeros = np.zeros(DIM, np.float32)
        for k in (0, 1, 2, 5, rounds - 1):
            assert _bits(tap.pulled[k][1], family.computed_on(
                k, [tap.pushed], (w0, zeros, zeros), **rule))
    # the reference on its own gradients: the same run to rounding
    v_own, _g, (w_own, _z, _n) = family.solo(
        w0, shard, rounds, batch=BATCH, rule=rule, lr=0.2)
    assert np.linalg.norm(w_own - w) <= 1e-4 * np.linalg.norm(w)
    for k in range(rounds):
        assert np.linalg.norm(v_own[k] - tap.pulled[k][1]) <= 1e-4 * max(
            np.linalg.norm(v_own[k]), 1e-3)


# -- (b) the order on the connection ------------------------------------------
def test_the_connection_carries_the_rules_order_over_two_fits():
    """Across an epoch's end, at ``finish`` and over a second ``fit``:
    each ``fit`` is the rule's sequence from ``L_0, L_1``, every
    operation on the comm thread, and no pull for a round that does not
    run."""
    with _Job(_cfg()) as job:
        (worker,) = job.workers
        tap = _Tap(worker)
        worker.fit(epochs=2)
        first = list(tap.ops)
        assert worker.in_flight == 0 and not worker._keyed_flight
        worker.fit(epochs=1)
        assert worker.in_flight == 0
        worker.fit(epochs=0)
    n = 2 * WINDOWS
    assert first == _the_rule(n)
    again = [(op, k - n) for op, k in tap.ops[len(first):]]
    assert again == _the_rule(WINDOWS)
    assert tap.threads == {"ps-comm-0_0"}
    # L_{k+1} after P_{k-1} is acknowledged and before P_k is issued
    for (op, k), (acked, _flying) in zip(first, tap.seen):
        if op == "L":
            assert acked == max(k - 1, 0)


@pytest.mark.parametrize("rounds,want", [
    (1, "L0 P0"), (2, "L0 L1 P0 P1"), (3, "L0 L1 P0 L2 P1 P2")])
def test_a_short_fit_pulls_for_no_round_that_does_not_run(rounds, want):
    cfg = _cfg()
    with _Job(cfg) as job:
        (worker,) = job.workers
        # a shard of `rounds` windows: the fit is one epoch of it
        worker.close()
        worker = job.workers[0] = PSWorker(
            cfg, 0, job.group.hosts,
            train_iter=SparseDataIter(*_shard(8, rounds * BATCH), BATCH),
            test_iter=SparseDataIter(*_shard(99, 256), -1))
        worker.load_data()
        tap = _Tap(worker)
        worker.fit(epochs=1)
        assert worker.in_flight == 0
    assert " ".join(f"{op}{k}" for op, k in tap.ops) == want


def test_an_eval_and_a_checkpoint_find_nothing_in_flight_and_move_no_op(
        tmp_path):
    """``test_interval`` 1 and ``checkpoint_interval`` 2 over three
    epochs of three rounds: rank 0's eval and checkpoint pulls come with
    every push of the rounds run acknowledged and nothing at the comm
    thread, and the keyed operations round them are the rule's sequence
    all the same (a reply taken early is still its round's)."""
    from distlr_tpu.train.checkpoint import Checkpointer

    cfg = _cfg(test_interval=1, checkpoint_interval=2,
               checkpoint_dir=str(tmp_path / "ckpt"))
    with _Job(cfg) as job:
        (worker,) = job.workers
        tap = _Tap(worker)
        ckpt = Checkpointer(cfg.checkpoint_dir)
        try:
            worker.fit(epochs=3, ckpt=ckpt, eval_fn=lambda *a: None)
            kept = ckpt.restore(2)["weights"]
        finally:
            ckpt.close()
        w, _z, _n = job.state()
    rounds = 3 * WINDOWS
    keyed = [op for op in tap.ops if op[0] in "LP"]
    assert keyed == _the_rule(rounds)
    observers = [(op[0], acked, flying)
                 for op, (acked, flying) in zip(tap.ops, tap.seen)
                 if op[0] in "EC"]
    # evals after epochs 1, 2, 3; checkpoints after 2 and at the fit's end
    assert observers == [("E", 3, 0), ("E", 6, 0), ("C", 6, 0), ("E", 9, 0),
                        ("C", 9, 0)]
    # the pulls for rounds 3 and 4 were out before the first eval
    at = tap.ops.index(("E",))
    assert tap.ops[:at][-2:] == [("P", 2), ("L", 4)]
    assert ("L", 3) in tap.ops[:at]
    # the run is the unobserved one: one worker alone has a trajectory
    _v, _g, (w_r, _zr, _nr) = family.solo(
        np.zeros(DIM, np.float32), job.shards[0], rounds, batch=BATCH,
        rule=RULE, pushes=[g for _k, g in tap.pushed])
    assert _bits(w, w_r)
    assert kept.shape == w.shape and np.isfinite(kept).all()


# -- (c) four workers ----------------------------------------------------------
def test_four_workers_pulls_are_each_one_own_push_behind(tmp_path):
    from distlr_tpu.train.checkpoint import Checkpointer

    cfg = _cfg(workers=4, test_interval=2, checkpoint_interval=2,
               checkpoint_dir=str(tmp_path / "ckpt"))
    with _Job(cfg) as job:
        taps = [_Tap(w) for w in job.workers]
        before = [_lineage(r) for r in range(4)]
        ckpt = Checkpointer(cfg.checkpoint_dir)
        try:
            for epochs in (2, 1):
                _in_threads(job.workers, lambda w: w.fit(
                    epochs=epochs, ckpt=ckpt if w.rank == 0 else None,
                    eval_fn=lambda *a: None))
                assert [w.in_flight for w in job.workers] == [0] * 4
        finally:
            ckpt.close()
        counted = [_rise(b, _lineage(r)) for r, b in enumerate(before)]
    rounds = 3 * WINDOWS
    for tap, rise in zip(taps, counted):
        keyed = [(op, seen) for op, seen in zip(tap.ops, tap.seen)
                 if op[0] in "LP"]
        own = 0  # rounds of earlier fits
        for fit_rounds in (2 * WINDOWS, WINDOWS):
            for (op, k), (acked, _f) in keyed:
                if op == "L" and own <= k < own + fit_rounds:
                    # every pull but the fit's first: one own push behind
                    assert k - acked == (0 if k == own else 1), (k, acked)
            own += fit_rounds
        assert rise == {"0": 2, "1": rounds - 2}
        for op, (acked, flying) in zip(tap.ops, tap.seen):
            if op[0] in "EC":
                assert flying == 0 and acked in (2 * WINDOWS, rounds)
    assert sum(op == ("E",) for op in taps[0].ops) == 1
    assert not any(op[0] in "EC" for t in taps[1:] for op in t.ops)


# -- (d) the ring's fence ------------------------------------------------------
def test_the_comm_thread_writes_the_other_vector_of_the_ring(monkeypatch):
    """The fence of ``test_ps_round_chain``'s keyed chain, for the ring:
    between the ``device_put`` of a round's vector and the return of its
    step the vector's bits stay as they were, and whatever the comm
    thread writes meanwhile (the room made for a reply, the reply) goes
    to the OTHER vector; round k's weights are in vector k mod 2."""
    with _Job(_cfg()) as job:
        (worker,) = job.workers
        ring = worker._keyed_ring
        assert len(ring) == 2 and worker._keyed_vector is ring[0]
        index = {id(v.buf): i for i, v in enumerate(ring)}
        events, lock = [], threading.Lock()
        reading = []  # the vector a step has put and not yet returned from

        def note(*what):
            with lock:
                events.append((threading.current_thread().name, *what,
                               tuple(reading)))

        for i, vector in enumerate(ring):
            def tapped(count, room=vector.room, i=i):
                note("room", i)
                return room(count)
            vector.room = tapped
        pull, step = worker.kv.pull, worker.grad_step

        def tapped_pull(keys=None, out=None, **kw):
            i = index[id(out)]
            note("pull", i)
            got = pull(keys=keys, out=out, **kw)
            note("pulled", i)
            return got

        def tapped_step(w_u, window):
            held = [i for i, v in enumerate(ring) if v.holds(w_u)]
            note("step", held[0] if held else None)
            g = step(w_u, window)
            reading.clear()
            return g

        real_put, real_ready = jax.device_put, jax.block_until_ready

        def device_put(x, *a, **kw):
            if id(x) in index:
                reading.append(index[id(x)])
                note("put", index[id(x)], x.tobytes())
            return real_put(x, *a, **kw)

        def block_until_ready(x):
            got = real_ready(x)
            if reading and threading.current_thread().name == "MainThread":
                note("ready", reading[0], ring[reading[0]].buf.tobytes())
            return got

        worker.kv.pull, worker.grad_step = tapped_pull, tapped_step
        monkeypatch.setattr(jax, "device_put", device_put)
        monkeypatch.setattr(jax, "block_until_ready", block_until_ready)
        worker.fit(epochs=3)
        monkeypatch.undo()
    steps = [e for e in events if e[1] == "step"]
    assert [e[2] for e in steps] == [k % 2 for k in range(3 * WINDOWS)]
    puts = [e for e in events if e[1] == "put"]
    readies = [e for e in events if e[1] == "ready"]
    assert len(puts) == len(readies) == 3 * WINDOWS
    for put, ready in zip(puts, readies):
        assert put[2] == ready[2] and put[3] == ready[3]  # the same bits
    writes = [e for e in events if e[0] != "MainThread"]
    assert {e[1] for e in writes} == {"room", "pull", "pulled"}
    assert len(writes) == 3 * 3 * WINDOWS
    # what the comm thread wrote while a step read: never that vector
    under = [e for e in writes if e[-1]]
    assert under, "no reply ever landed under a step"
    assert all(e[2] != e[-1][0] for e in under)


def _scribbled_run(when):
    """One worker's three epochs with NaNs written over a vector of the
    ring every round: ``"idle"``, the round's own once its step has
    returned (its next writer is the pull two rounds on, not yet handed
    over); ``"live"``, the round's own before its step puts it."""
    with _Job(_cfg()) as job:
        (worker,) = job.workers
        ring, step = worker._keyed_ring, worker.grad_step
        pushed = []

        def tapped_step(w_u, window):
            vector = next(v for v in ring if v.holds(w_u))
            if when == "live":
                vector.buf[:] = np.nan
            g = step(w_u, window)
            if when == "idle":
                vector.buf[:] = np.nan
            pushed.append(np.array(g))
            return g

        if when:
            worker.grad_step = tapped_step
        worker.fit(epochs=3)
        return job.state(), pushed


def test_a_scribble_on_the_idle_vector_changes_nothing_and_on_the_live_is_seen():
    clean, _ = _scribbled_run(None)
    idle, pushed = _scribbled_run("idle")
    assert len(pushed) == 3 * WINDOWS
    assert all(np.isfinite(g).all() for g in pushed)
    for a, b in zip(clean, idle):
        assert _bits(a, b)
    # the tap has teeth: the same scribble before the step reads it
    try:
        live, pushed = _scribbled_run("live")
    except Exception:  # noqa: BLE001  (a NaN the servers refused)
        return
    assert (not all(np.isfinite(g).all() for g in pushed)
            or not all(_bits(a, b) for a, b in zip(clean, live)))


# -- (e) what is refused -------------------------------------------------------
@pytest.mark.parametrize("kw,says", [
    (dict(model="binary_lr"), "needs sync_mode for a dense model"),
    (dict(model="softmax"), "needs sync_mode for a dense model"),
    (dict(sync_mode=True), "in lock step is for dense models"),
    (dict(model="blocked_lr"), "written for sparse_lr alone"),
    (dict(model="sparse_softmax"), "written for sparse_lr alone"),
    (dict(model="blocked_lr", sync_mode=True), "dense models"),
    (dict(ps_pipeline=False), "needs ps_pipeline"),
    (dict(ps_accum_start=2, ps_accum_max=2), "ps_accum_max > 1"),
    (dict(ps_compress="int8"), "ps_compress='int8'"),
    (dict(ps_max_delay=2), "must be 0 or 1"),
], ids=lambda v: "-".join(f"{k}={x}" for k, x in v.items())
    if isinstance(v, dict) else None)
def test_config_refuses_what_the_keyed_delay_cannot_keep(kw, says):
    with pytest.raises(ValueError, match="ps_max_delay") as e:
        _cfg(**kw)
    assert says in str(e.value)


@pytest.mark.parametrize("optimizer", ["ftrl", "sgd"])
def test_config_accepts_the_delay_on_the_asynchronous_sparse_lr_job(optimizer):
    cfg = _cfg(ps_optimizer=optimizer)
    assert cfg.ps_max_delay == 1 and not cfg.sync_mode
    assert _cfg(ps_max_delay=0).ps_max_delay == 0


@pytest.mark.parametrize("iterator,why", [
    (lambda s: SparseDataIter(*s, BATCH, shuffle=True, seed=1),
     "does not serve the rows in the order"),
    (lambda s: SparseDataIter(*(a[:, :2] if a.ndim == 2 else a for a in s),
                              16), "under the size worth a jax dispatch"),
], ids=["shuffled", "a-step-too-small"])
def test_load_data_refuses_a_shard_that_is_not_resident_and_windowed(
        iterator, why):
    """No silent fall-back to the serialized exchange: the worker says
    why its shard has no ``_window_keys`` and raises."""
    cfg = _cfg()
    with ps_trainer.server_group(cfg) as group:
        test = SparseDataIter(*_shard(99, 256), -1)
        worker = PSWorker(cfg, 0, group.hosts,
                          train_iter=iterator(_shard(4)), test_iter=test)
        try:
            with pytest.raises(ValueError, match="ps_max_delay=1") as e:
                worker.load_data()
            assert why in str(e.value) and "serialized" in str(e.value)
            assert worker._window_keys is None or worker._train is None
        finally:
            worker.close()
        # the same shard loads without the delay, on the host's path
        plain = PSWorker(_cfg(ps_max_delay=0), 0, group.hosts,
                         train_iter=iterator(_shard(4)), test_iter=test)
        try:
            plain.load_data()
            assert type(plain._exchange()) is ps_trainer._Serialized
            assert len(plain._keyed_ring) <= 1
        finally:
            plain.close()


def test_the_exchange_is_chosen_by_the_delay_alone():
    with _Job(_cfg()) as job:
        assert type(job.workers[0]._exchange(3)) is ps_trainer._KeyedDelayed
    with _Job(_cfg(ps_max_delay=0)) as job:
        (worker,) = job.workers
        assert type(worker._exchange()) is ps_trainer._Serialized
        assert len(worker._keyed_ring) == 1
        assert worker._keyed_ring[0] is worker._keyed_vector


# -- (g) spans and counters ----------------------------------------------------
def test_a_traced_fit_keeps_the_exchanges_spans_and_its_counter():
    rounds = 2 * WINDOWS
    with _Job(_cfg()) as job:
        (worker,) = job.workers
        tracer = get_tracer()
        tracer.reset()
        first = worker.rounds + 1  # the step of round 0
        worker.fit(epochs=2)
        doc = tracer.chrome_trace()
    events = [e for e in doc["traceEvents"] if e["args"].get("rank") == 0]
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    loop = by["round"][0]["tid"]
    # the comm thread: a wire a task, a push and a pull inside, each
    # under the step of the round it is for and with its key count
    comm = by["wire"][0]["tid"]
    assert comm != loop and len(by["wire"]) == rounds + min(2, rounds)
    assert len(by["wire_handoff"]) == len(by["wire"])
    ids = {e["args"]["id"]: e for e in events}
    for name in ("push", "pull"):
        assert len(by[name]) == rounds
        assert [e["args"]["step"] for e in by[name]] == list(
            range(first, first + rounds))
        for e in by[name]:
            assert e["tid"] == comm and e["args"]["keys"] > 0
            assert ids[e["args"]["parent"]]["name"] == "wire"
    # a task is submitted by round k and holds P_k and L_{k+2}
    for wire in by["wire"]:
        inside = sorted((e["name"], e["args"]["step"] - wire["args"]["step"])
                        for e in events if e["args"].get("parent")
                        == wire["args"]["id"] and e["name"] in ("push", "pull"))
        assert inside in ([("pull", 0)], [("pull", 1)],
                          [("pull", 2), ("push", 0)], [("push", 0)])
    for e in by["wire_handoff"]:
        assert ids[e["args"]["parent"]]["name"] == "wire"
    # the loop: one exchange_wait a round and the fit's first, a
    # reply_wake inside each that took a reply
    waits = by["exchange_wait"]
    assert all(e["tid"] == loop for e in waits)
    assert len([e for e in waits if "drain" not in e["args"]]) == rounds + 1
    assert len(by["reply_wake"]) == rounds
    for e in by["reply_wake"]:
        assert ids[e["args"]["parent"]]["name"] == "exchange_wait"
    # the device chain says what stood at the comm thread
    for name in ("w_put", "compute", "grad_d2h"):
        assert len(by[name]) == rounds
        flying = [e["args"]["in_flight"] for e in by[name]]
        assert set(flying) <= {0, 1, 2} and any(flying)
    assert not doc["otherData"].get("dropped_events")
    fam = get_registry().get(LINEAGE)
    assert fam is not None and {labels[1] for labels, _c in fam.children()
                                } >= {"0", "1"}
