"""Bounded delay, tau = 1 (``ps_max_delay``), on the lock-step job: what
the exchange puts on the wire and holds, over a recording connection;
and four workers against two native servers, held to the plain reference
(``chipbench/families/dense_ps_bsp_delay.py``) on seeded random weights
and rows: the delayed trajectory and not lock step's, the lineage of
every round's weights by digest, a reply that comes early, the eval and
the checkpoint on drained weights, nothing in flight after ``fit``, the
configurations ``Config`` refuses, and ``ps_max_delay=0`` as it was."""

import threading

import numpy as np
import pytest

from chipbench.drivers.ps_minibatch_epochs import Lineage, lineage_broken
from chipbench.families import dense_ps_bsp, dense_ps_bsp_delay
from distlr_tpu.config import Config
from distlr_tpu.data.iterator import DataIter
from distlr_tpu.data.synthetic import write_synthetic_shards
from distlr_tpu.obs.registry import family_total, get_registry
from distlr_tpu.obs.tracing import get_tracer
from distlr_tpu.ps import KVWorker, ServerGroup
from distlr_tpu.train import ps_trainer
from distlr_tpu.train.ps_trainer import PSWorker, ps_param_dim

DIM, ROWS, WORKERS, SERVERS, LR = 2048, 40, 4, 2, 0.2
DELAYED = "distlr_ps_delayed_rounds_total"
#: float32 against the reference's float64 sum: the numpy step rounds a
#: gradient's 2,048 coordinates at 6e-8 each, a server three more times
TOL = 2e-5


# -- over a recording connection ---------------------------------------------
class Recording:
    """A connection whose servers are one array under plain SGD."""

    def __init__(self, hosts, dim, **kw):
        self.dim, self.calls, self.replies = dim, [], []
        self.table = np.random.default_rng(3).standard_normal(dim).astype(
            np.float32) * 0.1

    def supports_vals_per_key(self, vpk):
        return True

    def pull(self, keys=None, *, vals_per_key=1):
        self.calls.append("pull")
        return self.table.copy()

    def push_pull(self, vals, keys=None, *, vals_per_key=1):
        self.calls.append("push_pull")
        self.table -= LR * np.asarray(vals)
        self.replies.append(self.table.copy())
        return self.replies[-1]

    def close(self):
        pass


class Rounds:
    """In the step timer's place and round the gradient call: what the
    worker held when each round ended, what each round ran on, and
    whether a push stood in flight while it ran."""

    def __init__(self, worker):
        self.worker, self.step = worker, worker.grad_step
        self.held, self.ran_on, self.flying = [], [], []
        self.samples_per_sec = 0.0
        worker.timer, worker.grad_step = self, self

    def start(self):
        pass

    def stop(self, n):
        self.held.append(self.worker._w_cache.copy())

    def __call__(self, w, batch):
        self.ran_on.append(w.copy())
        self.flying.append(self.worker.in_flight)
        return self.step(w, batch)


def _recorded_worker(monkeypatch, rank=1, **kw):
    monkeypatch.setattr(ps_trainer, "KVWorker", Recording)
    rng = np.random.default_rng(11)
    train = DataIter(rng.standard_normal((48, 12)).astype(np.float32),
                     rng.integers(0, 2, 48), 16)
    test = DataIter(rng.standard_normal((8, 12)).astype(np.float32),
                    rng.integers(0, 2, 8), -1)
    cfg = Config(model="binary_lr", num_feature_dim=12, batch_size=16,
                 learning_rate=LR, l2_c=0.0, num_workers=2, sync_mode=True,
                 ps_max_delay=1, **{"test_interval": 0, **kw})
    worker = PSWorker(cfg, rank, "nowhere:0", train_iter=train,
                      test_iter=test if rank == 0 else None)
    worker.load_data()
    return worker, worker.kv


def _spans(name):
    return [e["args"] for e in get_tracer().chrome_trace()["traceEvents"]
            if e["name"] == name]


def _same(got, want):
    assert len(got) == len(want)
    for k, (a, b) in enumerate(zip(got, want)):
        assert np.array_equal(a, b), k


def _counted(rank):
    fam = get_registry().get(DELAYED)
    return [fam.labels(rank=str(rank), behind=b).value for b in ("0", "1")]


def test_delayed_runs_a_round_on_the_reply_before_last_across_epochs(
        monkeypatch):
    worker, kv = _recorded_worker(monkeypatch)
    assert type(worker._exchange()) is ps_trainer._Delayed
    opening = kv.table.copy()
    rounds = Rounds(worker)
    get_tracer().reset()
    before = _counted(1)
    worker.fit(epochs=2)   # two epochs of three rounds: one fit of six
    r = kv.replies
    assert kv.calls == ["pull"] + ["push_pull"] * 6
    # no epoch's end is a boundary: round k runs on the reply to push k-2
    _same(rounds.ran_on, [opening, opening, r[0], r[1], r[2], r[3]])
    _same(rounds.held, [opening, r[0], r[1], r[2], r[3], r[4]])
    # ... with the push before it out, but the fit's first
    assert rounds.flying == [0, 1, 1, 1, 1, 1]
    assert [a["in_flight"] for a in _spans("compute")] == rounds.flying
    # the one drain is fit's return, and the worker then holds w_6
    assert [a["step"] for a in _spans("push") if a.get("drain")] == [6]
    assert np.array_equal(worker._w_cache, r[5]) and worker.in_flight == 0
    assert [b - a for a, b in zip(before, _counted(1))] == [1, 5]
    # the next fit begins on what this one ended with: rounds 0 and 1
    rounds.ran_on.clear()
    worker.fit(epochs=1)
    _same(rounds.ran_on, [r[5], r[5], r[6]])
    assert kv.calls.count("pull") == 1
    assert [b - a for a, b in zip(before, _counted(1))] == [2, 7]
    worker.close()


def test_a_drain_for_an_observer_changes_nothing_a_round_runs_on(monkeypatch,
                                                                 tmp_path):
    """Rank 0 with an eval every epoch and a checkpoint every second:
    each finds nothing in flight and the servers at the last reply; the
    rounds run on what they run on without them."""
    worker, kv = _recorded_worker(monkeypatch, rank=0, test_interval=1,
                                  checkpoint_interval=2,
                                  checkpoint_dir=str(tmp_path))
    opening = kv.table.copy()
    rounds = Rounds(worker)
    seen = []

    class Ckpt:
        def save(self, step, weights, *, extra=None):
            seen.append(("ckpt", step, worker.in_flight, weights.copy(),
                         len(kv.replies)))

        def latest_step(self):
            return 2

    real = worker._dense_eval

    def evaluate(w, test):
        seen.append(("eval", worker.epochs_done + 1, worker.in_flight,
                     kv.table.copy(), len(kv.replies)))
        return real(w, test)

    worker._dense_eval = evaluate
    get_tracer().reset()
    worker.fit(epochs=2, ckpt=Ckpt(), eval_fn=lambda *a: None)
    r = kv.replies
    _same(rounds.ran_on, [opening, opening, r[0], r[1], r[2], r[3]])
    assert [(what, at, flying, n) for what, at, flying, _w, n in seen] == [
        ("eval", 1, 0, 3), ("eval", 2, 0, 6), ("ckpt", 2, 0, 6)]
    for _what, _at, _flying, weights, n in seen:
        assert np.array_equal(weights, r[n - 1])
    # the eval's drain and fit's own: nothing was out at the checkpoint
    assert [a["step"] for a in _spans("push") if a.get("drain")] == [3, 6]
    worker.close()


# -- against native servers ---------------------------------------------------
def _cfg(tmp_path, workers=WORKERS, **kw):
    d = str(tmp_path / f"job-{workers}")
    # a fifth of the rows is the test split
    write_synthetic_shards(d, ROWS * workers * 5 // 4, DIM, num_parts=workers,
                           seed=6, sparsity=0.9)
    base = dict(data_dir=d, num_feature_dim=DIM, model="binary_lr",
                num_workers=workers, num_servers=SERVERS, sync_mode=True,
                batch_size=-1, num_iteration=6, learning_rate=LR, l2_c=0.0,
                test_interval=0, ps_max_delay=1)
    return Config(**{**base, **kw})


def _as_coo(X):
    cols = np.tile(np.arange(X.shape[1], dtype=np.int32), (X.shape[0], 1))
    return cols, np.asarray(X, np.float32)


class Job:
    """A started group, its loaded and started workers, a probe, and the
    rows as the reference takes them."""

    def __init__(self, cfg, seed=21):
        self.cfg, self.workers, self.probe = cfg, [], None
        self.group = ServerGroup(
            cfg.num_servers, cfg.num_workers, ps_param_dim(cfg),
            learning_rate=cfg.learning_rate, sync=True).start()
        try:
            self.probe = KVWorker(self.group.hosts, DIM, client_id=0xFC00)
            self.w0 = (np.random.default_rng(seed).standard_normal(DIM)
                       * 0.3).astype(np.float32)
            self.probe.wait(self.probe.push_init(self.w0))
            self.workers = [PSWorker(cfg, r, self.group.hosts)
                            for r in range(cfg.num_workers)]
            for w in self.workers:
                w.load_data()
            self.shards = []
            for w in self.workers:
                X, y, _mask = w._train.whole_shard()
                self.shards.append((*_as_coo(X), y))
            self.all(lambda w: w.start())
        except BaseException:
            self.close(wait=False)
            raise

    def all(self, call):
        """``call(worker)`` on every worker at once; a worker that fails
        takes the group down, so that its peers fail and do not wait."""
        errors = []

        def one(w):
            try:
                call(w)
            except Exception as e:  # noqa: BLE001  (handed back)
                errors.append((w.rank, e))
                self.group.stop()

        threads = [threading.Thread(target=one, args=(w,)) for w in
                   self.workers]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        return errors

    def servers(self):
        return [self.probe.stats(r) for r in range(self.cfg.num_servers)]

    def close(self, wait=True):
        for w in self.workers:
            w.close(wait=wait)
        if self.probe is not None:
            self.probe.close()
        self.group.stop()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close(wait=exc[0] is None)


class Tap:
    """Round one worker's gradient call and its connection's push-pull:
    the weights every round ran on and every reply, as arrays."""

    def __init__(self, worker):
        self.worker, self.step = worker, worker.grad_step
        self.ran_on, self.replies = [], []
        real = worker.kv.push_pull

        def push_pull(*a, **kw):
            self.replies.append(real(*a, **kw))
            return self.replies[-1]

        worker.grad_step, worker.kv.push_pull = self, push_pull

    def __call__(self, wf, batch):
        self.ran_on.append(np.array(wf))
        return self.step(wf, batch)


def _off(a, b, scale):
    return float(np.linalg.norm(np.asarray(a, np.float64) - b)
                 / np.linalg.norm(scale))


def _bits(a, b):
    return np.array_equal(a.view(np.uint32), b.view(np.uint32))


def test_six_rounds_follow_the_delayed_reference_and_not_lock_steps(tmp_path):
    with Job(_cfg(tmp_path)) as job:
        taps = [Tap(w) for w in job.workers]
        servers = job.servers()
        assert not job.all(lambda w: w.fit(epochs=6))
        after = job.servers()
        held = [w._w_cache for w in job.workers]
        flying = [w.in_flight for w in job.workers]
        pulled = job.probe.pull()
    ref = dense_ps_bsp_delay.rounds(job.w0, job.shards, LR, 6)
    lock, w = [], job.w0
    for _ in range(6):
        w = dense_ps_bsp.round(w, job.shards, LR)
        lock.append(w)
    got = taps[0].replies
    assert len(got) == 6
    for k in range(6):
        moved = ref[k].astype(np.float64) - job.w0
        assert _off(got[k], ref[k], moved) <= TOL, k
        if k >= 1:   # w_1 is lock step's too; from w_2 on they part
            assert _off(got[k], lock[k], moved) > 100 * TOL, k
    # every worker ran round k on the same bits: v_0 = v_1 = w_0, v_k = w_{k-1}
    on = dense_ps_bsp_delay.computed_on(job.w0, got)
    for tap in taps:
        assert len(tap.ran_on) == 6
        for k in range(6):
            assert _bits(tap.ran_on[k], on[k]), k
        for k in range(6):
            assert _bits(tap.replies[k], got[k]), k
    # fit returned with nothing out, holding w_6, which the servers hold
    assert flying == [0] * WORKERS
    assert all(_bits(h, got[5]) and _bits(h, pulled) for h in held)
    # the servers saw six lock-step rounds: W pushes each, none left open
    for b, a in zip(servers, after):
        assert a["sync_rounds"] - b["sync_rounds"] == 6
        assert a["total_pushes"] - b["total_pushes"] == 6 * WORKERS
        assert a["pending_sync_pushes"] == b["pending_sync_pushes"] == 0


def test_the_lineage_holds_by_digest_over_two_fits(tmp_path):
    with Job(_cfg(tmp_path)) as job:
        before = [_counted(w.rank) for w in job.workers]
        taps = [Lineage(w) for w in job.workers]
        assert not job.all(lambda w: (w.fit(epochs=5), w.fit(epochs=5)))
        lins = [t.remove() for t in taps]
        counted = [[b - a for a, b in zip(was, _counted(w.rank))]
                   for w, was in zip(job.workers, before)]
    for lin in lins:
        assert len(lin["rounds"]) == len(lin["replies"]) == 10
        # rounds 0 and 1 of a fit on what it began with, round k on the
        # reply to push k - 2: a fit is the "epoch" of the rule
        assert lineage_broken(lin, 5) == 0
        assert lin["rounds"] == lins[0]["rounds"]
        assert lin["replies"] == lins[0]["replies"]
    # and it is not lock step's lineage, whose round k runs on reply k - 1
    lock = {**lins[0], "rounds": [lins[0]["opening"], *lins[0]["replies"][:9]]}
    assert lineage_broken(lock, 5) > 0
    assert counted == [[2, 8]] * WORKERS


def test_a_reply_that_is_in_early_is_not_taken_early(tmp_path):
    """Every push-pull completes inside ``submit``, so each reply is in
    before the next compute starts: a round still runs on the reply
    before it."""
    from concurrent.futures import Future

    class Inline:
        def submit(self, fn, *a):
            fut = Future()
            fut.set_result(fn(*a))
            return fut

        def shutdown(self, **kw):
            pass

    with Job(_cfg(tmp_path)) as job:
        for w in job.workers:
            w._comm_pool = Inline
        taps = [Lineage(w) for w in job.workers]
        arrays = Tap(job.workers[0])
        done_early = []
        real = arrays.step

        def noting(wf, batch):
            fut = job.workers[0]._in_flight
            done_early.append(fut is not None and fut.done())
            return real(wf, batch)

        arrays.step = noting
        assert not job.all(lambda w: w.fit(epochs=6))
        lins = [t.remove() for t in taps]
    assert done_early == [False] + [True] * 5
    for lin in lins:
        assert lineage_broken(lin, 6) == 0
    ref = dense_ps_bsp_delay.rounds(job.w0, job.shards, LR, 6)
    moved = ref[5].astype(np.float64) - job.w0
    assert _off(arrays.replies[5], ref[5], moved) <= TOL


@pytest.mark.parametrize("delay", [1, 0], ids=["delay-1", "lock-step"])
def test_the_eval_and_the_checkpoint_see_drained_weights(tmp_path, delay):
    """``test_interval`` 2 and ``checkpoint_interval`` 3 over six rounds:
    rank 0's evals and checkpoints find nothing in flight and read the
    weights after that round, bit for bit the reply to that round's push;
    the evals are counted as in lock step; under delay no round's weights
    move for them."""
    from distlr_tpu.train.checkpoint import Checkpointer

    cfg = _cfg(tmp_path, test_interval=2, checkpoint_interval=3,
               checkpoint_dir=str(tmp_path / "ckpt"), ps_max_delay=delay)
    evals = ["distlr_ps_evals_total", "distlr_ps_eval_rows_total"]
    with Job(cfg) as job:
        rank0 = job.workers[0]
        taps = [Lineage(w) for w in job.workers[1:]]
        arrays = Tap(rank0)
        seen = []
        pull = rank0.kv.pull

        def noted_pull(*a, **kw):
            got = pull(*a, **kw)
            seen.append((rank0.in_flight, len(arrays.replies), got))
            return got

        rank0.kv.pull = noted_pull
        counted = [family_total(s) for s in evals]
        ckpt = Checkpointer(cfg.checkpoint_dir)
        try:
            assert not job.all(lambda w: w.fit(
                epochs=6, ckpt=ckpt if w.rank == 0 else None,
                eval_fn=lambda *a: None))
            kept = {s: ckpt.restore(s)["weights"] for s in (3, 6)}
        finally:
            ckpt.close()
        lins = [t.remove() for t in taps]
        counted = [family_total(s) - c for s, c in zip(evals, counted)]
        test_rows = rank0._test.num_samples
    # the fit's opening pull; evals after rounds 2, 4, 6; checkpoints
    # after 3 and 6
    assert [(flying, n) for flying, n, _w in seen] == [
        (0, 0), (0, 2), (0, 3), (0, 4), (0, 6), (0, 6)]
    assert _bits(seen[0][2], job.w0)
    for _flying, n, w in seen[1:]:
        assert _bits(w, arrays.replies[n - 1])
    assert _bits(kept[3], arrays.replies[2]) and _bits(kept[6],
                                                       arrays.replies[5])
    assert counted == [3, 3 * test_rows]
    if delay:
        on = dense_ps_bsp_delay.computed_on(job.w0, arrays.replies)
        assert all(_bits(a, b) for a, b in zip(arrays.ran_on, on))
        assert all(lineage_broken(lin, 6) == 0 for lin in lins)


def test_a_worker_that_raises_mid_fit_leaves_its_push_to_close(tmp_path):
    with Job(_cfg(tmp_path)) as job:
        bad = job.workers[2]
        step, calls = bad.grad_step, []

        def third_round_fails(wf, batch):
            calls.append(bad.in_flight)
            if len(calls) == 4:
                raise RuntimeError("round 3 of rank 2")
            return step(wf, batch)

        bad.grad_step = third_round_fails
        errors = job.all(lambda w: w.fit(epochs=6))
        assert (2, "round 3 of rank 2") in [(r, str(e)) for r, e in errors]
        # its push of round 2 was out when it raised, and still is its
        # connection's: close(wait=False) hands it to the reaper
        assert calls == [0, 1, 1, 1] and bad.in_flight == 1
        for w in job.workers:
            w.close(wait=False)
        left = [t for t in threading.enumerate()
                if t.name.startswith(("ps-close-", "ps-comm-"))]
        for t in left:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in left)
        job.workers = []


@pytest.mark.parametrize("kw,says", [
    (dict(ps_max_delay=2), "must be 0 or 1"),
    (dict(ps_max_delay=-1), "must be 0 or 1"),
    (dict(ps_max_delay=1, sync_mode=False), "needs sync_mode"),
    (dict(ps_max_delay=1, model="sparse_lr"), "dense models"),
    (dict(ps_max_delay=1, model="blocked_lr"), "dense models"),
    (dict(ps_max_delay=1, model="sparse_softmax"), "dense models"),
    (dict(ps_max_delay=1, ps_pipeline=False), "needs ps_pipeline"),
    (dict(ps_max_delay=1, ps_accum_start=2, ps_accum_max=2),
     "ps_accum_max > 1"),
    (dict(ps_max_delay=1, sync_last_gradient=True), "sync_last_gradient"),
    (dict(ps_max_delay=1, compat_mode="reference"), "sync_last_gradient"),
    (dict(ps_max_delay=1, ps_compress="int8"), "ps_compress='int8'"),
    (dict(ps_max_delay=1, ps_compress="signsgd"), "ps_compress='signsgd'"),
], ids=lambda v: "-".join(f"{k}={x}" for k, x in v.items())
    if isinstance(v, dict) else None)
def test_config_refuses_what_the_delay_cannot_keep(kw, says):
    with pytest.raises(ValueError, match="ps_max_delay") as e:
        Config(**kw)
    assert says in str(e.value)


@pytest.mark.parametrize("kw", [
    dict(ps_max_delay=1), dict(ps_max_delay=1, model="softmax"),
    dict(ps_max_delay=0, sync_mode=False), dict(ps_max_delay=0,
                                                ps_compress="int8"),
], ids=lambda kw: "-".join(f"{k}={x}" for k, x in kw.items()))
def test_config_accepts_the_delay_on_the_dense_lock_step_job(kw):
    assert Config(**kw).ps_max_delay == kw["ps_max_delay"]


def test_the_launcher_hands_the_delay_to_the_config(monkeypatch, tmp_path):
    from distlr_tpu import launch

    got = []
    monkeypatch.setattr(ps_trainer, "run_ps_local",
                        lambda cfg, **kw: got.append(cfg))
    ps = ["ps", "--data-dir", str(tmp_path), "--cpu-devices", "1"]
    assert launch.main([*ps, "--ps-max-delay", "1"]) == 0
    assert launch.main(ps) == 0
    assert [cfg.ps_max_delay for cfg in got] == [1, 0]
    with pytest.raises(SystemExit):
        launch.main([*ps, "--ps-max-delay", "2"])
    with pytest.raises(ValueError, match="needs sync_mode"):
        launch.main([*ps, "--async", "--ps-max-delay", "1"])
    assert len(got) == 2


def test_without_the_delay_the_lock_step_job_is_bit_for_bit_what_it_was(
        tmp_path):
    """``ps_max_delay=0``: the exchange is the fused one, and two workers'
    lock-step rounds (two float32 gradients add to the same bits in
    either order) are the serialized protocol's, bit for bit."""
    runs = {}
    for name, kw in (("fused", {}), ("serialized", {"ps_pipeline": False})):
        cfg = _cfg(tmp_path / name, workers=2, ps_max_delay=0, **kw)
        with Job(cfg) as job:
            assert type(job.workers[0]._exchange()) is (
                ps_trainer._Fused if name == "fused"
                else ps_trainer._Serialized)
            taps = [Tap(w) for w in job.workers]
            assert not job.all(lambda w: w.fit(epochs=6))
            runs[name] = ([t.ran_on for t in taps], job.probe.pull(),
                          [w.in_flight for w in job.workers])
    fused, serial = runs["fused"], runs["serialized"]
    assert fused[2] == serial[2] == [0, 0]
    assert _bits(fused[1], serial[1])
    for a, b in zip(fused[0], serial[0]):
        assert len(a) == len(b) == 6
        assert all(_bits(x, y) for x, y in zip(a, b))
    # and it is lock step: round k on the weights after round k - 1
    w = runs["fused"][0][0]
    assert not _bits(w[1], w[0]) and not _bits(w[2], w[1])
