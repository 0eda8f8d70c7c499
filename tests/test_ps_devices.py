"""Which device a PS worker's dense step runs on: worker *i* of a process
takes local device ``i % len(devices)`` through ``run_ps_workers`` /
``run_ps_local`` (the suite's eight virtual CPU devices stand for chips),
its shard, weights and gradient live there, one device gives what there
was before, and a lock-step job over four devices follows the plain
reference's trajectory."""

import logging
import threading

import jax
import numpy as np
import pytest

from chipbench import datagen
from chipbench.families import dense_ps_bsp
from distlr_tpu.config import Config
from distlr_tpu.data.sharding import part_name
from distlr_tpu.data.synthetic import write_synthetic_shards
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.obs.tracing import get_tracer
from distlr_tpu.ps import KVWorker, ServerGroup
from distlr_tpu.train import ps_trainer
from distlr_tpu.train.ps_trainer import (
    PSWorker,
    ps_compute_device,
    ps_param_dim,
    run_ps_local,
    worker_devices,
)

DIM = 24


pytestmark = pytest.mark.usefixtures("ps_steps_on_device")


def _cfg(tmp_path, num_workers, **kw):
    d = str(tmp_path / f"job-{num_workers}")
    write_synthetic_shards(d, 64 * num_workers, DIM, num_parts=num_workers,
                           seed=5, sparsity=0.0)
    base = dict(data_dir=d, num_feature_dim=DIM, model="binary_lr",
                num_workers=num_workers, num_servers=2, sync_mode=True,
                batch_size=-1, num_iteration=3, learning_rate=0.2, l2_c=0.0,
                test_interval=0)
    return Config(**{**base, **kw})


def _group(cfg):
    return ServerGroup(cfg.num_servers, cfg.num_workers, ps_param_dim(cfg),
                       learning_rate=cfg.learning_rate, sync=cfg.sync_mode)


class _Pinned(logging.Handler):
    def __init__(self):
        super().__init__()
        self.lines = []

    def emit(self, record):
        if "dense steps pinned" in record.getMessage():
            self.lines.append(record.getMessage())


@pytest.fixture
def pinned():
    handler = _Pinned()
    logger = logging.getLogger(ps_trainer.__name__)
    logger.addHandler(handler)
    yield handler.lines
    logger.removeHandler(handler)


def _step_devices():
    return {int(labels[0]): int(child.value) for labels, child
            in get_registry().get("distlr_ps_step_device").children()}


def _in_threads(workers, call):
    errors = []

    def one(w):
        try:
            call(w)
        except Exception as e:  # noqa: BLE001  (surfaced below)
            errors.append(e)

    threads = [threading.Thread(target=one, args=(w,)) for w in workers]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors


def test_worker_i_of_a_process_takes_device_i_and_more_workers_wrap(
        monkeypatch):
    local = jax.local_devices()
    assert len(local) == 8  # the suite's virtual devices
    assert worker_devices(4) == local[:4]
    assert worker_devices(11) == [*local, *local[:3]]
    monkeypatch.setattr(jax, "local_devices", lambda: local[2:4])
    assert worker_devices(5) == [local[2], local[3], local[2], local[3],
                                 local[2]]
    monkeypatch.setattr(jax, "local_devices", lambda: local[:1])
    assert worker_devices(4) == [local[0]] * 4


def test_the_device_stands_where_the_choice_was_the_default_backend_only(
        monkeypatch, ps_steps_on):
    dev = jax.local_devices()[3]
    big = Config(num_feature_dim=1 << 20, batch_size=64)
    assert ps_compute_device(big) is None
    assert ps_compute_device(big, device=dev) is dev
    assert ps_compute_device(big.replace(batch_size=-1), device=dev) is dev
    small = Config(num_feature_dim=123, batch_size=64)
    # a step over both thresholds, whatever its size
    assert ps_compute_device(small, 64, device=dev) is dev
    # the thresholds keep deciding host or accelerator: under both ...
    with ps_steps_on("size"):
        assert ps_compute_device(small, 64, device=dev) == "numpy"
    with ps_steps_on("numpy"):
        assert ps_compute_device(big, 64, device=dev) == "numpy"
    # ... and between them, where the default backend is an accelerator
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with ps_steps_on("cpu"):
        cpu = ps_compute_device(small, 64, device=dev)
    assert cpu.platform == "cpu" and cpu is jax.devices("cpu")[0]


def test_four_workers_of_a_process_compute_on_four_devices(tmp_path, pinned):
    """Through ``run_ps_local``, no option: worker *r*'s shard, weights
    and gradient are on device *r*."""
    cfg = _cfg(tmp_path, 4)
    seen = {}
    real = PSWorker._bind_dense_step

    def watch(self, train, test):
        real(self, train, test)
        step = self.grad_step

        def grad_step(wf, batch):
            g = step(wf, batch)
            seen.setdefault(self.rank, set()).update(
                d.id for leaf in batch for d in leaf.devices())
            return g
        self.grad_step = grad_step

    with pytest.MonkeyPatch.context() as m:
        m.setattr(PSWorker, "_bind_dense_step", watch)
        out = run_ps_local(cfg)
    assert len(out) == 4 and all(np.array_equal(out[0], w) for w in out)
    ids = [d.id for d in jax.local_devices()[:4]]
    assert seen == {r: {ids[r]} for r in range(4)}
    assert _step_devices() == dict(enumerate(ids))
    assert len(pinned) == 4
    for r, line in enumerate(sorted(pinned)):
        assert line.startswith(f"rank {r} dense steps pinned: train -> cpu:")
        assert f"(id {ids[r]})" in line


def test_the_round_lives_on_the_workers_device(tmp_path):
    """``w_put``, the step and the readback: the arrays of a round are on
    the device the worker was handed, and on no other."""
    cfg = _cfg(tmp_path, 1, sync_mode=False)
    dev = jax.local_devices()[5]
    real = ps_trainer._compiled_fns
    on = []

    def spying(*a):
        fn = real(*a)

        def grad(w, X, y, mask, **how):
            g = fn(w, X, y, mask, **how)
            on.append([leaf.devices() for leaf in (w, X, y, mask, g)])
            return g
        grad._cache_size = fn._cache_size  # the worker's probe reads it
        return grad

    with _group(cfg) as group, pytest.MonkeyPatch.context() as m:
        m.setattr(ps_trainer, "_compiled_fns", spying)
        w = PSWorker(cfg, 0, group.hosts, device=dev)
        try:
            w.run(save=False)
            acc, ll = w.evaluate(w.final_weights)
        finally:
            w.close()
    assert len(on) == cfg.num_iteration
    assert all(devs == {dev} for round_ in on for devs in round_)
    assert all(leaf.devices() == {dev} for leaf in w._resident)
    assert w._eval_dev is dev and 0.0 <= acc <= 1.0 and np.isfinite(ll)
    assert _step_devices()[0] == dev.id


def test_one_device_is_as_before(tmp_path, pinned, monkeypatch):
    """A process with one device: every worker on it, the pinned line as
    it was, the loop's spans and ``distlr_ps_grad_rounds_total`` as a
    worker built with no device at all gives them."""
    first = jax.local_devices()[0]
    monkeypatch.setattr(jax, "local_devices", lambda: [first])
    rounds = get_registry().get("distlr_ps_grad_rounds_total")

    def counted():
        return {labels: child.value for labels, child in rounds.children()}

    def one_run(build):
        cfg = _cfg(tmp_path, 2)
        before, tracer = counted(), get_tracer()
        tracer.reset()
        del pinned[:]
        build(cfg)
        spans = {name: s["count"] for name, s in tracer.breakdown().items()}
        rise = {k: v - before.get(k, 0) for k, v in counted().items()
                if v != before.get(k, 0)}
        return sorted(pinned), spans, rise

    def bare(cfg):  # what run_ps_workers did before it handed out devices
        with _group(cfg) as group:
            workers = [PSWorker(cfg, r, group.hosts) for r in range(2)]
            try:
                _in_threads(workers, lambda w: w.run(save=False))
            finally:
                for w in workers:
                    w.close()

    lines, spans, rise = one_run(lambda cfg: run_ps_local(cfg))
    lines0, spans0, rise0 = one_run(bare)
    assert lines == lines0 and len(lines) == 2
    assert all(f"train -> cpu:cpu (id {first.id})" in ln for ln in lines)
    assert spans == spans0 and spans["compute"] == 2 * 3
    assert rise == rise0 == {("0", "two_pass"): 3, ("1", "two_pass"): 3}
    assert _step_devices()[0] == _step_devices()[1] == first.id


def test_a_four_device_bsp_fit_follows_the_reference_round(tmp_path):
    """Four lock-step workers over four devices on seeded rows, against
    ``chipbench/families/dense_ps_bsp.round``: every worker sees the same
    weights bit for bit, and the trajectory is the reference's inside the
    rehearsal's limits (XLA's CPU program rounds to bfloat16)."""
    dim, n, workers, rounds, lr = 4096, 32, 4, 4, 0.2
    cols, vals, y = datagen.make_rows(
        77, "train", workers * n, fields="criteo-kaggle", num_buckets=dim,
        label_scale=0.5, label_bias=-1.0)
    shards = [tuple(a[r * n:(r + 1) * n] for a in (cols, vals, y))
              for r in range(workers)]
    d = tmp_path / "rows"
    for r, shard in enumerate(shards):
        datagen.write_libsvm(str(d / "train" / part_name(r)), *shard)
    datagen.write_libsvm(str(d / "test" / part_name(0)), *shards[0])
    cfg = Config(data_dir=str(d), num_feature_dim=dim, model="binary_lr",
                 num_workers=workers, num_servers=2, sync_mode=True,
                 batch_size=-1, num_iteration=rounds, learning_rate=lr,
                 l2_c=0.0, test_interval=0)
    w0 = (np.random.default_rng(3).standard_normal(dim) * 0.01).astype(
        np.float32)
    seen = {r: [] for r in range(workers)}
    real = PSWorker._bind_dense_step

    def record(self, train, test):
        real(self, train, test)
        step = self.grad_step

        def grad_step(wf, batch):
            seen[self.rank].append(np.array(wf))
            return step(wf, batch)
        self.grad_step = grad_step

    group = ps_trainer.server_group(cfg)
    with group, pytest.MonkeyPatch.context() as m, \
            KVWorker(group.hosts, dim, client_id=0xFC00) as probe:
        m.setattr(PSWorker, "_bind_dense_step", record)
        probe.wait(probe.push_init(w0))
        out = ps_trainer.run_ps_workers(cfg, group.hosts, range(workers))
    assert _step_devices() == {r: dev.id for r, dev
                               in enumerate(jax.local_devices()[:workers])}
    # weights_disagree 0: round k on the same bits everywhere
    for k in range(rounds):
        assert all(np.array_equal(seen[0][k].view(np.uint32),
                                  seen[r][k].view(np.uint32))
                   for r in range(1, workers))
    assert np.array_equal(seen[0][0], w0)
    w_ref = w0
    for k in range(rounds):
        w_next = dense_ps_bsp.round(w_ref, shards, lr)
        got = (seen[0][k + 1] if k + 1 < rounds else out[0]).astype(np.float64)
        u, u_ref = got - seen[0][k], w_next.astype(np.float64) - w_ref
        n_ref = np.linalg.norm(u_ref)
        assert abs(np.linalg.norm(u) - n_ref) <= 0.01 * n_ref     # update_norm_rel_gap
        assert np.linalg.norm(u - u_ref) <= 0.02 * n_ref           # update_diff_rel
        w_ref = w_next
