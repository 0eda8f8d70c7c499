"""The keyed sparse PS cell under FTRL-Proximal servers: its plain
reference (Algorithm 1 against the paper's lines in float64 and against
the native servers bit for bit), the whole runs that must not be
``correct`` (each by the row that names its fault), its configuration,
its per-layer readers and the place of its entries in ``BENCHMARK.json``."""

import copy
import dataclasses
import importlib
import json
import math

import numpy as np
import pytest

from chipbench import datagen, manifest, reference, run
from chipbench.drivers import ps_keyed_epochs as sibling_driver
from chipbench.drivers import ps_keyed_ftrl_epochs as driver
from chipbench.families import sparse_ps_keyed, sparse_ps_keyed_ftrl

CELL = "sparse-ps-async-keyed-ftrl-1chip"
CONFIG = "criteo-ps-async-keyed-ftrl-1m"
SIBLING = "sparse-ps-async-keyed-1chip"
READERS = ["kf_round_ms", "kf_pull_ms", "kf_push_ms", "kf_server_apply_ms",
           "kf_server_lock_wait_ms", "kf_ftrl_ns_per_step",
           "kf_launch_wait_ms"]
#: the accepted metrics with no ``workloads`` list: read in every cell
LIST_LESS = ["compile_s", "input_wait_share", "step_ms", "step_hbm_roofline"]
ROWS = ["grad_norm_rel_gap", "grad_diff_rel", "replay_rel",
        "pulled_stale_rel", "n_conservation_rel", "update_missing",
        "steps_miscount", "unacknowledged_recorded", "closed_form_rel",
        "zeros_mismatch", "untouched_moved", "no_opt_state",
        "test_logloss_rel_gap", "unacknowledged_window", "keys_mismatch",
        "window_rows_short", "dense_frames", "resident_short", "host_steps"]
WARM, RECORDED, PACE = 1, 1, 1  # the traffic file's epochs before the window
RULE = dict(alpha=0.1, beta=1.0, l1=3e-3, l2=0.25)
F32 = np.float32


def _rehearse(capsys, *extra):
    rc = run.main(["--workload", CELL, "--seed", "3100000053", "--seconds",
                   "0.2", "--trace", "0", "--rehearse", *extra])
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert rc == 0 and last.startswith("REHEARSAL ")
    return json.loads(last[len("REHEARSAL "):]), out


def _bad(doc):
    return {r["name"] for r in doc["compared"] if not r["ok"]}


# -- the reference: Algorithm 1 ------------------------------------------------
def _paper(w, z, n, g, alpha, beta, l1, l2):
    """One coordinate, the paper's four lines in Python's float64."""
    sigma = (math.sqrt(n + g * g) - math.sqrt(n)) / alpha
    z = z + g - sigma * w
    n = n + g * g
    if abs(z) <= l1:
        return 0.0, z, n
    return (-(z - math.copysign(l1, z)) / ((beta + math.sqrt(n)) / alpha + l2),
            z, n)


def test_a_step_is_the_papers_four_lines_to_float32s_rounding():
    rng = np.random.default_rng(2)
    w = (rng.standard_normal(500) * 0.02).astype(F32)
    z = (rng.standard_normal(500) * 0.01).astype(F32)
    n = (rng.random(500) * 1e-3).astype(F32)
    g = (rng.standard_normal(500) * 5e-3).astype(F32)
    g[::7] = 0.0
    got = sparse_ps_keyed_ftrl.ftrl_step(w, z, n, g, **RULE)
    assert all(a.dtype == F32 for a in got)
    for i in range(500):
        if g[i] == 0:  # nothing changes, in any bit
            assert [a[i].tobytes() for a in got] == [
                a[i].tobytes() for a in (w, z, n)]
            continue
        want = _paper(float(w[i]), float(z[i]), float(n[i]), float(g[i]),
                      **RULE)
        for a, b, scale in zip(got, want, (1e-2, 1e-2, 1e-3)):
            assert abs(float(a[i]) - b) <= 4e-7 * max(abs(b), scale)
        assert (got[0][i] == 0) == (want[0] == 0.0)
    assert 0 < int((got[0] == 0).sum()) < 400


@pytest.mark.parametrize("z,n,want", [
    # |z| = l1 exactly, from either side: inside, an exact zero
    (3e-3, 0.5, 0.0), (-3e-3, 0.5, 0.0), (0.0, 0.0, 0.0), (2.9e-3, 0.0, 0.0),
    # a key whose n is still 0 (its squares underflowed): the rate is
    # alpha / beta, -(z - sgn(z) l1) / (beta / alpha + l2)
    (1.0, 0.0, -(1.0 - 3e-3) / (1.0 / 0.1 + 0.25)),
    (-1.0, 0.0, (1.0 - 3e-3) / (1.0 / 0.1 + 0.25)),
    # one float32 past the edge: no longer zero, and of z's other sign
    (float(np.nextafter(F32(3e-3), F32(1))), 4.0, None),
    (-float(np.nextafter(F32(3e-3), F32(1))), 4.0, None),
], ids=["at-l1", "at-minus-l1", "never-stepped", "under-l1-n-0", "n-0",
        "n-0-negative", "just-over-l1", "just-under-minus-l1"])
def test_the_closed_form_at_its_edges(z, n, want):
    got = sparse_ps_keyed_ftrl.closed_form(
        np.array([z], F32), np.array([n], F32), **RULE)
    assert got.dtype == F32 and got.shape == (1,)
    if want is None:
        assert got[0] != 0 and np.sign(got[0]) == -np.sign(z)
        assert abs(got[0]) < 1e-9
    elif want == 0.0:
        assert got[0].tobytes() == F32(0.0).tobytes()  # +0.0, not -0.0
    else:
        assert got[0] == pytest.approx(want, rel=2e-7)


def _frames(seed, workers, rounds, dim, keys_a_frame):
    """Scattered keyed frames of several workers, interleaved as they
    would arrive: keys come again (within a worker and across them) and a
    share of the entries is exactly zero."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(rounds):
        for _w in range(workers):
            keys = np.sort(rng.choice(dim, size=keys_a_frame,
                                      replace=False)).astype(np.uint64)
            g = (rng.standard_normal(keys_a_frame) * 4e-3).astype(F32)
            g[rng.random(keys_a_frame) < 0.1] = 0.0
            out.append((keys, g))
    return out


def test_the_replay_leaves_what_the_native_servers_leave_bit_for_bit():
    from distlr_tpu.ps import KVWorker, ServerGroup

    dim, workers = 3000, 3
    frames = _frames(17, workers, 30, dim, 700)
    assert len(np.unique(np.concatenate([k for k, _g in frames]))) < sum(
        len(k) for k, _g in frames) / 5  # every key comes many times
    with ServerGroup(2, workers, dim, sync=False, optimizer="ftrl",
                     ftrl_alpha=RULE["alpha"], ftrl_beta=RULE["beta"],
                     ftrl_l1=RULE["l1"], ftrl_l2=RULE["l2"]) as g:
        kvs = [KVWorker(g.hosts, dim, client_id=r) for r in range(workers)]
        try:
            kvs[0].wait(kvs[0].push_init(np.zeros(dim, F32)))
            pulled = []
            for k, (keys, vals) in enumerate(frames):
                kv = kvs[k % workers]
                pulled.append(kv.pull(keys=keys))
                kv.wait(kv.push(vals, keys=keys))
            w = kvs[0].pull()
        finally:
            for kv in kvs:
                kv.close()
        z, n = [], []
        for r in range(2):
            lo, hi = g.key_range(r)
            with KVWorker(f"127.0.0.1:{g.ports[r]}", hi - lo, client_id=9,
                          sync_group=False) as one:
                zr, nr = one.pull_opt_state()
            z.append(zr)
            n.append(nr)
    z, n = np.concatenate(z), np.concatenate(n)
    zeros = np.zeros(dim, F32)
    (w_r, z_r, n_r), stood = sparse_ps_keyed_ftrl.replay(
        frames, zeros, zeros, zeros, **RULE)
    for got, want in ((w, w_r), (z, z_r), (n, n_r)):
        assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    # a pull saw every push before it and none after
    for got, want in zip(pulled, stood):
        assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    assert np.array_equal(
        sparse_ps_keyed_ftrl.closed_form(z, n, **RULE).view(np.uint32),
        w.view(np.uint32))
    stepped = n > 0
    assert 0.1 < (w[stepped] == 0).mean() < 0.9
    assert np.array_equal(zeros, zeros * 0)  # the tables handed in are whole


@pytest.fixture(scope="module")
def shard():
    cols, vals, y = datagen.make_rows(
        91, "train", 600, fields="criteo-kaggle", num_buckets=2048,
        label_scale=0.5, label_bias=-1.0)
    w = np.random.default_rng(5).standard_normal(2048).astype(F32) * 0.05
    w[::3] = 0.0  # as L1 leaves them
    return w, cols, vals, y


def test_the_window_is_the_rule_worked_out():
    win = sparse_ps_keyed_ftrl.window
    assert sparse_ps_keyed_ftrl.rounds_an_epoch(600, 256) == 3
    assert [win(k, 600, 256) for k in range(4)] == [
        slice(0, 256), slice(256, 512), slice(512, 600), slice(0, 256)]
    assert win(240, 3932160, 16384) == slice(0, 16384)


def test_the_gradient_is_the_keyed_part_of_numpys_in_float64(shard):
    w, cols, vals, y = shard
    at = slice(256, 512)
    u = sparse_ps_keyed_ftrl.keys(cols[at])
    X = np.zeros((256, 2048))
    np.add.at(X, (np.arange(256)[:, None], cols[at]), vals[at])
    full = np.zeros(2048)
    full[u] = w[u]
    z = X @ full
    want = (X.T @ (1.0 / (1.0 + np.exp(-z)) - y[at]) / 256)[u]
    got = sparse_ps_keyed_ftrl.gradient(w[u], cols[at], vals[at], y[at])
    assert got.dtype == F32 and got.shape == u.shape
    assert np.linalg.norm(got - want) <= 2e-6 * np.linalg.norm(want)
    # its own copy computes what the sibling family's computes
    theirs = sparse_ps_keyed.gradient(w[u], cols[at], vals[at], y[at])
    assert np.linalg.norm(got - theirs) <= 1e-6 * np.linalg.norm(theirs)
    low = sparse_ps_keyed_ftrl.gradient(w[u], cols[at], vals[at], y[at],
                                        precision="bfloat16")
    assert np.linalg.norm(low - got) > 1e-4 * np.linalg.norm(got)


def test_the_familys_step_is_a_gradient_and_one_push_of_the_rule(shard):
    w, cols, vals, y = shard
    rule = dict(alpha=0.1, beta=1.0, l1=1e-3)
    z, n = np.zeros(2048, F32), np.zeros(2048, F32)
    loss, after = sparse_ps_keyed_ftrl.step(
        w, cols[:256], vals[:256], y[:256], 0.2, 0.0, state=(z, n), **rule)
    u = sparse_ps_keyed_ftrl.keys(cols[:256])
    g = sparse_ps_keyed_ftrl.gradient(w[u], cols[:256], vals[:256], y[:256])
    (w_r, z_r, n_r), _ = sparse_ps_keyed_ftrl.replay(
        [(u, g)], w, np.zeros(2048), np.zeros(2048), l2=0.0, **rule)
    assert np.array_equal(np.asarray(after), w_r)
    assert np.array_equal(z, z_r) and np.array_equal(n, n_r)
    rest = np.setdiff1d(np.arange(2048), u)
    assert np.array_equal(np.asarray(after)[rest], w[rest])
    assert float(loss) == pytest.approx(float(sparse_ps_keyed.step(
        w, cols[:256], vals[:256], y[:256], 0.2, 0.0)[0]), rel=1e-6)
    acc, ll = sparse_ps_keyed_ftrl.evaluate(w, cols, vals, y)
    assert 0.0 <= acc <= 1.0
    assert ll == pytest.approx(
        reference.logloss("sparse_ps_keyed_ftrl", w, cols, vals, y), rel=1e-6)


def test_the_family_states_its_precision_and_uses_nothing_of_the_program():
    with open(sparse_ps_keyed_ftrl.__file__) as f:
        text = f.read()
    assert "distlr_tpu" not in text and "test_ftrl" not in text
    assert "ftrl_oracle" not in text
    assert "import sparse_ps_keyed" not in text
    assert "families import" not in text and "families." not in text
    assert 'jax.default_matmul_precision("highest")' in text
    assert "KDD 2013" in text and "Algorithm 1" in text
    assert reference.family("sparse_ps_keyed_ftrl") is sparse_ps_keyed_ftrl
    floor = sparse_ps_keyed_ftrl.step_bytes_floor(
        rows=16384, nnz=16384 * 39, keys=88000, dim=1000000)
    assert floor == 16384 * 39 * 8 + 2 * 88000 * 4 + 16384 * 4
    assert floor == sparse_ps_keyed.step_bytes_floor(
        rows=16384, nnz=16384 * 39, keys=88000, dim=1000000)


# -- the configuration --------------------------------------------------------
def test_the_configuration_states_what_it_is_and_what_it_cut():
    bench = manifest.load_benchmark()
    cell = manifest.Cell(bench, CELL)
    conf, traffic = cell.config, cell.traffic
    sibling = manifest.Cell(bench, SIBLING).config
    assert conf["reduced"] == ["train_rows", "test_rows", "num_iteration"]
    assert set(conf["reduced_why"]) == set(conf["reduced"])
    assert "No width is cut" in conf["reduced_why"]["train_rows"]
    assert conf["architecture"] is None
    assert conf["family"] == "sparse_ps_keyed_ftrl"
    assert conf["control"]["program"] == {"ps_compress": "int8"}
    assert conf["control"]["precision"] == "bfloat16"
    assert len(conf["guarantees"]) == 9
    for said, where in (("exactly once", 0), ("a zero entry steps nothing", 0),
                        ("sum of the squares", 1), ("closed form", 2),
                        ("exactly 0.0", 2), ("known order", 3),
                        ("exactly the keys asked", 4)):
        assert said in conf["guarantees"][where]
    for said in ("ftrl_l1", "ftrl_alpha_beta_l2", "mean_scale",
                 "initial_weights", "from_memory", "batch_size", "rows"):
        assert said in conf["assumed"]
    assert "MEAN" in conf["assumed"]["mean_scale"]
    assert "zero" in conf["assumed"]["initial_weights"]
    for said in ("KDD 2013", "Algorithm 1", "sigma_i", "lambda_1",
                 "OSDI 2014", "section 5.1", "SYNC_MODE=0", "FROM MEMORY"):
        assert said in conf["source_says"]
    # everything as the sibling but the rule and the start
    prog, gen = conf["program"], conf["generator"]
    rule = {"ps_optimizer": "ftrl", "ftrl_alpha": 0.1, "ftrl_beta": 1.0,
            "ftrl_l2": 0.0, "ftrl_l1": prog["ftrl_l1"]}
    assert prog == {**sibling["program"], **rule}
    assert 0 < prog["ftrl_l1"] < 1e-2
    assert gen == sibling["generator"]
    assert gen["rows_per_worker"] == 240 * 16384
    # the share of exact zeros the value was chosen for, and its reading
    said = conf["assumed"]["ftrl_l1"]
    assert repr(prog["ftrl_l1"]) in said or f"{prog['ftrl_l1']:g}" in said
    assert "chip" in said and "%" in said
    # the counts admit 0 only
    for name in ("unacknowledged_pushes", "keys_mismatch", "steps_miscount",
                 "window_rows_short", "dense_frames", "resident_short",
                 "host_steps", "update_missing", "zeros_mismatch",
                 "untouched_moved", "no_opt_state"):
        assert conf["limits"][name] == 0.5
    assert set(conf["limits"]) == (set(ROWS) - {
        "unacknowledged_recorded", "unacknowledged_window"}) | {
        "unacknowledged_pushes"}
    for name in conf["limits"]:
        assert name in conf["limits_from"], name
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] and len(conf["source"]) <= 200
    assert "KDD 2013" in conf["source"] and "OSDI 2014 5.1" in conf["source"]
    assert entry["reduced"] == conf["reduced"]
    # what stays on the chip: over the floor, a quarter of its memory
    resident = prog["num_workers"] * sibling_driver.shard_bytes(
        gen["rows_per_worker"], prog["batch_size"], 39)
    assert round(resident / 1e9, 2) == 5.03
    assert resident >= 0.25 * 16 * 2**30
    assert "TBD" not in json.dumps(conf)
    assert traffic["kind"] == "ps_keyed_ftrl_epochs"
    assert (traffic["warm_epochs"], traffic["recorded_epochs"],
            traffic["checked_rounds"], traffic["pace_epochs"]) == (
        WARM, RECORDED, 3, PACE)


def test_the_program_takes_the_configuration_as_it_is_written():
    from distlr_tpu import Config
    from distlr_tpu.train import ps_trainer

    conf = manifest.Cell(manifest.load_benchmark(), CELL).config
    cfg = Config(data_dir="nowhere", test_interval=0, **conf["program"])
    assert cfg.model == "sparse_lr" and not cfg.sync_mode
    assert ps_trainer.server_optimizer(cfg) == "ftrl"
    group = ps_trainer.server_group(cfg)
    assert group.has_ftrl and group.num_servers == 2
    assert driver.rule_of(conf["program"]) == {
        "alpha": cfg.ftrl_alpha, "beta": cfg.ftrl_beta, "l1": cfg.ftrl_l1,
        "l2": cfg.ftrl_l2}
    small = {**conf["program"], **conf["rehearsal"]["program"]}
    # the mean of 512 rows is on a larger scale than the mean of 16,384
    assert small["ftrl_l1"] > conf["program"]["ftrl_l1"]


# -- whole runs ----------------------------------------------------------------
def test_the_rehearsal_is_correct_and_names_every_new_metric(capsys):
    doc, out = _rehearse(capsys)
    assert doc["correct"] is True, out
    # the trace's one (kf_launch_wait_ms) has nothing to read untraced
    assert set(READERS) - {"kf_launch_wait_ms"} <= set(doc["layer_metrics"])
    assert {"compile_s", "input_wait_share", "step_ms"} <= set(
        doc["layer_metrics"])
    assert not {m for m in doc["layer_metrics"] if m.startswith("kx_")}
    assert [r["name"] for r in doc["compared"]] == ROWS
    assert "optimizer=ftrl" in out and "exact_zero_share=" in out
    assert "dense_frames=0" in out and "ftrl_steps=" in out
    assert out.count("a window of the resident localised shard") >= 4
    # L1 left exact zeros among the keys the warm-up epoch stepped
    share = float(out.split("exact_zero_share=")[1].split()[0])
    assert 0.05 < share < 0.95


def _with_program(monkeypatch, over):
    real = driver.effective_config

    def changed(cell, rehearsal):
        conf = copy.deepcopy(real(cell, rehearsal))
        conf["program"].update(over(conf))
        return conf

    monkeypatch.setattr(driver, "effective_config", changed)


def _the_nth_keyed_push(monkeypatch, nth, fault):
    """``fault(real_push, self, vals, keys, **kw)`` in the place of every
    worker connection's ``nth`` keyed push (from 0), under the tap: the
    tap keeps what the worker meant to push."""
    from distlr_tpu.ps import KVWorker

    real = KVWorker.push
    seen: dict = {}

    def push(self, vals, keys=None, **kw):
        if keys is not None:
            seen[id(self)] = seen.get(id(self), -1) + 1
            if seen[id(self)] == nth:
                return fault(real, self, vals, keys, **kw)
        return real(self, vals, keys=keys, **kw)

    monkeypatch.setattr(KVWorker, "push", push)


#: a worker's rounds an epoch at the rehearsal's size
PER = 3
#: round 0 of the recorded epoch (the serial prefix), and round 1
PREFIX, FREE = WARM * PER, WARM * PER + 1


def _a_push_dropped(monkeypatch):
    """The worker is told its push went out, and nothing did: the frame
    the servers see is of the same keys and steps none of them."""
    _the_nth_keyed_push(
        monkeypatch, PREFIX,
        lambda real, kv, vals, keys, **kw: real(
            kv, np.zeros_like(vals), keys=keys, **kw))


def _a_push_applied_twice(monkeypatch):
    def twice(real, kv, vals, keys, **kw):
        kv.wait(real(kv, vals, keys=keys, **kw))
        return real(kv, vals, keys=keys, **kw)

    _the_nth_keyed_push(monkeypatch, FREE, twice)


def _servers_on_sgd(monkeypatch):
    _with_program(monkeypatch, lambda conf: {"ps_optimizer": "sgd"})


def _l1_ignored(monkeypatch):
    """The servers are spawned with no L1; the job believes the
    configuration's."""
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.server_group
    monkeypatch.setattr(
        ps_trainer, "server_group",
        lambda cfg: real(dataclasses.replace(cfg, ftrl_l1=0.0)))


def _z_stepped_without_w(monkeypatch):
    """When the recorded phase ends a server holds a z that moved on
    without its w (seeded so through the opt-state op): the state that
    is read is the servers' own."""
    real = driver.state
    calls = []

    def state(job):
        calls.append(1)
        if len(calls) == 3 + 1:  # warm-up, before, held, after
            one = job.ranks[0]
            z, n = one.pull_opt_state()
            moved = np.where(n > 0, z + np.float32(5e-3), z)
            one.wait(one.push_init_opt_state(moved, n, force=True))
        return real(job)

    monkeypatch.setattr(driver, "state", state)


def _a_stale_pull(monkeypatch):
    """A keyed pull answers with what the same keys held an epoch ago."""
    from distlr_tpu.ps import KVWorker

    real = KVWorker.pull
    kept: dict = {}

    def pull(self, keys=None, **kw):
        got = real(self, keys=keys, **kw)
        if keys is None:
            return got
        tag = (id(self), np.asarray(keys).tobytes())
        old, kept[tag] = kept.get(tag), np.array(got)
        return got if old is None else old

    monkeypatch.setattr(KVWorker, "pull", pull)


def _a_key_outside_the_window(monkeypatch):
    """A worker's second recorded round pushes one key more than its
    window holds: over the tap, as the worker's own exchange sends it."""
    from distlr_tpu.train import ps_trainer

    real = ps_trainer._Serialized.push
    seen: dict = {}

    def push(self, g, keys):
        rank = self.w.rank
        seen[rank] = seen.get(rank, -1) + 1
        if seen[rank] == FREE:
            extra = np.setdiff1d(np.arange(len(keys) + 1), keys)[:1]
            at = np.searchsorted(keys, extra)
            keys = np.insert(keys, at, extra.astype(keys.dtype))
            g = np.insert(g, at, np.float32(1e-3))
        return real(self, g, keys)

    monkeypatch.setattr(ps_trainer._Serialized, "push", push)


def _the_int8_wire(monkeypatch):
    _with_program(monkeypatch, lambda conf: conf["control"]["program"])


GRADIENTS = {"grad_norm_rel_gap", "grad_diff_rel"}
RULE_ROWS = {"closed_form_rel", "zeros_mismatch", "untouched_moved",
             "no_opt_state"}
COUNTS = {"keys_mismatch", "window_rows_short", "dense_frames",
          "resident_short", "host_steps"}
ACKED = {"unacknowledged_recorded", "unacknowledged_window"}


@pytest.mark.parametrize("fault,must_fail,must_hold", [
    (_a_push_dropped, {"replay_rel", "n_conservation_rel", "steps_miscount"},
     GRADIENTS | RULE_ROWS | COUNTS | ACKED),
    # the client counts both frames as acknowledged, as the servers do:
    # what tells is n, the count of steps, and the bytes sent
    (_a_push_applied_twice, {"n_conservation_rel", "steps_miscount"},
     GRADIENTS | RULE_ROWS | ACKED | (COUNTS - {"dense_frames"})
     | {"replay_rel", "pulled_stale_rel"}),
    (_servers_on_sgd,
     {"no_opt_state", "n_conservation_rel", "update_missing",
      "steps_miscount", "replay_rel"},
     GRADIENTS | COUNTS | ACKED),
    (_l1_ignored, {"closed_form_rel", "zeros_mismatch", "replay_rel"},
     GRADIENTS | COUNTS | ACKED | {"n_conservation_rel", "steps_miscount",
                                   "untouched_moved", "no_opt_state"}),
    (_z_stepped_without_w, {"closed_form_rel"},
     GRADIENTS | COUNTS | ACKED | {"replay_rel", "pulled_stale_rel",
                                   "n_conservation_rel", "steps_miscount",
                                   "untouched_moved", "no_opt_state"}),
    (_a_stale_pull, {"pulled_stale_rel"},
     GRADIENTS | RULE_ROWS | COUNTS | ACKED | {"replay_rel",
                                               "n_conservation_rel",
                                               "steps_miscount"}),
    (_a_key_outside_the_window, {"keys_mismatch"},
     RULE_ROWS | ACKED | {"replay_rel", "pulled_stale_rel",
                          "n_conservation_rel", "steps_miscount",
                          "dense_frames", "resident_short", "host_steps"}),
    # sound gradients of the right keys; the servers square and sum
    # something else
    (_the_int8_wire, {"n_conservation_rel"},
     GRADIENTS | RULE_ROWS | COUNTS | ACKED),
], ids=["push-dropped", "push-applied-twice", "servers-on-sgd", "l1-ignored",
        "z-stepped-without-w", "stale-pull", "key-outside-the-window",
        "int8-wire"])
def test_a_faulted_run_is_not_correct(capsys, monkeypatch, fault, must_fail,
                                      must_hold):
    fault(monkeypatch)
    doc, out = _rehearse(capsys)
    assert doc["correct"] is False
    assert must_fail <= _bad(doc), out
    assert not must_hold & _bad(doc), out


def test_a_program_without_the_two_counters_leaves_at_once(monkeypatch):
    """What the parent of the PR that added the cell does: its servers
    count no FTRL step, so the driver says so and makes no row."""
    monkeypatch.setattr(driver, "FTRL_STATS", ("ftrl_steps", "no_such_slot"))
    monkeypatch.setattr(driver, "prepare", None)  # never reached
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.2",
                  "--trace", "0", "--rehearse"])
    assert e.value.code not in (0, None)
    assert "no_such_slot" in str(e.value.code)
    assert "ftrl_steps" not in str(e.value.code).split("kStats")[1].split(
        ":")[0]


def test_the_control_tool_reads_all_three_sides(capsys):
    rc = driver.main(["--workload", CELL, "--seeds", "21,22",
                      "--controls", "2", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out.strip().splitlines()[-1][len("CONTROL "):])
    got = doc["summary"]
    # the int8 wire: n's conservation, and nothing of the gradients
    row = got["n_conservation_rel"]
    assert row["sound_max"] < row["limit"] < row["control_min"]
    for name in GRADIENTS:
        assert got[name]["sound_max"] < got[name]["limit"] < got[name][
            "bfloat16_min"], name
        assert got[name]["control_min"] < got[name]["limit"]
    # the reference in bfloat16 stands in the gradients' place only
    for name in ("replay_rel", "n_conservation_rel", "closed_form_rel",
                 "pulled_stale_rel"):
        assert got[name]["bfloat16_min"] <= got[name]["sound_max"] < got[
            name]["limit"]
    # neither control moves a key, a row, a frame or a step's place
    for name in COUNTS | {"unacknowledged_recorded", "zeros_mismatch",
                          "untouched_moved", "no_opt_state"}:
        assert got[name]["sound_max"] == got[name]["control_min"] == got[
            name]["bfloat16_min"] == 0, name
    # what ftrl_l1 is chosen from, a reading a program run
    assert [s["seed"] for s in doc["l1"]] == [21, 22]
    for s in doc["l1"]:
        assert 0 < s["exact_zero_share"] < 1 and s["keys_stepped"] > 0
        q1, q2, q3 = s["abs_z_quartiles"]
        assert 0 < q1 <= q2 <= q3


def test_the_tap_holds_round_0_to_rank_order_and_sums_the_squares():
    import threading
    import time

    order, lock = [], threading.Lock()

    class KV:
        def __init__(self, rank):
            self.rank = rank

        def pull(self, keys=None, **kw):
            with lock:
                order.append(("pull", self.rank))
            return np.zeros(len(keys), np.float32)

        def push(self, vals, keys=None, **kw):
            with lock:
                order.append(("push", self.rank))
            return 7

        def wait(self, ts):
            assert ts == 7
            time.sleep(0.01)
            with lock:
                order.append(("acked", self.rank))

    held_at = []
    workers = []
    for r in range(3):
        w = type("W", (), {})()
        w.kv, w.rank = KV(r), r
        workers.append(w)
    turns = [threading.Event() for _ in workers]
    turns[0].set()
    held = threading.Barrier(3, action=lambda: held_at.append(len(order)))
    taps = [driver.OrderedTap(w, 2, 16, turns, held) for w in workers]

    def rounds(w):
        for k in range(2):
            keys = np.array([1, 4 + w.rank, 9], np.uint64)
            w.kv.pull(keys=keys)
            w.kv.wait(w.kv.push(np.array([0.5, 0.0, -2.0], np.float32),
                                keys=keys))

    threads = [threading.Thread(target=rounds, args=(w,))
               for w in reversed(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    for t in taps:
        t.remove()
    assert all(not vars(w.kv).keys() & {"pull", "push", "wait"}
               for w in workers)
    # round 0: a worker's pull after the acknowledgement before it
    assert order[:9] == [(what, r) for r in range(3)
                         for what in ("pull", "push", "acked")]
    assert held_at == [9]  # read with all three held, nothing in flight
    for r, t in enumerate(taps):
        assert (t.rounds, t.keys_moved, t.nonzero, t.acked) == (2, 6, 4, 2)
        want = np.zeros(16)
        want[1], want[9] = 2 * 0.25, 2 * 4.0
        assert np.array_equal(t.squares, want)
        assert len(t.pulls) == len(t.pushes) == 2


def test_a_state_is_held_to_the_rule_key_by_key():
    rule = dict(alpha=0.1, beta=1.0, l1=1e-3, l2=0.0)
    rng = np.random.default_rng(4)
    n = np.where(rng.random(200) < 0.7, rng.random(200) * 1e-4, 0).astype(F32)
    z = np.where(n > 0, rng.standard_normal(200) * 2e-3, 0).astype(F32)
    w = sparse_ps_keyed_ftrl.closed_form(z, n, **rule)
    sound = driver.held_to_the_rule({"w": w, "z": z, "n": n},
                                    sparse_ps_keyed_ftrl, rule)
    assert sound == {"closed_form_rel": 0.0, "zeros_mismatch": 0,
                     "untouched_moved": 0}
    stepped = np.nonzero(n > 0)[0]
    zeroed = stepped[w[stepped] == 0]
    assert 0 < len(zeroed) < len(stepped)
    # a weight that should be zero and is not; one that is and should not
    bad = w.copy()
    bad[zeroed[0]] = 1e-6
    live = stepped[w[stepped] != 0][0]
    bad[live] = 0.0
    got = driver.held_to_the_rule({"w": bad, "z": z, "n": n},
                                  sparse_ps_keyed_ftrl, rule)
    assert got["zeros_mismatch"] == 2 and got["closed_form_rel"] > 1e-4
    # a key never stepped that holds a weight
    bad = w.copy()
    bad[np.nonzero(n == 0)[0][0]] = 0.5
    got = driver.held_to_the_rule({"w": bad, "z": z, "n": n},
                                  sparse_ps_keyed_ftrl, rule)
    assert got == {"closed_form_rel": 0.0, "zeros_mismatch": 0,
                   "untouched_moved": 1}
    share, count = driver.zero_share({"w": w, "n": n})
    assert count == len(stepped) and share == len(zeroed) / len(stepped)


# -- the per-layer readers -------------------------------------------------
def _run(**over):
    spans = {name: {"seconds": s, "count": 400, "self_seconds": s}
             for name, s in (("pull", 0.8), ("push", 1.2), ("compute", 1.6))}
    base = {"window": {"wall_s": 4.0, "spans": spans},
            "kf": {"rounds_per_worker": 400, "rounds": 1600,
                   "keys": 1600 * 88000, "server_pushes": 3200,
                   "server_merge_s": 6.4, "lock_wait_s": 1.6,
                   "ftrl_steps": 3200 * 40000, "ftrl_zeroed": 3200 * 10000},
            "trace": None}
    return {**base, **over}


@pytest.mark.parametrize("name,want", [
    ("kf_round_ms", 10.0), ("kf_pull_ms", 2.0), ("kf_push_ms", 3.0),
    ("kf_server_apply_ms", 2.0), ("kf_server_lock_wait_ms", 0.5),
    ("kf_ftrl_ns_per_step", 50.0)])
def test_a_reader_on_a_recorded_run(name, want):
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    assert read(_run()) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_nothing_where_the_run_has_no_such_side(name):
    """Another cell's run (the sibling's carries ``kx``), or servers that
    counted nothing: the reader says nothing and does not raise."""
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    other = _run(kx={"rounds_per_worker": 400, "server_pushes": 3200,
                     "server_merge_s": 1.6})
    del other["kf"]
    assert read(other) is None
    if name != "kf_launch_wait_ms":
        empty = _run(kf={}, window={"wall_s": 4.0, "spans": {}})
        assert read(empty) is None


@pytest.mark.parametrize("name", [
    "kx_round_ms", "kx_pull_ms", "kx_push_ms", "kx_w_put_ms",
    "kx_grad_d2h_ms", "kx_server_scatter_ms", "kx_wire_share",
    "kx_launch_wait_ms", "kx_localise_s", "kx_shard_put_s"])
def test_the_siblings_readers_say_nothing_of_this_cells_run(name):
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    assert read(_run()) is None


def test_kf_launch_wait_ms_reads_the_keyed_programs_runs():
    read = importlib.import_module(
        "chipbench.layer_metrics.kf_launch_wait_ms").read
    runs = [(0.100, 0.103), (0.103, 0.106), (0.106, 0.109), (0.109, 0.112)]
    marks = [(0.0995, e + 0.0001) for _s, e in runs]
    xtrace = {"/device:TPU:0": {"XLA Modules": [
        ("jit_ps_keyed_grad_step(1)", s, e - s) for s, e in runs]}}
    for k, (s, e) in enumerate(marks):
        xtrace[f"/host:CPU/{k}"] = {f"thread-{k}": [("compute", s, e - s)]}
    traced = _run(trace={"xtrace": xtrace,
                         "step_program": driver.STEP_PROGRAM,
                         "window": (0.0, 1.0)})
    assert read(traced) == pytest.approx((0.5 + 3.5 + 6.5 + 9.5) / 4)
    del traced["kf"]
    assert read(traced) is None


def test_the_roofline_share_asks_this_familys_floor():
    read = importlib.import_module(
        "chipbench.layer_metrics.step_hbm_roofline").read
    xtrace = {"/device:TPU:0": {
        "XLA Modules": [("jit_ps_keyed_grad_step(1)", 0.1, 0.002)],
        "XLA Ops": [("fusion", 0.1, 0.002)]}}
    step = {"rows": 16384, "nnz": 16384 * 39, "keys": 88000.5,
            "dim": 1000000}
    run_ = _run(family="sparse_ps_keyed_ftrl", device_kind="TPU v5 lite",
                step=step,
                trace={"xtrace": xtrace, "step_program": driver.STEP_PROGRAM,
                       "window": (0.0, 1.0)})
    floor = 16384 * 39 * 8 + 2 * 88000.5 * 4 + 16384 * 4
    assert read(run_) == pytest.approx(100.0 * floor / 819e9 / 0.002)
    assert read(run_) < 100.0


# -- BENCHMARK.json ------------------------------------------------------------
def test_every_new_metric_is_read_in_its_own_cell_only():
    bench = manifest.load_benchmark()
    mine = {m["name"] for m in manifest.Cell(bench, CELL).per_layer}
    assert mine == set(READERS) | set(LIST_LESS)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for other in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        theirs = {m["name"] for m in manifest.Cell(bench, other).per_layer}
        assert not set(READERS) & theirs
    e2e = {m["name"] for m in manifest.Cell(bench, CELL).end_to_end}
    assert e2e == {"train_samples_per_s", "setup_s"}
    for name in READERS:
        assert entries[name]["workloads"] == [CELL]
        assert callable(manifest.Cell(bench, CELL).layer_reader(name))
        assert set(entries[name]) == {"name", "unit", "better", "source",
                                      "layer", "moves", "workloads"}
    # no new layer: each is named as the accepted benchmark names it
    assert [(entries[n]["layer"], entries[n]["moves"], entries[n]["source"],
             entries[n]["unit"]) for n in READERS] == [
        ("PS worker round", "train_samples_per_s", "host_clock", "ms"),
        ("PS exchange", "train_samples_per_s", "program_span", "ms"),
        ("PS exchange", "train_samples_per_s", "program_span", "ms"),
        ("PS server apply", "train_samples_per_s", "program_counter", "ms"),
        ("PS server apply", "train_samples_per_s", "program_counter", "ms"),
        ("PS server apply", "train_samples_per_s", "program_counter", "ns"),
        ("PS worker round", "train_samples_per_s", "device_trace", "ms")]
    assert all(entries[n]["better"] == "lower" for n in READERS)
    were = {m["layer"] for m in bench["per_layer"]
            if m["name"] not in READERS}
    assert {entries[n]["layer"] for n in READERS} <= were
    for name in LIST_LESS:
        assert "workloads" not in entries[name]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "ps-keyed-ftrl-epochs", 1)
    assert len(cell["why"]) <= 200 and "FTRL-Proximal" in cell["why"]
    for name in [CELL, CONFIG, cell["traffic"], *READERS]:
        assert manifest.NAME_RE.match(name)
    assert all(manifest.UNIT_RE.match(entries[n]["unit"]) for n in READERS)


def test_the_entries_that_were_there_are_as_they_were():
    """What ``test_sparse_ps_keyed.py``'s last two tests say of PR 51's
    and PR 49's entries, without their place in the lists and without the
    count of names (``tests/conftest.py`` expects the second of them to
    fail since this cell's seven entries stand behind PR 51's ten);
    nothing here says where in the lists this cell's own entries stand."""
    from tests.chipbench import test_ps_host_readers as host
    from tests.chipbench import test_sparse_ps_keyed as keyed

    bench = manifest.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) == len(set(names))
    entries = {m["name"]: m for m in bench["per_layer"]}
    # PR 49's seven, each as written, in their order
    new = list(host.NEW)
    at = names.index(new[0])
    assert names[at:at + len(new)] == new
    layers = {m["layer"] for m in bench["per_layer"][:at]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"][at:at + len(new)]:
        layer, listed = host.NEW[m["name"]]
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": layer,
                     "moves": "train_samples_per_s", "workloads": listed}
        assert layer in layers and set(listed) <= cells
    # PR 51's ten behind them, in their order, each held to its one cell
    kx = names.index(keyed.READERS[0])
    assert kx == at + len(new)
    assert names[kx:kx + len(keyed.READERS)] == keyed.READERS
    for name in keyed.READERS:
        assert entries[name]["workloads"] == [keyed.CELL]
    assert all(names.index(n) >= kx + len(keyed.READERS) for n in READERS)
    order = [w["name"] for w in bench["workloads"]]
    assert order[:9] == ["dense-sync-1chip", "dense-ps-async-1chip",
                         "dense-ps-bsp-1chip", "dense-ps-bsp-4chip",
                         "dense-ps-bsp-eval-1chip",
                         "dense-ps-async-minibatch-1chip",
                         "softmax-ps-async-1chip",
                         "dense-ps-bsp-delay1-1chip", keyed.CELL]
    assert order.index(CELL) >= 9
    assert [c["name"] for c in bench["configs"]].index(CONFIG) >= 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(order) // 4)
    # 2 + 14 runs a cell of run_seconds + 60, 2 x 90 more a cell, 1200 spare
    n = len(order)
    assert ((2 + 14 * n) * (bench["run_seconds"] + 60) + 180 * n
            + 1200) <= 43200
    assert bench["run_seconds"] == 40
    assert [(m["name"], m["bound"]) for m in bench["end_to_end"]] == [
        ("train_samples_per_s", 0.08), ("setup_s", 0.1)]
    assert driver.STEP_PROGRAM == sibling_driver.STEP_PROGRAM == (
        "jit_ps_keyed_grad_step")
