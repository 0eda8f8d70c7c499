"""The keyed sparse PS cell: its plain reference, the whole runs that must
not be ``correct`` (each by the row that names its fault), its
configuration, its per-layer readers and the place of its entries in
``BENCHMARK.json``."""

import copy
import importlib
import json

import numpy as np
import pytest

from chipbench import datagen, manifest, reference, run
from chipbench.drivers import ps_keyed_epochs as driver
from chipbench.families import sparse, sparse_ps_keyed

CELL = "sparse-ps-async-keyed-1chip"
CONFIG = "criteo-ps-async-keyed-1m"
READERS = ["kx_round_ms", "kx_pull_ms", "kx_push_ms", "kx_w_put_ms",
           "kx_grad_d2h_ms", "kx_server_scatter_ms", "kx_wire_share",
           "kx_launch_wait_ms", "kx_localise_s", "kx_shard_put_s"]
#: the accepted metrics with no ``workloads`` list: read in every cell
LIST_LESS = ["compile_s", "input_wait_share", "step_ms", "step_hbm_roofline"]
ROWS = ["grad_norm_rel_gap", "grad_diff_rel", "conservation_rel",
        "update_missing", "unacknowledged_recorded", "test_logloss_rel_gap",
        "unacknowledged_window", "pulled_stale", "keys_mismatch",
        "window_rows_short", "dense_frames", "resident_short", "host_steps"]
RECORDED, PACE = 1, 1  # the traffic file's epochs before the window


def _rehearse(capsys, *extra):
    rc = run.main(["--workload", CELL, "--seed", "3100000051", "--seconds",
                   "0.2", "--trace", "0", "--rehearse", *extra])
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert rc == 0 and last.startswith("REHEARSAL ")
    return json.loads(last[len("REHEARSAL "):]), out


def _bad(doc):
    return {r["name"] for r in doc["compared"] if not r["ok"]}


# -- the reference ------------------------------------------------------------
@pytest.fixture(scope="module")
def shard():
    cols, vals, y = datagen.make_rows(
        91, "train", 600, fields="criteo-kaggle", num_buckets=2048,
        label_scale=0.5, label_bias=-1.0)
    w = np.random.default_rng(5).standard_normal(2048).astype(np.float32) * 0.05
    return w, cols, vals, y


def test_the_window_is_the_rule_worked_out():
    win = sparse_ps_keyed.window
    assert sparse_ps_keyed.rounds_an_epoch(600, 256) == 3
    assert [win(k, 600, 256) for k in range(4)] == [
        slice(0, 256), slice(256, 512), slice(512, 600), slice(0, 256)]
    assert win(239, 3932160, 16384) == slice(239 * 16384, 3932160)
    assert win(240, 3932160, 16384) == slice(0, 16384)


def test_the_gradient_is_the_keyed_part_of_numpys_in_float64(shard):
    w, cols, vals, y = shard
    at = slice(256, 512)
    u = sparse_ps_keyed.keys(cols[at])
    assert np.array_equal(u, np.unique(cols[at])) and u[0] == 0
    X = np.zeros((256, 2048))
    np.add.at(X, (np.arange(256)[:, None], cols[at]), vals[at])
    full = np.zeros(2048)
    full[u] = w[u]
    z = X @ full
    want = (X.T @ (1.0 / (1.0 + np.exp(-z)) - y[at]) / 256)[u]
    got = sparse_ps_keyed.gradient(w[u], cols[at], vals[at], y[at])
    assert got.dtype == np.float32 and got.shape == u.shape
    assert np.linalg.norm(got - want) <= 2e-6 * np.linalg.norm(want)
    # a mask takes rows out, and the count with them
    mask = np.arange(256) < 100
    half = sparse_ps_keyed.gradient(w[u], cols[at], vals[at], y[at], mask)
    z = X[:100] @ full
    want = (X[:100].T @ (1.0 / (1.0 + np.exp(-z)) - y[at][:100]) / 100)[u]
    assert np.linalg.norm(half - want) <= 2e-6 * np.linalg.norm(want)
    # bfloat16 is another gradient
    low = sparse_ps_keyed.gradient(w[u], cols[at], vals[at], y[at],
                                   precision="bfloat16")
    assert np.linalg.norm(low - got) > 1e-4 * np.linalg.norm(got)


def test_the_step_is_the_sparse_familys_on_the_same_rows(shard):
    w, cols, vals, y = shard
    lr = np.float32(0.2)
    loss_s, after_s = sparse.step(w, cols[:256], vals[:256], y[:256], lr,
                                  np.float32(0.0))
    loss_k, after_k = sparse_ps_keyed.step(w, cols[:256], vals[:256],
                                           y[:256], lr, 0.0)
    assert float(loss_k) == pytest.approx(float(loss_s), rel=1e-6)
    moved = np.linalg.norm(np.asarray(after_s) - w)
    assert moved > 0
    assert np.linalg.norm(np.asarray(after_k) - np.asarray(after_s)) <= (
        2e-6 * moved)
    # a key the window does not touch is not moved, in any bit
    u = sparse_ps_keyed.keys(cols[:256])
    rest = np.setdiff1d(np.arange(2048), u)
    assert np.array_equal(np.asarray(after_k)[rest], w[rest])
    acc, ll = sparse_ps_keyed.evaluate(w, cols, vals, y)
    assert 0.0 <= acc <= 1.0
    assert ll == pytest.approx(
        reference.logloss("sparse_ps_keyed", w, cols, vals, y), rel=1e-6)


def test_the_family_states_its_precision_and_uses_nothing_of_the_program():
    with open(sparse_ps_keyed.__file__) as f:
        text = f.read()
    assert "distlr_tpu" not in text
    assert 'jax.default_matmul_precision("highest")' in text
    assert reference.family("sparse_ps_keyed") is sparse_ps_keyed
    floor = sparse_ps_keyed.step_bytes_floor(
        rows=16384, nnz=16384 * 39, keys=88000, dim=1000000)
    assert floor == 16384 * 39 * 8 + 2 * 88000 * 4 + 16384 * 4


# -- the configuration --------------------------------------------------------
def test_the_configuration_states_what_it_is_and_what_it_cut():
    bench = manifest.load_benchmark()
    cell = manifest.Cell(bench, CELL)
    conf, traffic = cell.config, cell.traffic
    assert conf["reduced"] == ["train_rows", "test_rows", "num_iteration"]
    assert set(conf["reduced_why"]) == set(conf["reduced"])
    assert "No width is cut" in conf["reduced_why"]["train_rows"]
    assert conf["architecture"] is None
    assert conf["family"] == "sparse_ps_keyed"
    assert conf["control"]["program"] == {"ps_compress": "int8"}
    assert conf["control"]["precision"] == "bfloat16"
    assert len(conf["guarantees"]) == 6
    for said in ("batch_size", "servers_and_workers", "learning_rate",
                 "rows", "initial_weights", "keys_a_round"):
        assert said in conf["assumed"]
    for said in ("OSDI 2014", "section 5.1", "SYNC_MODE=0",
                 "src/main.cc:79-84", "39 non-zeros a row"):
        assert said in conf["source_says"]
    prog, gen = conf["program"], conf["generator"]
    assert (prog["model"], prog["sync_mode"], prog["batch_size"],
            prog["num_feature_dim"], prog["num_workers"],
            prog["num_servers"], prog["learning_rate"]) == (
        "sparse_lr", False, 16384, 1000000, 4, 2, 0.2)
    assert gen["rows_per_worker"] == 240 * 16384
    # the counts admit 0 only
    for name in ("unacknowledged_pushes", "pulled_stale", "keys_mismatch",
                 "window_rows_short", "dense_frames", "resident_short",
                 "host_steps", "update_missing"):
        assert conf["limits"][name] == 0.5
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] and len(conf["source"]) <= 200
    assert "OSDI 2014" in conf["source"] and "5.1" in conf["source"]
    assert entry["reduced"] == conf["reduced"]
    # what stays on the chip: over the floor, a quarter of its memory
    resident = prog["num_workers"] * driver.shard_bytes(
        gen["rows_per_worker"], prog["batch_size"], 39)
    assert round(resident / 1e9, 2) == 5.03
    assert resident >= 0.25 * 16 * 2**30
    assert "TBD" not in json.dumps(conf)
    assert traffic["kind"] == "ps_keyed_epochs"
    assert (traffic["recorded_epochs"], traffic["checked_rounds"],
            traffic["pace_epochs"]) == (RECORDED, 3, PACE)


def test_the_program_takes_the_configuration_as_it_is_written():
    from distlr_tpu import Config
    from distlr_tpu.train import ps_trainer

    conf = manifest.Cell(manifest.load_benchmark(), CELL).config
    cfg = Config(data_dir="nowhere", test_interval=0, **conf["program"])
    assert cfg.model == "sparse_lr" and not cfg.sync_mode
    # the rule sends the cell's step past the accelerator's threshold and
    # the rehearsal's past numpy's
    work = cfg.batch_size * 39 * ps_trainer._PS_KEYED_ENTRY_WORK
    assert work >= ps_trainer._PS_AUTO_CPU_THRESHOLD
    small = conf["rehearsal"]["program"]["batch_size"]
    assert (small * 39 * ps_trainer._PS_KEYED_ENTRY_WORK
            >= ps_trainer._PS_AUTO_NUMPY_THRESHOLD)


# -- whole runs ----------------------------------------------------------------
def test_the_rehearsal_is_correct_and_names_every_new_metric(capsys):
    doc, out = _rehearse(capsys)
    assert doc["correct"] is True, out
    # the trace's one (kx_launch_wait_ms) has nothing to read untraced
    assert set(READERS) - {"kx_launch_wait_ms"} <= set(doc["layer_metrics"])
    assert {"compile_s", "input_wait_share", "step_ms"} <= set(
        doc["layer_metrics"])
    assert [r["name"] for r in doc["compared"]] == ROWS
    assert "keys_a_round=" in out and "dense_frames=0" in out
    assert out.count("a window of the resident localised shard") >= 4


def _with_program(monkeypatch, over):
    real = driver.effective_config

    def changed(cell, rehearsal):
        conf = copy.deepcopy(real(cell, rehearsal))
        conf["program"].update(over(conf))
        return conf

    monkeypatch.setattr(driver, "effective_config", changed)


def _the_int8_wire(monkeypatch):
    _with_program(monkeypatch, lambda conf: conf["control"]["program"])


def _worker():
    from distlr_tpu.train import ps_trainer

    return ps_trainer.PSWorker


def _a_window_that_never_advances(monkeypatch):
    from distlr_tpu.data.iterator import SparseDataIter, Window

    real = SparseDataIter.next_window

    def stuck(self):
        got = real(self)
        return Window(0, got.rows)

    monkeypatch.setattr(SparseDataIter, "next_window", stuck)


def _half_a_shard_masked(monkeypatch):
    real = _worker()._place_keyed_shard

    def half(self, train):
        why = real(self, train)
        if self.rank == 1 and why is None:
            p, v, y, mask = self._resident
            self._resident = (p, v, y,
                              mask * (np.arange(mask.shape[0]) % 2 == 0))
        return why

    monkeypatch.setattr(_worker(), "_place_keyed_shard", half)


def _the_dense_frame_in_the_keyed_ones_place(monkeypatch):
    """A worker's keyed pull and push cross as default-key frames of all
    D slots: the same values reach the same keys."""
    from distlr_tpu.ps import KVWorker

    pull, push = KVWorker.pull, KVWorker.push

    def dense_pull(self, keys=None, **kw):
        if keys is None:
            return pull(self, **kw)
        return pull(self)[np.asarray(keys).astype(np.int64)]

    def dense_push(self, vals, keys=None, **kw):
        if keys is None:
            return push(self, vals, **kw)
        full = np.zeros(self.dim, np.float32)
        full[np.asarray(keys).astype(np.int64)] = vals
        return push(self, full)

    monkeypatch.setattr(KVWorker, "pull", dense_pull)
    monkeypatch.setattr(KVWorker, "push", dense_push)


def _the_shard_streamed_every_round(monkeypatch):
    import jax

    real = _worker()._keyed_device_step

    def streamed(self, train):
        step = real(self, train)

        def grad_step(w_u, window):
            with self._span("h2d"):
                self._resident = tuple(
                    jax.device_put(np.asarray(a)) for a in self._resident)
            return step(w_u, window)
        return grad_step

    monkeypatch.setattr(_worker(), "_keyed_device_step", streamed)


def _the_numpy_step(monkeypatch):
    """The resident shard stays; the step is numpy's over a copy of the
    window, and counts as the host's."""
    from distlr_tpu.models import host_math
    from distlr_tpu.train import ps_trainer

    real = _worker()._keyed_device_step

    def on_the_host(self, train):
        real(self, train)
        B, slots = train.batch_size, self._keyed_slots
        lines = B * slots // 128
        p, v, y, mask = (np.asarray(a) for a in self._resident)
        counted = ps_trainer._GRAD_ROUNDS.labels(rank=str(self.rank),
                                                 path="keyed_host")

        def grad_step(w_u, window):
            j = window.first // B
            at, rows = slice(j * lines, (j + 1) * lines), slice(j * B, j * B + B)
            with self._span("compute", marks_step=True):
                g = host_math.sparse_batch_grad(
                    w_u, p[at].reshape(B, slots), v[at].reshape(B, slots),
                    y[rows], mask[rows], 0.0, False)
            counted.inc()
            return g
        return grad_step

    monkeypatch.setattr(_worker(), "_keyed_device_step", on_the_host)


def _a_window_one_round_short(monkeypatch):
    real = _worker().fit

    def one_short(self, epochs=None, **kw):
        # a worker's third fit is the window: the recorded epoch and the
        # pacing epoch come before it
        if self.epochs_done == RECORDED + PACE:
            epochs -= 1
        return real(self, epochs, **kw)

    monkeypatch.setattr(_worker(), "fit", one_short)


GRADIENTS = {"grad_norm_rel_gap", "grad_diff_rel"}
SERVERS = {"conservation_rel", "update_missing", "unacknowledged_recorded",
           "unacknowledged_window"}
COUNTS = {"pulled_stale", "keys_mismatch", "window_rows_short",
          "dense_frames", "resident_short", "host_steps"}


@pytest.mark.parametrize("fault,must_fail,must_hold", [
    # sound gradients of the right keys; the servers apply something else
    (_the_int8_wire, {"conservation_rel"},
     GRADIENTS | COUNTS | {"unacknowledged_recorded",
                           "unacknowledged_window"}),
    (_a_window_that_never_advances, {"keys_mismatch", "grad_diff_rel"},
     SERVERS | {"pulled_stale", "dense_frames", "resident_short",
                "host_steps"}),
    (_half_a_shard_masked, {"grad_diff_rel"},
     SERVERS | (COUNTS - {"window_rows_short"})),
    (_the_dense_frame_in_the_keyed_ones_place, {"dense_frames"},
     GRADIENTS | SERVERS | (COUNTS - {"dense_frames"})),
    (_the_shard_streamed_every_round, {"resident_short"},
     GRADIENTS | SERVERS | (COUNTS - {"resident_short"})),
    (_the_numpy_step, {"host_steps"},
     GRADIENTS | SERVERS | (COUNTS - {"host_steps"})),
    # the rounds that were not run are not shown to be the device's
    (_a_window_one_round_short, {"window_rows_short"},
     set(ROWS) - {"window_rows_short", "host_steps"}),
], ids=["int8-wire", "window-never-advances", "half-a-shard-masked",
        "dense-frames", "shard-streamed", "numpy-step", "one-round-short"])
def test_a_faulted_run_is_not_correct(capsys, monkeypatch, fault, must_fail,
                                      must_hold):
    fault(monkeypatch)
    doc, out = _rehearse(capsys)
    assert doc["correct"] is False
    assert must_fail <= _bad(doc), out
    assert not must_hold & _bad(doc), out


def test_a_program_without_the_keyed_device_path_leaves_at_once(monkeypatch):
    """What the parent of the PR that added the cell does: it counts no
    keyed round, so the driver says so and makes no row."""
    monkeypatch.setattr(driver, "KEYED_KEYS", "distlr_ps_no_such_total")
    monkeypatch.setattr(driver, "prepare", None)  # never reached
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.2",
                  "--trace", "0", "--rehearse"])
    assert e.value.code not in (0, None)
    assert "distlr_ps_no_such_total" in str(e.value.code)


def test_the_control_tool_reads_all_three_sides(capsys):
    rc = driver.main(["--workload", CELL, "--seeds", "21,22",
                      "--controls", "2", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out.strip().splitlines()[-1][len("CONTROL "):])
    got = doc["summary"]
    # the int8 wire: conservation, and nothing of the gradients
    assert got["conservation_rel"]["sound_max"] < got["conservation_rel"][
        "limit"] < got["conservation_rel"]["control_min"]
    for name in GRADIENTS:
        assert got[name]["sound_max"] < got[name]["limit"] < got[name][
            "bfloat16_min"], name
        assert got[name]["control_min"] < got[name]["limit"]
    # neither control moves a key, a row, a frame or a step
    for name in COUNTS | {"unacknowledged_recorded"}:
        assert got[name]["sound_max"] == got[name]["control_min"] == got[
            name]["bfloat16_min"] == 0, name


def test_the_counts_of_a_phase_are_held_to_what_it_ran():
    before = {"keys": 100, "rows": 50, "paths": {"keyed_device": 3},
              "sent": 1000, "received": 400}
    servers = [{"run_frames": 7}, {"run_frames": 7}]

    def after(**over):
        return {"keys": 100 + 600, "rows": 50 + 96,
                "paths": {"keyed_device": 3 + 6},
                "sent": 1000 + 600 * 20 + 48, "received": 400 + 600 * 4,
                **over}

    sound = driver.phase_counts(before, after(), servers, servers, 6, 96, 600)
    assert (sound["window_rows_short"], sound["host_steps"],
            sound["dense_frames"], sound["keys"]) == (0, 0, 0, 600)
    # a round under another path, and one not counted at all
    got = driver.phase_counts(
        before, after(paths={"keyed_device": 7, "keyed_host": 1}),
        servers, servers, 6, 96, 600)
    assert got["host_steps"] == 1 + 2
    # a run frame, and bytes beyond the keys' and the values'
    got = driver.phase_counts(before, after(sent=1000 + 600 * 20 * 2),
                              servers, [{"run_frames": 8}, {"run_frames": 7}],
                              6, 96, 600)
    assert got["dense_frames"] == 1 + 1
    got = driver.phase_counts(before, after(rows=50 + 90, keys=100 + 590),
                              servers, servers, 6, 96, 600)
    assert got["window_rows_short"] == 6 + 10


def test_the_tap_keeps_the_first_rounds_and_leaves_the_connection_as_it_was():
    import threading

    class KV:
        def pull(self, keys=None, **kw):
            return np.arange(len(keys), dtype=np.float32)

        def push(self, vals, keys=None, **kw):
            return 0

    worker = type("W", (), {})()
    worker.kv = KV()
    tap = driver.WireTap(worker, 2, 16, threading.Barrier(1))
    for k in range(3):
        keys = np.array([1, 3 + k, 9], np.uint64)
        w = worker.kv.pull(keys=keys, vals_per_key=1)
        worker.kv.push(w + 1, keys=keys, vals_per_key=1)
    tap.remove()
    assert "pull" not in vars(worker.kv) and "push" not in vars(worker.kv)
    assert len(tap.pulls) == len(tap.pushes) == 2
    assert (tap.rounds, tap.keys_moved) == (3, 9)
    want = np.zeros(16)
    want[1], want[9] = 3, 9
    want[3], want[4], want[5] = 2, 2, 2
    assert np.array_equal(tap.total, want)


# -- the per-layer readers -------------------------------------------------
def _run(**over):
    spans = {name: {"seconds": s, "count": 400, "self_seconds": s}
             for name, s in (("pull", 0.8), ("push", 1.2), ("w_put", 0.2),
                             ("grad_d2h", 0.4), ("compute", 1.6))}
    base = {"window": {"wall_s": 4.0, "spans": spans},
            "kx": {"rounds_per_worker": 400, "rounds": 1600,
                   "keys": 1600 * 88000, "sent_bytes": 1600 * 88000 * 20,
                   "received_bytes": 1600 * 88000 * 4,
                   "dense_round_bytes": 8000000, "server_pushes": 3200,
                   "server_merge_s": 1.6, "mapped_frames": 6400},
            "trace": None}
    return {**base, **over}


@pytest.mark.parametrize("name,want", [
    ("kx_round_ms", 10.0), ("kx_pull_ms", 2.0), ("kx_push_ms", 3.0),
    ("kx_w_put_ms", 0.5), ("kx_grad_d2h_ms", 1.0),
    ("kx_server_scatter_ms", 0.5),
    ("kx_wire_share", 100.0 * 88000 * 24 / 8000000)])
def test_a_reader_on_a_recorded_run(name, want):
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    assert read(_run()) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_nothing_where_the_run_has_no_such_side(name):
    """Another cell's run, or a program from before the keyed device
    step: the reader says nothing and does not raise."""
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    other = _run()
    del other["kx"]
    assert read(other) is None
    if name not in ("kx_localise_s", "kx_shard_put_s", "kx_launch_wait_ms"):
        empty = _run(kx={}, window={"wall_s": 4.0, "spans": {}})
        assert read(empty) is None


def test_kx_launch_wait_ms_reads_the_keyed_programs_runs():
    read = importlib.import_module(
        "chipbench.layer_metrics.kx_launch_wait_ms").read
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
    del traced["kx"]
    assert read(traced) is None


def test_the_roofline_share_asks_this_familys_floor():
    read = importlib.import_module(
        "chipbench.layer_metrics.step_hbm_roofline").read
    xtrace = {"/device:TPU:0": {
        "XLA Modules": [("jit_ps_keyed_grad_step(1)", 0.1, 0.002)],
        "XLA Ops": [("fusion", 0.1, 0.002)]}}
    step = {"rows": 16384, "nnz": 16384 * 39, "keys": 88000.5,
            "dim": 1000000}
    run_ = _run(family="sparse_ps_keyed", device_kind="TPU v5 lite", step=step,
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
    # no new layer: each is named as the accepted benchmark names it
    assert [(entries[n]["layer"], entries[n]["moves"], entries[n]["source"])
            for n in READERS] == [
        ("PS worker round", "train_samples_per_s", "host_clock"),
        ("PS exchange", "train_samples_per_s", "program_span"),
        ("PS exchange", "train_samples_per_s", "program_span"),
        ("PS worker round", "train_samples_per_s", "program_span"),
        ("PS worker round", "train_samples_per_s", "program_span"),
        ("PS server apply", "train_samples_per_s", "program_counter"),
        ("PS exchange", "train_samples_per_s", "program_counter"),
        ("PS worker round", "train_samples_per_s", "device_trace"),
        ("loader", "setup_s", "program_span"),
        ("loader", "setup_s", "program_span")]
    assert entries["kx_wire_share"]["unit"] == "%"
    assert all(entries[n]["better"] == "lower" for n in READERS)
    were = {m["layer"] for m in bench["per_layer"]
            if m["name"] not in READERS}
    assert {entries[n]["layer"] for n in READERS} <= were
    for name in LIST_LESS:
        assert "workloads" not in entries[name]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "ps-keyed-epochs", 1)
    assert len(cell["why"]) <= 200 and "keyed pull and push" in cell["why"]


def test_the_entries_that_were_there_are_as_they_were():
    """What ``test_dense_ps_bsp_delay.py``'s last test says of PR 47's
    entries, without their place; nothing here says where in the lists
    this cell's own entries stand."""
    bench = manifest.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    dl = ["dl_round_ms", "dl_overlap_share", "dl_push_wait_ms",
          "dl_barrier_hold_ms", "dl_launch_wait_ms", "dl_rounds_behind",
          "dl_shard_put_s"]
    at = names.index(dl[0])
    assert names[at:at + 7] == dl
    assert all(names.index(n) > at + 6 for n in READERS)
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:8] == ["dense-sync-1chip", "dense-ps-async-1chip",
                         "dense-ps-bsp-1chip", "dense-ps-bsp-4chip",
                         "dense-ps-bsp-eval-1chip",
                         "dense-ps-async-minibatch-1chip",
                         "softmax-ps-async-1chip",
                         "dense-ps-bsp-delay1-1chip"]
    assert cells.index(CELL) >= 8
    assert [c["name"] for c in bench["configs"]].index(CONFIG) >= 8
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # 2 + 14 runs a cell of run_seconds + 60, 2 x 90 more a cell, 1200 spare
    n = len(bench["workloads"])
    assert ((2 + 14 * n) * (bench["run_seconds"] + 60) + 180 * n
            + 1200) <= 43200
    assert driver.STEP_PROGRAM == "jit_ps_keyed_grad_step"


def test_the_host_readers_entries_are_as_they_were():
    """What ``test_ps_host_readers.py::
    test_the_seven_entries_stand_at_the_end_with_a_file_each`` says of PR
    49's seven entries, without their place and without the count of
    names (``tests/conftest.py`` expects that test to fail since this
    cell's ten entries stand behind them)."""
    from tests.chipbench import test_ps_host_readers as theirs

    bench = manifest.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    new = list(theirs.NEW)
    at = names.index(new[0])
    assert names[at:at + len(new)] == new
    assert len(names) == len(set(names)) == 86 + len(READERS)
    assert names[-len(READERS):] == READERS
    layers = {m["layer"] for m in bench["per_layer"][:at]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"][at:at + len(new)]:
        layer, listed = theirs.NEW[m["name"]]
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": layer,
                     "moves": "train_samples_per_s", "workloads": listed}
        assert layer in layers and set(listed) <= cells
        assert callable(manifest.Cell(bench, listed[0]).layer_reader(m["name"]))
