"""The PS cell: its reference, the runs that must not be ``correct``,
what is left behind after a run, and its per-layer readers."""

import copy
import importlib
import json
import socket

import numpy as np
import pytest

from chipbench import datagen, manifest, reference, run
from chipbench.drivers import ps_epochs
from chipbench.families import dense, dense_ps

CELL = "dense-ps-async-1chip"
READERS = ["ps_round_ms", "ps_wait_ms", "ps_wire_ms", "ps_server_push_cpu_ms",
           "grad_d2h_ms", "w_put_ms", "ps_pushes_behind", "shard_put_s",
           "ps_load_s", "ps_launch_wait_ms"]


def _rehearse(capsys, *extra):
    rc = run.main(["--workload", CELL, "--seed", "3000000019", "--seconds",
                   "0.2", "--trace", "0", "--rehearse", *extra])
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert rc == 0 and last.startswith("REHEARSAL ")
    return json.loads(last[len("REHEARSAL "):]), out


def _bad(doc):
    return {r["name"] for r in doc["compared"] if not r["ok"]}


# -- the reference --------------------------------------------------------
@pytest.fixture(scope="module")
def shard():
    cols, vals, y = datagen.make_rows(
        77, "train", 200, fields="criteo-kaggle", num_buckets=4096,
        label_scale=0.5, label_bias=-1.0)
    w = np.random.default_rng(3).standard_normal(4096).astype(np.float32) * 0.05
    return w, cols, vals, y


def test_the_reference_gradient_is_float64_numpys(shard):
    w, cols, vals, y = shard
    X = np.zeros((len(y), len(w)))
    np.add.at(X, (np.arange(len(y))[:, None], cols), vals)
    z = X @ w.astype(np.float64)
    want = X.T @ (1.0 / (1.0 + np.exp(-z)) - y) / len(y)
    got = np.asarray(dense_ps.gradient(w, cols, vals, y))
    assert got.dtype == np.float32
    assert np.linalg.norm(got - want) <= 2e-6 * np.linalg.norm(want)


def test_the_reference_agrees_with_the_dense_familys_step(shard):
    """``families/dense.py`` follows SGD steps; its first step's update
    over the rate is the gradient this family computes."""
    w, cols, vals, y = shard
    lr = 0.25
    _, w1 = dense.step(np.asarray(w), cols, vals, y, np.float32(lr),
                       np.float32(0.0))
    theirs = (w - np.asarray(w1)) / lr
    ours = np.asarray(dense_ps.gradient(w, cols, vals, y))
    assert np.linalg.norm(ours - theirs) <= 1e-5 * np.linalg.norm(ours)
    loss, w1_ours = dense_ps.step(np.asarray(w), cols, vals, y,
                                  np.float32(lr), np.float32(0.0))
    assert np.allclose(np.asarray(w1_ours), np.asarray(w1), atol=1e-7)
    assert abs(float(loss) - reference.logloss("dense_ps", w, cols, vals, y)) < 1e-6


def test_the_family_brings_its_floor_and_nothing_of_the_program():
    assert dense_ps.step_bytes_floor(rows=384, dim=1_000_000, nnz=0) == (
        384 * 1_000_000 * 4 + 2 * 1_000_000 * 4)
    with open(dense_ps.__file__) as f:
        assert "distlr_tpu" not in f.read()
    assert reference.family("dense_ps") is dense_ps


def test_the_configuration_states_its_guarantees_and_its_size():
    conf = manifest.Cell(manifest.load_benchmark(), CELL).config
    assert conf["guarantees"] == [
        "every acknowledged push is applied exactly once, whole, to the "
        "ranges that own its keys",
        "the weights a push-pull returns include that push",
        "float32 on the wire and in the servers",
        "no push merged, dropped, thinned or deferred"]
    prog, gen = conf["program"], conf["generator"]
    resident = (prog["num_workers"] * gen["rows_per_worker"]
                * prog["num_feature_dim"] * 4)
    assert resident >= 0.25 * 16 * 2**30  # the floor: a quarter of the chip
    assert conf["control"]["program"] == {"ps_compress": "int8"}
    assert conf["architecture"] is None
    assert (prog["sync_mode"], prog["batch_size"], prog["learning_rate"],
            prog["num_workers"], prog["num_servers"]) == (False, -1, 0.2, 4, 2)


# -- runs that must not be correct ---------------------------------------
def test_the_int8_wire_in_the_float32_wires_place_is_not_correct(
        capsys, monkeypatch):
    real = ps_epochs.effective_config

    def with_control(cell, rehearsal):
        conf = copy.deepcopy(real(cell, rehearsal))
        conf["program"].update(conf["control"]["program"])
        return conf

    monkeypatch.setattr(ps_epochs, "effective_config", with_control)
    doc, out = _rehearse(capsys)
    assert doc["correct"] is False
    assert "conservation_rel" in _bad(doc), out
    # what the workers computed was sound: the wire lost it
    assert not _bad(doc) & {"grad_norm_rel_gap", "grad_diff_rel",
                            "unacknowledged_recorded", "unacknowledged_window"}


def test_a_push_dropped_after_its_acknowledgement_is_not_correct(
        capsys, monkeypatch):
    from distlr_tpu.ps import client

    real = client.KVWorker.push_pull
    calls = {"n": 0}

    def drops_one(self, vals, *a, **kw):
        calls["n"] += 1
        if calls["n"] == 7:  # in the recorded phase
            client._OPS_TOTAL.labels(op="push_pull", status="ok").inc()
            return self.pull()
        return real(self, vals, *a, **kw)

    monkeypatch.setattr(client.KVWorker, "push_pull", drops_one)
    doc, out = _rehearse(capsys)
    assert doc["correct"] is False
    assert {"unacknowledged_recorded", "conservation_rel"} <= _bad(doc), out
    assert "unacknowledged_window" not in _bad(doc)


def test_a_push_dropped_inside_the_window_is_not_correct(capsys, monkeypatch):
    from distlr_tpu.ps import client

    real = client.KVWorker.push_pull
    calls = {"n": 0}

    def drops_one(self, vals, *a, **kw):
        calls["n"] += 1
        if calls["n"] == 4 * (12 + 64) + 5:  # past set-up's rounds
            client._OPS_TOTAL.labels(op="push_pull", status="ok").inc()
            return self.pull()
        return real(self, vals, *a, **kw)

    monkeypatch.setattr(client.KVWorker, "push_pull", drops_one)
    doc, out = _rehearse(capsys)
    assert doc["correct"] is False
    assert _bad(doc) == {"unacknowledged_window"}, out


def test_a_gradient_of_half_the_shard_is_not_correct(capsys, monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker._place_shard

    def half(self, train, dev):
        X, y, mask = real(self, train, dev)
        return X, y, mask.at[: mask.shape[0] // 2].set(False)

    monkeypatch.setattr(ps_trainer.PSWorker, "_place_shard", half)
    doc, out = _rehearse(capsys)
    assert doc["correct"] is False
    assert {"grad_diff_rel"} <= _bad(doc) <= {"grad_diff_rel",
                                              "grad_norm_rel_gap"}, out


def test_a_window_one_iteration_short_is_not_correct(capsys, monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker.fit

    def one_short(self, epochs=None, **kw):
        return real(self, epochs - 1 if epochs > 1 else epochs, **kw)

    monkeypatch.setattr(ps_trainer.PSWorker, "fit", one_short)
    doc, out = _rehearse(capsys)
    assert doc["correct"] is False
    assert not _bad(doc), out


# -- what a run leaves behind ---------------------------------------------
@pytest.fixture
def groups(monkeypatch):
    from distlr_tpu.train import ps_trainer

    real, made = ps_trainer.server_group, []

    def remembered(cfg):
        made.append(real(cfg))
        return made[-1]

    monkeypatch.setattr(ps_trainer, "server_group", remembered)
    return made


def _nothing_listens(group, ports):
    assert group.procs == []
    for port in ports:
        with socket.socket() as s:
            assert s.connect_ex(("127.0.0.1", port)) != 0


def test_a_passing_run_leaves_no_server_and_no_port(capsys, groups):
    doc, _ = _rehearse(capsys)
    assert doc["correct"] is True
    (group,) = groups
    assert len(group.ports) == 2 and all(group.ports)  # found free, not fixed
    _nothing_listens(group, group.ports)


def test_a_failing_run_leaves_no_server_and_no_port(capsys, groups, monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker.fit

    def breaks(self, epochs=None, **kw):
        if self.rank == 2 and self.epochs_done >= 12:
            raise RuntimeError("worker 2 fell over")
        return real(self, epochs, **kw)

    monkeypatch.setattr(ps_trainer.PSWorker, "fit", breaks)
    with pytest.raises(RuntimeError, match="fell over"):
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.2",
                  "--trace", "0", "--rehearse"])
    assert "REHEARSAL" not in capsys.readouterr().out
    (group,) = groups
    _nothing_listens(group, group.ports)


def test_the_control_tool_reads_both_sides(capsys):
    rc = ps_epochs.main(["--workload", CELL, "--seeds", "11,12",
                         "--controls", "1", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out.strip().splitlines()[-1][len("CONTROL "):])
    cons = doc["summary"]["conservation_rel"]
    assert cons["sound_max"] < cons["limit"] < cons["control_min"]
    # the reference in bfloat16 in the program's place leaves the servers'
    # part as it was recorded, and reads a gradient that is not float32's
    assert cons["bfloat16_min"] <= cons["sound_max"] < cons["limit"]
    assert doc["summary"]["grad_diff_rel"]["bfloat16_min"] > 1e-4


def test_the_cells_limits_tell_the_bfloat16_reference_from_float32(shard):
    """The reference's own gradient, in float32 and in bfloat16, where a
    worker's pushed gradient stands, held to the limits the cell has on
    the chip (the rehearsal's are wider: XLA's CPU program rounds)."""
    w, cols, vals, y = shard
    limits = manifest.Cell(manifest.load_benchmark(), CELL).config["limits"]
    rows = {"shards": [(cols, vals, y)], "test": (cols, vals, y)}
    sound = np.asarray(dense_ps.gradient(w, cols, vals, y))
    got = {"first": [[(w, sound)]], "w_before": w, "w_after": w - 0.2 * sound,
           "pushed_sum": sound.astype(np.float64), "unacknowledged": 0,
           "test_logloss": reference.logloss("dense_ps", w - 0.2 * sound,
                                             cols, vals, y)}
    assert all(r["ok"] for r in ps_epochs.compare(rows, got, "dense_ps", 0.2,
                                                  limits))
    low = ps_epochs.lowered(rows, got, "dense_ps", "bfloat16")
    bad = {r["name"] for r in ps_epochs.compare(rows, low, "dense_ps", 0.2,
                                                limits) if not r["ok"]}
    assert {"grad_norm_rel_gap", "grad_diff_rel"} & bad
    assert "conservation_rel" not in bad


# -- the per-layer readers -------------------------------------------------
def _recorded_run():
    spans = {"push": {"seconds": 2.0, "count": 400, "self_seconds": 2.0},
             "wire": {"seconds": 2.4, "count": 400, "self_seconds": 2.4},
             "grad_d2h": {"seconds": 0.4, "count": 400, "self_seconds": 0.4},
             "w_put": {"seconds": 0.2, "count": 400, "self_seconds": 0.2}}
    return {"window": {"wall_s": 8.0, "spans": spans},
            "ps": {"workers": 4, "rounds_per_worker": 400,
                   "server_pushes": 3200, "server_push_cpu_s": 1.6,
                   "pushes_behind_sum": 900.0, "pushes_behind_count": 300}}


@pytest.mark.parametrize("name,want", [
    ("ps_round_ms", 20.0), ("ps_wait_ms", 5.0), ("ps_wire_ms", 6.0),
    ("ps_server_push_cpu_ms", 0.5), ("grad_d2h_ms", 1.0), ("w_put_ms", 0.5),
    ("ps_pushes_behind", 3.0)])
def test_a_reader_on_a_recorded_run(name, want):
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    assert read(_recorded_run()) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_nothing_on_a_program_without_its_span(name,
                                                                monkeypatch):
    """The parent of this PR records none of these: the reader says
    nothing and does not raise."""
    from distlr_tpu.obs import registry

    monkeypatch.setattr(registry, "REGISTRY", registry.MetricsRegistry())
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    sync_run = {"window": {"wall_s": 8.0, "spans": {
        "compute": {"seconds": 1.0, "count": 10, "self_seconds": 1.0}}},
        "trace": None}
    assert read(sync_run) is None


@pytest.mark.parametrize("metric,span", [("shard_put_s", "shard_put"),
                                         ("ps_load_s", "load_data")])
def test_a_set_up_reader_sums_the_workers_spans(metric, span):
    from distlr_tpu.obs.tracing import trace_phase

    read = importlib.import_module(f"chipbench.layer_metrics.{metric}").read
    before = read({}) or 0.0
    for rank in range(2):
        with trace_phase(span, 0, rank):
            pass
    assert read({}) >= before


def _traced(runs, marks, program="jit_ps_grad_step(123)"):
    """A trace of one device that ran ``runs`` and of host threads, one a
    mark, each inside a ``compute`` annotation."""
    xtrace = {"/device:TPU:0": {"XLA Modules": [(program, s, e - s)
                                                for s, e in runs]}}
    for k, (s, e) in enumerate(marks):
        xtrace[f"/host:CPU/{k}"] = {f"thread-{k}": [("compute", s, e - s)]}
    return {"trace": {"xtrace": xtrace, "step_program": "jit_ps_grad_step",
                      "window": (0.0, 1.0)}}


def test_ps_launch_wait_ms_takes_the_last_run_that_ends_inside_a_span():
    """Four threads launch one program and their spans overlap: a span's
    own run is the one that ends as the span does, not the first to end
    after it began."""
    read = importlib.import_module(
        "chipbench.layer_metrics.ps_launch_wait_ms").read
    runs = [(0.100, 0.104), (0.104, 0.108), (0.108, 0.112)]
    # the second worker dispatched at 0.101 and ran from 0.104: 3 ms; the
    # third dispatched at 0.1035 and ran from 0.108: 4.5 ms
    marks = [(0.0995, 0.1041), (0.101, 0.1081), (0.1035, 0.1121)]
    assert read(_traced(runs, marks)) == pytest.approx((0.5 + 3.0 + 4.5) / 3)
    assert read(_traced(runs, marks, program="jit_step(9)")) is None
    assert read(_traced(runs, [])) is None


def test_every_new_metric_is_read_in_its_own_cell_only():
    bench = manifest.load_benchmark()
    mine = {m["name"] for m in manifest.Cell(bench, CELL).per_layer}
    assert set(READERS) <= mine
    assert {"compile_s", "input_wait_share", "step_ms",
            "step_hbm_roofline"} <= mine
    old = {m["name"] for m in manifest.Cell(bench, "dense-sync-1chip").per_layer}
    assert not set(READERS) & old


def test_the_new_entries_are_appended_behind_the_ones_that_were_there():
    """What ``test_input_readers.py`` says of PR 24's eight entries but for
    their place: that test holds them to the end of ``per_layer``, where
    the benchmark's contract has every later PR append (``conftest.py``)."""
    bench = manifest.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(READERS):] == READERS
    load = ["load_s", "load_parse_s", "load_densify_s", "load_pack_s",
            "load_cast_s"]
    loop = ["h2d_wait_ms", "feed_host_ms", "launch_wait_ms"]
    held = [*loop[:2], *load, loop[2]]
    assert names[-len(READERS) - len(held):-len(READERS)] == held
    entries = {m["name"]: m for m in bench["per_layer"]}
    cell = manifest.Cell(bench, "dense-sync-1chip")
    for name in held:
        assert entries[name]["workloads"] == ["dense-sync-1chip"]
        assert callable(cell.layer_reader(name))
    for name in load:
        assert (entries[name]["source"], entries[name]["moves"],
                entries[name]["layer"]) == ("program_span", "setup_s", "loader")
    for name in loop:
        assert (entries[name]["moves"], entries[name]["layer"]) == (
            "train_samples_per_s", "input, sync")
    assert entries["launch_wait_ms"]["source"] == "device_trace"
