"""The lock-step PS cell: its reference's round, the whole runs that must
not be ``correct``, its configuration, its per-layer readers and the
place of its entries in ``BENCHMARK.json``."""

import copy
import importlib
import json

import numpy as np
import pytest

from chipbench import datagen, manifest, reference, run
from chipbench.drivers import ps_bsp_epochs
from chipbench.families import dense_ps, dense_ps_bsp

CELL = "dense-ps-bsp-1chip"
ASYNC_CELL = "dense-ps-async-1chip"
READERS = ["bsp_round_ms", "bsp_push_wait_ms", "bsp_barrier_hold_ms",
           "bsp_arrival_spread_ms", "bsp_release_cpu_ms",
           "bsp_server_push_cpu_ms", "bsp_launch_wait_ms", "bsp_w_put_ms",
           "bsp_grad_d2h_ms", "bsp_shard_put_s"]
#: the ten PR 26 appended, in their order (tests/chipbench/test_dense_ps.py)
PS_ASYNC_READERS = ["ps_round_ms", "ps_wait_ms", "ps_wire_ms",
                    "ps_server_push_cpu_ms", "grad_d2h_ms", "w_put_ms",
                    "ps_pushes_behind", "shard_put_s", "ps_load_s",
                    "ps_launch_wait_ms"]
RECORDED, PACE = 12, 64  # the traffic file's rounds before the window


def _rehearse(capsys, *extra):
    rc = run.main(["--workload", CELL, "--seed", "3100000023", "--seconds",
                   "0.2", "--trace", "0", "--rehearse", *extra])
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert rc == 0 and last.startswith("REHEARSAL ")
    return json.loads(last[len("REHEARSAL "):]), out


def _bad(doc):
    return {r["name"] for r in doc["compared"] if not r["ok"]}


# -- the reference ----------------------------------------------------------
@pytest.fixture(scope="module")
def shards():
    cols, vals, y = datagen.make_rows(
        91, "train", 96, fields="criteo-kaggle", num_buckets=2048,
        label_scale=0.5, label_bias=-1.0)
    w = np.random.default_rng(5).standard_normal(2048).astype(np.float32) * 0.05
    return w, [tuple(a[r * 32:(r + 1) * 32] for a in (cols, vals, y))
               for r in range(3)]


def _numpy_gradient(w, cols, vals, y):
    X = np.zeros((len(y), len(w)))
    np.add.at(X, (np.arange(len(y))[:, None], cols), vals)
    z = X @ w.astype(np.float64)
    return X.T @ (1.0 / (1.0 + np.exp(-z)) - y) / len(y)


def test_the_round_is_the_mean_of_the_workers_gradients_in_numpy(shards):
    w, parts = shards
    lr = 0.2
    want = w.astype(np.float64) - lr * np.mean(
        [_numpy_gradient(w, *p) for p in parts], axis=0)
    got = dense_ps_bsp.round(w, parts, lr)
    assert got.dtype == np.float32 and got.shape == w.shape
    moved = np.linalg.norm(want - w)
    assert np.linalg.norm(got - want) <= 2e-5 * moved
    # not one worker's gradient over W, upstream's shortcut
    last_only = w.astype(np.float64) - lr * _numpy_gradient(w, *parts[-1]) / 3
    assert np.linalg.norm(got - last_only) > 0.1 * moved


def test_a_round_of_one_worker_is_the_async_familys_step(shards):
    w, parts = shards
    lr = 0.25
    _, w1 = dense_ps.step(np.asarray(w), *parts[0], np.float32(lr),
                          np.float32(0.0))
    got = dense_ps_bsp.round(w, parts[:1], lr)
    assert np.allclose(got, np.asarray(w1), atol=1e-7)


def test_the_family_is_the_async_familys_gradient_and_floor():
    assert dense_ps_bsp.gradient is dense_ps.gradient
    assert dense_ps_bsp.logits is dense_ps.logits
    assert dense_ps_bsp.step_bytes_floor is dense_ps.step_bytes_floor
    with open(dense_ps_bsp.__file__) as f:
        assert "distlr_tpu" not in f.read()
    assert reference.family("dense_ps_bsp") is dense_ps_bsp


# -- the configuration ------------------------------------------------------
def test_the_configuration_differs_from_the_async_one_in_the_mode_alone():
    bench = manifest.load_benchmark()
    conf = manifest.Cell(bench, CELL).config
    other = manifest.Cell(bench, ASYNC_CELL).config
    prog = dict(conf["program"])
    assert prog.pop("sync_mode") is True
    assert prog.pop("sync_last_gradient") is False
    assert prog == {k: v for k, v in other["program"].items()
                    if k != "sync_mode"}
    assert other["program"]["sync_mode"] is False
    assert conf["generator"] == other["generator"]
    assert conf["reduced"] == ["train_rows", "test_rows", "num_iteration"]
    assert conf["architecture"] is None
    assert conf["control"]["program"] == {"sync_last_gradient": True}
    assert conf["control"]["precision"] == "bfloat16"
    assert "src/main.cc:71" in conf["assumed"]["update_rule"]
    assert any("same weights, bit for bit" in g for g in conf["guarantees"])
    assert any("(sum over the W workers of g_r) / W" in g
               for g in conf["guarantees"])
    entry = next(c for c in bench["configs"] if c["name"] == conf["name"])
    assert entry["source"] == conf["source"] and len(conf["source"]) <= 200
    assert "SYNC_MODE=1" in conf["source"]
    resident = (prog["num_workers"] * conf["generator"]["rows_per_worker"]
                * prog["num_feature_dim"] * 4)
    assert resident >= 0.25 * 16 * 2**30  # the floor: a quarter of the chip


# -- whole runs --------------------------------------------------------------
def test_the_rehearsal_is_correct_and_names_every_new_metric(capsys):
    doc, out = _rehearse(capsys)
    assert doc["correct"] is True, out
    # the trace's one (bsp_launch_wait_ms) has nothing to read untraced
    assert set(READERS) - {"bsp_launch_wait_ms"} <= set(doc["layer_metrics"])
    assert {"compile_s", "input_wait_share", "step_ms"} <= set(
        doc["layer_metrics"])
    assert [r["name"] for r in doc["compared"]] == [
        "weights_disagree", "grad_norm_rel_gap", "grad_diff_rel",
        "update_norm_rel_gap", "update_diff_rel", "conservation_rel",
        "test_logloss_rel_gap", "round_miscount_recorded",
        "unacknowledged_recorded", "round_miscount_window",
        "unacknowledged_window"]
    assert "grad_rounds=" in out


def _with_program(monkeypatch, over):
    real = ps_bsp_epochs.effective_config

    def changed(cell, rehearsal):
        conf = copy.deepcopy(real(cell, rehearsal))
        conf["program"].update(over(conf))
        return conf

    monkeypatch.setattr(ps_bsp_epochs, "effective_config", changed)


def _the_last_gradient(monkeypatch):
    _with_program(monkeypatch, lambda conf: conf["control"]["program"])


def _no_barrier(monkeypatch):
    _with_program(monkeypatch, lambda conf: {"sync_mode": False})


def _half_a_shard_from_one_worker(monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker._place_shard

    def half(self, train, dev):
        X, y, mask = real(self, train, dev)
        if self.rank != 1:
            return X, y, mask
        return X, y, mask.at[: mask.shape[0] // 2].set(False)

    monkeypatch.setattr(ps_trainer.PSWorker, "_place_shard", half)


def _a_window_one_round_short(monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker.fit

    def one_short(self, epochs=None, **kw):
        # a worker's third fit is the window: the recorded phase and the
        # pacing rounds come before it
        if self.epochs_done == RECORDED + PACE:
            epochs -= 1
        return real(self, epochs, **kw)

    monkeypatch.setattr(ps_trainer.PSWorker, "fit", one_short)


@pytest.mark.parametrize("fault,must_fail,must_hold", [
    # upstream's shortcut: the workers' gradients are sound and every
    # worker still sees the same weights; the servers left the mean
    (_the_last_gradient, {"update_diff_rel", "conservation_rel"},
     {"weights_disagree", "grad_diff_rel", "grad_norm_rel_gap",
      "round_miscount_recorded", "round_miscount_window",
      "unacknowledged_recorded", "unacknowledged_window"}),
    # the same job with no barrier: nobody waits for anybody
    (_no_barrier, {"weights_disagree", "round_miscount_recorded",
                   "round_miscount_window"},
     {"unacknowledged_recorded", "unacknowledged_window"}),
    (_half_a_shard_from_one_worker, {"grad_diff_rel", "update_diff_rel"},
     {"weights_disagree", "conservation_rel", "round_miscount_recorded",
      "round_miscount_window"}),
    (_a_window_one_round_short, {"round_miscount_window"},
     {"weights_disagree", "grad_diff_rel", "update_diff_rel",
      "conservation_rel", "round_miscount_recorded",
      "unacknowledged_recorded", "unacknowledged_window"}),
], ids=["last-gradient", "no-barrier", "half-a-shard", "one-round-short"])
def test_a_faulted_run_is_not_correct(capsys, monkeypatch, fault, must_fail,
                                      must_hold):
    fault(monkeypatch)
    doc, out = _rehearse(capsys)
    assert doc["correct"] is False
    assert must_fail <= _bad(doc), out
    assert not must_hold & _bad(doc), out


def test_a_program_without_the_barriers_counters_leaves_at_once(monkeypatch):
    """What the parent of the PR that added the cell does: its servers
    count no rounds, so the driver says so and makes no row."""
    from distlr_tpu.ps import client

    monkeypatch.setattr(client, "STATS_FIELDS", client.STATS_FIELDS[:11])
    monkeypatch.setattr(ps_bsp_epochs, "prepare", None)  # never reached
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.2",
                  "--trace", "0", "--rehearse"])
    assert e.value.code not in (0, None) and "sync_rounds" in str(e.value.code)


def test_the_control_tool_reads_the_program_both_controls_and_the_limits(capsys):
    rc = ps_bsp_epochs.main(["--workload", CELL, "--seeds", "21,22",
                             "--controls", "1", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out.strip().splitlines()[-1][len("CONTROL "):])
    for name in ("update_diff_rel", "conservation_rel"):
        got = doc["summary"][name]
        assert got["sound_max"] < got["limit"] < got["control_min"], name
    # the reference in bfloat16 in the program's place leaves the servers'
    # part and the recorded weights as they were
    cons = doc["summary"]["conservation_rel"]
    assert cons["bfloat16_min"] <= cons["sound_max"]
    assert doc["summary"]["grad_diff_rel"]["bfloat16_min"] > 1e-4
    assert doc["summary"]["weights_disagree"]["control_min"] == 0


def test_the_cells_limits_tell_the_bfloat16_reference_from_float32(shards):
    """The reference's own round where the program's stands, held to the
    limits the cell has on the chip, in float32 and then with its
    gradients in bfloat16 (the rehearsal's limits are wider: XLA's CPU
    program rounds)."""
    w, parts = shards
    lr = 0.2
    limits = manifest.Cell(manifest.load_benchmark(), CELL).config["limits"]
    test = tuple(np.concatenate(a) for a in zip(*parts))
    rows = {"shards": parts, "test": test}
    traj = [w]
    for _ in range(3):
        traj.append(dense_ps_bsp.round(traj[-1], parts, lr))
    first = [[(wk.copy(), np.asarray(dense_ps.gradient(wk, *p))) for wk in traj]
             for p in parts]
    pushed = sum(g.astype(np.float64) for rounds in first
                 for _w, g in rounds[:3])
    got = {"first": first, "w_before": w, "w_after": traj[3],
           "pushed_sum": pushed, "round_miscount": 0, "unacknowledged": 0,
           "test_logloss": reference.logloss("dense_ps_bsp", traj[3], *test)}
    sound = ps_bsp_epochs.compare(rows, got, "dense_ps_bsp", lr, limits)
    assert all(r["ok"] for r in sound), sound
    low = ps_bsp_epochs.lowered(rows, got, "dense_ps_bsp", "bfloat16")
    bad = {r["name"] for r in ps_bsp_epochs.compare(
        rows, low, "dense_ps_bsp", lr, limits) if not r["ok"]}
    assert {"grad_norm_rel_gap", "grad_diff_rel"} & bad
    assert not bad & {"weights_disagree", "update_diff_rel",
                      "conservation_rel"}
    # one worker's weights off by one bit in one round
    off = copy.deepcopy(got)
    w_bits = off["first"][2][1][0].view(np.uint32)
    w_bits[7] ^= 1
    assert {r["name"] for r in ps_bsp_epochs.compare(
        rows, off, "dense_ps_bsp", lr, limits) if not r["ok"]} >= {
            "weights_disagree"}


# -- the per-layer readers ---------------------------------------------------
def _recorded_run():
    spans = {"push": {"seconds": 4.4, "count": 400, "self_seconds": 4.4},
             "grad_d2h": {"seconds": 0.4, "count": 400, "self_seconds": 0.4},
             "w_put": {"seconds": 0.6, "count": 400, "self_seconds": 0.6}}
    return {"window": {"wall_s": 8.0, "spans": spans},
            "ps": {"workers": 4, "rounds_per_worker": 400,
                   "server_pushes": 3200, "server_push_cpu_s": 9.6},
            "bsp": {"server_rounds": 800, "hold_s": 25.6, "spread_s": 5.6,
                    "release_cpu_s": 4.0}}


@pytest.mark.parametrize("name,want", [
    ("bsp_round_ms", 20.0), ("bsp_push_wait_ms", 11.0),
    ("bsp_barrier_hold_ms", 8.0), ("bsp_arrival_spread_ms", 7.0),
    ("bsp_release_cpu_ms", 5.0), ("bsp_server_push_cpu_ms", 3.0),
    ("bsp_w_put_ms", 1.5), ("bsp_grad_d2h_ms", 1.0)])
def test_a_reader_on_a_recorded_run(name, want):
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    assert read(_recorded_run()) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_nothing_where_no_round_was_counted(name,
                                                             monkeypatch):
    """An asynchronous run, or a program without the counters: the reader
    says nothing and does not raise."""
    from distlr_tpu.obs import registry

    monkeypatch.setattr(registry, "REGISTRY", registry.MetricsRegistry())
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    async_run = {**_recorded_run(), "trace": None}
    del async_run["bsp"]
    assert read(async_run) is None
    zeros = {**_recorded_run(), "trace": None,
             "bsp": {"server_rounds": 0, "hold_s": 0.0, "spread_s": 0.0,
                     "release_cpu_s": 0.0}}
    if name in ("bsp_barrier_hold_ms", "bsp_arrival_spread_ms",
                "bsp_release_cpu_ms", "bsp_launch_wait_ms",
                "bsp_shard_put_s"):
        assert read(zeros) is None


def test_bsp_launch_wait_ms_reads_the_async_readers_trace():
    read = importlib.import_module(
        "chipbench.layer_metrics.bsp_launch_wait_ms").read
    runs = [(0.100, 0.103), (0.103, 0.106), (0.106, 0.109), (0.109, 0.112)]
    # released together, all four dispatch at 0.0995: 0.5, 3.5, 6.5, 9.5 ms
    marks = [(0.0995, e + 0.0001) for _s, e in runs]
    xtrace = {"/device:TPU:0": {"XLA Modules": [
        ("jit_ps_grad_step(1)", s, e - s) for s, e in runs]}}
    for k, (s, e) in enumerate(marks):
        xtrace[f"/host:CPU/{k}"] = {f"thread-{k}": [("compute", s, e - s)]}
    traced = {**_recorded_run(), "trace": {
        "xtrace": xtrace, "step_program": "jit_ps_grad_step",
        "window": (0.0, 1.0)}}
    assert read(traced) == pytest.approx((0.5 + 3.5 + 6.5 + 9.5) / 4)


# -- BENCHMARK.json ----------------------------------------------------------
def test_every_new_metric_is_read_in_its_own_cell_only():
    bench = manifest.load_benchmark()
    mine = {m["name"] for m in manifest.Cell(bench, CELL).per_layer}
    assert set(READERS) <= mine
    assert {"compile_s", "input_wait_share", "step_ms",
            "step_hbm_roofline"} <= mine
    assert not mine & set(PS_ASYNC_READERS)
    for other in ("dense-sync-1chip", ASYNC_CELL):
        theirs = {m["name"] for m in manifest.Cell(bench, other).per_layer}
        assert not set(READERS) & theirs
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    for name in READERS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["layer"] in layers
    assert entries["bsp_shard_put_s"]["moves"] == "setup_s"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "distlr-ps-bsp-1m", "ps-bsp-epochs", 1)


def test_the_entries_that_were_there_keep_their_order_and_the_new_follow():
    """What ``test_dense_ps.py::
    test_the_new_entries_are_appended_behind_the_ones_that_were_there``
    says of PR 26's ten entries and PR 24's eight but for their place:
    that test holds PR 26's to the end of ``per_layer``, where the
    benchmark's contract has every later PR append (``tests/conftest.py``
    marks it an expected failure)."""
    bench = manifest.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(READERS):] == READERS
    load = ["load_s", "load_parse_s", "load_densify_s", "load_pack_s",
            "load_cast_s"]
    loop = ["h2d_wait_ms", "feed_host_ms", "launch_wait_ms"]
    held = [*loop[:2], *load, loop[2]]
    were_there = ["compile_s", "input_wait_share", "step_ms",
                  "step_hbm_roofline", *held, *PS_ASYNC_READERS]
    assert names[:len(were_there)] == were_there
    assert names == were_there + READERS
    entries = {m["name"]: m for m in bench["per_layer"]}
    sync = manifest.Cell(bench, "dense-sync-1chip")
    for name in held:
        assert entries[name]["workloads"] == ["dense-sync-1chip"]
        assert callable(sync.layer_reader(name))
    for name in load:
        assert (entries[name]["source"], entries[name]["moves"],
                entries[name]["layer"]) == ("program_span", "setup_s", "loader")
    for name in loop:
        assert (entries[name]["moves"], entries[name]["layer"]) == (
            "train_samples_per_s", "input, sync")
    assert entries["launch_wait_ms"]["source"] == "device_trace"
    for name in PS_ASYNC_READERS:
        assert entries[name]["workloads"] == [ASYNC_CELL]
