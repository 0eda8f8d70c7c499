"""The lock-step PS cell with the launcher's eval inside: its reference's
eval, the whole runs that must not be ``correct``, its configuration, its
seven per-layer readers and its entries in ``BENCHMARK.json``."""

import copy
import functools
import importlib
import json

import numpy as np
import pytest

from chipbench import datagen, manifest, run
from chipbench.drivers import ps_bsp_eval_epochs as driver
from chipbench.families import dense_ps_bsp, dense_ps_bsp_eval

CELL = "dense-ps-bsp-eval-1chip"
SIBLING = "dense-ps-bsp-1chip"
READERS = ["eval_ms", "eval_pull_ms", "eval_compute_ms", "eval_share",
           "eval_round_stall_ms", "eval_hbm_roofline", "test_put_s"]
LIST_LESS = ["compile_s", "input_wait_share", "step_ms", "step_hbm_roofline"]
EVAL_ROWS = ["evals_miscount_recorded", "evals_miscount_window",
             "eval_rows_short", "eval_weights_stale", "eval_logloss_rel_gap",
             "eval_label_flips", "test_resident_short"]
RECORDED, PACE = 20, 64  # the traffic file's rounds before the window


def _rehearse(capsys, *extra):
    rc = run.main(["--workload", CELL, "--seed", "3600000011", "--seconds",
                   "0.2", "--trace", "0", "--rehearse", *extra])
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert rc == 0 and last.startswith("REHEARSAL ")
    return json.loads(last[len("REHEARSAL "):]), out


def _bad(doc):
    return {r["name"] for r in doc["compared"] if not r["ok"]}


# -- the reference ----------------------------------------------------------
@pytest.fixture(scope="module")
def split():
    cols, vals, y = datagen.make_rows(
        92, "test", 64, fields="criteo-kaggle", num_buckets=2048,
        label_scale=0.5, label_bias=-1.0)
    w = np.random.default_rng(6).standard_normal(2048).astype(np.float32) * 0.2
    return w, (cols, vals, y)


def test_the_references_eval_is_numpys_in_float64(split):
    w, (cols, vals, y) = split
    X = np.zeros((len(y), len(w)))
    np.add.at(X, (np.arange(len(y))[:, None], cols), vals)
    z = X @ w.astype(np.float64)
    acc, ll, z_ref = dense_ps_bsp_eval.evaluate(w, cols, vals, y)
    assert z_ref.shape == (len(y),) and z_ref.dtype == np.float32
    np.testing.assert_allclose(z_ref, z, rtol=2e-6, atol=2e-6)
    assert acc == np.mean((z > 0) == (y > 0))
    assert ll == pytest.approx(np.mean(np.logaddexp(0.0, z) - y * z), rel=1e-6)
    # in bfloat16 the weights are rounded first: another number
    _acc, low, _z = dense_ps_bsp_eval.evaluate(w, cols, vals, y,
                                               precision="bfloat16")
    assert 1e-5 < abs(low - ll) / ll < 1e-1


def test_the_family_is_the_bsp_familys_round_and_floor():
    for name in ("round", "gradient", "logits", "step", "step_bytes_floor"):
        assert getattr(dense_ps_bsp_eval, name) is getattr(dense_ps_bsp, name)
    assert dense_ps_bsp_eval.eval_bytes_floor(rows=256, dim=1_003_904) == (
        256 * 1_003_904 * 4 + 1_003_904 * 4)


# -- the configuration --------------------------------------------------------
def test_the_configuration_differs_from_the_siblings_in_the_eval_alone():
    bench = manifest.load_benchmark()
    mine = manifest.Cell(bench, CELL).config
    theirs = manifest.Cell(bench, SIBLING).config
    for key in ("architecture", "generator", "program", "reduced"):
        assert mine[key] == theirs[key], key
    assert mine["architecture"] is None
    assert mine["family"] == "dense_ps_bsp_eval"
    assert mine["guarantees"][:6] == theirs["guarantees"]
    assert len(mine["guarantees"]) == 9
    assert "evaluation" not in mine["assumed"]
    assert {k: v for k, v in mine["assumed"].items()
            if k not in ("l2", "eval_metrics")} == {
        k: v for k, v in theirs["assumed"].items() if k != "evaluation"}
    assert "include/lr.h:10" in mine["assumed"]["l2"]
    assert mine["program"]["l2_c"] == 0.0
    assert "test_interval" not in mine["program"]
    assert mine["control"]["program"] == theirs["control"]["program"]
    assert mine["control"]["eval"] == "previous_weights"
    assert {k: v for k, v in mine["limits"].items()
            if k in theirs["limits"]} == theirs["limits"]
    for key in ("evals_miscount", "eval_rows_short", "eval_weights_stale",
                "eval_label_flips", "test_resident_short"):
        assert mine["limits"][key] == 0.5  # admit 0 only
    assert 0 < mine["limits"]["eval_logloss_rel_gap"] < 1e-5
    assert mine["rehearsal"]["generator"] == theirs["rehearsal"]["generator"]
    assert mine["rehearsal"]["program"] == theirs["rehearsal"]["program"]
    entry = next(c for c in bench["configs"] if c["name"] == mine["name"])
    assert entry["source"] == mine["source"]
    for cited in ("local.sh:16", "src/main.cc:162-166", "src/lr.cc:47-63"):
        assert cited in entry["source"]
    traffic = manifest.Cell(bench, CELL).traffic
    assert (traffic["kind"], traffic["test_interval"],
            traffic["recorded_rounds"], traffic["checked_rounds"],
            traffic["pace_rounds"]) == ("ps_bsp_eval_epochs", 10, RECORDED, 4,
                                        PACE)
    # what stays on the chip: four shards and the split, over the floor
    gen, dim_held = mine["generator"], 1_003_904
    resident = (4 * gen["rows_per_worker"] + gen["test_rows"]) * dim_held * 4
    assert "7.20 GB" in mine["device_memory"]
    assert round(resident / 1e9, 2) == 7.2
    assert resident >= 0.25 * 16 * 2**30


# -- whole runs --------------------------------------------------------------
def test_the_rehearsal_is_correct_and_names_every_new_metric(capsys):
    doc, out = _rehearse(capsys)
    assert doc["correct"] is True, out
    # the trace's one (eval_hbm_roofline) has nothing to read untraced
    assert set(READERS) - {"eval_hbm_roofline"} <= set(doc["layer_metrics"])
    assert {"compile_s", "input_wait_share", "step_ms"} <= set(
        doc["layer_metrics"])
    names = [r["name"] for r in doc["compared"]]
    assert names[-len(EVAL_ROWS):] == EVAL_ROWS
    assert names[:len(names) - len(EVAL_ROWS)] == [
        "weights_disagree", "grad_norm_rel_gap", "grad_diff_rel",
        "update_norm_rel_gap", "update_diff_rel", "conservation_rel",
        "test_logloss_rel_gap", "round_miscount_recorded",
        "unacknowledged_recorded", "round_miscount_window",
        "unacknowledged_window"]
    assert "evals=[10, 20]" in out and "placing_spans=0" in out
    assert out.count("chipbench eval round=") == 2


def _with_program(monkeypatch, over):
    real = driver.effective_config

    def changed(cell, rehearsal):
        conf = copy.deepcopy(real(cell, rehearsal))
        conf["program"].update(over(conf))
        return conf

    monkeypatch.setattr(driver, "effective_config", changed)


def _the_last_gradient(monkeypatch):
    _with_program(monkeypatch, lambda conf: conf["control"]["program"])


def _no_eval_at_all(monkeypatch):
    """``test_interval=0`` in the program's place: the job the sibling
    cell runs."""
    real = driver.prepare
    monkeypatch.setattr(
        driver, "prepare",
        lambda conf, seed, say, interval, **kw: real(conf, seed, say, 0, **kw))


def _half_the_split(monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker._test_batch

    def half(test):
        X, y, mask = real(test)
        mask = mask.copy()
        mask[: len(mask) // 2] = False
        return X, y, mask

    monkeypatch.setattr(ps_trainer.PSWorker, "_test_batch", staticmethod(half))


def _evals_on_the_previous_evals_weights(monkeypatch):
    monkeypatch.setattr(driver, "record_evals", functools.partial(
        driver.record_evals, stale=True))


def _the_split_streamed_at_every_eval(monkeypatch):
    from distlr_tpu.train import ps_trainer

    monkeypatch.setattr(ps_trainer, "_device_free_bytes", lambda device: 0)


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


SERVERS_SOUND = {"weights_disagree", "round_miscount_recorded",
                 "round_miscount_window", "unacknowledged_recorded",
                 "unacknowledged_window"}
TRAJECTORY_SOUND = SERVERS_SOUND | {"grad_diff_rel", "grad_norm_rel_gap",
                                    "update_diff_rel", "conservation_rel"}


@pytest.mark.parametrize("fault,must_fail,must_hold", [
    (_no_eval_at_all, {"evals_miscount_recorded", "evals_miscount_window",
                       "eval_logloss_rel_gap"}, TRAJECTORY_SOUND),
    (_half_the_split, {"eval_rows_short", "eval_logloss_rel_gap"},
     TRAJECTORY_SOUND | {"evals_miscount_recorded", "evals_miscount_window",
                         "eval_weights_stale"}),
    (_evals_on_the_previous_evals_weights,
     {"eval_weights_stale", "eval_logloss_rel_gap"},
     TRAJECTORY_SOUND | {"evals_miscount_recorded", "evals_miscount_window",
                         "eval_rows_short", "test_resident_short"}),
    (_the_split_streamed_at_every_eval, {"test_resident_short"},
     TRAJECTORY_SOUND | {"evals_miscount_recorded", "evals_miscount_window",
                         "eval_rows_short", "eval_weights_stale",
                         "eval_logloss_rel_gap"}),
    # upstream's shortcut: the evals are sound, the servers left the mean
    (_the_last_gradient, {"update_diff_rel", "conservation_rel"},
     SERVERS_SOUND | {"grad_diff_rel", "evals_miscount_recorded",
                      "evals_miscount_window", "eval_weights_stale",
                      "eval_rows_short"}),
    (_a_window_one_round_short, {"round_miscount_window"},
     {"weights_disagree", "grad_diff_rel", "update_diff_rel",
      "conservation_rel", "round_miscount_recorded", "eval_weights_stale",
      "evals_miscount_recorded", "eval_rows_short"}),
], ids=["test-interval-0", "half-the-split", "previous-evals-weights",
        "split-streamed", "last-gradient", "one-round-short"])
def test_a_faulted_run_is_not_correct(capsys, monkeypatch, fault, must_fail,
                                      must_hold):
    fault(monkeypatch)
    doc, out = _rehearse(capsys)
    assert doc["correct"] is False
    assert must_fail <= _bad(doc), out
    assert not must_hold & _bad(doc), out


def test_a_program_that_counts_no_evals_leaves_at_once(monkeypatch):
    """What the parent of the PR that added the cell does: its worker
    keeps none of the three series, so the driver says which it misses
    and makes no row."""
    from distlr_tpu.obs import registry

    monkeypatch.setattr(registry, "REGISTRY", registry.MetricsRegistry())
    monkeypatch.setattr(driver, "prepare", None)  # never reached
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.2",
                  "--trace", "0", "--rehearse"])
    said = str(e.value.code)
    assert e.value.code not in (0, None)
    for series in (driver.EVALS, driver.EVAL_ROWS, driver.TEST_RESIDENT):
        assert series in said


def test_the_control_tool_reads_the_program_and_its_three_stand_ins(capsys):
    rc = driver.main(["--workload", CELL, "--seeds", "21,22", "--controls",
                      "1", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out.strip().splitlines()[-1][len("CONTROL "):])
    got = doc["summary"]
    assert set(EVAL_ROWS) - {"evals_miscount_window"} <= set(got)
    for name in ("update_diff_rel", "conservation_rel"):
        assert (got[name]["sound_max"] < got[name]["limit"]
                < got[name]["control_min"]), name
        # the eval's control leaves the trajectory alone
        assert got[name]["previous_weights_min"] < got[name]["limit"]
    for name in ("eval_weights_stale", "eval_logloss_rel_gap"):
        assert (got[name]["sound_max"] < got[name]["limit"]
                < got[name]["previous_weights_min"]), name
    assert got["eval_weights_stale"]["control_min"] == 0
    assert got["evals_miscount_recorded"]["previous_weights_min"] == 0
    assert out.count("chipbench previous_weights seed=") == 1
    assert out.count("chipbench bfloat16 seed=") == 2


def test_the_cells_limits_tell_the_bfloat16_reference_from_float32(split):
    """The reference's own evals where the program's stand, held to the
    limits the cell has on the chip: sound in float32, not in bfloat16
    (the rehearsal's limit is wider: XLA's CPU program rounds)."""
    w, test = split
    limits = manifest.Cell(manifest.load_benchmark(), CELL).config["limits"]
    rows = {"test": test}
    evals = []
    for k, scale in enumerate((1.0, 0.9)):
        at = (w * np.float32(scale)).astype(np.float32)
        acc, ll, _z = dense_ps_bsp_eval.evaluate(at, *test)
        evals.append({"round": 10 * (k + 1), "ran_on": at,
                      "after_round": at.copy(),
                      "accuracy": acc, "logloss": ll})
    got = {"evals": evals, "evals_miscount": 0, "eval_rows_short": 0,
           "resident_bytes": len(test[2]) * len(w) * 4}
    sound = driver.compare_evals(rows, got, "dense_ps_bsp_eval", len(w),
                                 limits)
    assert all(r["ok"] for r in sound), sound
    low = driver.lowered_evals(rows, got, "dense_ps_bsp_eval", "bfloat16")
    bad = {r["name"] for r in driver.compare_evals(
        rows, low, "dense_ps_bsp_eval", len(w), limits) if not r["ok"]}
    assert "eval_logloss_rel_gap" in bad
    assert not bad & {"eval_weights_stale", "evals_miscount_recorded",
                      "eval_rows_short", "test_resident_short"}
    # one eval's weights off by one bit from what its round returned
    off = copy.deepcopy(got)
    off["evals"][1]["ran_on"].view(np.uint32)[7] ^= 1
    assert {r["name"] for r in driver.compare_evals(
        rows, off, "dense_ps_bsp_eval", len(w), limits) if not r["ok"]} == {
            "eval_weights_stale"}
    # one right answer more than the reference counts, every row sure
    more = copy.deepcopy(got)
    more["evals"][0]["accuracy"] += 1.0 / len(test[2])
    assert "eval_label_flips" in {r["name"] for r in driver.compare_evals(
        rows, more, "dense_ps_bsp_eval", len(w), limits) if not r["ok"]}
    # a split one row short of resident
    less = {**got, "resident_bytes": got["resident_bytes"] - 1}
    assert "test_resident_short" in {r["name"] for r in driver.compare_evals(
        rows, less, "dense_ps_bsp_eval", len(w), limits) if not r["ok"]}


def test_the_stand_ins_are_the_program_underneath():
    class Worker:
        rounds, _w_cache = 10, np.arange(3, dtype=np.float32)

    calls = []

    def program(w, X, panels=None):
        calls.append((np.asarray(w).copy(), panels))
        return np.float32(0.5), np.float32(0.7)

    recorder = driver.EvalRecorder(program, Worker())
    stale = driver.StaleEval(recorder)
    first, second = np.ones(3, np.float32), np.full(3, 2.0, np.float32)
    assert stale(first, None, panels="plan") == (0.5, np.float32(0.7))
    stale(second, None, panels="plan")
    # the first on its own weights, the second on the first's
    assert [c[0].tolist() for c in calls] == [[1, 1, 1], [1, 1, 1]]
    assert calls[0][1] == "plan"
    assert [c["round"] for c in recorder.calls] == [10, 10]
    assert recorder.calls[1]["ran_on"].tolist() == [1, 1, 1]
    assert recorder.calls[1]["after_round"].tolist() == [0, 1, 2]
    assert driver._due(0, 20, 10) == 2 and driver._due(84, 123, 10) == 4
    assert driver._due(20, 29, 10) == 0 and driver._due(29, 30, 10) == 1


# -- the per-layer readers ---------------------------------------------------
def _recorded_run():
    spans = {"eval": {"seconds": 1.2, "count": 240, "self_seconds": 0.1},
             "eval_pull": {"seconds": 0.36, "count": 240, "self_seconds": 0.0},
             "eval_compute": {"seconds": 0.48, "count": 240,
                              "self_seconds": 0.48},
             "push": {"seconds": 64.0, "count": 9600, "self_seconds": 6.0}}
    return {"family": "dense_ps_bsp_eval", "device_kind": "TPU v5 lite",
            "window": {"wall_s": 40.0, "spans": {}},
            "eval": {"spans": spans, "wall_s": 40.0,
                     "pushes": {"after": {"seconds": 9.0, "count": 720},
                                "other": {"seconds": 45.36, "count": 6480}},
                     "rows": 256, "dim_held": 1_003_904,
                     "program": "jit_ps_eval"},
            "trace": None}


@pytest.mark.parametrize("name,want", [
    ("eval_ms", 5.0), ("eval_pull_ms", 1.5), ("eval_compute_ms", 2.0),
    ("eval_share", 3.0), ("eval_round_stall_ms", 12.5 - 7.0)])
def test_a_reader_on_a_recorded_run(name, want):
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    assert read(_recorded_run()) == pytest.approx(want)


def test_eval_hbm_roofline_reads_the_eval_programs_runs_by_name():
    read = importlib.import_module(
        "chipbench.layer_metrics.eval_hbm_roofline").read
    floor = 256 * 1_003_904 * 4 + 1_003_904 * 4
    ideal = floor / 819e9
    # two runs of the eval program, busy 1.25 and 1.75 of the ideal time,
    # among runs of the gradient step that the reader must not count
    ops, modules, t = [], [], 0.010
    for k, busy in enumerate((1.25 * ideal, 1.75 * ideal)):
        modules.append((f"jit_ps_eval({k})", t, busy + 1e-4))
        ops.append(("multiply_reduce_fusion", t, busy))
        t += 0.01
        modules.append((f"jit_ps_grad_step({k})", t, 2.3e-3))
        ops.append(("lr_grad_panels", t, 2.3e-3))
        t += 0.01
    traced = {**_recorded_run(), "trace": {
        "xtrace": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules}},
        "window": (0.0, 1.0), "step_program": "jit_ps_grad_step"}}
    assert read(traced) == pytest.approx(100.0 / 1.5)
    assert read(_recorded_run()) is None            # untraced
    no_plane = {**traced, "trace": {**traced["trace"], "xtrace": {}}}
    assert read(no_plane) is None                   # the CPU's trace


def test_test_put_s_reads_the_registrys_phase(monkeypatch):
    from distlr_tpu.obs import registry
    from distlr_tpu.obs.tracing import PhaseTracer

    fresh = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "REGISTRY", fresh)
    read = importlib.import_module("chipbench.layer_metrics.test_put_s").read
    assert read(_recorded_run()) is None
    PhaseTracer(fresh).completed("test_put", 1.0, 0.25)
    assert read(_recorded_run()) == pytest.approx(0.25)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_nothing_where_the_run_has_no_eval_side(
        name, monkeypatch):
    """Another cell's run, or a program from before the eval had its
    phases: the reader says nothing and does not raise."""
    from distlr_tpu.obs import registry

    monkeypatch.setattr(registry, "REGISTRY", registry.MetricsRegistry())
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    other = _recorded_run()
    del other["eval"]
    assert read(other) is None
    bare = _recorded_run()
    bare["eval"]["spans"] = {"push": bare["eval"]["spans"]["push"]}
    bare["eval"]["pushes"] = {"after": {"seconds": 0.0, "count": 0},
                              "other": {"seconds": 1.0, "count": 10}}
    assert read(bare) is None


def test_the_barriers_side_of_an_eval_is_split_by_the_round_it_follows():
    def push(rank, step, ms):
        return {"name": "push", "dur": ms * 1e3, "ts": 0.0,
                "args": {"rank": rank, "step": step}}

    events = [push(0, 11, 1.0), push(1, 11, 9.0), push(2, 21, 11.0),
              push(3, 12, 4.0), push(1, 20, 6.0), push(2, 1, 5.0),
              {"name": "push", "dur": 3e3, "ts": 0.0,
               "args": {"rank": 0, "step": 0}},          # the seed push
              {"name": "pull", "dur": 2e3, "ts": 0.0,
               "args": {"rank": 1, "step": 11}}]
    got = driver._after_eval_pushes(events, 10)
    assert got["after"] == {"seconds": pytest.approx(0.020), "count": 2}
    # round 1 follows no eval; rank 0 is the one that evaluates
    assert got["other"] == {"seconds": pytest.approx(0.015), "count": 3}


# -- BENCHMARK.json ----------------------------------------------------------
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
    accepted = {m["layer"] for m in bench["per_layer"]
                if m["name"] not in READERS}
    for name in READERS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] in e2e
        assert callable(manifest.Cell(bench, CELL).layer_reader(name))
        # one new layer, the eval's own; the others are named as they were
        assert (entries[name]["layer"] == "PS worker eval"
                or entries[name]["layer"] in accepted)
    assert [entries[n]["layer"] for n in READERS] == [
        "PS worker eval", "PS exchange", "PS worker eval", "PS worker eval",
        "PS server barrier", "XLA step program", "loader"]
    assert entries["test_put_s"]["moves"] == "setup_s"
    assert (entries["eval_share"]["unit"]
            == entries["eval_hbm_roofline"]["unit"] == "%")
    assert entries["eval_hbm_roofline"]["source"] == "device_trace"
    assert entries["eval_hbm_roofline"]["better"] == "higher"
    for name in LIST_LESS:
        assert "workloads" not in entries[name]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "distlr-ps-bsp-1m-eval", "ps-bsp-eval-epochs", 1)


def test_the_new_entries_stand_at_the_end_of_their_lists():
    bench = manifest.load_benchmark()
    assert [m["name"] for m in bench["per_layer"]][-7:] == READERS
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "distlr-ps-bsp-1m-eval"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["workloads"]) == len(bench["configs"]) == 5
    # 2 + 14 runs a cell of run_seconds + 60, 2 x 90 more a cell, 1200 spare
    cells = len(bench["workloads"])
    assert ((2 + 14 * cells) * (bench["run_seconds"] + 60) + 180 * cells
            + 1200) <= 43200
