"""The bounded-delay PS cell: its reference's delayed rounds, the whole
runs that must not be ``correct`` (each by the row that names its fault),
its configuration, its per-layer readers and the place of its entries in
``BENCHMARK.json``."""

import copy
import dataclasses
import importlib
import json

import numpy as np
import pytest

from chipbench import datagen, manifest, reference, run
from chipbench.drivers import ps_bsp_delay_epochs as driver
from chipbench.drivers import ps_bsp_epochs, ps_epochs
from chipbench.families import dense_ps, dense_ps_bsp, dense_ps_bsp_delay

CELL = "dense-ps-bsp-delay1-1chip"
CONFIG = "distlr-ps-bsp-1m-delay1"
SIBLING = "dense-ps-bsp-1chip"
READERS = ["dl_round_ms", "dl_overlap_share", "dl_push_wait_ms",
           "dl_barrier_hold_ms", "dl_launch_wait_ms", "dl_rounds_behind",
           "dl_shard_put_s"]
#: the accepted metrics with no ``workloads`` list: read in every cell
LIST_LESS = ["compile_s", "input_wait_share", "step_ms", "step_hbm_roofline"]
ROWS = ["weights_disagree", "grad_norm_rel_gap", "grad_diff_rel",
        "update_norm_rel_gap", "update_diff_rel", "conservation_rel",
        "test_logloss_rel_gap", "round_miscount_recorded",
        "unacknowledged_recorded", "round_miscount_window",
        "unacknowledged_window", "lineage_broken", "delay_miscount",
        "in_flight_at_return"]
RECORDED, PACE = 12, 64  # the traffic file's rounds before the window


def _rehearse(capsys, *extra):
    rc = run.main(["--workload", CELL, "--seed", "3100000047", "--seconds",
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
    z = X @ np.asarray(w, np.float64)
    return X.T @ (1.0 / (1.0 + np.exp(-z)) - y) / len(y)


def test_the_rounds_are_the_delayed_recurrence_in_numpy(shards):
    w0, parts = shards
    lr, n = 0.2, 5
    w = [w0.astype(np.float64)]
    for k in range(n):
        on = w[max(k - 1, 0)]          # v_0 = v_1 = w_0, v_k = w_{k-1}
        w.append(w[k] - lr * np.mean(
            [_numpy_gradient(on, *p) for p in parts], axis=0))
    got = dense_ps_bsp_delay.rounds(w0, parts, lr, n)
    assert len(got) == n and all(g.dtype == np.float32 for g in got)
    for k in range(n):
        moved = np.linalg.norm(w[k + 1] - w[k])
        assert np.linalg.norm(got[k] - w[k + 1]) <= 5e-5 * moved, k
    on = dense_ps_bsp_delay.computed_on(w0, got)
    assert len(on) == n + 1
    assert on[0] is on[1] and np.array_equal(on[0], w0)
    assert all(on[k] is got[k - 2] for k in range(2, n + 1))


def test_the_first_update_is_lock_steps_and_the_second_is_not(shards):
    w0, parts = shards
    lr = 0.2
    got = dense_ps_bsp_delay.rounds(w0, parts, lr, 3)
    lock = [dense_ps_bsp.round(w0, parts, lr)]
    lock.append(dense_ps_bsp.round(lock[0], parts, lr))
    assert np.array_equal(got[0], lock[0])
    moved = np.linalg.norm(lock[1].astype(np.float64) - lock[0])
    assert np.linalg.norm(got[1].astype(np.float64) - lock[1]) > 1e-3 * moved
    # w_2 = w_1 - lr * mean g(w_0): round 1 ran on w_0
    want = lock[0].astype(np.float64) - lr * np.mean(
        [_numpy_gradient(w0, *p) for p in parts], axis=0)
    assert np.linalg.norm(got[1] - want) <= 5e-5 * moved


def test_the_family_is_the_siblings_but_for_the_rounds():
    assert dense_ps_bsp_delay.gradient is dense_ps.gradient
    assert dense_ps_bsp_delay.logits is dense_ps.logits
    assert dense_ps_bsp_delay.step_bytes_floor is dense_ps.step_bytes_floor
    with open(dense_ps_bsp_delay.__file__) as f:
        text = f.read()
    assert "distlr_tpu" not in text
    for stated in ("v_0 = v_1 = w_0", "v_k = w_{k-1}", "``highest``"):
        assert stated in text
    assert reference.family("dense_ps_bsp_delay") is dense_ps_bsp_delay


# -- the configuration ------------------------------------------------------
def test_the_configuration_differs_from_the_siblings_in_the_delay_alone():
    bench = manifest.load_benchmark()
    conf = manifest.Cell(bench, CELL).config
    sib = manifest.Cell(bench, SIBLING).config
    assert conf["program"] == {**sib["program"], "ps_max_delay": 1}
    assert conf["generator"] == sib["generator"]
    assert conf["reduced"] == ["train_rows", "test_rows", "num_iteration"]
    assert set(conf["reduced_why"]) == set(conf["reduced"])
    assert "No width is cut" in conf["reduced_why"]["train_rows"]
    assert conf["architecture"] is None
    assert conf["family"] == "dense_ps_bsp_delay"
    assert conf["control"]["program"] == {"ps_max_delay": 0}
    assert conf["control"]["precision"] == "bfloat16"
    assert len(conf["guarantees"]) == 8
    assert conf["guarantees"][0] == sib["guarantees"][0]
    assert "after round k-2" in conf["guarantees"][1]
    assert "never fresher" in conf["guarantees"][1]
    assert "(sum over the W workers of g_r) / W" in conf["guarantees"][2]
    assert "at most one round open" in conf["guarantees"][6]
    assert "when fit returns" in conf["guarantees"][7]
    for said in ("v_0 = v_1 = w_0", "v_k = w_{k-1}",
                 "w_{k+1} = float32(w_k - 0.2 * (sum_r g_r(v_k)) / 4)"):
        assert said in conf["deployment"]
    for said in ("section_numbers", "max_delay", "where_the_delay_resets"):
        assert said in conf["assumed"]
    for said in ("BOUNDED DELAY", "SURVEY.md:106", "src/lr.cc:131"):
        assert said in conf["source_says"]
    # the sibling's limits as it states them, and three that admit 0 only
    assert set(conf["limits"]) == set(sib["limits"]) | {
        "lineage_broken", "delay_miscount", "in_flight_at_return"}
    for name in ("weights_disagree", "round_miscount",
                 "unacknowledged_pushes", "lineage_broken", "delay_miscount",
                 "in_flight_at_return"):
        assert conf["limits"][name] == 0.5
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] and len(conf["source"]) <= 200
    assert "OSDI 2014" in conf["source"] and "tau=1" in conf["source"]
    assert entry["reduced"] == conf["reduced"]
    prog, gen = conf["program"], conf["generator"]
    resident = prog["num_workers"] * gen["rows_per_worker"] * 1003904 * 4
    assert round(resident / 1e9, 2) == 6.17
    assert resident >= 0.25 * 16 * 2**30  # the floor: a quarter of the chip
    traffic = manifest.Cell(bench, CELL).traffic
    assert traffic["kind"] == "ps_bsp_delay_epochs"
    assert (traffic["max_delay"], traffic["checked_rounds"],
            traffic["recorded_rounds"], traffic["pace_rounds"]) == (
        1, 5, RECORDED, PACE)


def test_the_program_takes_the_configuration_as_it_is_written():
    from distlr_tpu import Config

    conf = manifest.Cell(manifest.load_benchmark(), CELL).config
    cfg = Config(data_dir="nowhere", test_interval=0, **conf["program"])
    assert cfg.ps_max_delay == 1 and cfg.sync_mode
    # the sibling's control is refused with the delay, so it is not offered
    with pytest.raises(ValueError, match="sync_last_gradient"):
        Config(**{**conf["program"], "sync_last_gradient": True})


# -- whole runs --------------------------------------------------------------
def test_the_rehearsal_is_correct_and_names_every_new_metric(capsys):
    doc, out = _rehearse(capsys)
    assert doc["correct"] is True, out
    # the trace's one (dl_launch_wait_ms) has nothing to read untraced
    assert set(READERS) - {"dl_launch_wait_ms"} <= set(doc["layer_metrics"])
    assert {"compile_s", "input_wait_share", "step_ms"} <= set(
        doc["layer_metrics"])
    assert [r["name"] for r in doc["compared"]] == ROWS
    assert "delayed_rounds=" in out and "computes in_flight=" in out


def _with_program(monkeypatch, over):
    real = driver.effective_config

    def changed(cell, rehearsal):
        conf = copy.deepcopy(real(cell, rehearsal))
        conf["program"].update(over(conf))
        return conf

    monkeypatch.setattr(driver, "effective_config", changed)


def _lock_step(monkeypatch):
    _with_program(monkeypatch, lambda conf: conf["control"]["program"])


def _delayed():
    from distlr_tpu.train import ps_trainer

    return ps_trainer._Delayed


def _two_rounds_stale(monkeypatch):
    """``weights()`` hands out what it held a round ago: round k runs on
    the reply to push k - 3."""
    real = _delayed().weights

    def stale(self, keys):
        now = real(self, keys)
        before, self.held_before = getattr(self, "held_before", now), now
        return before

    monkeypatch.setattr(_delayed(), "weights", stale)


def _a_reply_taken_early(monkeypatch):
    """The reply in flight is waited for before the gradient and run on:
    lock step's lineage under the delay's name."""
    real = _delayed().weights

    def early(self, keys):
        self._wait()
        return real(self, keys)

    monkeypatch.setattr(_delayed(), "weights", early)


def _no_drain_before_the_recorded_fit_returns(monkeypatch):
    real = _delayed().finish

    def skipped(self):
        # the recorded fit alone: a later one ends as it should, so that
        # the way out (a pull on the loop's thread) meets no push
        if self.w.epochs_done != RECORDED:
            real(self)

    monkeypatch.setattr(_delayed(), "finish", skipped)


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
        # a worker's third fit is the window: the recorded fit and the
        # pacing rounds come before it
        if self.epochs_done == RECORDED + PACE:
            epochs -= 1
        return real(self, epochs, **kw)

    monkeypatch.setattr(ps_trainer.PSWorker, "fit", one_short)


SERVERS_PART = {"conservation_rel", "round_miscount_recorded",
                "round_miscount_window", "unacknowledged_recorded",
                "unacknowledged_window"}


@pytest.mark.parametrize("fault,must_fail,must_hold", [
    # the lock-step job: sound gradients, agreeing workers, conserving
    # servers, another trajectory and no delayed round
    (_lock_step, {"update_norm_rel_gap", "update_diff_rel", "lineage_broken",
                  "delay_miscount"},
     {"weights_disagree", "grad_diff_rel", "grad_norm_rel_gap",
      "in_flight_at_return"} | SERVERS_PART),
    (_two_rounds_stale, {"update_diff_rel", "lineage_broken"},
     {"weights_disagree", "grad_diff_rel", "grad_norm_rel_gap",
      "delay_miscount", "in_flight_at_return"} | SERVERS_PART),
    (_a_reply_taken_early, {"update_diff_rel", "lineage_broken"},
     {"weights_disagree", "grad_diff_rel", "grad_norm_rel_gap",
      "delay_miscount", "in_flight_at_return"} | SERVERS_PART),
    # what the servers and the lineage's tap read of the recorded fit's
    # last push depends on when the comm threads end: not held either way
    (_no_drain_before_the_recorded_fit_returns, {"in_flight_at_return"},
     {"weights_disagree", "grad_diff_rel", "grad_norm_rel_gap",
      "update_diff_rel", "delay_miscount", "round_miscount_window",
      "unacknowledged_window"}),
    (_half_a_shard_from_one_worker, {"grad_diff_rel", "update_diff_rel"},
     {"weights_disagree", "lineage_broken", "delay_miscount",
      "in_flight_at_return"} | SERVERS_PART),
    (_a_window_one_round_short, {"round_miscount_window"},
     set(ROWS) - {"round_miscount_window"}),
], ids=["lock-step", "two-rounds-stale", "reply-taken-early",
        "no-drain-at-return", "half-a-shard", "one-round-short"])
def test_a_faulted_run_is_not_correct(capsys, monkeypatch, fault, must_fail,
                                      must_hold):
    fault(monkeypatch)
    doc, out = _rehearse(capsys)
    assert doc["correct"] is False
    assert must_fail <= _bad(doc), out
    assert not must_hold & _bad(doc), out


@pytest.mark.parametrize("lacks", ["the-field", "the-series"])
def test_a_program_without_the_delay_leaves_at_once(monkeypatch, lacks):
    """What the parent of the PR that added the cell does: it has no
    ``Config.ps_max_delay`` and counts no delayed round, so the driver
    says so and makes no row."""
    if lacks == "the-field":
        real = dataclasses.fields
        monkeypatch.setattr(
            driver.dataclasses, "fields",
            lambda cls: [f for f in real(cls) if f.name != "ps_max_delay"])
        says = "Config.ps_max_delay"
    else:
        monkeypatch.setattr(driver, "DELAYED", "distlr_ps_no_such_total")
        says = "distlr_ps_no_such_total"
    monkeypatch.setattr(driver, "prepare", None)  # never reached
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.2",
                  "--trace", "0", "--rehearse"])
    assert e.value.code not in (0, None) and says in str(e.value.code)


def test_the_control_tool_reads_all_three_sides(capsys):
    rc = driver.main(["--workload", CELL, "--seeds", "21,22",
                      "--controls", "2", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out.strip().splitlines()[-1][len("CONTROL "):])
    got = doc["summary"]
    for name in ("update_norm_rel_gap", "update_diff_rel", "lineage_broken",
                 "delay_miscount"):
        assert got[name]["sound_max"] < got[name]["limit"] < got[name][
            "control_min"], name
    for name in ("grad_norm_rel_gap", "grad_diff_rel"):
        assert got[name]["sound_max"] < got[name]["limit"] < got[name][
            "bfloat16_min"], name
        # the lock-step job's gradients are sound
        assert got[name]["control_min"] < got[name]["limit"]
    # the lock-step job's workers agree, its servers conserve, it ends
    # with nothing out
    for name in ("weights_disagree", "in_flight_at_return"):
        assert got[name]["control_min"] == 0
    assert got["conservation_rel"]["control_min"] < got["conservation_rel"][
        "limit"]


def _as_recorded(w0, parts, lr, traj, checked):
    """The reference's own delayed run laid out as the driver records the
    program's: ``checked`` rounds of weights and gradients a worker, the
    first replies, a conserving 12th-round end."""
    on = dense_ps_bsp_delay.computed_on(w0, traj)[:checked]
    first = [[(v.copy(), np.asarray(dense_ps.gradient(v, *p))) for v in on]
             for p in parts]
    every = dense_ps_bsp_delay.computed_on(w0, traj)[:len(traj)]
    pushed = sum(np.asarray(dense_ps.gradient(v, *p), np.float64)
                 for p in parts for v in every)
    test = tuple(np.concatenate(a) for a in zip(*parts))
    got = {"first": first, "replies": [[t.copy() for t in traj[:checked]]
                                       for _ in parts],
           "w_before": w0, "w_after": traj[-1], "pushed_sum": pushed,
           "round_miscount": 0, "unacknowledged": 0, "lineage_broken": 0,
           "delay_miscount": 0, "in_flight_at_return": 0,
           "test_logloss": reference.logloss("dense_ps_bsp_delay", traj[-1],
                                             *test)}
    return {"shards": parts, "test": test}, got


def test_the_cells_limits_tell_bfloat16_and_lock_step_from_the_real_thing(
        shards):
    """The reference's own delayed rounds where the program's stand, held
    to the limits the cell has on the chip: as they are, with the
    gradients in bfloat16, and with lock step's trajectory in their
    place."""
    w0, parts = shards
    lr = 0.2
    limits = manifest.Cell(manifest.load_benchmark(), CELL).config["limits"]
    traj = dense_ps_bsp_delay.rounds(w0, parts, lr, 6)
    rows, got = _as_recorded(w0, parts, lr, traj, 5)
    sound = driver.compare(rows, got, "dense_ps_bsp_delay", lr, limits)
    assert [r["name"] for r in sound] == [
        n for n in ROWS if not n.endswith("_window")]
    assert all(r["ok"] for r in sound), sound
    low = ps_epochs.lowered(rows, got, "dense_ps_bsp_delay", "bfloat16")
    bad = {r["name"] for r in driver.compare(
        rows, low, "dense_ps_bsp_delay", lr, limits) if not r["ok"]}
    assert {"grad_norm_rel_gap", "grad_diff_rel"} & bad
    assert not bad & {"weights_disagree", "update_diff_rel",
                      "conservation_rel", "lineage_broken"}
    # lock step's own rounds, sound in themselves
    lock, w = [], w0
    for _ in range(6):
        w = dense_ps_bsp.round(w, parts, lr)
        lock.append(w)
    lock_on = [w0, *lock][:5]
    off = copy.deepcopy(got)
    off["first"] = [[(v.copy(), np.asarray(dense_ps.gradient(v, *p)))
                     for v in lock_on] for p in parts]
    off["replies"] = [[t.copy() for t in lock[:5]] for _ in parts]
    bad = {r["name"] for r in driver.compare(
        rows, off, "dense_ps_bsp_delay", lr, limits) if not r["ok"]}
    assert {"update_norm_rel_gap", "update_diff_rel"} <= bad
    assert not bad & {"weights_disagree", "grad_diff_rel",
                      "grad_norm_rel_gap"}
    # one worker's reply off by one bit
    off = copy.deepcopy(got)
    off["replies"][2][1].view(np.uint32)[7] ^= 1
    assert {r["name"] for r in driver.compare(
        rows, off, "dense_ps_bsp_delay", lr, limits) if not r["ok"]} == {
            "weights_disagree"}
    # a window's counts are added to the recorded fit's
    window = {"round_miscount": 0, "unacknowledged": 0, "delay_miscount": 3,
              "in_flight_at_return": 1}
    assert {r["name"] for r in driver.compare(
        rows, got, "dense_ps_bsp_delay", lr, limits, window)
        if not r["ok"]} == {"delay_miscount", "in_flight_at_return"}


def test_the_counters_rise_is_held_to_the_rounds_run():
    miscount = driver._delay_miscount
    assert miscount({"0": 4, "1": 44}, {"0": 8, "1": 88}, 4, 48) == 0
    # a program that counts nothing
    assert miscount({"0": 0, "1": 0}, {"0": 0, "1": 0}, 4, 48) == 48
    # every round counted as fresh; one counted two behind
    assert miscount({"0": 0, "1": 0}, {"0": 48, "1": 0}, 4, 48) == 88
    assert miscount({"0": 0, "1": 0}, {"0": 4, "1": 43, "2": 1}, 4, 48) == 2


def test_the_replies_tap_keeps_the_first_and_leaves_the_worker_as_it_was():
    class KV:
        def pull(self):
            return np.zeros(4, np.float32)

        def push_pull(self, g):
            return g + 1

    worker = type("W", (), {})()
    worker.kv, worker._w_cache = KV(), None
    worker.grad_step = step = lambda wf, batch: wf * 2
    tap = driver.Replies(worker, 2)
    w = worker.kv.pull()
    for _ in range(3):
        w = worker.kv.push_pull(worker.grad_step(w, None))
    lin = tap.remove()
    assert worker.grad_step is step and "push_pull" not in vars(worker.kv)
    assert len(lin["replies"]) == 3 and len(tap.kept) == 2
    assert np.array_equal(tap.kept[1], np.full(4, 3.0, np.float32))


# -- the per-layer readers ---------------------------------------------------
def _events():
    """Rank 0, one fit of three rounds (steps 5, 6, 7): round 6 runs 3 ms
    under round 5's 8 ms wire, round 7 all 2 ms of its compute under round
    6's 6 ms wire; the last wire is drained."""
    def ev(name, ts_ms, dur_ms, step, **more):
        return {"name": name, "ts": ts_ms * 1e3, "dur": dur_ms * 1e3,
                "args": {"rank": 0, "step": step, **more}}

    return [
        ev("compute", 0, 3, 5, in_flight=0), ev("wire", 4, 8, 5),
        ev("compute", 9, 5, 6, in_flight=1), ev("push", 15, 0.5, 6),
        ev("wire", 16, 6, 6),
        ev("compute", 18, 2, 7, in_flight=1), ev("push", 21, 1.5, 7),
        ev("wire", 23, 4, 7), ev("push", 23, 4, 7, drain=1),
        # another rank's compute hides nothing of rank 0's wire
        {"name": "compute", "ts": 4e3, "dur": 8e3,
         "args": {"rank": 1, "step": 6, "in_flight": 1}},
        {"name": "load_data", "ts": 0, "dur": 5, "args": {}},
    ]


def _run(**over):
    spans = {"push": {"seconds": 1.2, "count": 400, "self_seconds": 1.2},
             "compute": {"seconds": 3.4, "count": 400, "self_seconds": 3.4}}
    base = {"window": {"wall_s": 4.0, "spans": spans},
            "ps": {"workers": 4, "rounds_per_worker": 400,
                   "server_pushes": 3200, "server_push_cpu_s": 9.6},
            "bsp": {"server_rounds": 800, "hold_s": 25.6, "spread_s": 5.6,
                    "release_cpu_s": 4.0},
            "dl": driver.dl_side(_events(), 0, {"0": 1, "1": 2}),
            "trace": None}
    return {**base, **over}


def test_the_side_reads_the_events_and_the_counter():
    side = driver.dl_side(_events(), 7, {"0": 1, "1": 2})
    assert side["wire_s"] == pytest.approx(0.018)
    assert side["wire_under_compute_s"] == pytest.approx(0.003 + 0.002)
    assert (side["computes_in_flight"], side["computes_alone"]) == (3, 1)
    assert side["push"]["wait"] == {"seconds": pytest.approx(0.002),
                                    "count": 2}
    assert side["push"]["drain"] == {"seconds": pytest.approx(0.004),
                                     "count": 1}
    assert (side["rounds_behind_sum"], side["rounds_counted"],
            side["events_dropped"]) == (2, 3, 7)


@pytest.mark.parametrize("name,want", [
    ("dl_round_ms", 10.0),
    ("dl_overlap_share", 100.0 * 5 / 18),
    ("dl_push_wait_ms", 2.0 / 3),
    ("dl_barrier_hold_ms", 8.0),
    ("dl_rounds_behind", 2 / 3)])
def test_a_reader_on_a_recorded_run(name, want):
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    assert read(_run()) == pytest.approx(want)


@pytest.mark.parametrize("name", [r for r in READERS if r != "dl_shard_put_s"])
def test_a_reader_returns_nothing_where_the_run_has_no_such_side(name):
    """A lock-step or asynchronous run, or a program from before the
    delay: the reader says nothing and does not raise."""
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    other = _run()
    del other["dl"]
    assert read(other) is None
    # a program whose compute spans say nothing of what was in flight
    # and that counted no delayed round
    silent = [e for e in _events()]
    for e in silent:
        e["args"].pop("in_flight", None)
    quiet = _run(dl=driver.dl_side(silent, 0, {"0": 0, "1": 0}))
    if name in ("dl_overlap_share", "dl_push_wait_ms", "dl_rounds_behind",
                "dl_launch_wait_ms"):
        assert read(quiet) is None


def test_dl_launch_wait_ms_reads_the_async_readers_trace():
    read = importlib.import_module(
        "chipbench.layer_metrics.dl_launch_wait_ms").read
    runs = [(0.100, 0.103), (0.103, 0.106), (0.106, 0.109), (0.109, 0.112)]
    marks = [(0.0995, e + 0.0001) for _s, e in runs]
    xtrace = {"/device:TPU:0": {"XLA Modules": [
        ("jit_ps_grad_step(1)", s, e - s) for s, e in runs]}}
    for k, (s, e) in enumerate(marks):
        xtrace[f"/host:CPU/{k}"] = {f"thread-{k}": [("compute", s, e - s)]}
    traced = _run(trace={"xtrace": xtrace,
                         "step_program": "jit_ps_grad_step",
                         "window": (0.0, 1.0)})
    assert read(traced) == pytest.approx((0.5 + 3.5 + 6.5 + 9.5) / 4)
    del traced["dl"]
    assert read(traced) is None


def test_the_roofline_share_asks_this_familys_floor():
    read = importlib.import_module(
        "chipbench.layer_metrics.step_hbm_roofline").read
    xtrace = {"/device:TPU:0": {
        "XLA Modules": [("jit_ps_grad_step(1)", 0.1, 0.002)],
        "XLA Ops": [("tpu_custom_call", 0.1, 0.002)]}}
    run_ = _run(family="dense_ps_bsp_delay", device_kind="TPU v5 lite",
                step={"rows": 384, "dim": 1000000, "nnz": 384 * 39},
                trace={"xtrace": xtrace, "step_program": "jit_ps_grad_step",
                       "window": (0.0, 1.0)})
    floor = 384 * 1000000 * 4 + 2 * 1000000 * 4
    assert read(run_) == pytest.approx(100.0 * floor / 819e9 / 0.002)


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
    for name in READERS:
        assert entries[name]["workloads"] == [CELL]
        assert callable(manifest.Cell(bench, CELL).layer_reader(name))
    # no new layer: each is named as the accepted benchmark names it
    assert [(entries[n]["layer"], entries[n]["moves"], entries[n]["source"])
            for n in READERS] == [
        ("PS worker round, BSP", "train_samples_per_s", "host_clock"),
        ("PS exchange, BSP", "train_samples_per_s", "program_span"),
        ("PS exchange, BSP", "train_samples_per_s", "program_span"),
        ("PS server barrier", "train_samples_per_s", "program_counter"),
        ("PS worker round, BSP", "train_samples_per_s", "device_trace"),
        ("PS exchange, BSP", "train_samples_per_s", "program_counter"),
        ("loader", "setup_s", "program_span")]
    were = {m["layer"] for m in bench["per_layer"]
            if m["name"] not in READERS}
    assert {entries[n]["layer"] for n in READERS} <= were
    for name in LIST_LESS:
        assert "workloads" not in entries[name]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "ps-bsp-delay-epochs", 1)
    assert len(cell["why"]) <= 200 and "withheld reply" in cell["why"]


def test_the_entries_that_were_there_are_as_they_were():
    """What ``test_dense_ps_softmax.py``'s last test says of PR 44's
    entries, without their place; nothing here says where in the lists
    this cell's own entries stand."""
    bench = manifest.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    sm = ["sm_step_mxu_share", "sm_round_ms", "sm_push_wait_ms",
          "sm_launch_wait_ms", "sm_shard_put_s"]
    at = names.index(sm[0])
    assert names[at:at + 5] == sm
    assert all(names.index(n) > at + 4 for n in READERS)
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:7] == ["dense-sync-1chip", "dense-ps-async-1chip",
                         "dense-ps-bsp-1chip", "dense-ps-bsp-4chip",
                         "dense-ps-bsp-eval-1chip",
                         "dense-ps-async-minibatch-1chip",
                         "softmax-ps-async-1chip"]
    assert cells.index(CELL) >= 7
    assert [c["name"] for c in bench["configs"]].index(CONFIG) >= 7
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # 2 + 14 runs a cell of run_seconds + 60, 2 x 90 more a cell, 1200 spare
    n = len(bench["workloads"])
    assert ((2 + 14 * n) * (bench["run_seconds"] + 60) + 180 * n
            + 1200) <= 43200
    assert ps_epochs.STEP_PROGRAM == driver.STEP_PROGRAM == "jit_ps_grad_step"
    assert driver.needs_the_barriers_counters is (
        ps_bsp_epochs.needs_the_barriers_counters)
