"""The minibatch PS cell: the rule that says which rows a round reads, the
runs that must not be ``correct``, the lineage of a round's weights, and
the cell's per-layer readers."""

import copy
import importlib
import json

import numpy as np
import pytest

from chipbench import datagen, manifest, reference, run
from chipbench.drivers import ps_epochs
from chipbench.drivers import ps_minibatch_epochs as driver
from chipbench.families import dense_ps, dense_ps_minibatch

CELL = "dense-ps-async-minibatch-1chip"
CONFIG = "distlr-ps-async-1m-minibatch"
READERS = ["mb_round_ms", "mb_overlap_share", "mb_push_wait_ms", "mb_drain_ms"]
LIST_LESS = ["compile_s", "input_wait_share", "step_ms", "step_hbm_roofline"]
WINDOW_ROWS = ["window_rows_short", "resident_short", "lineage_broken",
               "two_pass_rounds"]
# the traffic file's set-up, in epochs of a worker
RECORDED, PACE = 4, 22


def _rehearse(capsys, *extra):
    rc = run.main(["--workload", CELL, "--seed", "3000000019", "--seconds",
                   "0.2", "--trace", "0", "--rehearse", *extra])
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert rc == 0 and last.startswith("REHEARSAL ")
    return json.loads(last[len("REHEARSAL "):]), out


def _bad(doc):
    return {r["name"] for r in doc["compared"] if not r["ok"]}


# -- the reference: which rows, and their gradient -------------------------
@pytest.mark.parametrize("rows,batch,want", [
    (384, 128, [(0, 128), (128, 256), (256, 384), (0, 128), (128, 256)]),
    (100, 40, [(0, 40), (40, 80), (80, 100), (0, 40)]),
    (64, 64, [(0, 64), (0, 64)]),
])
def test_the_rule_serves_a_shard_in_file_order_every_epoch_from_row_0(
        rows, batch, want):
    got = [dense_ps_minibatch.window(k, rows, batch) for k in range(len(want))]
    assert [(s.start, s.stop) for s in got] == want
    per = dense_ps_minibatch.rounds_an_epoch(rows, batch)
    seen = np.concatenate([np.arange(rows)[dense_ps_minibatch.window(
        k, rows, batch)] for k in range(per)])
    assert np.array_equal(seen, np.arange(rows))  # every row once an epoch


@pytest.fixture(scope="module")
def shard():
    cols, vals, y = datagen.make_rows(
        77, "train", 96, fields="criteo-kaggle", num_buckets=4096,
        label_scale=0.5, label_bias=-1.0)
    w = np.random.default_rng(3).standard_normal(4096).astype(np.float32) * 0.05
    return w, cols, vals, y


def test_a_rounds_gradient_is_the_siblings_on_exactly_its_rows(shard):
    w, cols, vals, y = shard
    for k in (0, 1, 2, 3):
        at = dense_ps_minibatch.window(k, 96, 40)
        want = np.asarray(dense_ps.gradient(w, cols[at], vals[at], y[at]))
        got = np.asarray(dense_ps_minibatch.window_gradient(
            w, cols, vals, y, k, 40))
        assert np.array_equal(got, want)
    whole = np.asarray(dense_ps.gradient(w, cols, vals, y))
    assert np.linalg.norm(got - whole) > 1e-2 * np.linalg.norm(whole)


def test_the_family_is_the_siblings_but_for_the_window():
    assert dense_ps_minibatch.gradient is dense_ps.gradient
    assert dense_ps_minibatch.step_bytes_floor is dense_ps.step_bytes_floor
    assert reference.family("dense_ps_minibatch") is dense_ps_minibatch
    with open(dense_ps_minibatch.__file__) as f:
        assert "distlr_tpu" not in f.read()
    # the floor the driver asks for is the window's, not the shard's
    assert dense_ps_minibatch.step_bytes_floor(
        rows=128, dim=1_000_000, nnz=0) == 128 * 1_000_000 * 4 + 8_000_000


def test_the_configuration_differs_from_the_siblings_in_the_batch_alone():
    bench = manifest.load_benchmark()
    conf = manifest.Cell(bench, CELL).config
    sib = manifest.Cell(bench, "dense-ps-async-1chip").config
    assert {**conf["program"], "batch_size": -1} == sib["program"]
    assert conf["program"]["batch_size"] == 128
    assert conf["generator"] == sib["generator"]
    assert conf["control"]["program"] == sib["control"]["program"] == {
        "ps_compress": "int8"}
    assert conf["control"]["precision"] == "bfloat16"
    assert conf["guarantees"][:4] == sib["guarantees"]
    assert len(conf["guarantees"]) == 7
    assert conf["architecture"] is None and conf["family"] == "dense_ps_minibatch"
    assert conf["reduced"] == ["train_rows", "test_rows", "num_iteration"]
    assert set(conf["reduced_why"]) == set(conf["reduced"])
    assert "one push in three" in conf["reduced_why"]["train_rows"]
    assert "B = 128" in conf["assumed"]["batch_size"]
    for ref in ("local.sh:19", "src/main.cc:154", "data_iter.h:40-59",
                "src/lr.cc:116-132"):
        assert ref in conf["source"]
    # the sibling's six limits as it states them, and four that admit 0
    assert {k: conf["limits"][k] for k in sib["limits"]} == sib["limits"]
    assert {k: conf["limits"][k] for k in WINDOW_ROWS} == dict.fromkeys(
        WINDOW_ROWS, 0.5)
    prog, gen = conf["program"], conf["generator"]
    resident = (prog["num_workers"] * gen["rows_per_worker"]
                * prog["num_feature_dim"] * 4)
    assert resident >= 0.25 * 16 * 2**30  # the floor: a quarter of the chip
    assert gen["rows_per_worker"] % prog["batch_size"] == 0
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == conf["reduced"]


# -- whole runs -------------------------------------------------------------
def test_the_rehearsal_is_correct_and_names_every_new_metric(capsys):
    doc, out = _rehearse(capsys)
    assert doc["correct"] is True, out
    assert set(READERS) | {"compile_s", "input_wait_share", "step_ms"} <= set(
        doc["layer_metrics"])
    names = [r["name"] for r in doc["compared"]]
    assert names == ["grad_norm_rel_gap", "grad_diff_rel", "conservation_rel",
                     "update_missing", "unacknowledged_recorded",
                     "test_logloss_rel_gap", "unacknowledged_window",
                     *WINDOW_ROWS]
    assert "rounds_an_epoch=3" in out and "placing_spans=0" in out
    assert f"recorded rounds={[3 * RECORDED] * 4}" in out
    assert "reference gradients of 4 x 6 rounds' windows" in out


def _with_program(monkeypatch, over):
    real = driver.effective_config

    def changed(cell, rehearsal):
        conf = copy.deepcopy(real(cell, rehearsal))
        conf["program"].update(over(conf))
        return conf

    monkeypatch.setattr(driver, "effective_config", changed)


def _a_window_that_never_advances(monkeypatch):
    from distlr_tpu.data.iterator import DataIter

    real = DataIter.next_window

    def stuck(self):
        return real(self)._replace(first=0)

    monkeypatch.setattr(DataIter, "next_window", stuck)


def _a_worker_that_streams(monkeypatch):
    from distlr_tpu.train import ps_trainer

    monkeypatch.setattr(ps_trainer.PSWorker, "_place_shard",
                        lambda self, train, dev: None)


def _the_int8_wire(monkeypatch):
    _with_program(monkeypatch, lambda conf: conf["control"]["program"])


def _half_a_shard(monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker._place_shard

    def half(self, train, dev):
        X, y, mask = real(self, train, dev)
        return X, y, mask.at[::2].set(False)

    monkeypatch.setattr(ps_trainer.PSWorker, "_place_shard", half)


def _a_window_one_epoch_short(monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker.fit

    def one_short(self, epochs=None, **kw):
        # a worker's third fit is the window: the recorded phase and the
        # pacing epochs come before it
        if self.epochs_done == RECORDED + PACE:
            epochs -= 1
        return real(self, epochs, **kw)

    monkeypatch.setattr(ps_trainer.PSWorker, "fit", one_short)


class _NoDrain:
    """A comm pool whose worker does not wait at an epoch's end: the
    future of an epoch's last push answers at once with the weights the
    worker already holds, and the push stays in flight into the next
    epoch, a second one behind it."""

    def __init__(self, pool, worker, rounds_an_epoch):
        self.pool, self.worker, self.per = pool, worker, rounds_an_epoch
        self.n = 0

    def submit(self, fn, *args):
        fut = self.pool.submit(fn, *args)
        self.n += 1
        if self.n % self.per:
            return fut
        held = self.worker._w_cache
        return type("Held", (), {"result": lambda self: held})()

    def __getattr__(self, name):
        return getattr(self.pool, name)


def _a_drain_skipped(monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker._comm_pool

    def pool(self):
        made = real(self)
        if not isinstance(made, _NoDrain):
            made = self._comm = _NoDrain(made, self, 3)
        return made

    monkeypatch.setattr(ps_trainer.PSWorker, "_comm_pool", pool)


WINDOWS_SOUND = {"window_rows_short", "resident_short", "lineage_broken"}
SERVERS_SOUND = {"conservation_rel", "unacknowledged_recorded",
                 "unacknowledged_window", "update_missing"}


@pytest.mark.parametrize("fault,must_fail,must_hold", [
    (_a_window_that_never_advances, {"grad_diff_rel"},
     WINDOWS_SOUND | SERVERS_SOUND),
    (_a_worker_that_streams, {"window_rows_short", "resident_short"},
     SERVERS_SOUND | {"grad_diff_rel", "grad_norm_rel_gap", "lineage_broken"}),
    # what the workers computed was sound, and of the right rows: the
    # wire lost it
    (_the_int8_wire, {"conservation_rel"},
     WINDOWS_SOUND | {"grad_diff_rel", "grad_norm_rel_gap",
                      "unacknowledged_recorded", "unacknowledged_window"}),
    (_half_a_shard, {"grad_diff_rel"}, WINDOWS_SOUND | SERVERS_SOUND),
    # the counts of the window disagree with the rate's; no row of the
    # recorded phase has anything to say
    (_a_window_one_epoch_short, {"window_rows_short"},
     SERVERS_SOUND - {"unacknowledged_window"}
     | {"grad_diff_rel", "lineage_broken", "resident_short"}),
    (_a_drain_skipped, {"lineage_broken"},
     {"grad_diff_rel", "grad_norm_rel_gap", "window_rows_short",
      "resident_short"}),
], ids=["window-never-advances", "streams", "int8-wire", "half-a-shard",
        "one-epoch-short", "drain-skipped"])
def test_a_faulted_run_is_not_correct(capsys, monkeypatch, fault, must_fail,
                                      must_hold):
    fault(monkeypatch)
    doc, out = _rehearse(capsys)
    assert doc["correct"] is False
    assert must_fail <= _bad(doc), out
    assert not must_hold & _bad(doc), out


def test_a_program_that_counts_no_windows_leaves_at_once(monkeypatch):
    """What the parent of the PR that added the cell does: its minibatch
    worker streams and keeps neither series, so the driver says which it
    misses and makes no row."""
    from distlr_tpu.obs import registry

    monkeypatch.setattr(registry, "REGISTRY", registry.MetricsRegistry())
    monkeypatch.setattr(driver, "prepare", None)  # never reached
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.2",
                  "--trace", "0", "--rehearse"])
    assert e.value.code not in (0, None)
    for series in (driver.WINDOW_ROUNDS, driver.WINDOW_ROWS):
        assert series in str(e.value.code)


def test_the_control_tool_reads_both_sides(capsys):
    rc = driver.main(["--workload", CELL, "--seeds", "11,12",
                      "--controls", "1", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out.strip().splitlines()[-1][len("CONTROL "):])
    cons = doc["summary"]["conservation_rel"]
    assert cons["sound_max"] < cons["limit"] < cons["control_min"]
    assert cons["bfloat16_min"] <= cons["sound_max"]
    assert doc["summary"]["grad_diff_rel"]["bfloat16_min"] > 1e-4
    for name in WINDOW_ROWS[:3]:
        assert doc["summary"][name]["sound_max"] == 0


def test_the_cells_limits_tell_the_bfloat16_reference_from_float32(shard):
    """The reference's own gradients of three rounds' windows, in float32
    and in bfloat16, where a worker's pushed gradients stand, held to the
    limits the cell has on the chip; and a gradient of the wrong window
    in float32 fails them too."""
    w, cols, vals, y = shard
    limits = manifest.Cell(manifest.load_benchmark(), CELL).config["limits"]
    rows = {"shards": [(cols, vals, y)], "test": (cols, vals, y)}
    grads = [np.asarray(dense_ps_minibatch.window_gradient(
        w, cols, vals, y, k, 32)) for k in range(4)]
    total = sum(g.astype(np.float64) for g in grads)
    after = (w - 0.2 * total).astype(np.float32)
    got = {"first": [[(w, g) for g in grads]], "w_before": w, "w_after": after,
           "pushed_sum": total, "unacknowledged": 0,
           "window_rows_short": 0, "two_pass_rounds": 0, "lineage_broken": 0,
           "resident": {"0": 96 * 4096 * 4},
           "test_logloss": reference.logloss("dense_ps_minibatch", after,
                                             cols, vals, y)}

    def bad(got):
        return {r["name"] for r in driver.compare(
            rows, got, "dense_ps_minibatch", 0.2, 4096, 32, limits)
            if not r["ok"]}

    assert not bad(got)
    low = driver.lowered(rows, got, "dense_ps_minibatch", "bfloat16", 32)
    assert {"grad_norm_rel_gap", "grad_diff_rel"} <= bad(low)
    assert "conservation_rel" not in bad(low)
    stuck = {**got, "first": [[(w, grads[0])] * 4]}
    assert "grad_diff_rel" in bad(stuck)
    assert "resident_short" in bad({**got, "resident": {}})


# -- the lineage of a round's weights ---------------------------------------
def _lineage(on, replies, opening=b"open"):
    return {"opening": opening, "rounds": list(on), "replies": list(replies)}


def test_the_lineage_is_one_push_behind_and_none_across_an_epochs_end():
    r = [bytes([k]) for k in range(6)]
    sound = [b"open", b"open", r[0], r[2], r[2], r[3]]
    assert driver.lineage_broken(_lineage(sound, r), 3) == 0
    # the drain skipped: the next epoch opens on the reply before the last
    skipped = [b"open", b"open", r[0], r[1], r[1], r[3]]
    assert driver.lineage_broken(_lineage(skipped, r), 3) == 2
    # serialized rounds (nothing in flight) are another lineage
    serial = [b"open", r[0], r[1], r[2], r[3], r[4]]
    assert driver.lineage_broken(_lineage(serial, r), 3) == 4
    # a whole-shard worker: every round opens an epoch
    assert driver.lineage_broken(
        _lineage([b"open", r[0], r[1]], r[:3]), 1) == 0
    # a reply that never came counts
    assert driver.lineage_broken(_lineage(sound, r[:5]), 3) == 1


def test_the_lineage_tap_leaves_the_worker_as_it_found_it():
    class KV:
        def pull(self):
            return np.zeros(4, np.float32)

        def push_pull(self, g):
            return g + 1

    worker = type("W", (), {})()
    worker.kv, worker._w_cache = KV(), None
    worker.grad_step = step = lambda wf, batch: wf * 2
    tap = driver.Lineage(worker)
    w = worker.kv.pull()
    g = worker.grad_step(w, None)
    worker.kv.push_pull(g)
    lin = tap.remove()
    assert worker.grad_step is step and "pull" not in vars(worker.kv)
    assert lin["opening"] == lin["rounds"][0] == driver._digest(w)
    assert lin["replies"] == [driver._digest(g + 1)]


# -- the per-layer readers ---------------------------------------------------
def _events():
    """Rank 0, two epochs of two rounds: compute starts every 10 ms; round
    1's wire (8 ms) lies 3 ms under round 2's chain; round 2's is drained."""
    def ev(name, ts_ms, dur_ms, step, **more):
        return {"name": name, "ts": ts_ms * 1e3, "dur": dur_ms * 1e3,
                "args": {"rank": 0, "step": step, **more}}

    return [
        ev("w_put", 0, 1, 1), ev("compute", 1, 3, 1), ev("grad_d2h", 4, 1, 1),
        ev("wire", 5, 8, 1),
        ev("w_put", 8, 1, 2), ev("compute", 11, 3, 2), ev("grad_d2h", 14, 1, 2),
        ev("push", 15, 0.5, 2), ev("wire", 16, 5, 2),
        ev("push", 16, 6, 2, drain=1),
        ev("compute", 21, 3, 3), ev("wire", 25, 2, 3),
        {"name": "load_data", "ts": 0, "dur": 5, "args": {}},
    ]


@pytest.mark.parametrize("name,want", [
    ("mb_round_ms", 10.0), ("mb_push_wait_ms", 0.5), ("mb_drain_ms", 6.0),
    # of 15 ms of wire: 8-9 and 11-13 under round 2's chain, none of round
    # 2's under round 3's (it began after the drain), none of round 3's
    ("mb_overlap_share", 100.0 * 3 / 15)])
def test_a_reader_on_recorded_events(name, want):
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    side = driver.mb_side(_events(), 0)
    assert read({"mb": side}) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_nothing_where_the_run_has_no_such_side(name):
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    run_ = {"window": {"wall_s": 8.0, "spans": {
        "compute": {"seconds": 1.0, "count": 10, "self_seconds": 1.0}}},
        "trace": None}
    assert read(run_) is None
    assert read({"mb": driver.mb_side([], 0)}) is None


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
        assert entries[name]["moves"] == "train_samples_per_s"
        assert entries[name]["source"] == "program_span"
        assert callable(manifest.Cell(bench, CELL).layer_reader(name))
    # no new layer: each is named as the accepted benchmark names it
    assert [entries[n]["layer"] for n in READERS] == [
        "PS worker round", "PS exchange", "PS exchange", "PS exchange"]
    assert (entries["mb_overlap_share"]["unit"],
            entries["mb_overlap_share"]["better"]) == ("%", "higher")
    for name in LIST_LESS:
        assert "workloads" not in entries[name]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "ps-minibatch-epochs", 1)
    assert len(cell["why"]) <= 200


def test_the_entries_that_were_there_are_as_they_were():
    """What ``test_dense_ps_bsp_eval.py``'s last test says of PR 36's
    entries but for their place (that test holds them to the end of their
    lists, where this cell's had to be appended: ``tests/conftest.py``).
    Nothing here says where in the lists this cell's own entries stand."""
    bench = manifest.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    evals = ["eval_ms", "eval_pull_ms", "eval_compute_ms", "eval_share",
             "eval_round_stall_ms", "eval_hbm_roofline", "test_put_s"]
    at = names.index("eval_ms")
    assert names[at:at + 7] == evals
    assert all(names.index(n) > at + 6 for n in READERS)
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:5] == ["dense-sync-1chip", "dense-ps-async-1chip",
                         "dense-ps-bsp-1chip", "dense-ps-bsp-4chip",
                         "dense-ps-bsp-eval-1chip"]
    assert cells.index(CELL) >= 5
    assert [c["name"] for c in bench["configs"]].index(CONFIG) >= 5
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # 2 + 14 runs a cell of run_seconds + 60, 2 x 90 more a cell, 1200 spare
    n = len(bench["workloads"])
    assert ((2 + 14 * n) * (bench["run_seconds"] + 60) + 180 * n
            + 1200) <= 43200
    assert ps_epochs.STEP_PROGRAM == driver.STEP_PROGRAM == "jit_ps_grad_step"
