"""The multiclass PS cell: its reference, its generator, the runs that
must not be ``correct`` (each by the row that names its fault), and its
per-layer readers."""

import copy
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import manifest, newsgen, reference, run
from chipbench.drivers import ps_epochs
from chipbench.drivers import ps_softmax_epochs as driver
from chipbench.families import dense_ps_softmax as family

CELL = "softmax-ps-async-1chip"
CONFIG = "news20-ps-async-softmax"
READERS = ["sm_step_mxu_share", "sm_round_ms", "sm_push_wait_ms",
           "sm_launch_wait_ms", "sm_shard_put_s"]
LIST_LESS = ["compile_s", "input_wait_share", "step_ms", "step_hbm_roofline"]
OWN_ROWS = ["resident_short", "classes_short"]
K = 20
# the traffic file's set-up, in iterations of a worker
RECORDED, PACE = 12, 64


def _rehearse(capsys, *extra):
    rc = run.main(["--workload", CELL, "--seed", "3000000019", "--seconds",
                   "0.2", "--trace", "0", "--rehearse", *extra])
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert rc == 0 and last.startswith("REHEARSAL ")
    return json.loads(last[len("REHEARSAL "):]), out


def _bad(doc):
    return {r["name"] for r in doc["compared"] if not r["ok"]}


# -- the generator ----------------------------------------------------------
@pytest.fixture(scope="module")
def rows():
    return newsgen.make_rows(77, "train", 300, vocab=2048, classes=K, nnz=80)


def test_the_generator_is_seeded_and_makes_unit_rows_of_80_words(rows):
    cols, vals, y = rows
    again = newsgen.make_rows(77, "train", 300, vocab=2048, classes=K, nnz=80)
    assert all(np.array_equal(a, b) for a, b in zip(rows, again))
    other = newsgen.make_rows(78, "train", 300, vocab=2048, classes=K, nnz=80)
    test = newsgen.make_rows(77, "test", 300, vocab=2048, classes=K, nnz=80)
    assert not np.array_equal(cols, other[0]) and not np.array_equal(cols, test[0])
    assert cols.shape == vals.shape == (300, 80)
    assert (cols.dtype, vals.dtype, y.dtype) == (np.int32, np.float32, np.int32)
    assert (np.diff(cols, axis=1) > 0).all()        # 80 distinct words a row
    assert cols.min() >= 0 and cols.max() < 2048
    assert (vals > 0).all()                          # tf-idf: positive
    assert np.allclose(np.linalg.norm(vals, axis=1), 1.0, atol=1e-6)
    assert set(np.unique(y)) == set(range(K))        # every class present


def test_the_law_is_zipfs_and_the_head_weighs_least(rows):
    law = newsgen.word_law(62061)
    assert law[0] / law[20] == pytest.approx(2.0)    # 1 / (rank + 20)
    assert law.sum() == pytest.approx(1.0)
    # at the cell's own width every class is in every worker's shard
    _, _, y = newsgen.make_rows(5, "train", 1024, vocab=62061, classes=K, nnz=80)
    counts = np.bincount(y, minlength=K)
    assert counts.min() > 0 and counts.max() < 4 * counts.min()


def test_the_workers_loader_reads_the_text_back_bit_for_bit(rows, tmp_path):
    from distlr_tpu.data import DataIter

    cols, vals, y = rows
    path = str(tmp_path / "train" / "part-001")
    newsgen.write_libsvm(path, cols, vals, y)
    X, yy, mask = DataIter.from_file(path, 2048, -1, multiclass=True).whole_shard()
    want = np.zeros((300, 2048), np.float32)
    want[np.arange(300)[:, None], cols] = vals
    assert np.array_equal(X, want) and np.array_equal(yy, y) and mask.all()


# -- the reference -----------------------------------------------------------
@pytest.fixture(scope="module")
def shard(rows):
    w = np.random.default_rng(3).standard_normal(2048 * K).astype(
        np.float32) * 0.5
    return (w, *rows)


def test_the_gradient_is_jax_grads_of_the_familys_own_loss(shard):
    w, cols, vals, y = shard
    want = np.asarray(jax.grad(
        lambda v: family.loss(v, cols, vals, y, K))(jnp.asarray(w)))
    got = np.asarray(family.gradient(w, cols, vals, y, K))
    assert got.dtype == np.float32 and got.shape == (2048 * K,)
    assert np.linalg.norm(got - want) <= 2e-6 * np.linalg.norm(want)


def test_the_gradient_is_float64_numpys_in_the_programs_order(shard):
    w, cols, vals, y = shard
    X = np.zeros((len(y), 2048))
    X[np.arange(len(y))[:, None], cols] = vals
    z = X @ w.astype(np.float64).reshape(2048, K)    # feature-major
    z -= z.max(axis=1, keepdims=True)
    p = np.exp(z) / np.exp(z).sum(axis=1, keepdims=True)
    ll = -np.log(p[np.arange(len(y)), y]).mean()
    p[np.arange(len(y)), y] -= 1.0
    want = (X.T @ p / len(y)).reshape(-1)
    got = np.asarray(family.gradient(w, cols, vals, y, K))
    assert np.linalg.norm(got - want) <= 2e-6 * np.linalg.norm(want)
    got_ll, acc = family.evaluate(w, cols, vals, y, K)
    assert got_ll == pytest.approx(ll, rel=1e-6)
    assert acc == pytest.approx((z.argmax(axis=1) == y).mean())
    # the server's rule on one push
    before, after = family.step(w, cols, vals, y, 0.2, K)
    assert float(before) == pytest.approx(ll, rel=1e-6)
    assert np.allclose(np.asarray(after), w - 0.2 * want, atol=1e-6)


def test_the_bfloat16_reference_is_another_gradient(shard):
    w, cols, vals, y = shard
    f32 = np.asarray(family.gradient(w, cols, vals, y, K))
    low = np.asarray(family.gradient(w, cols, vals, y, K, precision="bfloat16"))
    assert 1e-4 < np.linalg.norm(low - f32) / np.linalg.norm(f32) < 2e-2


def test_the_family_brings_its_floors_and_nothing_of_the_program():
    assert reference.family("dense_ps_softmax") is family
    with open(family.__file__) as f:
        assert "distlr_tpu" not in f.read()
    assert family.step_bytes_floor(rows=3968, dim=62061, classes=20, nnz=0) == (
        3968 * 62061 * 4 + 2 * 62061 * 20 * 4)
    assert family.step_flops(rows=3968, dim=62061, classes=20) == (
        4 * 3968 * 62061 * 20)


def test_the_configuration_states_its_deployment_its_cut_and_its_size():
    bench = manifest.load_benchmark()
    conf = manifest.Cell(bench, CELL).config
    sib = manifest.Cell(bench, "dense-ps-async-1chip").config
    prog, gen = conf["program"], conf["generator"]
    assert (prog["model"], prog["num_classes"], prog["num_feature_dim"]) == (
        "softmax", 20, 62061)
    assert prog["feature_dtype"] == prog["compute_dtype"] == "float32"
    # the sibling's job but for the family
    shared = ("sync_mode", "num_workers", "num_servers", "batch_size",
              "learning_rate", "l2_c", "ps_optimizer", "ps_compress",
              "ps_accum_max")
    assert {k: prog[k] for k in shared} == {k: sib["program"][k] for k in shared}
    assert conf["architecture"] is None and conf["family"] == "dense_ps_softmax"
    assert conf["reduced"] == ["train_rows", "test_rows", "num_iteration"]
    assert set(conf["reduced_why"]) == set(conf["reduced"])
    assert "15,935 -> 15,872" in conf["reduced_why"]["train_rows"]
    assert "3,993 -> 3,968" in conf["reduced_why"]["test_rows"]
    assert "from memory" in conf["assumed"]["figures"]
    assert conf["guarantees"][:4] == sib["guarantees"]
    assert len(conf["guarantees"]) == 7
    assert conf["control"]["program"] == {"compute_dtype": "bfloat16"}
    assert conf["control"]["precision"] == "bfloat16"
    assert {k: conf["limits"][k] for k in OWN_ROWS} == dict.fromkeys(
        OWN_ROWS, 0.5)
    assert set(sib["limits"]) <= set(conf["limits"])
    # whole sublane groups, four equal shards, and over the floor with the split
    assert gen["rows_per_worker"] == gen["test_rows"] == 31 * 128
    assert prog["num_workers"] * gen["rows_per_worker"] == 15872
    held = (prog["num_workers"] * gen["rows_per_worker"] + gen["test_rows"]) \
        * prog["num_feature_dim"] * 4
    assert held >= 0.25 * 16 * 2**30
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == conf["reduced"]
    assert "news20" in entry["source"] and "62,061" in entry["source"]


# -- whole runs ---------------------------------------------------------------
def test_the_rehearsal_is_correct_and_names_every_new_metric(capsys):
    doc, out = _rehearse(capsys)
    assert doc["correct"] is True, out
    # the two readers of the device's trace read nothing untraced
    assert (set(READERS) - {"sm_step_mxu_share", "sm_launch_wait_ms"}
            | {"compile_s", "input_wait_share", "step_ms"}) <= set(
        doc["layer_metrics"])
    names = [r["name"] for r in doc["compared"]]
    assert names == ["grad_norm_rel_gap", "grad_diff_rel", "conservation_rel",
                     "update_missing", "unacknowledged_recorded",
                     "test_logloss_rel_gap", "unacknowledged_window", *OWN_ROWS]
    assert f"recorded rounds={[RECORDED] * 4}" in out
    assert ("held path=['two_pass'] distlr_ps_step_classes=[20, 20, 20, 20] "
            "resident_layout=['default']") in out
    assert "placing_spans=0" in out
    assert "reference gradients of 4 x 3 rounds" in out


def _with_program(monkeypatch, over):
    real = driver.effective_config

    def changed(cell, rehearsal):
        conf = copy.deepcopy(real(cell, rehearsal))
        conf["program"].update(over)
        return conf

    monkeypatch.setattr(driver, "effective_config", changed)


def _bfloat16_products(monkeypatch):
    _with_program(monkeypatch, {"compute_dtype": "bfloat16"})


def _the_int8_wire(monkeypatch):
    _with_program(monkeypatch, {"ps_compress": "int8"})


def _half_a_shard(monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker._place_shard

    def half(self, train, dev):
        X, y, mask = real(self, train, dev)
        return X, y, mask.at[::2].set(False)

    monkeypatch.setattr(ps_trainer.PSWorker, "_place_shard", half)


def _a_class_column_zeroed_before_the_push(monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker._bind_dense_step

    def bind(self, train, test):
        real(self, train, test)
        step = self.grad_step

        def zeroed(wf, batch):
            g = step(wf, batch).reshape(-1, K).copy()
            g[:, 7] = 0.0
            return g.reshape(-1)

        self.grad_step = zeroed

    monkeypatch.setattr(ps_trainer.PSWorker, "_bind_dense_step", bind)


def _the_split_streamed_at_the_evaluate(monkeypatch):
    from distlr_tpu.train import ps_trainer

    # the device says it has no room: the eval streams the split
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


SERVERS_SOUND = {"conservation_rel", "unacknowledged_recorded",
                 "unacknowledged_window", "update_missing"}
GRADS_SOUND = {"grad_norm_rel_gap", "grad_diff_rel"}


@pytest.mark.parametrize("fault,must_fail,must_hold", [
    # the control: one MXU pass where the configuration states float32
    (_bfloat16_products, GRADS_SOUND, SERVERS_SOUND | set(OWN_ROWS)),
    # what the workers computed was sound: the wire lost it, and carried
    # a quarter of the bytes 20 float32 columns come to
    (_the_int8_wire, {"conservation_rel", "classes_short"},
     GRADS_SOUND | {"resident_short", "unacknowledged_recorded",
                    "unacknowledged_window"}),
    (_half_a_shard, {"grad_diff_rel"}, SERVERS_SOUND | set(OWN_ROWS)),
    (_a_class_column_zeroed_before_the_push, {"classes_short"},
     SERVERS_SOUND | {"resident_short"}),
    (_the_split_streamed_at_the_evaluate, {"resident_short"},
     SERVERS_SOUND | GRADS_SOUND | {"classes_short", "test_logloss_rel_gap"}),
    # the window pushed a round's bytes less than the rate's rounds carry
    (_a_window_one_round_short, {"classes_short"},
     SERVERS_SOUND - {"unacknowledged_window"} | GRADS_SOUND
     | {"resident_short"}),
], ids=["bfloat16-products", "int8-wire", "half-a-shard", "a-class-zeroed",
        "split-streamed", "one-round-short"])
def test_a_faulted_run_is_not_correct(capsys, monkeypatch, fault, must_fail,
                                      must_hold):
    fault(monkeypatch)
    doc, out = _rehearse(capsys)
    assert doc["correct"] is False
    assert must_fail <= _bad(doc), out
    assert not must_hold & _bad(doc), out


def test_a_program_that_states_no_class_axis_leaves_at_once(monkeypatch):
    """What the parent of the PR that added the cell does: its PSWorker
    keeps neither series (and its float32 products state no precision),
    so the driver says which it misses and makes no row."""
    from distlr_tpu.obs import registry

    monkeypatch.setattr(registry, "REGISTRY", registry.MetricsRegistry())
    monkeypatch.setattr(driver, "prepare", None)  # never reached
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.2",
                  "--trace", "0", "--rehearse"])
    assert e.value.code not in (0, None)
    for series in (driver.STEP_CLASSES, driver.RESIDENT_LAYOUT):
        assert series in str(e.value.code)


def test_the_control_tool_reads_all_three_sides(capsys):
    rc = driver.main(["--workload", CELL, "--seeds", "11,12",
                      "--controls", "1", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out.strip().splitlines()[-1][len("CONTROL "):])
    for name in ("grad_norm_rel_gap", "grad_diff_rel"):
        got = doc["summary"][name]
        assert got["sound_max"] < got["limit"] < min(got["control_min"],
                                                     got["bfloat16_min"])
    cons = doc["summary"]["conservation_rel"]
    assert max(cons["sound_max"], cons["control_min"]) < cons["limit"]
    for name in OWN_ROWS:
        assert doc["summary"][name]["sound_max"] == 0


def test_the_cells_limits_tell_bfloat16_from_float32_and_a_class_from_none(shard):
    """The reference's own gradients in float32 and in bfloat16 where a
    worker's pushed gradients stand, held to the limits the cell has on
    the chip; and the two rows of the cell's own."""
    w, cols, vals, y = shard
    w = w * np.float32(0.1)  # small beside the pushes, as a run's are
    limits = manifest.Cell(manifest.load_benchmark(), CELL).config["limits"]
    rows = {"shards": [(cols, vals, y)], "test": (cols, vals, y)}
    grads = [np.asarray(family.gradient(w, cols, vals, y, K))] * 3
    total = sum(g.astype(np.float64) for g in grads)
    after = (w - 0.2 * total).astype(np.float32)
    nbytes = len(y) * 2048 * 4
    got = {"first": [[(w, g) for g in grads]], "w_before": w, "w_after": after,
           "pushed_sum": total, "unacknowledged": 0,
           "held": {"resident": {"0": nbytes}, "test_resident": nbytes,
                    "classes": {"0": K}, "layout": {"0": "default"}},
           "test_logloss": family.evaluate(after, cols, vals, y, K)[0]}

    def bad(got, window=None):
        return {r["name"] for r in driver.compare(
            rows, got, "dense_ps_softmax", 0.2, 2048, K, limits, window)
            if not r["ok"]}

    assert not bad(got)
    low = driver.lowered(rows, got, "dense_ps_softmax", K, "bfloat16")
    assert {"grad_norm_rel_gap", "grad_diff_rel"} & bad(low)
    assert "grad_diff_rel" in bad(low) and "conservation_rel" not in bad(low)
    one_gone = grads[0].reshape(-1, K).copy()
    one_gone[:, 3] = 0.0
    assert "classes_short" in bad({**got, "first": [[(w, one_gone.reshape(-1))]]})
    held = got["held"]
    assert bad({**got, "held": {**held, "resident": {}}}) == {"resident_short"}
    assert bad({**got, "held": {**held, "test_resident": 0}}) == {
        "resident_short"}
    sound = {"unacknowledged": 0, "placed": 0, "bytes_short": 0}
    assert not bad(got, sound)
    assert bad(got, {**sound, "placed": 1}) == {"resident_short"}
    assert bad(got, {**sound, "bytes_short": 4964880}) == {"classes_short"}
    assert bad(got, {**sound, "unacknowledged": 1}) == {"unacknowledged_window"}


def test_live_columns_counts_the_classes_a_gradient_carries():
    g = np.zeros((5, 4), np.float32)
    assert driver.live_columns(g.reshape(-1), 4) == 0
    g[2, 1] = 1e-30
    g[4, 3] = -2.0
    assert driver.live_columns(g.reshape(-1), 4) == 2


# -- the per-layer readers ----------------------------------------------------
def _run(**more):
    return {"window": {"wall_s": 8.0, "spans": {
        "compute": {"seconds": 1.0, "count": 10, "self_seconds": 1.0},
        "push": {"seconds": 0.25, "count": 100, "self_seconds": 0.25}}},
        "trace": None, **more}


def _traced():
    """Two runs of the step program on the device, 10 ms busy each, the
    second worker's queued behind the first's; each ``compute`` annotation
    opens 1 ms before the first run and ends with its own."""
    step = "jit_ps_grad_step(123)"
    xtrace = {
        "/device:TPU:0": {
            "XLA Modules": [(step, 1.001, 0.010), (step, 1.011, 0.010)],
            "XLA Ops": [("fusion.8", 1.001, 0.005), ("fusion.3", 1.006, 0.005),
                        ("fusion.8", 1.011, 0.005), ("fusion.3", 1.016, 0.005)]},
        "/host:CPU": {"worker-0": [("compute", 1.000, 0.0111)],
                      "worker-1": [("compute", 1.000, 0.0211)]}}
    return {"xtrace": xtrace, "window": (0.9, 1.1),
            "step_program": driver.STEP_PROGRAM}


@pytest.mark.parametrize("name,want", [
    ("sm_round_ms", 1e3 * 8.0 / 400), ("sm_push_wait_ms", 2.5),
    # 4e9 useful flops in 10 ms busy, of 197e12 a second
    ("sm_step_mxu_share", 100.0 * 4e9 / 197e12 / 0.010),
    # the first worker's run starts 1 ms into its span, the second's 11 ms
    ("sm_launch_wait_ms", 6.0)])
def test_a_reader_on_a_recorded_run(name, want):
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    run_ = _run(sm={"rounds_per_worker": 400, "step_flops": 4e9},
                device_kind="TPU v5 lite", trace=_traced())
    assert read(run_) == pytest.approx(want)


def test_the_shard_put_reader_sums_the_workers_spans():
    from distlr_tpu.obs.tracing import loop_span

    read = importlib.import_module("chipbench.layer_metrics.sm_shard_put_s").read
    before = read(_run(sm={})) or 0.0
    with loop_span("shard_put", 0, rank=0):
        pass
    assert read(_run(sm={"rounds_per_worker": 1})) >= before
    assert read(_run(sm={"rounds_per_worker": 1})) is not None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_nothing_where_the_run_has_no_such_side(name):
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    assert read(_run()) is None
    assert read(_run(trace=_traced(), device_kind="TPU v5 lite")) is None


def test_the_roofline_share_asks_this_familys_floor():
    read = importlib.import_module(
        "chipbench.layer_metrics.step_hbm_roofline").read
    run_ = _run(family="dense_ps_softmax", device_kind="TPU v5 lite",
                step={"rows": 3968, "dim": 62061, "classes": 20, "nnz": 0},
                trace=_traced())
    floor = 3968 * 62061 * 4 + 2 * 62061 * 20 * 4
    assert read(run_) == pytest.approx(100.0 * floor / 819e9 / 0.010)


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
        ("XLA step program", "train_samples_per_s", "device_trace"),
        ("PS worker round", "train_samples_per_s", "host_clock"),
        ("PS exchange", "train_samples_per_s", "program_span"),
        ("PS worker round", "train_samples_per_s", "device_trace"),
        ("loader", "setup_s", "program_span")]
    assert (entries["sm_step_mxu_share"]["unit"],
            entries["sm_step_mxu_share"]["better"]) == ("%", "higher")
    for name in LIST_LESS:
        assert "workloads" not in entries[name]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "ps-softmax-epochs", 1)
    assert len(cell["why"]) <= 200
    assert manifest.Cell(bench, CELL).traffic["kind"] == "ps-softmax-epochs"


def test_the_entries_that_were_there_are_as_they_were():
    """What ``test_dense_ps_minibatch.py``'s last test says of PR 40's
    entries, without their place; nothing here says where in the lists
    this cell's own entries stand."""
    bench = manifest.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    mb = ["mb_round_ms", "mb_overlap_share", "mb_push_wait_ms", "mb_drain_ms"]
    at = names.index("mb_round_ms")
    assert names[at:at + 4] == mb
    assert all(names.index(n) > at + 3 for n in READERS)
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:6] == ["dense-sync-1chip", "dense-ps-async-1chip",
                         "dense-ps-bsp-1chip", "dense-ps-bsp-4chip",
                         "dense-ps-bsp-eval-1chip",
                         "dense-ps-async-minibatch-1chip"]
    assert cells.index(CELL) >= 6
    assert [c["name"] for c in bench["configs"]].index(CONFIG) >= 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # 2 + 14 runs a cell of run_seconds + 60, 2 x 90 more a cell, 1200 spare
    n = len(bench["workloads"])
    assert ((2 + 14 * n) * (bench["run_seconds"] + 60) + 180 * n
            + 1200) <= 43200
    assert ps_epochs.STEP_PROGRAM == driver.STEP_PROGRAM == "jit_ps_grad_step"
