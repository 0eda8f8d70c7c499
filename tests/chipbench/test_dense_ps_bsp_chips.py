"""The lock-step PS cell laid out a worker to a chip: its whole runs on
four virtual devices (sound, and faulted: not ``correct``), the program
that cannot place a worker on a chip of its own, its configuration, its
per-layer readers on a hand-built four-plane trace, and its entries in
``BENCHMARK.json`` (what
``test_dense_ps_bsp.py::test_the_entries_that_were_there_keep_their_order_and_the_new_follow``
says of the entries that were there, without their place: that test holds
PR 30's ten to the end of ``per_layer``, where every later PR appends, and
``tests/conftest.py`` marks it an expected failure)."""

import copy
import importlib
import json

import pytest

from chipbench import manifest, run
from chipbench.drivers import ps_bsp_epochs_chips as chips

CELL = "dense-ps-bsp-4chip"
ONE_CHIP_CELL = "dense-ps-bsp-1chip"
READERS = ["chips_round_ms", "chips_push_wait_ms", "chips_w_put_ms",
           "chips_grad_d2h_ms", "chips_launch_wait_ms",
           "chips_arrival_spread_ms", "chips_barrier_hold_ms",
           "chips_release_cpu_ms", "chips_server_lock_wait_ms",
           "chips_busy_spread", "chips_shard_put_s"]
TRACE_READERS = {"chips_launch_wait_ms", "chips_busy_spread"}
LIST_LESS = ["compile_s", "input_wait_share", "step_ms", "step_hbm_roofline"]
#: the ten PR 26 appended and the ten PR 30 appended, in their order
#: (tests/chipbench/test_dense_ps.py, test_dense_ps_bsp.py)
PS_ASYNC_READERS = ["ps_round_ms", "ps_wait_ms", "ps_wire_ms",
                    "ps_server_push_cpu_ms", "grad_d2h_ms", "w_put_ms",
                    "ps_pushes_behind", "shard_put_s", "ps_load_s",
                    "ps_launch_wait_ms"]
BSP_READERS = ["bsp_round_ms", "bsp_push_wait_ms", "bsp_barrier_hold_ms",
               "bsp_arrival_spread_ms", "bsp_release_cpu_ms",
               "bsp_server_push_cpu_ms", "bsp_launch_wait_ms", "bsp_w_put_ms",
               "bsp_grad_d2h_ms", "bsp_shard_put_s"]
RECORDED, PACE = 12, 64  # the traffic file's rounds before the window
STEP = "jit_ps_grad_step"


def _rehearse(capsys, *extra, trace="0"):
    rc = run.main(["--workload", CELL, "--seed", "3100000029", "--seconds",
                   "0.2", "--trace", trace, "--rehearse", *extra])
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert rc == 0 and last.startswith("REHEARSAL ")
    return json.loads(last[len("REHEARSAL "):]), out


def _bad(doc):
    return {r["name"] for r in doc["compared"] if not r["ok"]}


# -- whole runs on four virtual devices ---------------------------------------
def test_the_rehearsal_is_correct_a_worker_to_a_device(capsys):
    doc, out = _rehearse(capsys)
    assert doc["correct"] is True, out
    assert doc["attempted"] > 0 and doc["failed"] == 0
    assert set(READERS) - TRACE_READERS <= set(doc["layer_metrics"])
    assert {"compile_s", "input_wait_share", "step_ms"} <= set(
        doc["layer_metrics"])
    assert "step devices by rank={0: 0, 1: 1, 2: 2, 3: 3}" in out
    for r in range(4):
        assert f"rank {r} dense steps pinned: train -> cpu:cpu (id {r})" in out
    assert "chips=4" in out and "memory peak_bytes a chip=" in out


def test_the_traced_rehearsal_finds_every_workers_marks_by_their_rank(capsys):
    """The CPU's trace has no device plane, so the planes' part of
    ``correct`` has nothing to hold; the ``compute`` annotations and the
    ``rank`` each carries are read as on the chip."""
    doc, out = _rehearse(capsys, trace="1")
    assert doc["correct"] is True, out
    line = next(ln for ln in out.splitlines() if "compute marks a rank=" in ln)
    marks = json.loads(line.split("compute marks a rank=")[1].split(
        " a rank's mean ms=")[0])
    rounds = int(line.split("rounds_a_worker=")[1].split()[0])
    assert marks == {str(r): rounds for r in range(4)}


def _with_program(monkeypatch, over):
    real = chips.effective_config

    def changed(cell, rehearsal):
        conf = copy.deepcopy(real(cell, rehearsal))
        conf["program"].update(over(conf))
        return conf

    monkeypatch.setattr(chips, "effective_config", changed)


def _all_four_on_one_device(monkeypatch):
    """What the parent's ``PSWorker`` does with four workers: the job's
    device is dropped and every step lands on the first."""
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker.__init__

    def first_device(self, cfg, rank, hosts, *, device=None, **kw):
        real(self, cfg, rank, hosts, **kw)

    monkeypatch.setattr(ps_trainer.PSWorker, "__init__", first_device)


def _the_last_gradient(monkeypatch):
    _with_program(monkeypatch, lambda conf: conf["control"]["program"])


def _half_a_shard_from_one_worker(monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker._place_shard

    def half(self, train, dev):
        X, y, mask = real(self, train, dev)
        if self.rank != 2:
            return X, y, mask
        return X, y, mask.at[: mask.shape[0] // 2].set(False)

    monkeypatch.setattr(ps_trainer.PSWorker, "_place_shard", half)


def _a_window_one_round_short(monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker.fit

    def one_short(self, epochs=None, **kw):
        if self.epochs_done == RECORDED + PACE:  # the third fit: the window
            epochs -= 1
        return real(self, epochs, **kw)

    monkeypatch.setattr(ps_trainer.PSWorker, "fit", one_short)


@pytest.mark.parametrize("fault,must_fail,must_hold", [
    # the arithmetic is sound wherever it runs: only the layout is wrong
    (_all_four_on_one_device, set(),
     {"weights_disagree", "grad_diff_rel", "update_diff_rel",
      "conservation_rel", "round_miscount_window", "unacknowledged_window"}),
    (_the_last_gradient, {"update_diff_rel", "conservation_rel"},
     {"weights_disagree", "grad_diff_rel", "round_miscount_window"}),
    (_half_a_shard_from_one_worker, {"grad_diff_rel", "update_diff_rel"},
     {"weights_disagree", "conservation_rel", "round_miscount_window"}),
    (_a_window_one_round_short, {"round_miscount_window"},
     {"weights_disagree", "grad_diff_rel", "update_diff_rel",
      "conservation_rel", "round_miscount_recorded"}),
], ids=["one-device", "last-gradient", "half-a-shard", "one-round-short"])
def test_a_faulted_run_is_not_correct(capsys, monkeypatch, fault, must_fail,
                                      must_hold):
    fault(monkeypatch)
    doc, out = _rehearse(capsys)
    assert doc["correct"] is False
    assert must_fail <= _bad(doc), out
    assert not must_hold & _bad(doc), out
    if fault is _all_four_on_one_device:
        assert "not every worker's step ran on its own chip" in out
        assert "devices by rank {0: 0, 1: 0, 2: 0, 3: 0}" in out


def test_a_program_that_cannot_place_a_worker_on_its_own_chip_leaves_at_once(
        monkeypatch):
    """What the parent of the PR that added the cell does: its
    ``PSWorker`` takes no device, so the driver says so and makes no row
    (four shards of the cell's size do not fit the first chip)."""
    from distlr_tpu.train import ps_trainer

    class Parent:
        def __init__(self, cfg, rank, hosts, *, train_iter=None,
                     test_iter=None):
            raise AssertionError("never built")

    monkeypatch.setattr(ps_trainer, "PSWorker", Parent)
    monkeypatch.setattr(chips, "prepare", None)  # never reached
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.2",
                  "--trace", "0", "--rehearse"])
    assert e.value.code not in (0, None)
    assert "takes no device" in str(e.value.code)
    with pytest.raises(SystemExit) as e:
        chips.main(["--workload", CELL, "--seeds", "5", "--rehearse"])
    assert "takes no device" in str(e.value.code)


def test_fewer_devices_than_workers_is_said_and_not_wrapped():
    import jax

    with pytest.raises(SystemExit) as e:
        chips.needs_a_chip_a_worker(jax.devices()[:3], 4)
    assert "4 workers need 4 devices" in str(e.value.code)


def test_the_control_tool_reads_the_program_and_both_controls(capsys):
    rc = chips.main(["--workload", CELL, "--seeds", "23,24", "--controls",
                     "1", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out.strip().splitlines()[-1][len("CONTROL "):])
    assert doc["cell"] == CELL
    for name in ("update_diff_rel", "conservation_rel"):
        got = doc["summary"][name]
        assert got["sound_max"] < got["limit"] < got["control_min"], name
    assert doc["summary"]["grad_diff_rel"]["bfloat16_min"] > 1e-4
    assert doc["summary"]["weights_disagree"]["control_min"] == 0
    assert out.count("dense steps pinned: train -> cpu:cpu (id 3)") >= 3


# -- the configuration --------------------------------------------------------
def test_the_configuration_differs_from_the_one_chip_one_in_the_layout_alone():
    bench = manifest.load_benchmark()
    conf = manifest.Cell(bench, CELL).config
    other = manifest.Cell(bench, ONE_CHIP_CELL).config
    assert conf["program"] == other["program"]  # every width, the mode, W, S
    gen, gen1 = dict(conf["generator"]), dict(other["generator"])
    assert (gen.pop("rows_per_worker"), gen1.pop("rows_per_worker")) == (
        1152, 384)
    assert gen == gen1
    assert conf["family"] == other["family"] == "dense_ps_bsp"
    assert conf["control"] == other["control"]
    assert conf["rehearsal"] == other["rehearsal"]
    assert set(conf["limits"]) == set(other["limits"])
    assert conf["reduced"] == ["train_rows", "test_rows", "num_iteration"]
    assert conf["architecture"] is None
    assert conf["guarantees"][:-1] == other["guarantees"]
    assert conf["guarantees"][-1] == ("no chip holds two workers' shards or "
                                      "runs two workers' steps")
    assert conf["layout"]["chips"] == 4 and "chip r" in conf["layout"]["workers"]
    assert "threads of one process" in conf["assumed"]["processes"]
    assert {k: v for k, v in conf["assumed"].items()
            if k not in ("processes", "rank_to_chip", "defaults")} == {
                k: v for k, v in other["assumed"].items() if k != "defaults"}
    entry = next(c for c in bench["configs"] if c["name"] == conf["name"])
    assert entry["source"] == conf["source"] and len(conf["source"]) <= 200
    assert "SYNC_MODE=1" in conf["source"] and "local.sh:45-48" in conf["source"]
    assert "limits_from" in conf and "1,152" in conf["limits_from"]
    # the floor, on the fullest chip: a quarter of ONE chip a worker
    from distlr_tpu.ops.pallas_lr import panel_plan

    rows = conf["generator"]["rows_per_worker"]
    dim = conf["program"]["num_feature_dim"]
    placed = rows * panel_plan(rows, dim).dim_padded * 4
    assert placed == 1152 * 1_003_904 * 4 >= 0.25 * 16 * 2**30
    assert (rows - 128) * panel_plan(rows, dim).dim_padded * 4 < 0.25 * 16 * 2**30
    assert "4.63 GB" in conf["device_memory"]


# -- the per-layer readers ----------------------------------------------------
def _recorded_run():
    spans = {"push": {"seconds": 2.0, "count": 400, "self_seconds": 2.0},
             "grad_d2h": {"seconds": 0.4, "count": 400, "self_seconds": 0.4},
             "w_put": {"seconds": 0.6, "count": 400, "self_seconds": 0.6}}
    return {"window": {"wall_s": 6.0, "spans": spans},
            "ps": {"workers": 4, "rounds_per_worker": 400,
                   "server_pushes": 3200, "server_push_cpu_s": 4.8},
            "bsp": {"server_rounds": 800, "hold_s": 12.8, "spread_s": 0.4,
                    "release_cpu_s": 2.8},
            "on_chips": {"device_of_rank": {0: 0, 1: 1, 2: 2, 3: 3},
                         "lock_wait_s": 1.6},
            "trace": None}


def _reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}").read


@pytest.mark.parametrize("name,want", [
    ("chips_round_ms", 15.0), ("chips_push_wait_ms", 5.0),
    ("chips_w_put_ms", 1.5), ("chips_grad_d2h_ms", 1.0),
    ("chips_arrival_spread_ms", 0.5), ("chips_barrier_hold_ms", 4.0),
    ("chips_release_cpu_ms", 3.5), ("chips_server_lock_wait_ms", 0.5)])
def test_a_reader_on_a_recorded_run(name, want):
    assert _reader(name)(_recorded_run()) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_nothing_where_no_worker_has_a_chip_of_its_own(
        name, monkeypatch):
    """The one-chip BSP run, or a program without what this layout adds:
    the reader says nothing and does not raise."""
    from distlr_tpu.obs import registry

    monkeypatch.setattr(registry, "REGISTRY", registry.MetricsRegistry())
    one_chip = _recorded_run()
    del one_chip["on_chips"]
    one_chip["trace"] = {"xtrace": _four_planes()[0], "step_program": STEP,
                         "window": (0.0, 1.0)}
    assert _reader(name)(one_chip) is None
    assert _reader(name)({**_recorded_run(), "ps": {
        "workers": 4, "rounds_per_worker": 0, "server_pushes": 0},
        "bsp": {"server_rounds": 0, "hold_s": 0.0, "spread_s": 0.0,
                "release_cpu_s": 0.0}}) is None or name in (
            "chips_push_wait_ms", "chips_w_put_ms", "chips_grad_d2h_ms")


def _four_planes(rounds=3, launch_ms=(0.2, 0.3, 0.4, 0.5), run_ms=7.0,
                 round_s=0.015):
    """Four chips in lock step: worker *r*'s ``compute`` mark opens at the
    round's start, its program starts ``launch_ms[r]`` later on plane *r*
    and runs ``run_ms``; the mark closes 0.1 ms after its run."""
    xtrace, marks = {}, {}
    for r in range(4):
        runs, ops = [], []
        for k in range(rounds):
            t = 0.1 + k * round_s
            s = t + 1e-3 * launch_ms[r]
            d = 1e-3 * (run_ms + 0.1 * r)
            runs.append((f"{STEP}({k})", s, d))
            ops.append(("tpu_custom_call", s, d))
            marks.setdefault(r, []).append((t, s + d + 1e-4))
        xtrace[f"/device:TPU:{r}"] = {"XLA Modules": runs, "XLA Ops": ops}
        xtrace[f"/host:CPU/{r}"] = {f"thread-{r}": [
            ("compute", lo, hi - lo) for lo, hi in marks[r]]}
    return xtrace, marks


def _traced(xtrace, marks, planes=None):
    return {**_recorded_run(), "trace": {
        "xtrace": xtrace, "step_program": STEP, "window": (0.0, 1.0),
        "marks": marks,
        "plane_of_rank": planes or {r: f"/device:TPU:{r}" for r in range(4)}}}


def test_chips_launch_wait_ms_reads_each_workers_own_plane():
    xtrace, marks = _four_planes()
    # the mark less the run: the launch before it and the 0.1 ms after
    assert _reader("chips_launch_wait_ms")(_traced(xtrace, marks)) == (
        pytest.approx((0.2 + 0.3 + 0.4 + 0.5) / 4 + 0.1))
    # a device clock that leads the host's by 1.5 ms moves nothing
    early = {p: {ln: [(n, s - (1.5e-3 if p.startswith("/device") else 0), d)
                      for n, s, d in evs] for ln, evs in lines.items()}
             for p, lines in xtrace.items()}
    assert _reader("chips_launch_wait_ms")(_traced(early, marks)) == (
        pytest.approx(0.45))
    assert chips.launch_and_tail(_traced(early, marks)["trace"])[0] == {
        "launch": pytest.approx(-1.3), "run": pytest.approx(7.0),
        "tail": pytest.approx(1.6), "mark": pytest.approx(7.3)}
    # the one-chip reader on the same trace sees the first plane alone and
    # takes every worker's mark for it
    one = _reader("ps_launch_wait_ms")(_traced(xtrace, marks))
    assert one == pytest.approx(0.2)
    assert chips.planes_hold_their_own(_traced(xtrace, marks)["trace"], 3) == []


def test_chips_busy_spread_is_the_planes_largest_less_smallest_over_their_mean():
    xtrace, marks = _four_planes()
    busy = [3 * (7.0 + 0.1 * r) for r in range(4)]
    assert _reader("chips_busy_spread")(_traced(xtrace, marks)) == (
        pytest.approx(100 * (busy[3] - busy[0]) / (sum(busy) / 4)))
    # a trace with no device plane (the CPU) has nothing to read
    hosts = {p: v for p, v in xtrace.items() if p.startswith("/host")}
    assert _reader("chips_busy_spread")(_traced(hosts, marks)) is None
    assert _reader("chips_launch_wait_ms")(_traced(hosts, marks)) is None
    assert chips.planes_hold_their_own(_traced(hosts, marks)["trace"], 3) == []


def test_a_plane_that_holds_another_workers_runs_is_a_fault():
    xtrace, marks = _four_planes()
    # worker 3's programs ran on chip 0 behind worker 0's
    moved = copy.deepcopy(xtrace)
    moved["/device:TPU:0"]["XLA Modules"] += [
        (n, s + 0.0072, d) for n, s, d in moved["/device:TPU:3"]["XLA Modules"]]
    moved["/device:TPU:3"]["XLA Modules"] = []
    faults = chips.planes_hold_their_own(_traced(moved, marks)["trace"], 3)
    assert any(f.startswith("rank 0: /device:TPU:0 holds 6 runs") for f in faults)
    assert any(f.startswith("rank 3: /device:TPU:3 holds 0 runs") for f in faults)
    # two workers handed one plane
    shared = {0: "/device:TPU:0", 1: "/device:TPU:1", 2: "/device:TPU:2",
              3: "/device:TPU:2"}
    faults = chips.planes_hold_their_own(
        _traced(xtrace, marks, shared)["trace"], 3)
    assert any("share a plane" in f for f in faults)
    assert any("planes of no worker ran the step program" in f for f in faults)
    # a round short on one plane
    short = copy.deepcopy(xtrace)
    short["/device:TPU:1"]["XLA Modules"].pop()
    assert [f[:7] for f in chips.planes_hold_their_own(
        _traced(short, marks)["trace"], 3)] == ["rank 1:"]


def test_marks_are_read_with_their_rank_from_a_profile(tmp_path):
    """The profiler's own file, taken here: ``loop_span``'s annotations
    carry ``rank``, and the loader keeps the marks apart by it."""
    import jax

    from chipbench import trace_reduce
    from distlr_tpu.obs.tracing import loop_span

    with jax.profiler.trace(str(tmp_path)):
        for rank in (0, 2, 2):
            with loop_span("compute", 7, rank=rank, marks_step=True):
                jax.block_until_ready(jax.numpy.ones(8) + 1)
        with loop_span("w_put", 7, rank=1):
            pass
    marks = chips.marks_by_rank(trace_reduce.find_xplane(str(tmp_path)))
    assert {r: len(m) for r, m in marks.items()} == {0: 1, 2: 2}
    assert all(hi > lo for m in marks.values() for lo, hi in m)


# -- BENCHMARK.json -----------------------------------------------------------
def test_every_new_metric_is_read_in_its_own_cell_only():
    bench = manifest.load_benchmark()
    mine = {m["name"] for m in manifest.Cell(bench, CELL).per_layer}
    assert mine >= set(READERS) | set(LIST_LESS)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for other in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        theirs = {m["name"] for m in manifest.Cell(bench, other).per_layer}
        assert not set(READERS) & theirs
    e2e = {m["name"] for m in manifest.Cell(bench, CELL).end_to_end}
    assert e2e >= {"train_samples_per_s", "setup_s"}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in READERS}
    for name in READERS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] in e2e
        assert callable(_reader(name))
        assert name == "chips_busy_spread" or entries[name]["layer"] in layers
    assert entries["chips_shard_put_s"]["moves"] == "setup_s"
    assert entries["chips_busy_spread"]["unit"] == "%"
    assert (entries["chips_launch_wait_ms"]["source"]
            == entries["chips_busy_spread"]["source"] == "device_trace")
    assert entries["chips_server_lock_wait_ms"]["source"] == "program_counter"
    for name in LIST_LESS:
        assert "workloads" not in entries[name]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "distlr-ps-bsp-1m-4chip", "ps-bsp-epochs-4chip", 4)
    assert manifest.Cell(bench, CELL).traffic["kind"] == "ps_bsp_epochs_chips"


def _lines_of_text(bench):
    """Every free line of ``BENCHMARK.json`` the contract holds to 1 to 200
    printable characters, under the name of its entry."""
    for word in bench["command"]:
        yield "command", word
    for c in bench["configs"]:
        yield f"config {c['name']} why", c["why"]
        yield f"config {c['name']} source", c["source"]
    for w in bench["workloads"]:
        yield f"cell {w['name']} why", w["why"]
    for layer in sorted({m["layer"] for m in bench["per_layer"]}):
        yield "layer", layer


@pytest.mark.parametrize("where,text", list(_lines_of_text(
    manifest.load_benchmark())), ids=lambda v: None)
def test_a_line_of_text_is_1_to_200_printable_characters(where, text):
    """The benchmark check refused this PR's first form for a configuration's
    ``why`` of 203 characters, which no test here looked at."""
    assert 1 <= len(text) <= 200, (where, len(text))
    assert text.isprintable() and text.isascii(), where


def test_an_entry_has_just_the_keys_of_its_kind():
    bench = manifest.load_benchmark()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert len(json.dumps(bench, indent=2)) <= 64 * 1024


def test_the_entries_that_were_there_are_as_they_were():
    """``test_dense_ps_bsp.py``'s clauses on the accepted entries without
    their place in the list: PR 24's eight, PR 26's ten and PR 30's ten
    keep their order among themselves, their cells, sources and layers."""
    bench = manifest.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    load = ["load_s", "load_parse_s", "load_densify_s", "load_pack_s",
            "load_cast_s"]
    loop = ["h2d_wait_ms", "feed_host_ms", "launch_wait_ms"]
    held = [*loop[:2], *load, loop[2]]
    were_there = [*LIST_LESS, *held, *PS_ASYNC_READERS, *BSP_READERS]
    assert [n for n in names if n in set(were_there)] == were_there
    assert set(were_there) | set(READERS) <= set(names)
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
        assert entries[name]["workloads"] == ["dense-ps-async-1chip"]
    for name in BSP_READERS:
        assert entries[name]["workloads"] == [ONE_CHIP_CELL]
