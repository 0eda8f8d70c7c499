"""Every cell's driver end to end on the CPU at the tiny sizes its
configuration gives under ``rehearsal``, the four-chip layout on four
virtual devices, runs with the timed path broken, and the manifest."""

import copy
import json
import os
import re
import shutil
import sys

import pytest

from chipbench import manifest, run

BENCH = manifest.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _rehearse(capsys, workload, *extra):
    rc = run.main(["--workload", workload, "--seed", "2147483653",
                   "--seconds", "0.3", "--trace", "0", "--rehearse", *extra])
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert rc == 0 and last.startswith("REHEARSAL ")
    assert '"metrics"' not in out
    return json.loads(last[len("REHEARSAL "):]), out


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_end_to_end_and_is_correct(capsys, workload):
    doc, out = _rehearse(capsys, workload)
    assert doc["correct"] is True, out
    assert doc["attempted"] > 0 and doc["failed"] == 0
    # every number compared is printed beside its limit
    for row in doc["compared"]:
        assert f"compared {row['name']} value=" in out
    assert {"compile_s", "step_ms", "input_wait_share"} <= set(doc["layer_metrics"])


def test_traced_rehearsal_reads_the_profiler_files(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "7", "--seconds", "0.2",
                   "--trace", "1", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0 and "REHEARSAL" in out.splitlines()[-1]


def _with_kept_cell(monkeypatch, name, config, chips):
    """A cell PERF.md keeps for a later PR, as that PR would enter it."""
    bench = copy.deepcopy(BENCH)
    if config not in {c["name"] for c in bench["configs"]}:
        bench["configs"].append({
            "name": config, "source": "x", "reduced": [], "why": "x",
            "file": f"chipbench/configs/{config}.json"})
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": "train-stream", "chips": chips,
                               "why": "rehearsal only"})
    monkeypatch.setattr(manifest, "load_benchmark", lambda root=None: bench)


@pytest.fixture(params=["dense-sync-1chip", "sparse-sync-1chip"])
def either_family(request, monkeypatch):
    """The committed cell's family and the other one the harness has."""
    if request.param not in CELLS:
        _with_kept_cell(monkeypatch, request.param, "criteo-sparse-1m", 1)
    return request.param


def test_sparse_configuration_through_the_same_driver(capsys, monkeypatch):
    """Both splits as the parser's arrays, float32, gather and segment
    sum: the cell PERF.md keeps for when its size is admitted."""
    _with_kept_cell(monkeypatch, "sparse-sync-1chip", "criteo-sparse-1m", 1)
    doc, out = _rehearse(capsys, "sparse-sync-1chip")
    assert doc["correct"] is True, out


def test_four_chip_layout_on_four_virtual_devices(capsys, monkeypatch):
    """The data-parallel mesh: same rows, same global batch, a quarter on
    each device."""
    _with_kept_cell(monkeypatch, "sparse-sync-4chip", "criteo-sparse-1m", 4)
    doc, out = _rehearse(capsys, "sparse-sync-4chip")
    assert doc["correct"] is True, out
    assert "chips=4" in out


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch, either_family):
    """The rest of a run with the timed path broken underneath."""
    from distlr_tpu.train import trainer as trainer_mod

    real = trainer_mod.make_sync_train_step

    def broken(model, cfg, mesh, **kw):
        step = real(model, cfg, mesh, **kw)

        def unchanged(w, batch):
            import jax.numpy as jnp

            _, metrics = step(jnp.copy(w), batch)
            return w, metrics
        unchanged._cache_size = step._cache_size  # the trainer's probe reads it
        return unchanged

    monkeypatch.setattr(trainer_mod, "make_sync_train_step", broken)
    doc, out = _rehearse(capsys, either_family)
    assert doc["correct"] is False
    bad = {r["name"] for r in doc["compared"] if not r["ok"]}
    assert "update_missing" in bad, out


def test_a_part_of_the_batch_left_out_is_not_correct(capsys, monkeypatch,
                                                     either_family):
    from distlr_tpu.train import trainer as trainer_mod

    real = trainer_mod.make_sync_train_step

    def broken(model, cfg, mesh, **kw):
        step = real(model, cfg, mesh, **kw)

        def half(w, batch):
            *feats, y, mask = batch
            mask = mask.at[: mask.shape[0] // 2].set(0.0)
            return step(w, (*feats, y, mask))
        half._cache_size = step._cache_size
        return half

    monkeypatch.setattr(trainer_mod, "make_sync_train_step", broken)
    doc, _ = _rehearse(capsys, either_family)
    assert doc["correct"] is False
    assert any(r["name"].startswith("loss_step") and not r["ok"]
               for r in doc["compared"])


def test_a_window_that_did_less_than_the_rate_counts_is_not_correct(
        capsys, monkeypatch, either_family):
    """The rate's rows are counted by the driver, E epochs of the split;
    a program whose own counter disagrees did other work."""
    from distlr_tpu.train import trainer as trainer_mod

    real = trainer_mod.Trainer.fit

    def one_epoch_short(self, *, epochs=None, **kw):
        return real(self, epochs=epochs - 1 if epochs > 1 else epochs, **kw)

    monkeypatch.setattr(trainer_mod.Trainer, "fit", one_epoch_short)
    doc, out = _rehearse(capsys, either_family)
    assert doc["correct"] is False
    assert all(r["ok"] for r in doc["compared"]), out


def test_without_a_tpu_a_run_exits_non_zero_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert e.value.code not in (0, None)
    assert '"metrics"' not in capsys.readouterr().out


# -- the manifest ---------------------------------------------------------
def test_manifest_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])


def test_names_units_and_files_use_only_the_permitted_characters():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(manifest.NAME_RE.match(n) for n in names), names
    for w in BENCH["workloads"]:
        assert manifest.NAME_RE.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert manifest.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for c in BENCH["configs"]:
        assert all(manifest.NAME_RE.match(k) for k in c["reduced"])
    for path in BENCH["paths"]:
        for dirpath, dirnames, files in os.walk(os.path.join(manifest.ROOT, path)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), manifest.ROOT)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


@pytest.mark.parametrize("workload", CELLS)
def test_everything_a_cell_names_resolves_by_name(workload):
    cell = manifest.Cell(BENCH, workload)
    assert callable(cell.driver.run)
    assert cell.config["name"] == cell.entry["config"]
    cfg_entry = next(c for c in BENCH["configs"] if c["name"] == cell.entry["config"])
    assert sorted(cfg_entry["reduced"]) == sorted(cell.config["reduced"])
    assert any(cfg_entry["file"].startswith(p + "/") for p in BENCH["paths"])
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.layer_reader(m["name"]))
        assert m["moves"] in e2e


def test_bounds_are_inside_the_contract():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def test_a_cell_a_mix_a_metric_a_family_and_a_driver_are_added_as_files_alone(
        tmp_path, monkeypatch):
    """A later PR adds files and entries and edits none: the harness finds
    a new configuration, traffic mix, driver and per-layer metric by the
    names in BENCHMARK.json."""
    import chipbench.drivers
    import chipbench.families
    import chipbench.layer_metrics
    from chipbench import reference, trace_reduce

    here = tmp_path / "chipbench"
    for d in ("configs", "traffic"):
        shutil.copytree(os.path.join(manifest.HERE, d), here / d)
    (here / "configs" / "avazu-sparse-1m.json").write_text(
        json.dumps({"name": "avazu-sparse-1m", "reduced": []}))
    (here / "traffic" / "ps-async.json").write_text(
        json.dumps({"kind": "ps_async", "workers": 2}))
    (tmp_path / "drivers").mkdir()
    (tmp_path / "drivers" / "ps_async.py").write_text(
        "def run(ctx):\n    return {'driver': 'ps_async'}\n")
    (tmp_path / "layer_metrics").mkdir()
    (tmp_path / "layer_metrics" / "ps_push_ms.py").write_text(
        "def read(run):\n    return 1.5\n")
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "blocked.py").write_text(
        "def step_bytes_floor(*, rows, dim, nnz):\n    return rows * 1400\n")
    monkeypatch.setattr(chipbench.families, "__path__",
                        [*chipbench.families.__path__,
                         str(tmp_path / "families")])
    monkeypatch.setattr(manifest, "HERE", str(here))
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    monkeypatch.setattr(chipbench.drivers, "__path__",
                        [*chipbench.drivers.__path__, str(tmp_path / "drivers")])
    monkeypatch.setattr(chipbench.layer_metrics, "__path__",
                        [*chipbench.layer_metrics.__path__,
                         str(tmp_path / "layer_metrics")])
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({"name": "avazu-sparse-1m", "source": "x",
                             "file": "chipbench/configs/avazu-sparse-1m.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "avazu-ps-async", "config": "avazu-sparse-1m",
                               "traffic": "ps-async", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "ps_push_ms", "unit": "ms", "better": "lower",
                               "source": "program_span", "layer": "PS worker",
                               "moves": "train_samples_per_s",
                               "workloads": ["avazu-ps-async"]})
    cell = manifest.Cell(bench, "avazu-ps-async")
    assert cell.driver.run(None) == {"driver": "ps_async"}
    assert cell.traffic["workers"] == 2
    assert cell.layer_reader("ps_push_ms")({}) == 1.5
    # a new model family brings its reference and its byte floor as a file
    assert trace_reduce.step_bytes_floor("blocked", rows=2, dim=8, nnz=80) == 2800
    assert reference.family("blocked").__name__ == "chipbench.families.blocked"
    for mod in ("families.blocked", "drivers.ps_async", "layer_metrics.ps_push_ms"):
        sys.modules.pop(f"chipbench.{mod}", None)  # they were this test's alone
    # and the metric is read in its own cell only
    old = manifest.Cell(bench, CELLS[0])
    assert "ps_push_ms" not in {m["name"] for m in old.per_layer}
