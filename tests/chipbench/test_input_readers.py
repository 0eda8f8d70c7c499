"""The readers PR 24 added: ``h2d_wait_ms`` and ``feed_host_ms`` over the
measured ``fit`` call's spans, ``load_s`` and the load's parts over the
registry's series, ``launch_wait_ms`` over the profiler's trace; on
hand-built runs, on a program without the spans, and in a rehearsal."""

import json

import pytest

from chipbench import manifest, run
from chipbench.layer_metrics import (
    feed_host_ms,
    h2d_wait_ms,
    launch_wait_ms,
    load_cast_s,
    load_densify_s,
    load_pack_s,
    load_parse_s,
    load_s,
)
from distlr_tpu.obs import registry
from distlr_tpu.obs.tracing import PhaseTracer

BENCH = manifest.load_benchmark()
LOOP = ("h2d_wait_ms", "feed_host_ms")
LOAD = ("load_s", "load_parse_s", "load_densify_s", "load_pack_s",
        "load_cast_s")
NEW = (*LOOP[:2], LOAD[0], *LOAD[1:], "launch_wait_ms")


def _run(**spans):
    return {"window": {"wall_s": 3.0, "steps": 14, "rows": 10752,
                       "spans": {k: {"seconds": s, "count": c,
                                     "self_seconds": s}
                                 for k, (s, c) in spans.items()}}}


def test_h2d_wait_ms_is_the_mean_wait_for_a_handed_over_batch():
    got = h2d_wait_ms.read(_run(h2d_wait=(2.8, 14), data_load=(2.9, 16)))
    assert got == pytest.approx(200.0)


def test_feed_host_ms_is_the_producers_busy_time_a_batch():
    got = feed_host_ms.read(_run(batch_slice=(0.07, 14), h2d=(0.49, 14)))
    assert got == pytest.approx(40.0)


@pytest.mark.parametrize("reader,spans", [
    (h2d_wait_ms, {"data_load": (0.1, 16), "compute": (3.0, 14)}),
    (h2d_wait_ms, {"h2d_wait": (0.0, 0)}),
    # the parent program: h2d without batch_slice
    (feed_host_ms, {"h2d": (0.5, 14), "compute": (3.0, 14)}),
    (feed_host_ms, {"batch_slice": (0.1, 1)}),
    (feed_host_ms, {"batch_slice": (0.0, 1), "h2d": (0.0, 0)}),
])
def test_a_reader_gives_nothing_where_its_span_is_missing(reader, spans):
    assert reader.read(_run(**spans)) is None


def test_load_s_is_the_load_data_series_of_the_registry(monkeypatch):
    reg = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "get_registry", lambda: reg)
    assert load_s.read({}) is None  # no family at all
    tracer = PhaseTracer(registry=reg)
    with tracer.phase("h2d"):
        pass
    assert load_s.read({}) is None  # the family without the series
    with tracer.phase("load_data"):
        with tracer.phase("load_parse"):
            pass
    tracer.reset()  # as the driver does before the window
    total = reg.get("distlr_phase_seconds").labels(phase="load_data").sum
    assert total > 0 and load_s.read({}) == total


@pytest.mark.parametrize("reader,phase", [
    (load_parse_s, "load_parse"), (load_densify_s, "load_densify"),
    (load_densify_s, "load_coo"), (load_pack_s, "load_pack"),
    (load_cast_s, "load_cast")])
def test_a_part_of_the_load_is_its_series_of_the_registry(monkeypatch, reader,
                                                          phase):
    reg = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "get_registry", lambda: reg)
    tracer = PhaseTracer(registry=reg)
    with tracer.phase("load_data"):
        pass
    assert reader.read({}) is None  # the parent span alone
    with tracer.phase("load_data"):
        for _ in range(3):
            with tracer.phase(phase):
                pass
    tracer.reset()
    series = reg.get("distlr_phase_seconds").labels(phase=phase)
    assert series.count == 3 and reader.read({}) == series.sum
    assert load_s.read({}) > reader.read({})


def _traced(host, modules, window=(0.0, 10.0)):
    """A trace as ``trace_reduce.load_xplane`` gives it: ``compute``
    spans on a host line, the step program's runs on the device."""
    return {"trace": {
        "xtrace": {"/host:CPU": {"python3": host, "other": [("h2d", 0, 1)]},
                   "/device:TPU:0": {"XLA Modules": modules, "XLA Ops": []}},
        "window": window, "step_program": "step"}}


def test_launch_wait_ms_is_from_the_compute_span_to_the_programs_start():
    host = [("compute", 1.000, 0.114), ("data_load", 1.114, 0.001),
            ("compute", 1.115, 0.007),
            # its run was cut by the window's end: not counted
            ("compute", 9.990, 0.020)]
    modules = [("jit_step(1)", 1.1066, 0.004),
               # the device's clock 0.1 ms early: no wait, not a negative
               ("jit_step(1)", 1.1149, 0.004),
               ("jit_eval_step(2)", 1.2, 0.001), ("jit_other", 1.3, 0.1)]
    got = launch_wait_ms.read(_traced(host, modules))
    assert got == pytest.approx((106.6 + 0.0) / 2)


@pytest.mark.parametrize("run", [
    {"trace": None}, {},
    # the parent program: its spans are not in the profiler's trace
    _traced([("data_load", 1.0, 0.1)], [("jit_step(1)", 1.05, 0.004)]),
    _traced([("compute", 1.0, 0.1)], []),
    # a run that ends after the span did is another step's
    _traced([("compute", 1.0, 0.1)], [("jit_step(1)", 1.2, 0.004)]),
])
def test_launch_wait_ms_gives_nothing_without_a_step_to_pair(run):
    assert launch_wait_ms.read(run) is None


def test_the_benchmark_names_the_readers_and_their_layers():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert tuple(entries)[-len(NEW):] == NEW
    cell = manifest.Cell(BENCH, "dense-sync-1chip")
    for name in NEW:
        assert entries[name]["workloads"] == ["dense-sync-1chip"]
        assert callable(cell.layer_reader(name))
    for name in LOAD:
        assert entries[name]["source"] == "program_span"
        assert entries[name]["moves"] == "setup_s"
        assert entries[name]["layer"] == "loader"
    for name in (*LOOP, "launch_wait_ms"):
        assert entries[name]["moves"] == "train_samples_per_s"
        assert entries[name]["layer"] == "input, sync"
    assert entries["launch_wait_ms"]["source"] == "device_trace"


def test_a_rehearsal_reports_the_readers_of_spans(capsys):
    rc = run.main(["--workload", "dense-sync-1chip", "--seed", "2147483659",
                   "--seconds", "0.3", "--trace", "0", "--rehearse"])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert rc == 0 and last.startswith("REHEARSAL ")
    doc = json.loads(last[len("REHEARSAL "):])
    assert doc["correct"] is True
    # bfloat16 features, so the cast is there; no trace, no launch_wait_ms
    assert {*LOOP, *LOAD} <= set(doc["layer_metrics"])
    assert "launch_wait_ms" not in doc["layer_metrics"]
