"""The trace -> metrics reduction on a hand-built trace and on one the
profiler records here, the byte floors, and the peaks table."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import trace_reduce as tr

DEV = "/device:TPU:0"


def _trace():
    # two runs of the step program, 10 ms each; ops fill 6 ms and 8 ms of
    # them, one all-reduce of 1 ms in each; idle between and around
    ops = [
        ("fusion.1", 1.000, 0.004), ("all-reduce.2", 1.004, 0.001),
        ("fusion.3", 1.004, 0.002),           # overlaps the all-reduce
        ("fusion.1", 1.020, 0.004), ("all-reduce.2", 1.024, 0.001),
        ("fusion.3", 1.025, 0.003),
        ("copy.9", 1.050, 0.002),             # outside any step program
    ]
    return {
        DEV: {
            tr.OPS_LINE: ops,
            tr.MODULES_LINE: [("jit_step(1)", 1.000, 0.010),
                              ("jit_step(1)", 1.020, 0.010),
                              ("jit_evaluate(2)", 1.050, 0.002)],
            "Steps": [("0", 1.0, 0.06)],
        },
        "/host:CPU": {"main": [(tr.ANCHOR, 0.990, 0.070)]},
    }


def test_union_merges_overlaps_and_keeps_gaps():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


def test_busy_is_the_union_inside_the_anchored_window():
    b = tr.busy(_trace())
    assert b["window_s"] == pytest.approx(0.070)
    # 6 ms + 8 ms + 2 ms; the overlapped all-reduce is not counted twice
    assert b["busy_s"] == pytest.approx(0.016)
    assert 1 - b["busy_s"] / b["window_s"] == pytest.approx(54 / 70)


def test_busy_averages_over_devices():
    t = _trace()
    t["/device:TPU:1"] = {tr.OPS_LINE: [("fusion.1", 1.0, 0.008)]}
    b = tr.busy(t)
    assert b["busy_s"] == pytest.approx((0.016 + 0.008) / 2)
    assert set(b["per_device_s"]) == {DEV, "/device:TPU:1"}


def test_per_step_grouping():
    t = _trace()
    assert [e - s for s, e in tr.module_runs(t, "step")] == pytest.approx(
        [0.010, 0.010])
    assert tr.busy_per_step(t, "step") == pytest.approx((0.006 + 0.008) / 2)
    assert tr.busy_per_step(t, "no_such_program") is None


def test_top_ops_sum_by_name():
    top = tr.top_ops(_trace(), n=2)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(0.008)
    assert len(top) == 2


def test_idle_gaps_go_to_what_the_host_was_in():
    t = _trace()
    # host clock = trace clock - 100 s
    off = 100.0
    spans = [("data_load", 1, 1.006 - off, 0.014),   # the gap between steps
             ("h2d", 2, 1.010 - off, 0.004),          # part of it, other thread
             ("compute", 1, 1.020 - off, 0.010)]
    gaps = dict(tr.idle_gaps(t, spans, off))
    assert gaps["h2d"] == pytest.approx(0.004)
    assert gaps["data_load"] == pytest.approx(0.010)
    total_idle = 0.070 - 0.016
    assert sum(gaps.values()) == pytest.approx(total_idle)
    # the step program's last 2 ms run nothing while the host is still
    # inside its compute span
    assert gaps["compute"] == pytest.approx(0.002)
    assert gaps["other"] == pytest.approx(total_idle - 0.014 - 0.002)


def test_no_device_plane_is_an_error_not_a_zero():
    with pytest.raises(ValueError):
        tr.busy({"/host:CPU": {"main": [(tr.ANCHOR, 0.0, 1.0)]}})


def test_recorded_trace_loads_and_holds_the_anchor(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tr.ANCHOR):
            jnp.sum(jnp.ones((256, 256)) @ jnp.ones((256, 256))).block_until_ready()
    trace = tr.load_xplane(tr.find_xplane(str(tmp_path)))
    lo, hi = tr.window_of(trace)
    assert hi > lo
    assert any(name == tr.ANCHOR for lines in trace.values()
               for events in lines.values() for name, _, _ in events)
    # the CPU has no device plane: nothing here may pass for a device number
    assert tr.device_planes(trace) == []


@pytest.mark.parametrize("family", ["sparse", "dense"])
def test_byte_floor_is_under_what_the_step_program_moves(family):
    """A share of the roofline cannot pass 100% if the floor counts no
    byte the step's own arguments and results do not hold."""
    from distlr_tpu.config import Config
    from distlr_tpu.models import get_model
    from distlr_tpu.parallel import make_mesh, make_sync_train_step

    rows, dim, width = 64, 4096, 39
    if family == "sparse":
        cfg = Config(model="sparse_lr", num_feature_dim=dim)
        batch = (np.zeros((rows, width), np.int32),
                 np.ones((rows, width), np.float32))
    else:
        cfg = Config(model="binary_lr", num_feature_dim=dim,
                     feature_dtype="bfloat16")
        batch = (jnp.zeros((rows, dim), jnp.bfloat16),)
    batch = (*batch, np.zeros(rows, np.int32), np.ones(rows, np.float32))
    step = make_sync_train_step(get_model(cfg), cfg, make_mesh({"data": 1}))
    ma = step.lower(jnp.zeros(dim, jnp.float32), batch).compile().memory_analysis()
    moved = ma.argument_size_in_bytes + ma.output_size_in_bytes
    floor = tr.step_bytes_floor(family, rows=rows, dim=dim, nnz=rows * width)
    assert 0.8 * moved <= floor <= moved


def test_byte_floor_refuses_an_unknown_family():
    with pytest.raises(ValueError):
        tr.step_bytes_floor("blocked", rows=1, dim=1, nnz=1)


def test_peaks_table_knows_the_v5e_and_refuses_the_rest():
    p = tr.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert p["source"]
    with pytest.raises(KeyError, match="peaks.json"):
        tr.peaks_for("TPU v9 imaginary")
