"""Smoke tests for the driver-facing benchmark entry points.

The driver runs ``bench.py`` and (this round) ``bench_configs.py`` to
produce the official artifacts; nothing else in the suite imports them,
so a refactor that breaks only a bench path would otherwise surface for
the first time inside the driver's one shot at the artifact.  These run
the quick/smoke modes end to end on the CPU — shapes are tiny, but every
line of plumbing (JSON schema, scratch-file divert) is the real one —
and pin that the full-size modes refuse anything but a TPU.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, timeout=600):
    # the CPU is an explicit choice (utils/backend.py): the benchmark
    # parents through JAX_PLATFORMS, their `launch` children through
    # DISTLR_CPU_DEVICES
    return subprocess.run(
        cmd, cwd=REPO, capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "DISTLR_CPU_DEVICES": "1"},
    )


def test_full_size_bench_refuses_a_non_tpu_platform():
    """No fallback that hides the device: without --smoke, bench.py
    measures the chip, and on any other platform it exits non-zero and
    prints no row."""
    r = _run([sys.executable, "bench.py"], timeout=120)
    assert r.returncode != 0
    assert "platform=cpu" in r.stderr and "TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_chip_smoke_fails_without_a_chip():
    """chip_smoke.py is the chip's check: on the CPU it exits non-zero
    before any leg and prints neither PASS nor a result line."""
    r = _run([sys.executable, "chip_smoke.py"], timeout=120)
    assert r.returncode != 0
    assert "PASS" not in r.stdout and "PASS" not in r.stderr
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_bench_configs_quick_writes_scratch_not_canonical():
    canonical = os.path.join(REPO, "BENCH_CONFIGS.json")
    scratch = os.path.join(REPO, "BENCH_CONFIGS_quick.json")
    before = open(canonical).read()
    scratch_preexisted = os.path.exists(scratch)
    try:
        r = _run([sys.executable, "benchmarks/bench_configs.py", "--quick",
                  "--configs", "1,5"])
        assert r.returncode == 0, r.stderr[-2000:]
        # canonical artifact untouched; quick rows went to the scratch file
        assert open(canonical).read() == before
        quick = json.load(open(scratch))
        assert quick["quick"] is True
        configs = [row["config"] for row in quick["rows"]]
        assert configs == [1, 5]
        row5 = quick["rows"][1]
        # the round-4 quality anchors must be present in the schema
        for field in ("oracle_accuracy", "converged_accuracy",
                      "samples_per_sec"):
            assert field in row5, row5
    finally:
        # clean up only what this test created — a developer's own quick
        # results from before the run are not ours to delete
        if not scratch_preexisted and os.path.exists(scratch):
            os.remove(scratch)


def test_bench_configs_explicit_out(tmp_path):
    out = str(tmp_path / "bc.json")
    r = _run([sys.executable, "benchmarks/bench_configs.py", "--quick",
              "--configs", "1", "--out", out])
    assert r.returncode == 0, r.stderr[-2000:]
    data = json.load(open(out))
    assert data["rows"][0]["config"] == 1
    assert data["rows"][0]["samples_per_sec"] > 0


def test_bench_config6_quick_keyed_ps_row():
    """Config 6 (blocked CTR over the keyed native PS plane) produces a
    rate and an end-of-run accuracy through real sockets."""
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "c6.json")
        r = _run([sys.executable, "benchmarks/bench_configs.py", "--quick",
                  "--configs", "6", "--out", out])
        assert r.returncode == 0, r.stderr[-2000:]
        row = json.load(open(out))["rows"][0]
    assert row["config"] == 6
    assert row["samples_per_sec"] > 0
    assert 0.0 <= row["accuracy"] <= 1.0


def test_quality_gate_prefers_operating_point(tmp_path, monkeypatch):
    """bench.py's blocked-R quality gate reads the operating-point
    verdict when the frontier artifact carries one, and falls back to
    scanning the equal-param regimes otherwise."""
    import bench

    art = tmp_path / "frontier.json"
    monkeypatch.setattr(bench, "_FRONTIER_PATH", str(art))
    # operating-point verdict wins outright
    art.write_text(json.dumps({"frontier": {
        "correlated_tuples": {
            "r32": {"delta_vs_scalar_pts": -9.45}},
        "operating_point": {"valid_default_rs": [8, 16, 32]},
    }}))
    assert bench._quality_valid_blocked_rs() == {8: True, 16: True, 32: True}
    # legacy artifact (no operating_point): per-regime scan, OR across
    # regimes, R=32 failing everywhere stays invalid
    art.write_text(json.dumps({"frontier": {
        "correlated_tuples": {
            "scalar": {"accuracy": 0.82},
            "r8": {"delta_vs_scalar_pts": 0.34},
            "r16": {"delta_vs_scalar_pts": -0.37},
            "r32": {"delta_vs_scalar_pts": -9.45},
            "largest_r_within_1pt": 16,
        },
        "high_card_iid": {
            "r8": {"delta_vs_scalar_pts": -23.99},
            "r16": {"delta_vs_scalar_pts": -23.5},
            "r32": {"delta_vs_scalar_pts": -23.42},
        },
    }}))
    assert bench._quality_valid_blocked_rs() == {8: True, 16: True, 32: False}
    # missing artifact: nothing validated (never everything)
    art.unlink()
    assert bench._quality_valid_blocked_rs() == {}


def test_quality_annotation_names_validating_regime(tmp_path, monkeypatch):
    """The per-R annotation must carry WHICH regime validates an R (and
    its row_load/recurrence) — the flat valid-list reads as 'always
    safe' when e.g. R=16 loses 17pt on low-card iid at the same
    operating point (VERDICT r5 weak #2)."""
    import bench

    art = tmp_path / "frontier.json"
    monkeypatch.setattr(bench, "_FRONTIER_PATH", str(art))
    art.write_text(json.dumps({"frontier": {"operating_point": {"regimes": {
        "low_card_iid": {"dc65536": {
            "r16": {"delta_vs_scalar_pts": -1.3, "row_load": 9.3,
                    "min_recurrence": 1.5, "groups": 2}},
            "dc1048576": {
            "scalar": {"accuracy": 0.77},
            "r16": {"delta_vs_scalar_pts": -17.0, "row_load": 0.58,
                    "min_recurrence": 1.5, "groups": 2},
            "r32_g3": {"delta_vs_scalar_pts": -0.4}}},  # pinned-G: skipped
        "correlated_tuples": {"dc1048576": {
            "r16": {"delta_vs_scalar_pts": 0.52, "row_load": 0.0156,
                    "min_recurrence": 112.0, "groups": 2}}},
    }}}}))
    detail = bench._quality_valid_rs_annotated()
    assert set(detail) == {"r16"}  # default-grouping rows only
    r16 = detail["r16"]
    assert r16["valid"] is True
    # validated by the tuple regime, failing on low-card iid — BOTH
    # visible, at the LARGEST dc only (the operating point)
    assert [v["regime"] for v in r16["validated_by"]] == ["correlated_tuples"]
    assert [v["regime"] for v in r16["fails_in"]] == ["low_card_iid"]
    assert r16["fails_in"][0]["delta_vs_scalar_pts"] == -17.0
    assert r16["validated_by"][0]["row_load"] == 0.0156
    # missing artifact -> empty annotation, never a fabricated verdict
    art.unlink()
    assert bench._quality_valid_rs_annotated() == {}


def test_bench_serve_quick_emits_bench_row():
    """bench_serve.py joins the bench trajectory: one JSON line, bench.py
    field conventions, engine + end-to-end + multi-engine (router) sub
    rows.  --smoke (the serve-smoke make target) is an alias of --quick."""
    r = _run([sys.executable, "benchmarks/bench_serve.py", "--smoke"],
             timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    row = json.loads(r.stdout.strip().splitlines()[-1])
    for field in ("metric", "value", "unit", "backend", "D", "best_e2e",
                  "best_route"):
        assert field in row, row
    assert row["unit"] == "rows/sec"
    assert row["value"] and row["value"] > 0
    assert row["best_e2e"]["qps"] > 0
    assert 0.0 <= row["best_e2e"]["mean_occupancy"] <= 1.0
    # ISSUE 4: the multi-engine pass rode the router with no sheds or
    # failovers on an idle localhost box
    assert row["best_route"]["qps"] > 0
    assert row["best_route"]["replicas"] == 2
    assert row["best_route"]["shed"] == 0
    assert row["best_route"]["retries"] == 0
    # ISSUE 2: serving bench rows carry the tracer's phase sums too
    phases = row["phase_breakdown"]["phases"]
    assert phases["engine_score"]["seconds"] > 0
    assert "e2e_clients" in phases and "route_clients" in phases


def test_bench_config4_quick_frontier_schema():
    """Config 4's frontier — the source bench.py's quality gate and the
    frontier-artifact refresh both read — keeps its schema: equal-param
    regimes with largest_r_within_1pt plus the operating_point section
    whose valid_default_rs verdict drives the headline."""
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "c4.json")
        r = _run([sys.executable, "benchmarks/bench_configs.py", "--quick",
                  "--configs", "4", "--out", out], timeout=900)
        assert r.returncode == 0, r.stderr[-2000:]
        row = json.load(open(out))["rows"][0]
    fr = row["blocked_frontier"]
    for regime in ("high_card_iid", "low_card_iid", "correlated_tuples"):
        assert "largest_r_within_1pt" in fr[regime]
        assert "delta_vs_scalar_pts" in fr[regime]["r16"]
    op = fr["operating_point"]
    assert set(op["valid_default_rs"]) <= {8, 16, 32}
    cell = next(iter(op["regimes"]["correlated_tuples"].values()))
    for label in ("scalar", "r8", "r16", "r32", "r32_g2", "r32_g3"):
        assert label in cell
    for diag in ("row_load", "min_recurrence", "groups"):
        assert diag in cell["r32_g3"]


def test_bench_smoke_phase_breakdown_sums_to_wall():
    """ISSUE-2 acceptance: bench.py's JSON line carries a phase_breakdown
    whose per-phase sums explain the headline wall clock to within 20% —
    an on-chip capture now says WHERE the time went, not just how fast.
    (--smoke shrinks shapes and skips sub-benches; the span plumbing is
    the real path.)"""
    r = _run([sys.executable, "bench.py", "--smoke"], timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert row.get("smoke") is True
    # a smoke row runs wherever JAX lands and says where that was
    assert (row["backend"], row["device_kind"]) == ("cpu", "cpu")
    assert row["devices"] >= 1
    pb = row["phase_breakdown"]
    phases = pb["phases"]
    # the measured loop's spans are present with real counts
    assert phases["compute"]["count"] >= 1
    assert "warmup_compile" in phases and "data_gen" in phases
    # self seconds, so that a phase nested in another counts once
    covered = sum(p["self_seconds"] for p in phases.values())
    assert pb["wall_s"] > 0
    assert abs(covered / pb["wall_s"] - 1.0) <= 0.2, pb
    assert pb["coverage"] == pytest.approx(covered / pb["wall_s"], abs=1e-3)


def test_bench_config3_quick_quality_columns():
    """Config 3 must keep its quality columns (accuracy/oracle/int8_dot)
    so the next on-chip BENCH_CONFIGS.json regeneration carries them
    (ROADMAP: the canonical table is r3-vintage and lacks them)."""
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "c3.json")
        r = _run([sys.executable, "benchmarks/bench_configs.py", "--quick",
                  "--configs", "3", "--out", out], timeout=900)
        assert r.returncode == 0, r.stderr[-2000:]
        row = json.load(open(out))["rows"][0]
    assert row["config"] == 3
    for field in ("accuracy", "test_logloss", "oracle_accuracy",
                  "int8_dot_accuracy", "samples_per_sec"):
        assert field in row, sorted(row)
    assert 0.0 <= row["accuracy"] <= 1.0


def test_bench_configs_default_covers_all_six():
    """The default --configs set regenerates the full canonical table —
    including config 6 (blocked CTR over keyed PS) — in ONE run, which
    is what the next on-chip run relies on."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_bc_probe", os.path.join(REPO, "benchmarks", "bench_configs.py"))
    # source-level probe (no exec)
    src = open(spec.origin).read()
    assert 'default="1,2,3,4,5,6"' in src
    for i in range(1, 7):
        assert f"def bench_config_{i}(" in src
