"""Headline benchmark: dense binary LR training throughput at the
north-star scale (1M features), single chip.

Prints ONE JSON line: ``{"metric": ..., "value": N, "unit": ...,
"vs_baseline": N}``.

* ``value`` — steady-state training samples/sec of the full sync step
  (forward + closed-form gradient + SGD update) with device-resident data.
* ``vs_baseline`` — ratio vs a CPU baseline measured here and now: the
  same O(B*D) vectorized math in numpy (multithreaded BLAS) — a *stronger*
  baseline than the reference's actual O(B*D^2) scalar loop
  (``src/lr.cc:35-41``), which would not finish a single 1M-feature batch.
  The reference itself publishes no numbers (BASELINE.md).

The per-step math matches the reference worker exactly (pull -> gradient
-> SGD update); at 1M features the reference would ship 4 MB per direction
per worker per step over ZeroMQ, while here weights never leave HBM.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from distlr_tpu.obs.tracing import get_tracer, trace_phase
from distlr_tpu.utils.backend import start_benchmark


def resilience_snapshot() -> dict:
    """Fault-cost counters of THIS process's registry at read time:
    in-place KV retries/reconnects, unknown-outcome pushes, and injected
    chaos faults.  Every bench row carries one (ISSUE 5), so any capture
    that ran under network faults — or silently fought a flaky link —
    banks what the faults cost next to what the run scored; all-zero is
    the healthy-network signature."""
    from distlr_tpu.obs.registry import family_total  # noqa: PLC0415

    return {
        "retries": int(family_total("distlr_ps_retries_total")),
        "reconnects": int(family_total("distlr_ps_reconnects_total")),
        "push_outcome_unknown": int(
            family_total("distlr_ps_push_outcome_unknown_total")),
        "chaos_faults": int(family_total("distlr_chaos_faults_total")),
    }


def _profile_top_n() -> int:
    """Parsed DISTLR_PROFILE_TOP: frames requested, 0 = off (both
    unset and an explicit 0/garbage — '0 disables' matches the
    --prof-hz 0 convention)."""
    try:
        return max(0, int(os.environ.get("DISTLR_PROFILE_TOP", "0") or 0))
    except ValueError:
        return 0


def maybe_arm_profiler() -> None:
    """Optional continuous-profiling of the bench itself (ISSUE 9):
    ``DISTLR_PROFILE_TOP=<N>`` (N > 0) arms the journal-less stack
    sampler at the default rate; the row then carries a
    ``profile_top_frames`` snapshot (see :func:`profile_snapshot`)
    naming where the measurement's own CPU went — the cheap answer to
    "was this row bound by the workload or by the harness"."""
    if _profile_top_n() > 0:
        from distlr_tpu.obs import profile  # noqa: PLC0415

        profile.configure(None, "bench", 0)


def profile_snapshot() -> dict:
    """Top self-time frames of this process's sampler since arming —
    empty when DISTLR_PROFILE_TOP is unset/0, so default rows are
    byte-stable."""
    from distlr_tpu.obs import profile  # noqa: PLC0415

    n = _profile_top_n()
    if n <= 0 or not profile.is_configured():
        return {}
    return {"profile_top_frames": profile.top_frames(n)}


def compression_snapshot() -> dict:
    """Push-byte accounting of THIS process's registry at read time
    (ISSUE 7): raw = dense-f32-equivalent bytes of every delivered
    gradient push, wire = what actually crossed (coded payloads +
    re-rowed keys + headers), ratio = raw/wire.  All-zero raw means the
    run never pushed to a PS (e.g. the on-device headline); a ratio of
    ~1.0 means pushes went dense f32."""
    from distlr_tpu.obs.registry import family_total  # noqa: PLC0415

    raw = family_total("distlr_ps_push_bytes_raw_total")
    wire = family_total("distlr_ps_push_bytes_wire_total")
    return {
        "push_bytes_raw": int(raw),
        "push_bytes_wire": int(wire),
        "compress_ratio": round(raw / wire, 3) if wire else 1.0,
    }


def _median_rate(state0, advance, samples_per_window: float,
                 windows: int = 3) -> float:
    """Median rate of ``windows`` timed applications of
    ``advance(state) -> state``, each ended by ``block_until_ready``.
    The driver runs bench.py exactly once per round, so one bad window
    must not become the round's number.  State is threaded through
    windows (donated steps consume their input buffer)."""
    rates = []
    state = state0
    for _ in range(windows):
        t0 = time.perf_counter()
        with trace_phase("compute"):
            state = jax.block_until_ready(advance(state))
        dt = time.perf_counter() - t0
        with trace_phase("checksum"):
            assert np.isfinite(float(jnp.sum(state)))
        rates.append(samples_per_window / dt)
    return float(np.median(rates))


def _bench_tpu(d: int, b: int, steps: int, lr: float, l2: float) -> float:
    from distlr_tpu.config import Config
    from distlr_tpu.models import BinaryLR

    cfg = Config(num_feature_dim=d, learning_rate=lr, l2_c=l2)
    model = BinaryLR(d)

    @jax.jit
    def make_data(key):
        kx, ky = jax.random.split(key)
        X = jax.random.normal(kx, (b, d), dtype=jnp.bfloat16)
        y = jax.random.bernoulli(ky, 0.5, (b,)).astype(jnp.int32)
        return X, y, jnp.ones((b,), jnp.float32)

    with trace_phase("data_gen"):
        batch = jax.block_until_ready(make_data(jax.random.PRNGKey(0)))

    @jax.jit
    def run(w, batch):
        def one_step(w, _):
            g = model.grad(w, batch, cfg)
            return w - cfg.learning_rate * g, None

        w, _ = jax.lax.scan(one_step, w, None, length=steps)
        return w

    w = jnp.zeros(d, jnp.float32)
    with trace_phase("warmup_compile"):
        w = run(w, batch)  # compile warmup
        assert np.isfinite(float(jnp.sum(w)))
    return _median_rate(w, lambda w: run(w, batch), b * steps)


def _bench_dense_int8dot(d: int, b: int, steps: int, lr: float) -> float:
    """Dense step with feature_dtype='int8_dot': int8-resident X and the
    native int8 x int8 -> int32 MXU contraction (no bf16 convert of the
    (B, D) tile).  Model built exactly as the Trainer builds it."""
    import dataclasses

    from distlr_tpu.config import Config
    from distlr_tpu.models import get_model

    cfg = Config(num_feature_dim=d, learning_rate=lr, l2_c=0.0,
                 feature_dtype="int8_dot")
    # feature_scale folded in as Trainer._quantize_features does
    model = dataclasses.replace(get_model(cfg), feature_scale=1.0 / 127.0)

    @jax.jit
    def make_data(key):
        kx, ky = jax.random.split(key)
        X = jax.random.randint(kx, (b, d), -127, 128, dtype=jnp.int8)
        y = jax.random.bernoulli(ky, 0.5, (b,)).astype(jnp.int32)
        return X, y, jnp.ones((b,), jnp.float32)

    batch = jax.block_until_ready(make_data(jax.random.PRNGKey(0)))

    @jax.jit
    def run(w, batch):
        def one_step(w, _):
            return w - cfg.learning_rate * model.grad(w, batch, cfg), None

        w, _ = jax.lax.scan(one_step, w, None, length=steps)
        return w

    w = run(jnp.zeros(d, jnp.float32), batch)  # compile warmup
    assert np.isfinite(float(jnp.sum(w)))
    return _median_rate(w, lambda w: run(w, batch), b * steps)


def _bench_sparse(d: int, b: int, fields: int, steps: int, lr: float) -> float:
    """Sparse one-hot LR step (config-4 style): F scalar gathers/sample,
    segment_sum scatter gradient.  Device-resident batch, donated weights."""
    import functools

    from distlr_tpu.config import Config
    from distlr_tpu.models import SparseBinaryLR

    cfg = Config(num_feature_dim=d, model="sparse_lr", l2_c=0.0)
    model = SparseBinaryLR(d)
    rng = np.random.default_rng(0)
    cols = jnp.asarray(rng.integers(0, d, size=(b, fields)), jnp.int32)
    vals = jnp.ones((b, fields), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, b), jnp.int32)
    mask = jnp.ones(b, jnp.float32)
    batch = (cols, vals, y, mask)

    @functools.partial(jax.jit, donate_argnums=0)
    def step(w, batch):
        return w - lr * model.grad(w, batch, cfg)

    w = step(jnp.zeros(d, jnp.float32), batch)  # compile warmup
    assert np.isfinite(float(jnp.sum(w)))

    def advance(w):
        for _ in range(steps):
            w = step(w, batch)
        return w

    return _median_rate(w, advance, b * steps)


def _bench_blocked(d: int, b: int, fields: int, r: int, steps: int,
                   lr: float) -> float:
    """Row-blocked CTR step: ceil(F/R) row gathers of R lanes/sample."""
    import functools

    from distlr_tpu.config import Config
    from distlr_tpu.data.hashing import make_uniform_blocked_batch
    from distlr_tpu.models import BlockedSparseLR

    nb = d // r
    cfg = Config(num_feature_dim=d, model="blocked_lr", block_size=r, l2_c=0.0)
    model = BlockedSparseLR(nb, r)
    rng = np.random.default_rng(0)
    blocks_np, lane_vals_np = make_uniform_blocked_batch(rng, b, fields, nb, r)
    y = jnp.asarray(rng.integers(0, 2, b), jnp.int32)
    mask = jnp.ones(b, jnp.float32)
    batch = (jnp.asarray(blocks_np), jnp.asarray(lane_vals_np), y, mask)

    @functools.partial(jax.jit, donate_argnums=0)
    def step(t, batch):
        return t - lr * model.grad(t, batch, cfg)

    t = step(jnp.zeros((nb, r), jnp.float32), batch)  # compile warmup
    assert np.isfinite(float(jnp.sum(t)))

    def advance(t):
        for _ in range(steps):
            t = step(t, batch)
        return t

    return _median_rate(t, advance, b * steps)


def _bench_cpu_baseline(d: int, b: int, steps: int, lr: float, l2: float) -> float:
    """Same math, vectorized numpy on host CPU (O(B*D), BLAS-parallel)."""
    rng = np.random.default_rng(0)
    X = rng.standard_normal((b, d)).astype(np.float32)
    y = rng.integers(0, 2, b).astype(np.float32)
    w = np.zeros(d, np.float32)

    def sigmoid(z):  # overflow-stable
        return 0.5 * (1.0 + np.tanh(0.5 * z))

    # one warmup step
    for _ in range(1):
        g = (sigmoid(X @ w) - y) @ X / b + l2 * w
        w -= lr * g
    t0 = time.perf_counter()
    for _ in range(steps):
        g = (sigmoid(X @ w) - y) @ X / b + l2 * w
        w -= lr * g
    dt = time.perf_counter() - t0
    return b * steps / dt


# target rate for the one-glance verdicts below: 100M samples/s on a
# v5e-8 = 12.5M per chip (BASELINE.md north star)
NORTH_STAR_PER_CHIP = 12_500_000
# ...and the D the target is defined at.  North-star verdicts are only
# computable from rows measured ON the accelerator AT this scale.
NORTH_STAR_D = 1_000_000

_FRONTIER_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "benchmarks", "FRONTIER_TPU.json"
)


def _quality_valid_blocked_rs(tol_pts: float = 1.0) -> dict[int, bool]:
    """Which blocked R values hold accuracy, per the measured frontier.

    Sourced from the rate-vs-quality frontier a full
    ``bench_configs.py`` run on the chip writes
    (``benchmarks/FRONTIER_TPU.json``; none exists for today's code): an
    R is quality-valid iff some measured workload regime keeps its
    accuracy within ``tol_pts`` of scalar hashing (the reference's only
    metric is accuracy — ``src/lr.cc:47-63`` — so a rate that loses it
    is not parity).  Missing/unreadable frontier -> empty dict (treated
    as nothing validated, never as everything).
    """
    try:
        with open(_FRONTIER_PATH) as f:
            frontier = json.load(f)["frontier"]
    except (OSError, ValueError, KeyError):
        return {}
    # Preferred source: the operating-point sweep (quality measured at
    # the same table scale as the rates, r5) — its verdict lists the
    # default-grouping R values that held within 1pt there.
    op = frontier.get("operating_point")
    if isinstance(op, dict) and "valid_default_rs" in op:
        valid = set(op["valid_default_rs"])
        return {r: r in valid for r in ({8, 16, 32} | valid)}
    out: dict[int, bool] = {}
    for regime in frontier.values():
        if not isinstance(regime, dict):
            continue
        for key, cell in regime.items():
            if not (key.startswith("r") and key[1:].isdigit()
                    and isinstance(cell, dict)):
                continue
            r = int(key[1:])
            ok = cell.get("delta_vs_scalar_pts", -1e9) >= -tol_pts
            out[r] = out.get(r, False) or ok
    return out


def _quality_valid_rs_annotated(tol_pts: float = 1.0) -> dict:
    """Per-R regime-annotated quality verdicts from the operating-point
    sweep (VERDICT r5 weak #2: the flat ``quality_frontier_valid_rs``
    list reads as "always safe" when e.g. default-grouping R=16 loses
    17pt on low-card iid at the very same operating point).

    For each default-grouping R at the LARGEST measured dc, returns::

        {"r32": {"valid": bool,
                 "validated_by": [{regime, dc, delta_vs_scalar_pts,
                                   row_load, min_recurrence, groups}],
                 "fails_in":    [...same records...]}}

    so a reader sees *on which workload regime* (and at what measured
    row_load/recurrence) each R holds — and where it does not.  Missing
    artifact -> empty dict.
    """
    try:
        with open(_FRONTIER_PATH) as f:
            regimes = json.load(f)["frontier"]["operating_point"]["regimes"]
    except (OSError, ValueError, KeyError, TypeError):
        return {}
    detail: dict = {}
    for regime_name, by_dc in regimes.items():
        if not isinstance(by_dc, dict):
            continue
        dcs = sorted((k for k in by_dc
                      if k.startswith("dc") and k[2:].isdigit()),
                     key=lambda k: int(k[2:]))
        if not dcs:
            continue
        dc = dcs[-1]  # the operating-point scale
        for variant, cell in by_dc[dc].items():
            if not (variant.startswith("r") and variant[1:].isdigit()
                    and isinstance(cell, dict)):
                continue  # default-grouping rows only (rN, not rN_gM)
            r = f"r{int(variant[1:])}"
            entry = detail.setdefault(
                r, {"valid": False, "validated_by": [], "fails_in": []})
            delta = cell.get("delta_vs_scalar_pts", -1e9)
            rec = {
                "regime": regime_name,
                "dc": int(dc[2:]),
                "delta_vs_scalar_pts": delta,
                "row_load": cell.get("row_load"),
                "min_recurrence": cell.get("min_recurrence"),
                "groups": cell.get("groups"),
            }
            if delta >= -tol_pts:
                entry["valid"] = True
                entry["validated_by"].append(rec)
            else:
                entry["fails_in"].append(rec)
    return detail


def main():
    # --smoke: tiny headline-only shapes for tier-1 CI (the plumbing —
    # JSON schema, phase_breakdown — is the real path; the rates are
    # meaningless).  It runs wherever JAX lands and says so in its row;
    # the full-size run measures the TPU and refuses anything else.
    smoke = "--smoke" in sys.argv
    maybe_arm_profiler()
    dev = start_benchmark("bench.py", full_size=not smoke)
    d, b, steps = 1_000_000, 2048, 20
    if smoke:
        d, b, steps = 8192, 256, 2
    lr, l2 = 0.2, 0.01

    # Headline phase accounting (ISSUE 2): the spans inside _bench_tpu /
    # _median_rate land in the process tracer; their per-phase sums must
    # explain the headline wall clock (asserted within 20% by
    # tests/test_benchmarks_smoke.py) — every future on-chip capture says
    # where its time went, not just how fast it was.
    tracer = get_tracer()
    tracer.reset()
    t_headline = time.perf_counter()
    value = _bench_tpu(d, b, steps, lr, l2)
    headline_wall = time.perf_counter() - t_headline
    phases = tracer.breakdown()
    # self seconds: a phase nested in another is counted once
    covered = sum(p["self_seconds"] for p in phases.values())
    phase_breakdown = {
        "phases": phases,
        "wall_s": round(headline_wall, 6),
        # fraction of the headline wall clock the spans explain; the
        # complement is unattributed (python glue, allocator, GC)
        "coverage": round(covered / headline_wall, 4) if headline_wall else 0.0,
    }
    baseline = _bench_cpu_baseline(d, min(b, 256), 2, lr, l2)

    # Sparse + blocked sub-rows at config-4 shape (D=1M, 21 CTR fields):
    # the artifact carries them, not just the dense headline.
    fields = 21
    sub_b = 65536
    sub_steps = 20
    subs: dict[str, float] = {}
    for name, fn in [] if smoke else [
        ("dense_int8dot_samples_per_sec",
         lambda: _bench_dense_int8dot(d, b, steps, lr)),
        ("sparse_samples_per_sec",
         lambda: _bench_sparse(d, sub_b, fields, sub_steps, lr)),
        ("blocked_r8_samples_per_sec",
         lambda: _bench_blocked(d, sub_b, fields, 8, sub_steps, lr)),
        ("blocked_r16_samples_per_sec",
         lambda: _bench_blocked(d, sub_b, fields, 16, sub_steps, lr)),
        ("blocked_r32_samples_per_sec",
         lambda: _bench_blocked(d, sub_b, fields, 32, sub_steps, lr)),
    ]:
        subs[name] = round(fn(), 1)

    best = max([value, *subs.values()])
    # Quality-aware headline (VERDICT r4 #2): the raw best may come from
    # a blocked R whose rate is memorization-only (frontier-measured
    # accuracy loss).  best_quality_valid excludes those rows, so the
    # artifact cannot be read as "north star cleared" unless quality held.
    valid_rs = _quality_valid_blocked_rs()
    quality_valid_rates = [value] + [
        v for name, v in subs.items()
        if not name.startswith("blocked_")
        or valid_rs.get(int(name.split("_")[1][1:]), False)
    ]
    best_quality_valid = max(quality_valid_rates)
    # North-star verdicts require on-accelerator rates AT north-star D;
    # a --smoke row can never carry one.
    ns_eligible = dev["backend"] == "tpu" and d >= NORTH_STAR_D
    row = {
        "metric": f"samples/sec, dense binary LR, D={d}, sync step, 1 chip",
        "value": round(value, 1),
        "unit": "samples/sec",
        "vs_baseline": round(value / baseline, 2),
        **dev,
        "D": d,
        "B": b,
        "steps": steps,
        # best rate across model families this run (blocked R=32 is the
        # north-star-class path: >=12.5M/chip target, BASELINE.md) —
        # quality-BLIND; judge against best_quality_valid_samples_per_sec
        "best_samples_per_sec": round(best, 1),
        "best_samples_per_sec_quality_valid": best_quality_valid == best,
        # largest rate among configs whose accuracy holds within 1pt of
        # scalar hashing per the frontier artifact, when one exists;
        # dense/sparse rows are scalar-exact and always eligible
        "best_quality_valid_samples_per_sec": round(best_quality_valid, 1),
        "quality_frontier_valid_rs": sorted(
            r for r, ok in valid_rs.items() if ok),
        # ...annotated per R with the validating regime and its measured
        # row_load / min_recurrence — the flat list above is exists-a-
        # regime semantics and must not be read as "safe on any data"
        "quality_frontier_valid_rs_detail": _quality_valid_rs_annotated(),
        "north_star_per_chip": NORTH_STAR_PER_CHIP,
        # on-accelerator at north-star D, else the verdict below is
        # suppressed (False) regardless of this run's shrunken rates
        "north_star_eligible": ns_eligible,
        # the one-glance verdict: a quality-holding configuration at or
        # above the target rate exists (rate from this run's rows,
        # validity from the measured frontier artifact) — only claimable
        # from an eligible (on-chip, D=1M) run
        "north_star_cleared_with_quality": bool(
            ns_eligible and best_quality_valid >= NORTH_STAR_PER_CHIP),
        "sub_B": sub_b,
        "sub_fields": fields,
        # where the headline measurement's time went (tracer span sums
        # vs the headline wall clock — see obs/tracing.py)
        "phase_breakdown": phase_breakdown,
        # fault-cost counters (retries/reconnects/unknown pushes/chaos
        # faults): all-zero = healthy network; non-zero explains a slow
        # row without re-running it
        "resilience": resilience_snapshot(),
        # push-byte accounting (raw/wire/ratio): zero for the on-device
        # headline, meaningful for any sub-run that pushed to a PS —
        # benchmarks/bench_compress.py measures the codecs head-on
        **compression_snapshot(),
        # optional DISTLR_PROFILE_TOP=<N> sampler snapshot: top self-
        # time frames of the bench process itself (absent by default)
        **profile_snapshot(),
        **subs,
    }
    if smoke:
        row["smoke"] = True
    print(json.dumps(row))


if __name__ == "__main__":
    main()
