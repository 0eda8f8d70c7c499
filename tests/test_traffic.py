"""Unit table for the shared traffic model (ISSUE 19 satellite).

:mod:`distlr_tpu.traffic` is the ONE offered-load model both
``distlr_tpu/serve/loadgen.py`` (real sockets) and fleetsim (simulated
arrivals) drive — these tests pin the arithmetic both drivers now
share: the diurnal curve and its deterministic send schedule, Zipf
popularity (sampling AND the closed-form ``mass`` the
reshard-convergence property uses), tenant-mix parsing/apportionment,
and the replayable label-delay distribution.
"""

from __future__ import annotations

import math
import random
import statistics

import pytest

from distlr_tpu.traffic import (
    LabelDelay,
    ZipfSampler,
    parse_tenant_mix,
    qps_at,
    schedule,
    split_by_mix,
)


class TestDiurnalCurve:
    def test_base_at_period_edges_peak_at_half(self):
        assert qps_at(0.0, 10.0, 50.0, 60.0) == pytest.approx(10.0)
        assert qps_at(60.0, 10.0, 50.0, 60.0) == pytest.approx(10.0)
        assert qps_at(30.0, 10.0, 50.0, 60.0) == pytest.approx(50.0)

    def test_curve_is_symmetric_about_the_peak(self):
        for dt in (1.0, 7.0, 13.0):
            assert qps_at(30.0 - dt, 10.0, 50.0, 60.0) == pytest.approx(
                qps_at(30.0 + dt, 10.0, 50.0, 60.0))

    def test_schedule_integrates_the_curve(self):
        """Send count over a whole period ~ the mean qps times the
        duration, offsets strictly non-decreasing, byte-identical on a
        re-run (no RNG anywhere in the open-loop schedule)."""
        times = schedule(60.0, 10.0, 50.0, 60.0)
        mean_qps = (10.0 + 50.0) / 2.0
        assert len(times) == pytest.approx(mean_qps * 60.0, rel=0.02)
        assert times == sorted(times)
        assert times == schedule(60.0, 10.0, 50.0, 60.0)

    def test_schedule_density_follows_the_curve(self):
        times = schedule(60.0, 10.0, 50.0, 60.0)
        trough = sum(1 for t in times if t < 10.0)
        crest = sum(1 for t in times if 25.0 <= t < 35.0)
        assert crest > 2 * trough


class TestZipfSampler:
    def test_validation_is_loud(self):
        with pytest.raises(ValueError, match="n >= 1"):
            ZipfSampler(0)
        with pytest.raises(ValueError, match="alpha"):
            ZipfSampler(8, alpha=-0.1)

    def test_alpha_zero_is_uniform(self):
        z = ZipfSampler(100, alpha=0.0)
        assert z.mass(0, 25) == pytest.approx(0.25)
        assert z.mass(25, 100) == pytest.approx(0.75)

    def test_mass_is_a_probability(self):
        z = ZipfSampler(64, alpha=1.1)
        assert z.mass(0, 64) == pytest.approx(1.0)
        assert z.mass(10, 10) == 0.0
        assert z.mass(-5, 3) == pytest.approx(z.mass(0, 3))
        parts = sum(z.mass(k, k + 1) for k in range(64))
        assert parts == pytest.approx(1.0)

    def test_head_is_hotter_than_the_tail(self):
        z = ZipfSampler(1 << 14, alpha=1.1)
        assert z.mass(0, 16) > 0.3
        assert z.mass(0, 16) > 100 * z.mass(1 << 13, (1 << 13) + 16)

    def test_samples_match_the_closed_form_mass(self):
        """The inverse-CDF sampler and ``mass`` describe the SAME
        distribution — the reshard property's hot-share bound is only
        meaningful if the closed form matches what a sampler would
        see."""
        z = ZipfSampler(32, alpha=1.0)
        rng = random.Random(7)
        n = 20_000
        hits = sum(1 for _ in range(n) if z.sample(rng) < 4)
        assert hits / n == pytest.approx(z.mass(0, 4), abs=0.01)

    def test_sampling_is_replayable(self):
        z = ZipfSampler(256, alpha=1.1)
        rng_a, rng_b = random.Random(3), random.Random(3)
        a = [z.sample(rng_a) for _ in range(200)]
        b = [z.sample(rng_b) for _ in range(200)]
        assert a == b
        assert all(0 <= k < 256 for k in a)


class TestTenantMix:
    def test_parse_normalizes(self):
        mix = parse_tenant_mix("v1=0.8, v2=0.2")
        assert mix == {"v1": pytest.approx(0.8), "v2": pytest.approx(0.2)}
        mix = parse_tenant_mix("a=2,b=6")
        assert mix["a"] == pytest.approx(0.25)
        assert mix["b"] == pytest.approx(0.75)

    def test_parse_accepts_a_ready_mapping(self):
        assert parse_tenant_mix({"m": 3, "n": 1})["m"] == pytest.approx(0.75)

    def test_parse_rejects_garbage_loudly(self):
        with pytest.raises(ValueError, match="empty"):
            parse_tenant_mix("")
        with pytest.raises(ValueError, match="twice"):
            parse_tenant_mix("v1=1,v1=2")
        with pytest.raises(ValueError, match="model=weight"):
            parse_tenant_mix("v1")
        with pytest.raises(ValueError, match="must be a number"):
            parse_tenant_mix("v1=lots")
        with pytest.raises(ValueError, match="positive"):
            parse_tenant_mix("v1=0")
        with pytest.raises(ValueError, match="positive"):
            parse_tenant_mix("v1=-2")

    def test_split_sums_and_is_deterministic(self):
        mix = parse_tenant_mix("a=0.5,b=0.3,c=0.2")
        out = split_by_mix(7, mix)
        assert sum(out.values()) == 7
        assert out == split_by_mix(7, mix)
        # largest remainder: everyone gets at least the floor
        assert out["a"] >= 3 and out["b"] >= 2 and out["c"] >= 1

    def test_split_edge_counts(self):
        mix = parse_tenant_mix("a=1,b=1")
        assert sum(split_by_mix(0, mix).values()) == 0
        assert sum(split_by_mix(1, mix).values()) == 1
        with pytest.raises(ValueError, match=">= 0"):
            split_by_mix(-1, mix)


class TestLabelDelay:
    def test_validation_is_loud(self):
        with pytest.raises(ValueError, match="p50_s <= p95_s"):
            LabelDelay(5.0, 2.0)
        with pytest.raises(ValueError, match="p50_s"):
            LabelDelay(0.0, 2.0)

    def test_degenerate_distribution_is_constant(self):
        d = LabelDelay(3.0, 3.0)
        assert d.sample(random.Random(1)) == 3.0

    def test_quantiles_pin_the_lognormal(self):
        d = LabelDelay(2.0, 30.0)
        rng = random.Random(5)
        draws = sorted(d.sample(rng) for _ in range(20_000))
        assert statistics.median(draws) == pytest.approx(2.0, rel=0.05)
        assert draws[int(0.95 * len(draws))] == pytest.approx(30.0,
                                                              rel=0.10)
        assert all(x > 0 and math.isfinite(x) for x in draws)

    def test_sampling_is_replayable(self):
        d = LabelDelay(2.0, 30.0)
        a = [d.sample(random.Random(9)) for _ in range(3)]
        b = [d.sample(random.Random(9)) for _ in range(3)]
        assert a == b
