"""Gradient compression on the PS wire (ISSUE 7).

Covers the codec subsystem end to end:

* the NumPy reference codecs (``distlr_tpu/compress/codecs.py``) —
  roundtrip error bounds and payload-size formulas;
* BIT-EXACT wire parity: what a real ``distlr_kv_server`` decodes from
  a native client's coded push equals the NumPy oracle, including the
  per-server-slice block layout;
* the signSGD majority-vote merge kernel vs a NumPy oracle (async
  one-voter and sync BSP vote-then-apply), mirroring the FTRL parity
  suite;
* capability negotiation: an old server (simulated with
  ``--compress=0``) answers kHello empty and the client falls back to
  dense f32 — gracefully, not desynchronized; reconnects re-negotiate;
* push-byte accounting: ``distlr_ps_push_bytes_{raw,wire}_total``
  count DELIVERED pushes exactly once — retries and absorbed
  unknown-outcome pushes cannot inflate the compression ratio;
* the ``GradientAccumulator`` (AdaBatch) schedule;
* trainer integration: both codecs converge on sync BSP and async
  Hogwild through ``run_ps_local``;
* the ROADMAP acceptance, tier-1-runnable: >= 8x push-byte reduction
  at <= 0.5pt accuracy cost at D=1M, dense gradient pushes through the
  chaos proxy's throttle mode.
"""

import argparse
import contextlib
import logging
import os
import threading

import numpy as np
import pytest

from distlr_tpu.chaos import ChaosFabric, parse_plan
from distlr_tpu.compress import (
    GradientAccumulator,
    QUANT_BLOCK,
    decode_sign,
    encode_int8,
    encode_sign,
    int8_error_bound,
    int8_roundtrip,
    payload_bytes,
    sign_roundtrip,
)
from distlr_tpu.config import Config
from distlr_tpu.ps import KVWorker, RetryPolicy, ServerGroup


def _counter_total(name: str) -> float:
    from distlr_tpu.obs.registry import get_registry

    fam = get_registry().get(name)
    if fam is None:
        return 0.0
    return float(sum(child.value for _v, child in fam.children()))


@contextlib.contextmanager
def _capture_client_logs():
    """Collect distlr_tpu.ps.client records (the module logger doesn't
    propagate, so caplog never sees them — attach directly)."""
    records: list[logging.LogRecord] = []
    handler = logging.Handler()
    handler.emit = records.append
    logger = logging.getLogger("distlr_tpu.ps.client")
    logger.addHandler(handler)
    try:
        yield records
    finally:
        logger.removeHandler(handler)


# ---------------------------------------------------------------------------
# NumPy reference codecs
# ---------------------------------------------------------------------------

class TestCodecReference:
    @pytest.mark.parametrize("n", [1, 5, 255, 256, 257, 1000, 4096])
    def test_int8_roundtrip_within_bound(self, n):
        rng = np.random.default_rng(n)
        # mixed magnitudes stress per-block scales: each block's error
        # bound is its OWN amax/254, not a global one
        v = (rng.normal(size=n) * (10.0 ** rng.integers(-3, 3, size=n))
             ).astype(np.float32)
        err = np.abs(int8_roundtrip(v) - v)
        assert np.all(err <= int8_error_bound(v))

    def test_int8_zero_block_exact(self):
        v = np.zeros(QUANT_BLOCK * 2, np.float32)
        v[QUANT_BLOCK:] = 3.5  # second block non-zero, first all-zero
        rt = int8_roundtrip(v)
        np.testing.assert_array_equal(rt[:QUANT_BLOCK], 0.0)
        # exact zeros inside a non-zero block also roundtrip exactly
        w = np.array([1.0, 0.0, -2.0, 0.0], np.float32)
        assert int8_roundtrip(w)[1] == 0.0 and int8_roundtrip(w)[3] == 0.0

    def test_payload_bytes_formulas(self):
        for n in (1, 255, 256, 257, 1 << 20):
            nb = (n + QUANT_BLOCK - 1) // QUANT_BLOCK
            assert payload_bytes("int8", n) == nb * 4 + n
            assert payload_bytes("signsgd", n) == (n + 7) // 8
            assert payload_bytes("none", n) == 4 * n
        with pytest.raises(ValueError, match="unknown codec"):
            payload_bytes("gzip", 8)

    def test_int8_encode_sizes_match_payload(self):
        v = np.random.default_rng(0).normal(size=300).astype(np.float32)
        scales, q = encode_int8(v)
        assert scales.nbytes + q.nbytes == payload_bytes("int8", 300)
        assert encode_sign(v).nbytes == payload_bytes("signsgd", 300)

    @pytest.mark.parametrize("n", [1, 7, 8, 9, 300])
    def test_sign_roundtrip(self, n):
        rng = np.random.default_rng(n)
        v = rng.normal(size=n).astype(np.float32)
        v[::3] = 0.0  # exact zeros decode -1 by convention
        got = decode_sign(encode_sign(v), n)
        np.testing.assert_array_equal(
            got, np.where(v > 0, np.float32(1.0), np.float32(-1.0)))


# ---------------------------------------------------------------------------
# wire parity: native encode -> server decode == NumPy oracle, bit for bit
# ---------------------------------------------------------------------------

class TestWireParity:
    """lr=1.0 and w0=0 make the pulled weights EXACTLY the negated
    decoded gradient — any bit of codec drift between the native
    EncodeGrad/DecodeGrad and the NumPy reference fails array_equal."""

    def test_int8_dense_push_bit_exact(self):
        d = 300  # one full block + one partial per... (300 < 2 blocks)
        g = np.random.default_rng(1).normal(size=d).astype(np.float32)
        with ServerGroup(1, 1, d, sync=False, learning_rate=1.0) as sg, \
                KVWorker(sg.hosts, d, sync_group=False,
                         compress="int8") as kv:
            assert kv.compress_active == "int8"
            kv.push_init(np.zeros(d, np.float32))
            kv.wait(kv.push(g))
            got = kv.pull()
        np.testing.assert_array_equal(got, -int8_roundtrip(g))

    def test_int8_dense_push_per_server_slice_blocks(self):
        """Each server's slice is its own coded frame: quant blocks
        restart at the slice boundary (600/2 = 300, NOT a multiple of
        QUANT_BLOCK), so a flat-vector oracle would be wrong."""
        d = 600
        g = np.random.default_rng(2).normal(size=d).astype(np.float32)
        with ServerGroup(2, 1, d, sync=False, learning_rate=1.0) as sg, \
                KVWorker(sg.hosts, d, sync_group=False,
                         compress="int8") as kv:
            kv.push_init(np.zeros(d, np.float32))
            kv.wait(kv.push(g))
            got = kv.pull()
        oracle = np.concatenate(
            [int8_roundtrip(g[:300]), int8_roundtrip(g[300:])])
        np.testing.assert_array_equal(got, -oracle)

    def test_int8_keyed_push_bit_exact(self):
        d = 600
        rng = np.random.default_rng(3)
        keys_lo = np.sort(rng.choice(300, size=5, replace=False))
        keys_hi = np.sort(rng.choice(300, size=7, replace=False)) + 300
        keys = np.concatenate([keys_lo, keys_hi]).astype(np.uint64)
        vals = rng.normal(size=keys.size).astype(np.float32)
        with ServerGroup(2, 1, d, sync=False, learning_rate=1.0) as sg, \
                KVWorker(sg.hosts, d, sync_group=False,
                         compress="int8") as kv:
            kv.push_init(np.zeros(d, np.float32))
            kv.wait(kv.push(vals, keys=keys))
            got = kv.pull()
        oracle = np.concatenate(
            [int8_roundtrip(vals[:5]), int8_roundtrip(vals[5:])])
        np.testing.assert_array_equal(got[keys.astype(np.int64)], -oracle)
        untouched = np.setdiff1d(np.arange(d), keys.astype(np.int64))
        np.testing.assert_array_equal(got[untouched], 0.0)

    def test_sign_dense_push_one_voter(self):
        """Async signSGD = a one-voter majority: w -= lr on +1 votes,
        w += lr on -1 votes (exact zeros decode -1 by convention)."""
        d = 40
        lr = 0.25  # exactly representable: array_equal below is exact
        g = np.random.default_rng(4).normal(size=d).astype(np.float32)
        g[::5] = 0.0
        with ServerGroup(1, 1, d, sync=False, learning_rate=lr,
                         optimizer="signsgd") as sg, \
                KVWorker(sg.hosts, d, sync_group=False,
                         compress="signsgd") as kv:
            assert kv.compress_active == "signsgd"
            kv.push_init(np.zeros(d, np.float32))
            kv.wait(kv.push(g))
            got = kv.pull()
        oracle = np.where(sign_roundtrip(g) > 0,
                          np.float32(-lr), np.float32(lr))
        np.testing.assert_array_equal(got, oracle)


# ---------------------------------------------------------------------------
# signSGD majority vote (sync BSP) vs NumPy oracle
# ---------------------------------------------------------------------------

def signsgd_vote_oracle(w0, rounds, lr):
    """NumPy mirror of the server's BSP vote-then-apply kernel:
    ``rounds`` is a sequence of per-round gradient lists (one per
    worker); each worker's vote is ``sign_roundtrip`` of its gradient
    (what kCodecSign decodes), the round applies ONE step
    ``w -= lr * sign(sum votes)`` with tied coordinates untouched."""
    w = np.array(w0, np.float32).copy()
    for grads in rounds:
        votes = np.sum([sign_roundtrip(g) for g in grads], axis=0)
        w = (w - np.float32(lr) * np.sign(votes).astype(np.float32)
             ).astype(np.float32)
    return w


class TestSignMajorityVote:
    def test_mostly_zero_push_warns_once(self):
        """1-bit signSGD has no abstention — an exact zero votes -1 and
        walks its weight +lr per round.  A first push that is mostly
        zeros (a sparse gradient sent full-width) is the signature of
        that misuse, and the client must say so; a genuinely dense
        gradient must stay silent."""
        d = 64
        sparse_g = np.zeros(d, np.float32)
        sparse_g[3] = 1.0
        with ServerGroup(1, 1, d, sync=False, learning_rate=0.1,
                         optimizer="signsgd") as sg, _capture_client_logs() \
                as records:
            with KVWorker(sg.hosts, d, sync_group=False,
                          compress="signsgd") as kv:
                kv.push_init(np.zeros(d, np.float32))
                kv.wait(kv.push(sparse_g))
                kv.wait(kv.push(sparse_g))  # checked once, warned once
            warns = [r for r in records
                     if "mostly exact zeros" in r.getMessage()]
            assert len(warns) == 1
            records.clear()
            with KVWorker(sg.hosts, d, sync_group=False, client_id=1,
                          compress="signsgd") as kv:
                kv.wait(kv.push(np.ones(d, np.float32)))
            assert not [r for r in records
                        if "mostly exact zeros" in r.getMessage()]

    def test_bsp_round_votes_and_ties(self):
        """One BSP round, two workers: agreeing coordinates step once
        by lr, disagreeing (tied) coordinates stay untouched."""
        d = 12
        lr = 0.25
        g1 = np.array([1, 1, -1, -1, 2, -2, 1, -1, 3, -3, 1, -1],
                      np.float32)
        g2 = np.array([2, 1, -2, -1, -1, 2, 1, -1, 3, -3, -1, 1],
                      np.float32)
        with ServerGroup(1, 2, d, sync=True, learning_rate=lr,
                         optimizer="signsgd") as sg, \
                KVWorker(sg.hosts, d, client_id=0,
                         compress="signsgd") as kv0, \
                KVWorker(sg.hosts, d, client_id=1,
                         compress="signsgd") as kv1:
            kv0.push_init(np.zeros(d, np.float32))

            t = threading.Thread(target=lambda: kv1.wait(kv1.push(g2)),
                                 daemon=True)
            t.start()
            kv0.wait(kv0.push(g1))  # blocking push = the BSP barrier
            t.join(timeout=30)
            assert not t.is_alive()
            got = kv0.pull()
        np.testing.assert_array_equal(
            got, signsgd_vote_oracle(np.zeros(d, np.float32),
                                     [[g1, g2]], lr))
        # ties (coords 4, 5, 10, 11 disagree) stayed exactly zero
        np.testing.assert_array_equal(got[[4, 5, 10, 11]], 0.0)

    def test_bsp_trajectory_matches_oracle(self):
        d = 32
        lr = 0.125
        rounds = 6
        rng = np.random.default_rng(7)
        ga = [rng.normal(size=d).astype(np.float32) for _ in range(rounds)]
        gb = [rng.normal(size=d).astype(np.float32) for _ in range(rounds)]
        ga[2][::4] = 0.0  # exact zeros ride the -1 decode convention
        with ServerGroup(2, 2, d, sync=True, learning_rate=lr,
                         optimizer="signsgd") as sg, \
                KVWorker(sg.hosts, d, client_id=0,
                         compress="signsgd") as kv0, \
                KVWorker(sg.hosts, d, client_id=1,
                         compress="signsgd") as kv1:
            kv0.push_init(np.zeros(d, np.float32))

            def worker(kv, grads):
                for g in grads:
                    kv.wait(kv.push(g))

            t = threading.Thread(target=worker, args=(kv1, gb), daemon=True)
            t.start()
            worker(kv0, ga)
            t.join(timeout=30)
            assert not t.is_alive()
            got = kv0.pull()
        oracle = signsgd_vote_oracle(
            np.zeros(d, np.float32),
            [[a, b] for a, b in zip(ga, gb)], lr)
        np.testing.assert_array_equal(got, oracle)


# ---------------------------------------------------------------------------
# capability negotiation / graceful fallback
# ---------------------------------------------------------------------------

class TestNegotiation:
    def test_old_server_falls_back_to_dense(self):
        """--compress=0 answers kHello like a pre-codec binary: the
        client logs a fallback ON THE FIRST CONNECT (the operator asked
        for a codec and must see the downgrade), compress_active stays
        'none', and the pushes that follow are plain dense f32
        (bit-exact)."""
        d = 64
        g = np.random.default_rng(5).normal(size=d).astype(np.float32)
        with ServerGroup(1, 1, d, sync=False, learning_rate=1.0,
                         compress=False) as sg, \
                _capture_client_logs() as records, \
                KVWorker(sg.hosts, d, sync_group=False,
                         compress="int8") as kv:
            assert kv.compress_active == "none"
            assert any("falling back to dense f32" in r.getMessage()
                       for r in records)
            kv.push_init(np.zeros(d, np.float32))
            kv.wait(kv.push(g))
            got = kv.pull()
        np.testing.assert_array_equal(got, -g)

    def test_mixed_group_falls_back(self):
        """Capabilities INTERSECT across the group: one legacy server
        downgrades every connection to dense f32 (degrade, don't
        desynchronize)."""
        d = 64
        with ServerGroup(1, 1, d // 2, sync=False) as new_sg, \
                ServerGroup(1, 1, d // 2, sync=False,
                            compress=False) as old_sg:
            hosts = f"{new_sg.hosts},{old_sg.hosts}"
            with KVWorker(hosts, d, sync_group=False,
                          compress="int8") as kv:
                assert kv.compress_active == "none"
                kv.push_init(np.zeros(d, np.float32))
                kv.wait(kv.push(np.ones(d, np.float32)))

    def test_sign_codec_needs_signsgd_server(self):
        """kCapCodecSign is advertised ONLY by --optimizer=signsgd
        servers: ±1 votes through plain SGD would be sign-mean, not
        majority vote — so an sgd group downgrades the client."""
        d = 16
        with ServerGroup(1, 1, d, sync=False) as sg, \
                KVWorker(sg.hosts, d, sync_group=False,
                         compress="signsgd") as kv:
            assert kv.compress_active == "none"

    def test_ftrl_group_advertises_int8(self):
        d = 16
        with ServerGroup(1, 1, d, sync=False, optimizer="ftrl") as sg, \
                KVWorker(sg.hosts, d, sync_group=False,
                         compress="int8") as kv:
            assert kv.compress_active == "int8"

    def test_reconnect_renegotiates(self):
        d = 300
        g = np.random.default_rng(6).normal(size=d).astype(np.float32)
        with ServerGroup(1, 1, d, sync=False, learning_rate=1.0) as sg, \
                KVWorker(sg.hosts, d, sync_group=False,
                         compress="int8") as kv:
            kv.push_init(np.zeros(d, np.float32))
            kv.reconnect()
            assert kv.compress_active == "int8"
            kv.wait(kv.push(g))
            np.testing.assert_array_equal(kv.pull(), -int8_roundtrip(g))

    def test_unknown_codec_name_rejected(self):
        with pytest.raises(ValueError, match="compress"):
            KVWorker("127.0.0.1:1", 8, compress="gzip")


# ---------------------------------------------------------------------------
# push-byte accounting
# ---------------------------------------------------------------------------

#: key bytes of one dense default-key op in the tests below: the keys
#: cross as row runs of v values under one u64 (v the largest count
#: <= MAX_VALS_PER_KEY that divides dim and every range boundary;
#: tests/test_ps_dense_frames.py holds the resolver to that), and every
#: d here is <= 4096 on one server, so v = d: ONE row key
ONE_ROW_KEY = 8


def _push_byte_deltas():
    return (_counter_total("distlr_ps_push_bytes_raw_total"),
            _counter_total("distlr_ps_push_bytes_wire_total"))


class TestByteAccounting:
    def test_int8_dense_counters_exact(self):
        """The wire counter is EXACT: header (24) + re-rowed key frame
        + per-block scales + int8 payload, per delivered push."""
        d = 512
        raw0, wire0 = _push_byte_deltas()
        with ServerGroup(1, 1, d, sync=False) as sg, \
                KVWorker(sg.hosts, d, sync_group=False,
                         compress="int8") as kv:
            kv.push_init(np.zeros(d, np.float32))
            for i in range(3):
                kv.wait(kv.push(np.full(d, float(i + 1), np.float32)))
        raw1, wire1 = _push_byte_deltas()
        # the dense default keys cross as row runs, coded or not:
        # d = 512 on one server is one vpk=512 row == ONE u64 key
        per_raw = ONE_ROW_KEY + d * 4   # row key + f32 vals
        per_wire = 24 + 8 + payload_bytes("int8", d)
        assert raw1 - raw0 == 3 * per_raw
        assert wire1 - wire0 == 3 * per_wire
        # the ratio is the codec's own saving (int8: 4 B -> ~1 B a value)
        assert (raw1 - raw0) / (wire1 - wire0) > 3.5

    def test_none_counters_wire_equals_raw_plus_headers(self):
        d = 128
        raw0, wire0 = _push_byte_deltas()
        with ServerGroup(1, 1, d, sync=False) as sg, \
                KVWorker(sg.hosts, d, sync_group=False) as kv:
            kv.push_init(np.zeros(d, np.float32))
            kv.wait(kv.push(np.ones(d, np.float32)))
        raw1, wire1 = _push_byte_deltas()
        per_raw = ONE_ROW_KEY + d * 4
        assert raw1 - raw0 == per_raw
        assert wire1 - wire0 == per_raw + 24

    def test_no_double_count_under_chaos_retries(self):
        """Retried and absorbed pushes cannot inflate the ratio: raw
        and wire tick once per DELIVERED push — issued minus the
        absorbed unknown-outcome ones — never per attempt."""
        d = 64
        plan = parse_plan({"faults": [
            {"kind": "reset", "after_ops": 5},
        ]})
        issued = 8
        raw0, wire0 = _push_byte_deltas()
        unknown0 = _counter_total("distlr_ps_push_outcome_unknown_total")
        retries0 = _counter_total("distlr_ps_retries_total")
        with ServerGroup(1, 1, d, sync=False) as sg, \
                ChaosFabric(sg.direct_hosts, plan) as fab, \
                KVWorker(fab.hosts, d, timeout_ms=2000, sync_group=False,
                         retry=RetryPolicy(attempts=6, backoff_ms=10),
                         compress="int8") as kv:
            kv.push_init(np.zeros(d, np.float32))
            for _ in range(issued):
                kv.wait(kv.push(np.ones(d, np.float32)))
            assert any(e[1] == "reset" for e in fab.events())
        raw1, wire1 = _push_byte_deltas()
        unknowns = int(
            _counter_total("distlr_ps_push_outcome_unknown_total")
            - unknown0)
        delivered = issued - unknowns
        per_raw = ONE_ROW_KEY + d * 4
        per_wire = 24 + 8 + payload_bytes("int8", d)
        assert raw1 - raw0 == delivered * per_raw
        assert wire1 - wire0 == delivered * per_wire
        # the fault actually cost something, and the accounting did not
        # follow the re-issues
        assert unknowns + (_counter_total("distlr_ps_retries_total")
                           - retries0) >= 1

    def test_compression_ratio_gauge_tracks_totals(self):
        from distlr_tpu.obs.registry import get_registry

        d = 256
        with ServerGroup(1, 1, d, sync=False) as sg, \
                KVWorker(sg.hosts, d, sync_group=False,
                         compress="int8") as kv:
            kv.push_init(np.zeros(d, np.float32))
            kv.wait(kv.push(np.ones(d, np.float32)))
        fam = get_registry().get("distlr_ps_push_compress_ratio")
        assert fam is not None
        (_, child), = fam.children()
        raw, wire = _push_byte_deltas()  # cumulative totals
        assert child.value == pytest.approx(raw / wire)

    def test_chaos_proxy_frames_coded_pushes(self):
        """The proxy's op counter advances across compressed pushes —
        i.e. it parsed the coded frames instead of degrading to a raw
        relay (which would silently disable op-offset faults)."""
        d = 300
        ops0 = _counter_total("distlr_chaos_ops_forwarded_total")
        with ServerGroup(1, 1, d, sync=False,
                         learning_rate=1.0) as sg, \
                ChaosFabric(sg.direct_hosts, parse_plan({"faults": [
                    {"kind": "delay", "delay_ms": 1}]})) as fab, \
                KVWorker(fab.hosts, d, sync_group=False,
                         compress="int8") as kv:
            kv.push_init(np.zeros(d, np.float32))
            g = np.random.default_rng(8).normal(size=d).astype(np.float32)
            for _ in range(3):
                kv.wait(kv.push(g))
            got = kv.pull()
        # hello + init + 3 pushes + pull >= 6 frames, all parsed
        assert _counter_total("distlr_chaos_ops_forwarded_total") - ops0 >= 6
        np.testing.assert_array_equal(
            got, 3.0 * -int8_roundtrip(g))


# ---------------------------------------------------------------------------
# AdaBatch accumulator
# ---------------------------------------------------------------------------

class TestAccumulator:
    def test_schedule_grows_and_caps(self):
        a = GradientAccumulator(4, start=1, growth=2.0, growth_every=2,
                                max_k=6)
        ks = []
        for _ in range(40):
            a.add(np.ones(4, np.float32))
            if a.ready:
                a.flush_dense()
                ks.append(a.k)
        # spans: 1,1 -> k=2; 2,2 -> k=4; 4,4 -> k=min(8, cap)=6; stays
        assert ks[0] == 1 and max(ks) == 6
        assert ks == sorted(ks)

    def test_flush_dense_is_span_mean(self):
        a = GradientAccumulator(3, start=2, max_k=2)
        a.add(np.array([1.0, 2.0, 3.0], np.float32))
        assert not a.ready
        a.add(np.array([3.0, 2.0, 1.0], np.float32))
        assert a.ready
        np.testing.assert_array_equal(a.flush_dense(),
                                      np.array([2.0, 2.0, 2.0]))
        assert a.flush_dense() is None  # empty span

    def test_flush_keyed_unions_touched_rows(self):
        a = GradientAccumulator(8, start=2, max_k=2)
        a.add_rows(np.array([1, 3]), np.array([1.0, 1.0], np.float32), vpk=1)
        a.add_rows(np.array([3, 5]), np.array([1.0, 3.0], np.float32), vpk=1)
        keys, vals = a.flush_keyed()
        np.testing.assert_array_equal(keys, [1, 3, 5])
        np.testing.assert_array_equal(vals, [0.5, 1.0, 1.5])

    def test_flush_keyed_vpk_rows(self):
        a = GradientAccumulator(8, start=1, max_k=1)
        a.add_rows(np.array([1, 3]),
                   np.array([1.0, 2.0, 3.0, 4.0], np.float32), vpk=2)
        keys, vals = a.flush_keyed(vpk=2)
        np.testing.assert_array_equal(keys, [1, 3])
        np.testing.assert_array_equal(vals, [1.0, 2.0, 3.0, 4.0])

    def test_cancelled_span_flushes_empty(self):
        a = GradientAccumulator(4, start=2, max_k=2)
        a.add(np.ones(4, np.float32))
        a.add(-np.ones(4, np.float32))
        keys, vals = a.flush_keyed()
        assert keys.size == 0 and vals.size == 0
        assert a.flushes == 1  # the schedule still advanced

    def test_validation(self):
        with pytest.raises(ValueError, match="start"):
            GradientAccumulator(4, start=0)
        with pytest.raises(ValueError, match="start"):
            GradientAccumulator(4, start=5, max_k=2)
        with pytest.raises(ValueError, match="growth"):
            GradientAccumulator(4, growth=0.5)
        with pytest.raises(ValueError, match="growth_every"):
            GradientAccumulator(4, growth_every=0)


# ---------------------------------------------------------------------------
# config / launch / plumbing
# ---------------------------------------------------------------------------

class TestConfigWiring:
    def test_config_validates_compress(self):
        assert Config(ps_compress="int8").ps_compress == "int8"
        with pytest.raises(ValueError, match="ps_compress"):
            Config(ps_compress="gzip")
        with pytest.raises(ValueError, match="sync_last_gradient"):
            Config(ps_compress="int8", compat_mode="reference")
        with pytest.raises(ValueError, match="signsgd"):
            Config(ps_compress="signsgd", ps_optimizer="ftrl")

    def test_config_validates_accum(self):
        assert Config(ps_accum_start=2, ps_accum_max=8).ps_accum_max == 8
        with pytest.raises(ValueError, match="accum"):
            Config(ps_accum_start=0)
        with pytest.raises(ValueError, match="accum"):
            Config(ps_accum_start=4, ps_accum_max=2)
        with pytest.raises(ValueError, match="ps_accum_growth "):
            Config(ps_accum_growth=0.9)
        with pytest.raises(ValueError, match="ps_accum_growth_every"):
            Config(ps_accum_growth_every=0)

    def test_launch_flags_reach_config(self):
        from distlr_tpu.launch import _config_from_args

        ns = argparse.Namespace(
            ps_compress="int8", ps_accum_start=2, ps_accum_growth=3.0,
            ps_accum_growth_every=16, ps_accum_max=32,
            ps_retry_adaptive=True)
        cfg = _config_from_args(ns)
        assert cfg.ps_compress == "int8"
        assert (cfg.ps_accum_start, cfg.ps_accum_growth,
                cfg.ps_accum_growth_every, cfg.ps_accum_max) == (2, 3.0,
                                                                 16, 32)
        assert cfg.ps_retry_adaptive is True

    def test_server_optimizer_mapping(self):
        from distlr_tpu.train.ps_trainer import server_optimizer

        assert server_optimizer(Config()) == "sgd"
        assert server_optimizer(Config(ps_optimizer="ftrl")) == "ftrl"
        assert server_optimizer(Config(ps_compress="signsgd")) == "signsgd"
        assert server_optimizer(Config(ps_compress="int8")) == "sgd"

    def test_server_group_signsgd_rejects_last_gradient(self):
        with pytest.raises(ValueError, match="last_gradient"):
            ServerGroup(1, 1, 8, optimizer="signsgd", last_gradient=True)

    def test_default_spawns_stay_pinned(self):
        """sgd + compress spawns must not grow flags: the command line
        is pinned across rounds (prebuilt-binary deployments)."""
        g = ServerGroup(1, 1, 8)
        assert g._args["optimizer"] == "sgd"
        assert g._args["compress"] is True


# ---------------------------------------------------------------------------
# trainer integration: both codecs, both paths
# ---------------------------------------------------------------------------

def _trainer_data(tmp_path, n=2400, d=24):
    from distlr_tpu.data.synthetic import write_synthetic_shards

    data_dir = str(tmp_path / "data")
    write_synthetic_shards(data_dir, n, d, num_parts=2, seed=11,
                           sparsity=0.0)
    return data_dir


def _trainer_accuracy(w, data_dir, d):
    from distlr_tpu.data import DataIter
    from distlr_tpu.data.sharding import part_name

    it = DataIter.from_file(os.path.join(data_dir, "test", part_name(0)),
                            d, -1)
    X, y, m = it.next_batch()
    z = np.asarray(X @ np.asarray(w), np.float64)
    m = np.asarray(m, np.float64)
    return float((((z > 0).astype(np.int64) == y) * m).sum()
                 / max(m.sum(), 1.0))


class TestTrainerIntegration:
    @pytest.mark.parametrize("sync", [False, True],
                             ids=["hogwild", "bsp"])
    def test_codecs_converge(self, tmp_path, sync):
        """int8 holds accuracy next to the dense run; signSGD (its own
        optimizer at a sign-scale lr) converges — on BOTH protocols."""
        from distlr_tpu.train.ps_trainer import run_ps_local

        d = 24
        data_dir = _trainer_data(tmp_path)
        base = dict(data_dir=data_dir, num_feature_dim=d, num_workers=2,
                    num_servers=2, num_iteration=10, l2_c=0.0,
                    batch_size=64, test_interval=0, ps_timeout_ms=5000,
                    sync_mode=sync)
        acc = {}
        for name, extra in (
                ("none", {"learning_rate": 0.2}),
                ("int8", {"learning_rate": 0.2, "ps_compress": "int8"}),
                ("signsgd", {"learning_rate": 0.02,
                             "ps_compress": "signsgd"}),
        ):
            w = run_ps_local(Config(**base, **extra), save=False)[0]
            acc[name] = _trainer_accuracy(w, data_dir, d)
        assert abs(acc["none"] - acc["int8"]) < 0.01, acc
        assert acc["signsgd"] > 0.8, acc

    @pytest.mark.parametrize("sync", [False, True],
                             ids=["hogwild", "bsp"])
    def test_accumulation_converges(self, tmp_path, sync):
        """AdaBatch spans (push every k batches, k growing) keep the
        trainers convergent on both protocols, compressed or not."""
        from distlr_tpu.train.ps_trainer import run_ps_local

        d = 24
        data_dir = _trainer_data(tmp_path)
        cfg = Config(data_dir=data_dir, num_feature_dim=d, num_workers=2,
                     num_servers=2, num_iteration=10, l2_c=0.0,
                     batch_size=64, test_interval=0, ps_timeout_ms=5000,
                     sync_mode=sync, learning_rate=0.2,
                     ps_compress="int8", ps_accum_start=1,
                     ps_accum_growth_every=8, ps_accum_max=4)
        w = run_ps_local(cfg, save=False)[0]
        # growing spans trade a little convergence speed for bytes: the
        # every-batch run lands ~0.86 on this data, spans land ~0.82
        assert _trainer_accuracy(w, data_dir, d) > 0.80

    def test_accumulation_cuts_push_bytes(self, tmp_path):
        """The cadence axis: a k=4 accumulation span divides delivered
        push bytes by ~k on top of whatever the codec saves."""
        from distlr_tpu.train.ps_trainer import run_ps_local

        d = 24
        data_dir = _trainer_data(tmp_path, n=1200)
        base = dict(data_dir=data_dir, num_feature_dim=d, num_workers=1,
                    num_servers=1, num_iteration=4, l2_c=0.0,
                    batch_size=64, test_interval=0, ps_timeout_ms=5000,
                    sync_mode=False, learning_rate=0.2)
        raw0, _ = _push_byte_deltas()
        run_ps_local(Config(**base), save=False)
        raw1, _ = _push_byte_deltas()
        run_ps_local(Config(**base, ps_accum_start=4, ps_accum_max=4),
                     save=False)
        raw2, _ = _push_byte_deltas()
        every_batch, accum = raw1 - raw0, raw2 - raw1
        assert every_batch > 0 and accum > 0
        # 4-batch spans -> ~1/4 the pushes (partial epoch-end spans
        # leave some slack)
        assert accum < every_batch / 2.5


# ---------------------------------------------------------------------------
# the ROADMAP acceptance, tier-1-runnable
# ---------------------------------------------------------------------------

def _run_compressed_ps(d, codec, *, n_train, n_test, batch, lr,
                       throttle_bytes_per_sec, num_servers, seed,
                       pool=1024, nnz=8) -> dict:
    """One epoch of dense full-width ``push_pull`` rounds at dim ``d``
    through a throttled chaos link, the same data and order for every
    codec; the ``pool`` of active columns is spread over ``[0, d)`` so
    that every quant block and server slice sees traffic."""
    import time

    from distlr_tpu.chaos import ChaosFabric, parse_plan

    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=pool).astype(np.float32)

    def draw(n):
        cols = rng.integers(0, pool, size=(n, nnz))
        y = (w_true[cols].sum(axis=1) > 0).astype(np.float32)
        return cols * max(1, d // pool), y

    tr_c, tr_y = draw(n_train)
    te_c, te_y = draw(n_test)
    plan = parse_plan({"faults": [
        {"kind": "throttle", "bytes_per_sec": int(throttle_bytes_per_sec)}]})
    raw0, wire0 = _push_byte_deltas()
    t0 = time.perf_counter()
    with ServerGroup(num_servers, 1, d, sync=False, learning_rate=lr) as sg, \
            ChaosFabric(sg.direct_hosts, plan) as fab, \
            KVWorker(fab.hosts, d, timeout_ms=120_000, sync_group=False,
                     compress=codec) as kv:
        assert codec in ("none", kv.compress_active), kv.compress_active
        kv.push_init(np.zeros(d, np.float32))
        w = np.zeros(d, np.float32)
        pushes = 0
        for lo in range(0, n_train, batch):
            cols, y = tr_c[lo:lo + batch], tr_y[lo:lo + batch]
            p = 1.0 / (1.0 + np.exp(-w[cols].sum(axis=1)))
            r = ((p - y) / np.float32(len(y))).astype(np.float32)
            g = np.zeros(d, np.float32)
            np.add.at(g, cols.reshape(-1), np.repeat(r, cols.shape[1]))
            w = kv.push_pull(g)
            pushes += 1
        kv.shutdown_servers()
    wall_s = time.perf_counter() - t0
    raw1, wire1 = _push_byte_deltas()
    acc = float(((w[te_c].sum(axis=1) > 0) == (te_y > 0)).mean())
    return {"codec": codec, "acc": acc, "pushes": pushes, "wall_s": wall_s,
            "push_bytes_raw": int(raw1 - raw0),
            "push_bytes_wire": int(wire1 - wire0)}


class TestAcceptanceSmoke:
    def test_d1m_throttled_reduction_at_half_point_quality(self):
        """The push-byte reduction at <= 0.5pt accuracy cost at the
        D=1M operating point, dense full-width gradient pushes through
        the chaos proxy's THROTTLE mode (the DCN stand-in; localhost
        alone won't show the win) — same data, same seed, same update
        structure for both codecs.  The ROADMAP's >= 8x is against the
        frame it was written for, a u64 key beside every float32 (12 B
        a value); the dense frame is row runs now, coded or not, so the
        codec's own share — int8 against the uncompressed frame as it
        is sent — is ~3.9x, and both are held."""
        d = 1 << 20
        kw = dict(n_train=2048, n_test=1024, batch=128,
                  lr=10.0, throttle_bytes_per_sec=32 << 20,
                  num_servers=2, seed=0)
        faults0 = _counter_total("distlr_chaos_faults_total")
        dense = _run_compressed_ps(d, "none", **kw)
        int8 = _run_compressed_ps(d, "int8", **kw)
        # the throttle really paced the links
        assert _counter_total("distlr_chaos_faults_total") > faults0
        # the uncompressed frame is exactly headers + row keys + f32:
        # 2^20 over two servers goes as runs of 4096, 128 keys a server
        per_dense = 2 * 24 + d // 4096 * 8 + d * 4
        assert dense["push_bytes_wire"] == dense["pushes"] * per_dense
        assert dense["push_bytes_raw"] == int8["push_bytes_raw"]
        reduction = dense["push_bytes_wire"] / int8["push_bytes_wire"]
        assert reduction >= 3.5, (dense, int8)
        flat_keyed = int8["pushes"] * d * 12
        assert flat_keyed / int8["push_bytes_wire"] >= 8.0, (dense, int8)
        # both runs actually learned (not a trivial-quality comparison)
        assert dense["acc"] > 0.70 and int8["acc"] > 0.70, (dense, int8)
        assert abs(dense["acc"] - int8["acc"]) <= 0.005, (dense, int8)
        # fewer wire bytes through the same paced link = faster wall
        # clock (pacing dominates both runs; int8 ships ~4x less c2s)
        assert int8["wall_s"] < dense["wall_s"], (dense, int8)
