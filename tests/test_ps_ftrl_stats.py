"""kStats slots 25 to 27: ``ftrl_steps`` (the coordinates the servers'
FTRL-Proximal step ran on), ``ftrl_zeroed`` (of those, the steps whose
``|z| <= l1`` branch left the weight exactly 0.0) and, since PR 54,
``ftrl_packed_steps`` (of those, the steps an asynchronous keyed push of
single rows took four at a time), counted where the step runs: an
asynchronous push's apply and a lock-step release's apply of the round's
mean.  Held against counted runs, against a server under another rule,
against a client and a server from before the slots, and in the
registry's mirror and the metrics document.
"""

import os
import socket
import struct
import threading

import numpy as np
import pytest

from distlr_tpu.obs.registry import get_registry
from distlr_tpu.ps import KVWorker, ServerGroup, wire
from distlr_tpu.ps.client import STATS_FIELDS
from test_ps_apply_bits import packed_steps  # what a frame lets go in fours
from test_ps_run_frames import Raw  # frames written by hand
from test_ps_sync_stats import _serve_a_reply_of  # a server of another vintage

DIM = 64
RULE = dict(ftrl_alpha=0.1, ftrl_beta=1.0, ftrl_l2=0.0)


def _frames(seed, count, keys_a_frame, scale):
    """Scattered keyed frames with zero entries among them and keys that
    come again in later frames."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(count):
        keys = np.sort(rng.choice(DIM, size=keys_a_frame,
                                  replace=False)).astype(np.uint64)
        g = (rng.standard_normal(keys_a_frame) * scale).astype(np.float32)
        g[::5] = 0.0
        out.append((keys, g))
    return out


def _per_server(frames, ranges, count):
    """``count(keys, g)`` summed over the part of every frame a server's
    range owns."""
    return [sum(count(k[(k >= lo) & (k < hi)], g[(k >= lo) & (k < hi)])
                for k, g in frames) for lo, hi in ranges]


def test_the_three_stand_last_in_the_wires_order():
    assert STATS_FIELDS[-3:] == ("ftrl_steps", "ftrl_zeroed",
                                 "ftrl_packed_steps")
    assert STATS_FIELDS[-4] == "mapped_frames"
    assert len(STATS_FIELDS) == wire.STATS_VALS == 28


@pytest.mark.parametrize("l1,zeroed_of", [
    # no L1: a step leaves 0.0 only where z itself is 0, which no entry
    # of these frames brings about
    (0.0, lambda steps: 0),
    # an L1 no z can pass: every step leaves an exact zero
    (100.0, lambda steps: steps),
], ids=["no-l1", "l1-over-every-z"])
def test_an_async_server_counts_a_step_a_non_zero_entry(l1, zeroed_of):
    frames = _frames(3, 9, 17, 0.05)
    with ServerGroup(2, 1, DIM, sync=False, optimizer="ftrl", ftrl_l1=l1,
                     **RULE) as g, KVWorker(g.hosts, DIM) as kv:
        kv.wait(kv.push_init(np.zeros(DIM, np.float32)))
        before = [kv.stats(r) for r in range(2)]
        assert all(b["ftrl_steps"] == b["ftrl_zeroed"] == 0 for b in before)
        for keys, vals in frames:
            kv.wait(kv.push(vals, keys=keys))
        after = [kv.stats(r) for r in range(2)]
        ranges = [g.key_range(r) for r in range(2)]
    want = _per_server(frames, ranges, lambda k, v: int(np.count_nonzero(v)))
    assert [a["ftrl_steps"] for a in after] == want and min(want) > 0
    assert [a["ftrl_zeroed"] for a in after] == [zeroed_of(s) for s in want]
    assert all(isinstance(a["ftrl_steps"], int) for a in after)
    # every fifth entry is a zero: a group of four with one in it goes a
    # coordinate at a time, the others four at a time
    packed = _per_server(frames, ranges, packed_steps)
    assert [a["ftrl_packed_steps"] for a in after] == packed
    assert all(p < s and p % 4 == 0 for p, s in zip(packed, want))
    assert sum(packed) > 0


def test_four_a_packed_group_and_nothing_for_a_step_alone():
    """Frames written by hand, a group at a time: what packs counts four,
    what does not counts its steps under ``ftrl_steps`` alone."""
    some = np.array([.5, -.25, .125, 2.0], np.float32)
    cases = [
        # (keys, entries, steps, packed)
        ([1, 3, 5, 7], some, 4, 4),
        ([1, 3, 5, 7, 9, 11, 13, 15, 20, 22, 24], np.tile(some, 3)[:11], 11, 8),
        ([1, 3, 5], some[:3], 3, 0),                    # under four: the tail
        ([1, 3, 3, 7], some, 4, 0),                     # a key twice
        ([7, 5, 3, 1], some, 4, 0),                     # descending
        ([1, 3, 5, 7], some * [1, 0, 1, 1], 3, 0),      # a zero entry
        ([8, 9, 10, 11], some, 4, 0),                   # a run: one range
        ([1, 3, 5, 7, 30, 3, 40, 41], np.tile(some, 2), 8, 4),
    ]
    with ServerGroup(1, 1, DIM, sync=False, optimizer="ftrl", ftrl_l1=0.0,
                     **RULE) as g, Raw(g.ports[0], client_id=5) as conn:
        conn.call(wire.OP_PUSH, keys=np.arange(DIM),
                  vals=np.zeros(DIM, np.float32), flags=wire.FLAG_INIT_PUSH)
        for keys, vals, steps, packed in cases:
            assert packed_steps(keys, vals) == packed
            before = conn.stats()
            conn.call(wire.OP_PUSH, keys=keys, vals=vals)
            after = conn.stats()
            assert after["ftrl_steps"] - before["ftrl_steps"] == steps, keys
            assert (after["ftrl_packed_steps"]
                    - before["ftrl_packed_steps"]) == packed, keys


def test_a_boundary_of_opt_segments_inside_a_group_packs_nothing():
    keys = np.array([1, 4, 6, 11, 17, 19, 22, 30, 33, 40, 41, 50, 63],
                    np.uint64)
    vals = np.full(keys.size, 0.5, np.float32)
    for segments, steps, packed in (
            ([(20, "ftrl"), (DIM, "sgd")], 6, 4),
            ([(20, "sgd"), (DIM, "ftrl")], 7, 4),
            ([(20, "ftrl"), (DIM, "ftrl")], 13, 8),
            ([(12, "ftrl"), (DIM, "ftrl")], 13, 12)):
        assert packed_steps(keys, vals, segments) == packed
        with ServerGroup(1, 1, DIM, sync=False, optimizer="ftrl",
                         opt_segments=segments, ftrl_l1=0.0, **RULE) as g, \
                KVWorker(g.hosts, DIM) as kv:
            kv.wait(kv.push_init(np.zeros(DIM, np.float32)))
            kv.wait(kv.push(vals, keys=keys))
            got = kv.stats(0)
        assert (got["ftrl_steps"], got["ftrl_packed_steps"]) == (steps, packed)


def test_the_zeroed_count_is_the_count_of_steps_that_ended_under_l1():
    """A middling L1: some steps end under it and some do not, and a key
    can leave zero and return; the count is held to the weights a pull
    shows after every single push."""
    l1 = 0.03
    frames = _frames(5, 12, 23, 0.04)
    zeroed = 0
    with ServerGroup(1, 1, DIM, sync=False, optimizer="ftrl", ftrl_l1=l1,
                     **RULE) as g, KVWorker(g.hosts, DIM) as kv:
        kv.wait(kv.push_init(np.zeros(DIM, np.float32)))
        for keys, vals in frames:
            kv.wait(kv.push(vals, keys=keys))
            w = kv.pull(keys=keys)
            zeroed += int(((w == 0) & (vals != 0)).sum())
        got = kv.stats(0)
    steps = sum(int(np.count_nonzero(v)) for _k, v in frames)
    assert got["ftrl_steps"] == steps
    assert got["ftrl_zeroed"] == zeroed and 0 < zeroed < steps


def test_a_lock_step_release_counts_the_steps_of_the_rounds_mean():
    """Two workers, dense pushes: the release steps every coordinate
    whose summed entry is not zero, once a round."""
    rounds = 4
    rng = np.random.default_rng(9)
    a = rng.standard_normal((rounds, DIM)).astype(np.float32)
    b = rng.standard_normal((rounds, DIM)).astype(np.float32)
    a[:, ::4] = 0.0
    b[:, ::4] = 0.0          # a quarter of the sums are exact zeros
    with ServerGroup(1, 2, DIM, sync=True, optimizer="ftrl", ftrl_l1=0.0,
                     **RULE) as g, \
            KVWorker(g.hosts, DIM, client_id=0) as kv0, \
            KVWorker(g.hosts, DIM, client_id=1) as kv1:
        kv0.wait(kv0.push_init(np.zeros(DIM, np.float32)))

        def worker(kv, grads):
            for grad in grads:
                kv.wait(kv.push(grad))

        threads = [threading.Thread(target=worker, args=(kv0, a)),
                   threading.Thread(target=worker, args=(kv1, b))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        got = kv0.stats(0)
    assert got["sync_rounds"] == rounds
    assert got["ftrl_steps"] == int(np.count_nonzero(a + b))
    assert got["ftrl_steps"] == rounds * DIM * 3 // 4
    assert got["ftrl_zeroed"] == 0
    # the release scans the merge buffer a coordinate at a time
    assert got["ftrl_packed_steps"] == 0


def test_a_server_under_another_rule_reads_zeros():
    with ServerGroup(1, 1, DIM, sync=False) as g, \
            KVWorker(g.hosts, DIM) as kv:
        kv.wait(kv.push_init(np.zeros(DIM, np.float32)))
        kv.wait(kv.push(np.ones(DIM, np.float32)))
        got = kv.stats(0)
    assert got["total_pushes"] == 2
    assert (got["ftrl_steps"], got["ftrl_zeroed"]) == (0, 0)
    assert got["ftrl_packed_steps"] == 0


def test_a_keyed_job_under_sgd_reads_zero_packed_steps():
    frames = _frames(13, 4, 16, 0.05)
    with ServerGroup(1, 1, DIM, sync=False) as g, \
            KVWorker(g.hosts, DIM) as kv:
        kv.wait(kv.push_init(np.zeros(DIM, np.float32)))
        for keys, vals in frames:
            kv.wait(kv.push(vals, keys=keys))
        got = kv.stats(0)
    assert got["total_pushes"] == 1 + len(frames)
    assert (got["ftrl_steps"], got["ftrl_packed_steps"]) == (0, 0)


def test_a_client_from_before_the_slots_gets_what_it_asks_for():
    """A pre-slot client's request (aux 25) is answered with exactly the
    25 counters it knows, ``mapped_frames`` last, PR 53's (aux 27) with
    its 27, ``ftrl_zeroed`` last; the new client's with 28, and the new
    ones hold what the job stepped."""
    frames = _frames(7, 3, 21, 0.05)
    with ServerGroup(1, 1, DIM, sync=False, optimizer="ftrl", ftrl_l1=100.0,
                     **RULE) as g:
        with KVWorker(g.hosts, DIM, client_id=0) as kv:
            kv.wait(kv.push_init(np.zeros(DIM, np.float32)))
            for keys, vals in frames:
                kv.wait(kv.push(vals, keys=keys))
        steps = sum(int(np.count_nonzero(v)) for _k, v in frames)
        with socket.create_connection(("127.0.0.1", g.ports[0])) as s:
            for aux, slots in ((25, 25), (26, 26), (27, 27), (28, 28),
                               (60, 28)):
                s.sendall(wire.HEADER_STRUCT.pack(
                    wire.MAGIC, wire.OP_STATS, 0, aux, 7, 1, 0))
                hdr = s.recv(wire.HEADER_STRUCT.size, socket.MSG_WAITALL)
                n = wire.HEADER_STRUCT.unpack(hdr)[-1]
                assert n == 2 * slots
                named = dict(zip(STATS_FIELDS, struct.unpack(
                    f"<{slots}d", s.recv(4 * n, socket.MSG_WAITALL))))
                assert named["total_pushes"] == 1 + len(frames)
                assert ("ftrl_steps" in named) == (slots >= 26)
                assert ("ftrl_zeroed" in named) == (slots >= 27)
                assert ("ftrl_packed_steps" in named) == (slots == 28)
                assert named.get("ftrl_steps", steps) == steps
                assert named.get("ftrl_zeroed", steps) == steps
                packed = sum(packed_steps(k, v) for k, v in frames)
                assert named.get("ftrl_packed_steps", packed) == packed > 0


@pytest.mark.parametrize("slots", [25, 26, 27])
def test_a_reply_from_before_the_slots_still_parses(slots):
    with socket.socket() as listener:
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        port = listener.getsockname()[1]
        server = threading.Thread(target=_serve_a_reply_of,
                                  args=(listener, slots), daemon=True)
        server.start()
        with KVWorker(f"127.0.0.1:{port}", 8, client_id=3) as kv:
            got = kv.stats(0, rank=52)
        server.join(timeout=5)
    assert list(got) == list(STATS_FIELDS[:slots])
    assert got["mapped_frames"] == 25
    assert ("ftrl_steps" in got) == (slots >= 26)
    assert ("ftrl_zeroed" in got) == (slots == 27)
    assert "ftrl_packed_steps" not in got


def test_a_health_probe_mirrors_the_three_into_the_gauges():
    frames = _frames(11, 4, 13, 0.05)
    with ServerGroup(2, 1, DIM, sync=False, optimizer="ftrl", ftrl_l1=100.0,
                     **RULE) as g:
        with KVWorker(g.hosts, DIM) as kv:
            kv.wait(kv.push_init(np.zeros(DIM, np.float32)))
            for keys, vals in frames:
                kv.wait(kv.push(vals, keys=keys))
        health = g.health()
        ranges = [g.key_range(r) for r in range(2)]
    want = _per_server(frames, ranges, lambda k, v: int(np.count_nonzero(v)))
    assert [h["ftrl_steps"] for h in health] == want
    gauges = {labels: series.value for labels, series
              in get_registry().get("distlr_ps_server_stat").children()}
    packed = _per_server(frames, ranges, packed_steps)
    for rank, steps in enumerate(want):
        assert gauges[(str(rank), "ftrl_steps")] == steps
        assert gauges[(str(rank), "ftrl_zeroed")] == steps
        assert gauges[(str(rank), "ftrl_packed_steps")] == packed[rank]


def test_the_documents_name_the_three():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "METRICS.md")) as f:
        metrics = f.read()
    row = next(ln for ln in metrics.splitlines()
               if ln.startswith("| `distlr_ps_server_stat`"))
    assert "ftrl_steps" in row and "ftrl_zeroed" in row
    assert "ftrl_packed_steps" in row
    with open(os.path.join(root, "distlr_tpu", "ps", "native",
                           "kv_protocol.h")) as f:
        header = f.read()
    at = header.index("Slots 25 and 26")
    assert header.index("ftrl_steps", at) < header.index("ftrl_zeroed", at)
    at = header.index("Slot 27", at)
    assert "ftrl_packed_steps" in header[at:at + 200]
    assert "constexpr uint64_t kStatsVals = 28;" in header
