"""kStats slots 25 and 26: ``ftrl_steps`` (the coordinates the servers'
FTRL-Proximal step ran on) and ``ftrl_zeroed`` (of those, the steps
whose ``|z| <= l1`` branch left the weight exactly 0.0), counted where
``FtrlStep`` runs: an asynchronous push's apply and a lock-step release's
apply of the round's mean.  Held against counted runs, against a server
under another rule, against a client and a server from before the two
slots, and in the registry's mirror and the metrics document.
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


def test_the_two_stand_last_in_the_wires_order():
    assert STATS_FIELDS[-2:] == ("ftrl_steps", "ftrl_zeroed")
    assert STATS_FIELDS[-3] == "mapped_frames"
    assert len(STATS_FIELDS) == wire.STATS_VALS == 27


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


def test_a_server_under_another_rule_reads_zeros():
    with ServerGroup(1, 1, DIM, sync=False) as g, \
            KVWorker(g.hosts, DIM) as kv:
        kv.wait(kv.push_init(np.zeros(DIM, np.float32)))
        kv.wait(kv.push(np.ones(DIM, np.float32)))
        got = kv.stats(0)
    assert got["total_pushes"] == 2
    assert (got["ftrl_steps"], got["ftrl_zeroed"]) == (0, 0)


def test_a_client_from_before_the_two_gets_the_twenty_five_it_asks_for():
    """A pre-slot client's request (aux 25) is answered with exactly the
    25 counters it knows, ``mapped_frames`` last; the new client's with
    27, and the two new ones hold what the job stepped."""
    frames = _frames(7, 3, 11, 0.05)
    with ServerGroup(1, 1, DIM, sync=False, optimizer="ftrl", ftrl_l1=100.0,
                     **RULE) as g:
        with KVWorker(g.hosts, DIM, client_id=0) as kv:
            kv.wait(kv.push_init(np.zeros(DIM, np.float32)))
            for keys, vals in frames:
                kv.wait(kv.push(vals, keys=keys))
        steps = sum(int(np.count_nonzero(v)) for _k, v in frames)
        with socket.create_connection(("127.0.0.1", g.ports[0])) as s:
            for aux, slots in ((25, 25), (26, 26), (27, 27), (60, 27)):
                s.sendall(wire.HEADER_STRUCT.pack(
                    wire.MAGIC, wire.OP_STATS, 0, aux, 7, 1, 0))
                hdr = s.recv(wire.HEADER_STRUCT.size, socket.MSG_WAITALL)
                n = wire.HEADER_STRUCT.unpack(hdr)[-1]
                assert n == 2 * slots
                named = dict(zip(STATS_FIELDS, struct.unpack(
                    f"<{slots}d", s.recv(4 * n, socket.MSG_WAITALL))))
                assert named["total_pushes"] == 1 + len(frames)
                assert ("ftrl_steps" in named) == (slots >= 26)
                assert ("ftrl_zeroed" in named) == (slots == 27)
                assert named.get("ftrl_steps", steps) == steps
                assert named.get("ftrl_zeroed", steps) == steps


@pytest.mark.parametrize("slots", [25, 26])
def test_a_reply_from_before_the_two_still_parses(slots):
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
    assert ("ftrl_steps" in got) == (slots == 26)
    assert "ftrl_zeroed" not in got


def test_a_health_probe_mirrors_the_two_into_the_gauges():
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
    for rank, steps in enumerate(want):
        assert gauges[(str(rank), "ftrl_steps")] == steps
        assert gauges[(str(rank), "ftrl_zeroed")] == steps


def test_the_documents_name_the_two():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "METRICS.md")) as f:
        metrics = f.read()
    row = next(ln for ln in metrics.splitlines()
               if ln.startswith("| `distlr_ps_server_stat`"))
    assert "ftrl_steps" in row and "ftrl_zeroed" in row
    with open(os.path.join(root, "distlr_tpu", "ps", "native",
                           "kv_protocol.h")) as f:
        header = f.read()
    at = header.index("Slots 25 and 26")
    assert header.index("ftrl_steps", at) < header.index("ftrl_zeroed", at)
    assert "constexpr uint64_t kStatsVals = 27;" in header
