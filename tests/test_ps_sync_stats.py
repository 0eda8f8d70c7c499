"""The BSP barrier's counters: the additive kStats tail (``sync_rounds``,
``sync_hold_seconds``, ``sync_spread_seconds``, ``cpu_release_seconds``),
its mirror in the registry, and a reply from before the tail.  Behind
them ``run_frames``: the pushes and pulls a server handled as one range
of slots (every default-key op of a dense job, no scattered frame), and
``lock_wait_seconds``: what the push handlers stood waiting for the
server's one lock; and last the release's fan-out,
``release_fanned_replies`` and ``release_wall_seconds``
(``test_ps_release_fanout.py`` has what they count)."""

import socket
import struct
import threading
import time

import numpy as np
import pytest

from distlr_tpu.obs.registry import get_registry
from distlr_tpu.ps import KVWorker, ServerGroup, wire
from distlr_tpu.ps.client import STATS_FIELDS

DIM, WORKERS, ROUNDS = 64, 3, 4
TAIL = ("sync_rounds", "sync_hold_seconds", "sync_spread_seconds",
        "cpu_release_seconds")


def _rounds(group, sync, delays):
    """``ROUNDS`` fused push-pulls a worker, worker ``r`` late by
    ``delays[r]`` seconds a round; each server's stats before and after."""
    with KVWorker(group.hosts, DIM, client_id=0xFC00) as probe:
        probe.wait(probe.push_init(np.ones(DIM, np.float32)))
        before = [probe.stats(r) for r in range(group.num_servers)]
        workers = [KVWorker(group.hosts, DIM, client_id=r, sync_group=sync)
                   for r in range(WORKERS)]

        def loop(w, delay):
            for _ in range(ROUNDS):
                time.sleep(delay)
                w.push_pull(np.full(DIM, 0.5, np.float32))

        threads = [threading.Thread(target=loop, args=(w, d))
                   for w, d in zip(workers, delays)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for w in workers:
            w.close()
        return before, [probe.stats(r) for r in range(group.num_servers)]


def test_the_tail_stands_after_epoch_in_the_wires_order():
    assert STATS_FIELDS[-len(TAIL) - 13:] == TAIL + (
        "run_frames", "lock_wait_seconds",
        "release_fanned_replies", "release_wall_seconds",
        # a push's phases (test_ps_push_phases.py has what they count)
        "recv_seconds", "merge_seconds", "sync_wait_seconds",
        "release_apply_seconds", "reply_write_seconds",
        # values that crossed in a mapping (test_ps_mapped_payload.py)
        "mapped_frames",
        # the FTRL step's three (test_ps_ftrl_stats.py)
        "ftrl_steps", "ftrl_zeroed", "ftrl_packed_steps")
    assert STATS_FIELDS[-len(TAIL) - 14] == "epoch"
    assert len(STATS_FIELDS) == wire.STATS_VALS == 28


@pytest.mark.parametrize("sync", [True, False], ids=["bsp", "async"])
def test_a_server_counts_its_rounds_and_an_async_one_reports_zeros(sync):
    late = 0.02
    with ServerGroup(2, WORKERS, DIM, sync=sync) as g:
        before, after = _rounds(g, sync, [0.0, late / 2, late])
        health = g.health()
    for b, a, h in zip(before, after, health):
        assert a["total_pushes"] - b["total_pushes"] == WORKERS * ROUNDS
        # a dense job's frames are all runs: every push and pull of the
        # window (a fused push-pull is one of each) took the run path
        assert isinstance(a["run_frames"], int)
        assert a["run_frames"] - b["run_frames"] == (
            a["total_pushes"] - b["total_pushes"]
            + a["total_pulls"] - b["total_pulls"]) == 2 * WORKERS * ROUNDS
        assert h["run_frames"] == a["run_frames"]
        assert all(b[name] == 0 for name in TAIL)
        assert isinstance(a["sync_rounds"], int)
        assert all(isinstance(a[name], float) for name in TAIL[1:])
        if not sync:
            assert all(a[name] == 0 for name in TAIL)
            continue
        # one release a round a server
        assert a["sync_rounds"] == ROUNDS == h["sync_rounds"]
        assert a["pending_sync_pushes"] == 0
        # the last worker is `late` behind the first every round, and
        # every push waits at least for the rest of its round
        assert a["sync_spread_seconds"] >= 0.8 * late * ROUNDS
        assert a["sync_hold_seconds"] >= a["sync_spread_seconds"] >= 0
        assert a["sync_hold_seconds"] <= WORKERS * (
            a["sync_spread_seconds"] + 1.0)
        # the release's cycles are the push handler's too
        assert 0 < a["cpu_release_seconds"] <= a["cpu_push_seconds"]
    reg = get_registry()
    mirrored = {labels: series.value for labels, series
                in reg.get("distlr_ps_server_stat").children()}
    for rank, a in enumerate(after):
        assert mirrored[(str(rank), "run_frames")] == a["run_frames"]
        for stat in ("sync_rounds", "sync_hold_seconds",
                     "sync_spread_seconds", "cpu_release_seconds"):
            assert mirrored[(str(rank), stat)] == pytest.approx(a[stat])
    # the release is not a handler of its own: its cycles are the push's
    handlers = {labels[1] for labels, _c in reg.get(
        "distlr_kv_server_cpu_seconds").children()}
    assert "release" not in handlers


def _serve_a_reply_of(listener, slots):
    """One connection of a server from before the tail: whatever the
    request's aux asks for, a kStats reply of ``slots`` counters."""
    conn, _ = listener.accept()
    with conn:
        while True:
            hdr = conn.recv(wire.HEADER_STRUCT.size, socket.MSG_WAITALL)
            if len(hdr) < wire.HEADER_STRUCT.size:
                return
            magic, op, _flags, _aux, cid, ts, _n = wire.HEADER_STRUCT.unpack(hdr)
            assert magic == wire.MAGIC
            n = slots if op == wire.OP_STATS else 0
            conn.sendall(wire.HEADER_STRUCT.pack(
                wire.MAGIC, op, wire.FLAG_RESPONSE, 0, cid, ts, 2 * n)
                + struct.pack(f"<{n}d", *range(1, n + 1)))


@pytest.mark.parametrize("sync", [True, False], ids=["bsp", "async"])
def test_a_keyed_job_of_scattered_frames_counts_no_run(sync):
    """Row keys with gaps, flat keys with gaps, and a fused frame of
    each: pushes and pulls counted, none of them a run.  One frame of
    consecutive flat keys beside them is one."""
    vpk = 4
    rows = np.array([0, 2, 5, 9, 12], np.uint64)        # of DIM / vpk = 16
    flat = np.array([1, 3, 4, 40, 63], np.uint64)
    with ServerGroup(1, 1, DIM, sync=sync) as g, \
            KVWorker(g.hosts, DIM, client_id=0, sync_group=sync) as kv:
        kv.wait(kv.push_init(np.ones(DIM, np.float32)))
        before = kv.stats(0)
        for _ in range(ROUNDS):
            kv.wait(kv.push(np.full(rows.size * vpk, 0.5, np.float32),
                            keys=rows, vals_per_key=vpk))
            kv.pull(keys=rows, vals_per_key=vpk)
            kv.push_pull(np.full(flat.size, 0.25, np.float32), keys=flat)
            kv.pull(keys=flat)
        mid = kv.stats(0)
        kv.pull(keys=np.arange(8, 24, dtype=np.uint64))
        after = kv.stats(0)
    assert mid["total_pushes"] - before["total_pushes"] == 2 * ROUNDS
    assert mid["total_pulls"] - before["total_pulls"] == 3 * ROUNDS
    assert mid["run_frames"] == before["run_frames"]
    assert after["run_frames"] - mid["run_frames"] == 1


def test_a_request_of_the_old_length_is_still_answered():
    """A client from before ``run_frames`` asks for fifteen counters and
    gets fifteen, the barrier's tail last, one from before
    ``lock_wait_seconds`` sixteen, one from before the release's fan-out
    seventeen, one from before a push's phases nineteen, one from before
    ``mapped_frames`` twenty-four, one from before ``ftrl_steps``
    twenty-five, one from before ``ftrl_packed_steps`` twenty-seven; one
    that asks for more than there are gets what there is."""
    with ServerGroup(1, 1, DIM, sync=False) as g:
        with KVWorker(g.hosts, DIM, client_id=0, sync_group=False) as kv:
            kv.wait(kv.push_init(np.ones(DIM, np.float32)))
            kv.pull()
        with socket.create_connection(("127.0.0.1", g.ports[0])) as s:
            for aux, slots in ((15, 15), (16, 16), (17, 17), (18, 18),
                               (19, 19), (24, 24), (25, 25), (27, 27),
                               (28, 28), (99, 28)):
                s.sendall(wire.HEADER_STRUCT.pack(
                    wire.MAGIC, wire.OP_STATS, 0, aux, 7, 1, 0))
                hdr = s.recv(wire.HEADER_STRUCT.size, socket.MSG_WAITALL)
                n = wire.HEADER_STRUCT.unpack(hdr)[-1]
                assert n == 2 * slots
                got = struct.unpack(f"<{slots}d",
                                    s.recv(4 * n, socket.MSG_WAITALL))
                named = dict(zip(STATS_FIELDS, got))
                assert named["total_pushes"] == 1
                assert named["total_pulls"] == 1
                assert named.get("run_frames", 2) == 2
                assert ("run_frames" in named) == (slots >= 16)
                assert ("lock_wait_seconds" in named) == (slots >= 17)
                assert named.get("lock_wait_seconds", 0.0) >= 0.0
                assert ("release_wall_seconds" in named) == (slots >= 19)
                assert ("reply_write_seconds" in named) == (slots >= 24)
                assert ("mapped_frames" in named) == (slots >= 25)
                assert ("ftrl_zeroed" in named) == (slots >= 27)
                assert ("ftrl_packed_steps" in named) == (slots == 28)
                # an SGD server steps no FTRL coordinate
                assert named.get("ftrl_steps", 0.0) == 0.0
                # an async server: the release's two read zero
                assert named.get("release_fanned_replies", 0.0) == 0.0
                assert named.get("release_wall_seconds", 0.0) == 0.0


@pytest.mark.parametrize("slots", [wire.STATS_VALS_V1, 11, 15, 16, 17])
def test_a_reply_from_before_the_tail_still_parses(slots):
    with socket.socket() as listener:
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        port = listener.getsockname()[1]
        server = threading.Thread(target=_serve_a_reply_of,
                                  args=(listener, slots), daemon=True)
        server.start()
        with KVWorker(f"127.0.0.1:{port}", 8, client_id=3) as kv:
            got = kv.stats(0)
        server.join(timeout=5)
    assert list(got) == list(STATS_FIELDS[:slots])
    assert got["total_pushes"] == 5 and "release_fanned_replies" not in got
    assert ("run_frames" in got) == (slots >= 16)
    assert ("lock_wait_seconds" in got) == (slots == 17)
    assert set(TAIL) <= set(got) if slots >= 15 else not set(TAIL) & set(got)


@pytest.mark.parametrize("sync", [True, False], ids=["bsp", "async"])
def test_lock_wait_rises_where_four_pushes_arrive_at_once(sync):
    """Four workers let go at the same instant, round after round, on a
    vector wide enough that a merge or an apply holds the lock for a
    while: a push stands behind its peers', and the counter says for how
    long.  One worker alone waits for nobody."""
    dim, workers, rounds = 1 << 20, 4, 12
    grad = np.full(dim, 1e-3, np.float32)
    with ServerGroup(1, workers, dim, sync=sync) as g, \
            KVWorker(g.hosts, dim, client_id=0xFC00) as probe:
        probe.wait(probe.push_init(np.zeros(dim, np.float32)))
        before = probe.stats(0)
        assert before["lock_wait_seconds"] < 0.05
        kvs = [KVWorker(g.hosts, dim, client_id=r, sync_group=sync)
               for r in range(workers)]
        gate = threading.Barrier(workers)

        def loop(kv):
            for _ in range(rounds):
                gate.wait()
                kv.push_pull(grad)

        threads = [threading.Thread(target=loop, args=(kv,)) for kv in kvs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for kv in kvs:
            kv.close()
        after = probe.stats(0)
        health = g.health()
    assert after["total_pushes"] - before["total_pushes"] == workers * rounds
    waited = after["lock_wait_seconds"] - before["lock_wait_seconds"]
    assert isinstance(after["lock_wait_seconds"], float) and waited > 0
    # no push can have waited longer than the whole exchange lasted
    if sync:
        assert waited <= after["sync_hold_seconds"] + 1e-3
    assert health[0]["lock_wait_seconds"] >= after["lock_wait_seconds"]
    mirrored = dict(get_registry().get(
        "distlr_ps_server_stat").children())
    assert mirrored[("0", "lock_wait_seconds")].value == pytest.approx(
        health[0]["lock_wait_seconds"])
