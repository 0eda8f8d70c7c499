"""A push's life on the server, phase by phase: the kStats tail's last
five sums (``recv_seconds``, ``merge_seconds``, ``sync_wait_seconds``,
``release_apply_seconds``, ``reply_write_seconds``), against the native
server, with the sums that were there; the registry's mirror, which every
kStats read refreshes; and a reply from before them."""

import socket
import struct
import threading
import time

import numpy as np
import pytest

from distlr_tpu.obs.registry import get_registry
from distlr_tpu.ps import KVWorker, ServerGroup, wire
from distlr_tpu.ps.client import STATS_FIELDS, mirror_server_stats
from test_ps_sync_stats import _serve_a_reply_of  # a server of another vintage

DIM, WORKERS, ROUNDS, SLEEP = 1 << 18, 4, 4, 0.05
PHASES = ("recv_seconds", "merge_seconds", "sync_wait_seconds",
          "release_apply_seconds", "reply_write_seconds")


def _job(group, sync, late, op="push_pull", rounds=ROUNDS):
    """``rounds`` dense ops a worker, the last worker late by ``late``
    seconds a round; each server's stats before and after (the rise)."""
    grad = np.full(DIM, 1e-3, np.float32)
    with KVWorker(group.hosts, DIM, client_id=0xFC00) as probe:
        probe.wait(probe.push_init(np.zeros(DIM, np.float32)))
        before = [probe.stats(r) for r in range(group.num_servers)]
        workers = [KVWorker(group.hosts, DIM, client_id=r, sync_group=sync)
                   for r in range(WORKERS)]

        def loop(w, delay):
            for _ in range(rounds):
                time.sleep(delay)
                if op == "push_pull":
                    w.push_pull(grad)
                else:
                    w.wait(w.push(grad))

        threads = [threading.Thread(target=loop, args=(
            w, late if r == WORKERS - 1 else 0.0))
            for r, w in enumerate(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for w in workers:
            w.close()
        after = [probe.stats(r) for r in range(group.num_servers)]
    return [{k: a[k] - b[k] for k in a} for b, a in zip(before, after)]


def test_the_five_stand_before_the_last_four_in_the_wires_order():
    assert STATS_FIELDS[-9:-4] == PHASES
    assert STATS_FIELDS[-10] == "release_wall_seconds"
    assert STATS_FIELDS[-4:] == ("mapped_frames", "ftrl_steps",
                                 "ftrl_zeroed", "ftrl_packed_steps")
    assert len(STATS_FIELDS) == wire.STATS_VALS == 28


def test_three_wait_for_the_one_held_back():
    with ServerGroup(2, WORKERS, DIM, sync=True) as g:
        rise = _job(g, True, SLEEP)
    for d in rise:
        assert d["sync_rounds"] == ROUNDS
        assert d["total_pushes"] == WORKERS * ROUNDS
        assert all(isinstance(d[name], float) for name in PHASES)
        # three pushes a round stand merged until the fourth arrives
        assert (0.8 * 3 * SLEEP * ROUNDS <= d["sync_wait_seconds"]
                <= 3 * (SLEEP + 0.05) * ROUNDS)
        assert d["sync_wait_seconds"] <= d["sync_hold_seconds"]
        # a push's hold is its merge (arrived_s is taken under the lock,
        # just before it), its wait, and its place in the release
        assert (d["sync_hold_seconds"] - d["sync_wait_seconds"]
                <= d["merge_seconds"]
                + WORKERS * d["release_wall_seconds"] + 1e-6)
        assert 0 < d["release_apply_seconds"] <= d["release_wall_seconds"]
        assert 0 < d["merge_seconds"] < d["sync_hold_seconds"]
        # 0.5 MB through a loopback socket is no microsecond
        assert 1e-5 * WORKERS * ROUNDS < d["recv_seconds"] < 2.0
        # W replies a round, each written inside the release, after the
        # apply: one write a reply, whoever wrote it
        assert d["release_fanned_replies"] == (WORKERS - 1) * ROUNDS
        assert (0 < d["reply_write_seconds"]
                <= WORKERS * (d["release_wall_seconds"]
                              - d["release_apply_seconds"]) + 1e-6)


def test_with_nobody_held_back_the_wait_is_a_part_of_the_spread():
    with ServerGroup(1, WORKERS, DIM, sync=True) as g:
        (d,) = _job(g, True, 0.0)
    # a push waits from its merge to the last one's: no longer than from
    # the first arrival to the last merge, W - 1 of them a round
    assert 0 <= d["sync_wait_seconds"] <= (WORKERS - 1) * (
        d["sync_spread_seconds"] + d["merge_seconds"]) + 1e-6


@pytest.mark.parametrize("sync", [True, False], ids=["bsp", "async"])
def test_a_header_only_reply_is_no_write_and_a_fused_one_is_one(sync):
    with ServerGroup(1, WORKERS, DIM, sync=sync) as g:
        (plain,) = _job(g, sync, 0.0, op="push")
        (fused,) = _job(g, sync, 0.0, op="push_pull")
    assert plain["total_pushes"] == fused["total_pushes"] == WORKERS * ROUNDS
    assert plain["reply_write_seconds"] == 0.0
    assert fused["reply_write_seconds"] > 1e-5 * WORKERS * ROUNDS
    for d in (plain, fused):
        assert d["recv_seconds"] > 0 and d["merge_seconds"] > 0


def test_an_async_server_reads_zeros_in_the_barriers_sums():
    with ServerGroup(2, WORKERS, DIM, sync=False) as g:
        rise = _job(g, False, 0.0)
    for d in rise:
        assert d["total_pushes"] == WORKERS * ROUNDS
        assert (d["sync_wait_seconds"] == d["release_apply_seconds"]
                == d["release_wall_seconds"] == d["sync_hold_seconds"] == 0.0)
        # the apply and the reply's copy under the lock; the read; the write
        assert d["merge_seconds"] > 0 and d["recv_seconds"] > 0
        assert d["reply_write_seconds"] > 0


def test_every_read_refreshes_the_mirror_and_health_is_one_of_them():
    reg = get_registry()

    def mirrored(stat):
        return {labels[0]: series.value for labels, series
                in reg.get("distlr_ps_server_stat").children()
                if labels[1] == stat and labels[0] in ("0", "1")}

    with ServerGroup(2, 1, 64, sync=True) as g, \
            KVWorker(g.hosts, 64, client_id=1) as kv:
        kv.wait(kv.push_init(np.ones(64, np.float32)))
        kv.push_pull(np.ones(64, np.float32))
        got = [kv.stats(r) for r in range(2)]      # no health() so far
        for stat in (*PHASES, "total_pushes", "sync_rounds"):
            assert mirrored(stat)["0"] == pytest.approx(got[0][stat])
            assert mirrored(stat)["1"] == pytest.approx(got[1][stat])
        assert mirrored("total_pushes")["0"] == 2
        # the barrier's tail and the handlers' CPU follow too: the tail
        # under the one series, with no gauge of its own beside it
        assert mirrored("sync_rounds") == {"0": 1, "1": 1}
        assert reg.get("distlr_ps_server_sync_rounds") is None
        cpu = {labels: s.value for labels, s in reg.get(
            "distlr_kv_server_cpu_seconds").children()}
        assert cpu[("1", "push")] == pytest.approx(got[1]["cpu_push_seconds"])
        kv.push_pull(np.ones(64, np.float32))
        assert mirrored("total_pushes")["1"] == 2  # as the last read left it
        health = g.health()
        assert mirrored("total_pushes") == {"0": 3, "1": 3}
        assert health[0]["total_pushes"] == 3
        # a handle on a part of the group says whose reply it parsed
        with KVWorker(f"127.0.0.1:{g.ports[1]}", 32, client_id=2,
                      sync_group=False) as one:
            one.stats(0, rank=1)
        assert mirrored("dim") == {"0": 32, "1": 32}


def test_a_shorter_reply_leaves_the_rest_of_the_mirror_as_it_was():
    mirror_server_stats(41, dict.fromkeys(STATS_FIELDS, 5))
    mirror_server_stats(41, {"total_pushes": 9})
    got = {labels[1]: series.value for labels, series
           in get_registry().get("distlr_ps_server_stat").children()
           if labels[0] == "41"}
    assert got["total_pushes"] == 9 and got["reply_write_seconds"] == 5
    assert set(got) == set(STATS_FIELDS)


@pytest.mark.parametrize("slots", [19, 21, 24])
def test_a_reply_from_before_the_five_still_parses(slots):
    with socket.socket() as listener:
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        port = listener.getsockname()[1]
        server = threading.Thread(target=_serve_a_reply_of,
                                  args=(listener, slots), daemon=True)
        server.start()
        with KVWorker(f"127.0.0.1:{port}", 8, client_id=3) as kv:
            got = kv.stats(0, rank=40)
        server.join(timeout=5)
    assert list(got) == list(STATS_FIELDS[:slots])
    assert got["release_wall_seconds"] == 19.0
    assert ("recv_seconds" in got) == (slots > 19)
    assert ("reply_write_seconds" in got) == (slots == 24)


def test_a_client_from_before_the_five_gets_the_nineteen_it_asks_for():
    with ServerGroup(1, 1, 64, sync=True) as g:
        with KVWorker(g.hosts, 64, client_id=0) as kv:
            kv.wait(kv.push_init(np.ones(64, np.float32)))
            kv.push_pull(np.ones(64, np.float32))
        with socket.create_connection(("127.0.0.1", g.ports[0])) as s:
            for aux, slots in ((19, 19), (22, 22), (24, 24), (25, 25),
                               (27, 27), (28, 28), (200, 28)):
                s.sendall(wire.HEADER_STRUCT.pack(
                    wire.MAGIC, wire.OP_STATS, 0, aux, 7, 1, 0))
                hdr = s.recv(wire.HEADER_STRUCT.size, socket.MSG_WAITALL)
                n = wire.HEADER_STRUCT.unpack(hdr)[-1]
                assert n == 2 * slots
                named = dict(zip(STATS_FIELDS, struct.unpack(
                    f"<{slots}d", s.recv(4 * n, socket.MSG_WAITALL))))
                assert named["total_pushes"] == 2 and named["sync_rounds"] == 1
                assert ("recv_seconds" in named) == (slots > 19)
                if slots == 24:
                    assert named["recv_seconds"] > 0
                    assert named["merge_seconds"] > 0
                    assert named["sync_wait_seconds"] == 0.0  # the one voter
                    assert named["release_apply_seconds"] > 0
                    assert named["reply_write_seconds"] > 0
