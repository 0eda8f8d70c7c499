"""The lock-step release answers its workers at once.

A BSP round's replies that carry values (a fused push-pull's post-round
weights) are written side by side, one thread a reply, by writers the
native server keeps; header-only replies (a plain push) stay on the
releasing thread, and so does everything where two of a round's replies
share a connection.  kStats ``release_fanned_replies`` counts the
replies a thread other than the releasing one wrote,
``release_wall_seconds`` the releases' length.

What must not change: every reply of a round holds the weights after
that round, bit for bit the float32 round rule on the gradients merged
in arrival order; a deferred push whose connection dies is rolled back.
What does: a peer that does not read its reply holds up nobody.

Frames are written by hand (``test_ps_run_frames.Raw``) so that a
round's arrival order, and with it the float32 merge, is fixed.
"""

import threading
import time

import numpy as np
import pytest

from distlr_tpu.ps import KVWorker, ServerGroup, wire
from test_ps_run_frames import (
    DIM,
    F32,
    LR,
    ROWS,
    VPK,
    Raw,
    _wait_pending,
    encodings,
)

W, ROUNDS = 4, 12
FANNED, WALL = "release_fanned_replies", "release_wall_seconds"


def _group(sync: bool = True, dim: int = DIM, workers: int = W):
    return ServerGroup(1, workers, dim, sync=sync, learning_rate=float(LR))


def _seed(probe: Raw, w0: np.ndarray):
    probe.call(wire.OP_PUSH, np.arange(ROWS), VPK, w0,
               flags=wire.FLAG_INIT_PUSH)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "plain"])
@pytest.mark.parametrize("encoding", ["run", "rows-scattered"])
def test_every_reply_of_a_round_is_the_round_rule_in_float32(encoding, fused):
    """Twelve rounds of four workers, arrivals in rank order: all four
    replies of a round are bit-equal to each other and to ``w - lr *
    (((g0 + g1) + g2) + g3) / W``; three of a fused round's four leave
    by a writer, none of a plain round's."""
    enc = encodings(0, ROWS)[encoding]
    rng = np.random.default_rng(33)
    w = rng.normal(size=DIM).astype(F32)
    grads = rng.normal(size=(ROUNDS, W, DIM)).astype(F32)
    op = wire.OP_PUSH_PULL if fused else wire.OP_PUSH
    with _group() as sg:
        conns = [Raw(sg.ports[0], cid) for cid in range(W)]
        probe = Raw(sg.ports[0], 0xFC00)
        try:
            _seed(probe, w)
            before = probe.stats()
            for rnd in range(ROUNDS):
                merge = np.zeros(DIM, F32)
                for r, c in enumerate(conns):
                    c.send(op, enc.keys, enc.vpk, enc.of(grads[rnd, r]))
                    merge += grads[rnd, r]
                    if r + 1 < W:
                        _wait_pending(probe, r + 1)
                w = w - LR * merge / F32(W)
                for c in conns:
                    got = c.recv()
                    if not fused:
                        assert got.size == 0
                        got = c.call(wire.OP_PULL, enc.keys, enc.vpk)
                    assert enc.back(got).tobytes() == w.tobytes(), rnd
            after = probe.stats()
        finally:
            for c in conns + [probe]:
                c.close()
    assert after["sync_rounds"] - before["sync_rounds"] == ROUNDS
    fanned = after[FANNED] - before[FANNED]
    assert fanned == ((W - 1) * ROUNDS if fused else 0)
    assert after[WALL] > before[WALL] >= 0.0
    # a release is inside its pushes' hold, and no longer than all of it
    assert after[WALL] <= after["sync_hold_seconds"]


@pytest.mark.parametrize("sync", [True, False], ids=["bsp", "async"])
def test_the_release_is_timed_on_a_bsp_server_and_zero_on_an_async_one(sync):
    grad = np.full(DIM, 0.5, np.float32)
    with _group(sync) as g, KVWorker(g.hosts, DIM, client_id=0xFC00) as probe:
        probe.wait(probe.push_init(np.ones(DIM, np.float32)))
        kvs = [KVWorker(g.hosts, DIM, client_id=r, sync_group=sync)
               for r in range(W)]

        def loop(kv):
            for _ in range(ROUNDS):
                kv.push_pull(grad)

        threads = [threading.Thread(target=loop, args=(kv,)) for kv in kvs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        for kv in kvs:
            kv.close()
        stats, health = probe.stats(0), g.health()[0]
    assert isinstance(stats[FANNED], int)
    assert isinstance(stats[WALL], float)
    if sync:
        assert stats[FANNED] == (W - 1) * ROUNDS == health[FANNED]
        assert 0 < stats[WALL] <= health[WALL]
    else:
        assert stats[FANNED] == 0 and stats[WALL] == 0.0


def test_two_replies_on_one_connection_leave_in_order_on_one_thread():
    """A client that pushes twice before it reads holds two of the
    round's replies on one socket: no writer takes either, and they
    come back in the order the pushes went."""
    enc = encodings(0, ROWS)["run"]
    rng = np.random.default_rng(34)
    w0 = rng.normal(size=DIM).astype(F32)
    a1, a2, b = rng.normal(size=(3, DIM)).astype(F32)
    with _group(workers=3) as sg:
        with Raw(sg.ports[0], 0) as A, Raw(sg.ports[0], 1) as B, \
                Raw(sg.ports[0], 0xFC00) as probe:
            _seed(probe, w0)
            A.send(wire.OP_PUSH_PULL, enc.keys, enc.vpk, enc.of(a1))
            first_ts = A.ts
            _wait_pending(probe, 1)
            A.send(wire.OP_PUSH_PULL, enc.keys, enc.vpk, enc.of(a2))
            _wait_pending(probe, 2)
            B.send(wire.OP_PUSH_PULL, enc.keys, enc.vpk, enc.of(b))
            got_b = B.recv()
            A.ts = first_ts           # Raw.recv checks the echoed stamp
            got_a1 = A.recv()
            A.ts = first_ts + 1
            got_a2 = A.recv()
            stats = probe.stats()
    want = w0 - LR * ((a1 + a2) + b) / F32(3)
    assert all(enc.back(g).tobytes() == want.tobytes()
               for g in (got_a1, got_a2, got_b))
    assert stats["sync_rounds"] == 1 and stats[FANNED] == 0


@pytest.mark.parametrize("encoding", ["run", "rows-scattered"])
def test_a_deferred_worker_that_closes_is_rolled_back_and_its_repush_counts_once(
        encoding):
    """A, B and C join the round and B's connection closes before the
    release: its gradient leaves the merge, the round waits; B's re-push
    and D make it whole, and all four replies (three by a writer) hold
    each gradient once."""
    enc = encodings(0, ROWS)[encoding]
    rng = np.random.default_rng(35)
    w0 = rng.normal(size=DIM).astype(F32)
    a, b, c_, d = rng.normal(size=(4, DIM)).astype(F32)
    with _group() as sg:
        port = sg.ports[0]
        A, B, C, D, probe = (Raw(port, cid) for cid in (0, 1, 2, 3, 0xFC00))
        try:
            _seed(probe, w0)
            for n, (x, g) in enumerate(((A, a), (B, b), (C, c_)), 1):
                x.send(wire.OP_PUSH_PULL, enc.keys, enc.vpk, enc.of(g))
                _wait_pending(probe, n)
            B.close()
            _wait_pending(probe, 2)
            B = Raw(port, 1)
            B.send(wire.OP_PUSH_PULL, enc.keys, enc.vpk, enc.of(b))
            _wait_pending(probe, 3)
            D.send(wire.OP_PUSH_PULL, enc.keys, enc.vpk, enc.of(d))
            got = [enc.back(x.recv()) for x in (A, B, C, D)]
            stats = probe.stats()
        finally:
            for x in (A, B, C, D, probe):
                x.close()
    merge = ((((a + b) + c_) - b) + b) + d     # the server's own order
    want = w0 - LR * merge / F32(W)
    assert all(g.tobytes() == want.tobytes() for g in got)
    assert stats["sync_rounds"] == 1 and stats["pending_sync_pushes"] == 0
    assert stats[FANNED] == W - 1


def test_a_peer_that_does_not_read_its_reply_holds_up_nobody():
    """A reply too large for the socket's buffers (16 MB), and the
    round's first pusher does not read its own: the other three return
    from ``push_pull`` all the same, and then it gets its reply.  One
    after another on the last voter's thread, the three stood behind
    the first reply's blocked write until their timeout."""
    vpk = wire.MAX_VALS_PER_KEY
    dim = 1024 * vpk                          # 4M float32 = 16 MB a reply
    w0 = np.zeros(dim, F32)
    grads = [np.full(dim, F32(r + 1)) for r in range(W)]
    want = w0 - LR * (((grads[0] + grads[1]) + grads[2]) + grads[3]) / F32(W)
    got, errors = [None] * W, []
    with _group(dim=dim) as sg, \
            KVWorker(sg.hosts, dim, client_id=0xFC00) as probe:
        probe.wait(probe.push_init(w0))
        with Raw(sg.ports[0], 0) as slow:
            slow.send(wire.OP_PUSH_PULL, np.arange(dim // vpk), vpk, grads[0])
            while probe.stats(0)["pending_sync_pushes"] != 1:
                time.sleep(0.005)

            def run(rank: int):
                try:
                    with KVWorker(sg.hosts, dim, client_id=rank,
                                  timeout_ms=15_000) as kv:
                        got[rank] = kv.push_pull(grads[rank])
                except Exception as e:  # noqa: BLE001 — reported below
                    errors.append(e)

            threads = [threading.Thread(target=run, args=(r,), daemon=True)
                       for r in range(1, W)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not errors, f"a reader was held up: {errors[0]!r}"
            assert not any(t.is_alive() for t in threads)
            got[0] = slow.recv()
        stats = probe.stats(0)
    assert all(g.tobytes() == want.tobytes() for g in got)
    assert stats["sync_rounds"] == 1 and stats[FANNED] == W - 1
