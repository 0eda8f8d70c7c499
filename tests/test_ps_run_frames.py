"""The server handles a keyed frame as the rows it is — and every slot
gets what the flat keys would have given it.

A frame is ``num_keys`` row keys with ``vals_per_key`` values each.  The
native server no longer writes the flat keys out: apply, merge, rollback
and replies walk the rows, and a frame whose keys are one ascending
consecutive run is handled as the one range of slots it is (kStats
``run_frames``).  These tests send the SAME values in three encodings
(the run a dense worker's default-key op is, explicit flat keys in
shuffled order, explicit row keys that are not consecutive) and hold
weights and replies to bit equality, over the optimizers, both modes and
both reply paths; then the edges: a run off row 0, ``vals_per_key`` 1,
descending and duplicate keys, the ``max_dim`` cap, an optimizer
boundary inside a run, upstream's last-gradient shortcut, and a worker
dropped in the middle of a round.

Frames are written by hand over a socket: the client library sends
strictly ascending keys only, and the order of a round's arrivals has to
be fixed for a bit-exact BSP merge (float32 ``(a + b) + c`` is not
``(a + c) + b``).
"""

import socket
import struct
import tempfile
import time

import numpy as np
import pytest

from distlr_tpu.obs.registry import get_registry
from distlr_tpu.ps import ServerGroup, wire
from distlr_tpu.ps.client import STATS_FIELDS

DIM, VPK = 96, 8          # twelve rows of eight
ROWS = DIM // VPK
LR = np.float32(0.1)
ROUNDS = 3
F32 = np.float32


class Raw:
    """One connection to one native server, its frames written by hand."""

    def __init__(self, port: int, client_id: int):
        self.s = socket.create_connection(("127.0.0.1", port), timeout=20)
        self.cid, self.ts = client_id, 0

    def close(self):
        self.s.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def send(self, op, keys=(), vpk=1, vals=None, flags=0, aux=None):
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        self.ts += 1
        frame = wire.HEADER_STRUCT.pack(
            wire.MAGIC, op, flags, vpk if aux is None else aux, self.cid,
            self.ts, keys.size) + keys.tobytes()
        if vals is not None:
            frame += np.ascontiguousarray(vals, dtype=F32).tobytes()
        self.s.sendall(frame)

    def recv(self) -> np.ndarray:
        """The next reply's values; ``ConnectionError`` once the server
        has dropped the connection."""
        hdr = self.s.recv(wire.HEADER_STRUCT.size, socket.MSG_WAITALL)
        if len(hdr) < wire.HEADER_STRUCT.size:
            raise ConnectionError("server closed the connection")
        magic, _op, flags, _aux, _cid, ts, n = wire.HEADER_STRUCT.unpack(hdr)
        assert magic == wire.MAGIC and flags & wire.FLAG_RESPONSE
        assert not flags & wire.FLAG_ERROR and ts == self.ts
        # piece by piece: one MSG_WAITALL read of a body larger than the
        # socket's buffers comes back short
        body = bytearray(4 * n)
        view, at = memoryview(body), 0
        while at < len(body):
            got = self.s.recv_into(view[at:])
            assert got, "the server closed the connection inside a reply"
            at += got
        return np.frombuffer(body, dtype=F32)

    def call(self, *a, **kw) -> np.ndarray:
        self.send(*a, **kw)
        return self.recv()

    def stats(self) -> dict:
        self.send(wire.OP_STATS, aux=wire.STATS_VALS)
        got = struct.unpack(f"<{wire.STATS_VALS}d", self.recv().tobytes())
        return dict(zip(STATS_FIELDS, got))


class Enc:
    """How one set of flat slots is addressed: ``keys`` with ``vpk``
    values each; ``slots`` are the flat slots in the frame's order."""

    def __init__(self, keys, vpk):
        self.keys = np.asarray(keys, np.uint64)
        self.vpk = vpk
        self.slots = (self.keys[:, None].astype(np.int64) * vpk
                      + np.arange(vpk)[None, :]).reshape(-1)

    def of(self, dense: np.ndarray) -> np.ndarray:
        """The frame's values, from an array over all ``DIM`` slots."""
        return dense[self.slots]

    def back(self, frame_vals: np.ndarray) -> np.ndarray:
        """A reply spread back over the slots (NaN where not asked)."""
        out = np.full(DIM, np.nan, F32)
        out[self.slots] = frame_vals
        return out


def encodings(lo_row: int, hi_row: int, seed: int = 5) -> dict[str, Enc]:
    """Rows ``[lo_row, hi_row)`` as the run they are, as flat keys in a
    shuffled order, and as row keys out of order (evens, then odds
    descending: no two consecutive in the frame)."""
    rows = np.arange(lo_row, hi_row)
    flat = np.arange(lo_row * VPK, hi_row * VPK)
    scattered = np.concatenate([rows[::2], rows[1::2][::-1]])
    assert not (np.diff(scattered.astype(np.int64)) == 1).all()
    return {
        "run": Enc(rows, VPK),
        "flat-shuffled": Enc(np.random.default_rng(seed).permutation(flat), 1),
        "rows-scattered": Enc(scattered, VPK),
    }


def _group(optimizer: str, sync: bool, workers: int, **kw) -> ServerGroup:
    if optimizer == "segments":
        # the boundary (slot 20) falls inside row 2 = slots [16, 24)
        kw.update(opt_segments=[(20, "ftrl"), (DIM, "sgd")])
        optimizer = "sgd"
    return ServerGroup(1, workers, DIM, sync=sync, learning_rate=float(LR),
                       optimizer=optimizer, ftrl_alpha=0.5, ftrl_l1=0.01,
                       **kw)


def _gradients(workers: int, seed: int = 31) -> np.ndarray:
    """``[ROUNDS, workers, DIM]`` float32 with exact zeros and both
    signs in every frame (FTRL skips a zero, signSGD abstains on one)."""
    rng = np.random.default_rng(seed)
    g = rng.normal(size=(ROUNDS, workers, DIM)).astype(F32)
    g[rng.random(g.shape) < 0.2] = 0.0
    return g


def _wait_pending(probe: Raw, n: int):
    deadline = time.monotonic() + 20
    while probe.stats()["pending_sync_pushes"] != n:
        assert time.monotonic() < deadline, "the push never joined the round"
        time.sleep(0.002)


def _drive(optimizer: str, sync: bool, fused: bool, enc: Enc):
    """ROUNDS rounds of W workers pushing the seeded gradients in
    ``enc`` (arrivals in rank order), each reading the weights back
    (the fused reply, or a pull in ``enc``).  Returns the replies spread
    over the slots, the final weights and the servers' counts."""
    workers = 3 if sync else 1
    grads = _gradients(workers)
    w0 = np.random.default_rng(7).normal(size=DIM).astype(F32)
    push_op = wire.OP_PUSH_PULL if fused else wire.OP_PUSH
    replies = []
    with _group(optimizer, sync, workers) as sg:
        port = sg.ports[0]
        conns = [Raw(port, r) for r in range(workers)]
        probe = Raw(port, 0xFC00)
        try:
            whole = Enc(np.arange(ROWS), VPK)
            probe.call(wire.OP_PUSH, whole.keys, VPK, w0,
                       flags=wire.FLAG_INIT_PUSH)
            before = probe.stats()
            for rnd in range(ROUNDS):
                acks = []
                for r, c in enumerate(conns):
                    c.send(push_op, enc.keys, enc.vpk, enc.of(grads[rnd, r]))
                    if not sync:
                        acks.append(c.recv())
                    elif r < workers - 1:
                        _wait_pending(probe, r + 1)
                if sync:    # the round's last push released every reply
                    acks = [c.recv() for c in conns]
                for c, got in zip(conns, acks):
                    assert got.size == (enc.slots.size if fused else 0)
                    if not fused:
                        got = c.call(wire.OP_PULL, enc.keys, enc.vpk)
                    replies.append(enc.back(got))
            after = probe.stats()
            final = probe.call(wire.OP_PULL, whole.keys, VPK)
        finally:
            for c in conns + [probe]:
                c.close()
    rise = {k: after[k] - before[k]
            for k in ("total_pushes", "total_pulls", "run_frames",
                      "sync_rounds")}
    return replies, final, rise, w0


@pytest.mark.parametrize("fused", [False, True], ids=["push+pull", "push_pull"])
@pytest.mark.parametrize("sync", [False, True], ids=["async", "sync-w3"])
@pytest.mark.parametrize("optimizer", ["sgd", "ftrl", "signsgd", "segments"])
def test_three_encodings_of_a_dense_frame_land_bit_for_bit(
        optimizer, sync, fused):
    workers = 3 if sync else 1
    got = {name: _drive(optimizer, sync, fused, enc)
           for name, enc in encodings(0, ROWS).items()}
    replies, final, rise, w0 = got["run"]
    assert len(replies) == ROUNDS * workers
    assert np.isfinite(final).all() and (final != w0).mean() > 0.5
    for name in ("flat-shuffled", "rows-scattered"):
        o_replies, o_final, o_rise, _ = got[name]
        assert o_final.tobytes() == final.tobytes(), name
        for a, b in zip(replies, o_replies):
            assert a.tobytes() == b.tobytes(), name
        # counted as pushes and pulls, none of them a run
        assert o_rise["run_frames"] == 0
        assert o_rise["total_pushes"] == rise["total_pushes"]
        assert o_rise["total_pulls"] == rise["total_pulls"]
    # every push and pull of the run encoding took the run path (a fused
    # frame stands in both counts)
    assert rise["total_pushes"] == ROUNDS * workers == rise["total_pulls"]
    assert rise["run_frames"] == 2 * ROUNDS * workers
    assert rise["sync_rounds"] == (ROUNDS if sync else 0)
    if sync:
        # BSP: every worker of a round read the same weights
        for rnd in range(ROUNDS):
            round_replies = replies[rnd * workers:(rnd + 1) * workers]
            assert len({r.tobytes() for r in round_replies}) == 1


def _sgd_frame(w: np.ndarray, enc: Enc, vals: np.ndarray) -> np.ndarray:
    """Plain SGD over a frame, slot after slot in frame order."""
    w = w.copy()
    for k, g in zip(enc.slots, vals):
        w[k] = w[k] - LR * g
    return w


@pytest.mark.parametrize("sync", [False, True], ids=["async", "sync-w1"])
@pytest.mark.parametrize("case", ["off-row-0", "vpk-1"])
def test_a_run_anywhere_in_the_slice_is_a_run(case, sync):
    """Rows 3..8 of the twelve, and ``vals_per_key`` 1 keys 10..49: the
    range's slots move as under shuffled flat keys, the rest stay."""
    if case == "off-row-0":
        run, lo, hi = Enc(np.arange(3, 9), VPK), 24, 72
    else:
        run, lo, hi = Enc(np.arange(10, 50), 1), 10, 50
    shuffled = Enc(np.random.default_rng(3).permutation(np.arange(lo, hi)), 1)
    w0 = np.random.default_rng(7).normal(size=DIM).astype(F32)
    g = np.random.default_rng(8).normal(size=DIM).astype(F32)
    finals = {}
    for name, enc in (("run", run), ("shuffled", shuffled)):
        with _group("sgd", sync, 1) as sg, Raw(sg.ports[0], 0) as c:
            c.call(wire.OP_PUSH, np.arange(ROWS), VPK, w0,
                   flags=wire.FLAG_INIT_PUSH)
            before = c.stats()
            reply = c.call(wire.OP_PUSH_PULL, enc.keys, enc.vpk, enc.of(g))
            pulled = c.call(wire.OP_PULL, enc.keys, enc.vpk)
            after = c.stats()
            finals[name] = c.call(wire.OP_PULL, np.arange(ROWS), VPK)
        assert reply.tobytes() == pulled.tobytes()
        assert enc.back(reply)[lo:hi].tobytes() == finals[name][lo:hi].tobytes()
        assert after["run_frames"] - before["run_frames"] == (
            3 if name == "run" else 0)
    want = w0.copy()
    want[lo:hi] = w0[lo:hi] - LR * g[lo:hi] / F32(1)
    assert finals["run"].tobytes() == finals["shuffled"].tobytes()
    assert finals["run"].tobytes() == want.tobytes()


@pytest.mark.parametrize("optimizer", ["sgd", "ftrl", "signsgd"])
@pytest.mark.parametrize("sync", [False, True], ids=["async", "sync-w1"])
def test_descending_and_duplicate_keys_keep_the_frames_order(sync, optimizer):
    """Row keys 2, 0, 2 and flat keys 7, 7, 3, 7, 2: a slot named twice
    gets its values in the order they were sent, exactly as when the
    client writes the rows out as flat keys itself."""
    rows = Enc([2, 0, 2], VPK)
    as_flat = Enc(rows.slots, 1)        # the same frame, keys written out
    dups = Enc([7, 7, 3, 7, 2], 1)
    rng = np.random.default_rng(11)
    w0 = rng.normal(size=DIM).astype(F32)
    g_rows = rng.normal(size=rows.slots.size).astype(F32)
    g_dups = rng.normal(size=dups.slots.size).astype(F32)
    out = {}
    for name, enc in (("rows", rows), ("flat", as_flat)):
        with _group(optimizer, sync, 1) as sg, Raw(sg.ports[0], 0) as c:
            c.call(wire.OP_PUSH, np.arange(ROWS), VPK, w0,
                   flags=wire.FLAG_INIT_PUSH)
            r1 = c.call(wire.OP_PUSH_PULL, enc.keys, enc.vpk, g_rows)
            mid = c.call(wire.OP_PULL, np.arange(ROWS), VPK)
            r2 = c.call(wire.OP_PUSH_PULL, dups.keys, 1, g_dups)
            r3 = c.call(wire.OP_PULL, dups.keys, 1)
            final = c.call(wire.OP_PULL, np.arange(ROWS), VPK)
            assert c.stats()["run_frames"] == 3     # init and the two pulls
        # a reply names a duplicate slot twice, with one value
        assert r1.tobytes() == mid[enc.slots].tobytes()
        assert r2.tobytes() == r3.tobytes() == final[dups.slots].tobytes()
        out[name] = (r1, mid, final)
    for a, b in zip(out["rows"], out["flat"]):
        assert a.tobytes() == b.tobytes()
    if optimizer == "sgd" and not sync:
        mid = _sgd_frame(w0, rows, g_rows)
        assert out["rows"][1].tobytes() == mid.tobytes()
        assert out["rows"][2].tobytes() == _sgd_frame(mid, dups, g_dups).tobytes()
    if optimizer == "sgd" and sync:
        # one worker's round: the merge adds in frame order, then one step
        merge = np.zeros(DIM, F32)
        for k, g in zip(rows.slots, g_rows):
            merge[k] = merge[k] + g
        assert out["rows"][1].tobytes() == (w0 - LR * merge / F32(1)).tobytes()


def test_the_last_row_may_touch_max_dim_and_none_may_pass_it():
    cap = 2 * DIM
    with _group("sgd", False, 1, max_dim=cap) as sg:
        port = sg.ports[0]
        ones = np.ones(VPK, F32)
        with Raw(port, 0) as c:
            c.call(wire.OP_PUSH, np.arange(ROWS), VPK, np.zeros(DIM, F32),
                   flags=wire.FLAG_INIT_PUSH)
            # a run whose last row ends on the cap: accepted, slice grown
            last = cap // VPK - 1
            got = c.call(wire.OP_PUSH_PULL, [last - 1, last], VPK,
                         np.concatenate([ones, 2 * ones]))
            assert got.tobytes() == np.concatenate(
                [-LR * ones, -LR * 2 * ones]).astype(F32).tobytes()
            assert c.stats()["dim"] == cap
            # one row past it, as the end of a run: the whole frame is
            # refused before a slot moves, and the connection dropped
            c.send(wire.OP_PUSH_PULL, [last - 1, last, last + 1], VPK,
                   np.ones(3 * VPK, F32))
            with pytest.raises(ConnectionError):
                c.recv()
        with Raw(port, 1) as c:     # the rank is alive and untouched
            stats = c.stats()
            assert stats["dim"] == cap and stats["total_pushes"] == 2
            tail = c.call(wire.OP_PULL, [last - 1, last], VPK)
            assert tail.tobytes() == got.tobytes()
            c.send(wire.OP_PULL, [last + 1], VPK)
            with pytest.raises(ConnectionError):
                c.recv()
        assert sg.procs[0].poll() is None


@pytest.mark.parametrize("encoding", ["run", "rows-scattered"])
def test_last_gradient_picks_one_push_of_run_frames(encoding):
    """Upstream's shortcut (``sync_last_gradient``): of a round's pushes
    the one with the highest client id is applied, over W; an empty
    "present" vote never wins the pick."""
    enc = encodings(0, ROWS)[encoding]
    w0 = np.random.default_rng(7).normal(size=DIM).astype(F32)
    grads = _gradients(3)[0]
    with ServerGroup(1, 3, DIM, sync=True, learning_rate=float(LR),
                     last_gradient=True) as sg:
        port = sg.ports[0]
        conns = [Raw(port, cid) for cid in (5, 9, 2, 12)]
        probe = conns.pop()
        try:
            probe.call(wire.OP_PUSH, np.arange(ROWS), VPK, w0,
                       flags=wire.FLAG_INIT_PUSH)
            want = w0
            for rnd, empty in enumerate((None, 1)):
                for r, c in enumerate(conns):
                    if r == empty:      # client 9 only votes "present"
                        c.send(wire.OP_PUSH_PULL, [], enc.vpk)
                    else:
                        c.send(wire.OP_PUSH_PULL, enc.keys, enc.vpk,
                               enc.of(grads[r]))
                    if r < 2:
                        _wait_pending(probe, r + 1)
                pick = grads[1] if empty is None else grads[0]
                want = want - LR * pick / F32(3)
                for r, c in enumerate(conns):
                    got = c.recv()
                    if r == empty:
                        assert got.size == 0
                    else:
                        assert enc.back(got).tobytes() == want.tobytes(), rnd
            assert probe.stats()["sync_rounds"] == 2
        finally:
            for c in conns + [probe]:
                c.close()


def test_last_gradient_on_run_frames_follows_the_oracle():
    """The job of ``test_reference_parity``'s Q1 case (two lock-step
    workers, two servers, D = 24: every push a run of one row of twelve)
    against the independent C++ oracle, with the client's count of the
    encoding it sent."""
    ref = pytest.importorskip("test_reference_parity")
    from distlr_tpu.config import Config

    oracle = ref.build_oracle()
    with tempfile.TemporaryDirectory() as tmp:
        data = tmp + "/data"
        ref.write_synthetic_shards(data, 1000, 24, num_parts=2, seed=3,
                                   sparsity=0.0)
        traj_o, w_o = ref.run_oracle(oracle, data, dim=24, workers=2,
                                     iters=20, batch=64, test_interval=5,
                                     lr=0.1, C=1, sync=1, seed=0)
        fam = get_registry().get("distlr_ps_dense_frames_total")

        def count(enc):
            return sum(fam.labels(op=op, encoding=enc).value
                       for op in ("push", "push_pull", "pull"))

        rows0, flat0 = count("rows"), count("flat")
        traj_f, w_f = ref.run_framework(Config(
            data_dir=data, sync_mode=True, num_workers=2, batch_size=64,
            **ref.BASE))
    assert count("rows") - rows0 >= 2 * 20 and count("flat") == flat0
    assert traj_f.keys() == traj_o.keys()
    for e in traj_o:
        assert abs(traj_f[e] - traj_o[e]) <= 0.01
    np.testing.assert_allclose(w_f, w_o, atol=3e-3)


@pytest.mark.parametrize("encoding", ["run", "rows-scattered"])
def test_a_worker_dropped_mid_round_is_rolled_back_and_its_retry_counts_once(
        encoding):
    """A joins the round, B joins and dies: B's gradient leaves the
    merge as it came (the rows walked again, subtracting), the barrier
    stands at one; B's retry on a new connection and C complete the
    round, which holds each gradient once."""
    enc = encodings(0, ROWS)[encoding]
    w0 = np.random.default_rng(7).normal(size=DIM).astype(F32)
    a, b, c_ = _gradients(3)[1]
    with _group("sgd", True, 3) as sg:
        port = sg.ports[0]
        A, B, C, probe = (Raw(port, cid) for cid in (0, 1, 2, 0xFC00))
        try:
            probe.call(wire.OP_PUSH, np.arange(ROWS), VPK, w0,
                       flags=wire.FLAG_INIT_PUSH)
            A.send(wire.OP_PUSH_PULL, enc.keys, enc.vpk, enc.of(a))
            _wait_pending(probe, 1)
            B.send(wire.OP_PUSH_PULL, enc.keys, enc.vpk, enc.of(b))
            _wait_pending(probe, 2)
            B.close()
            _wait_pending(probe, 1)
            B = Raw(port, 1)
            B.send(wire.OP_PUSH_PULL, enc.keys, enc.vpk, enc.of(b))
            _wait_pending(probe, 2)
            C.send(wire.OP_PUSH_PULL, enc.keys, enc.vpk, enc.of(c_))
            got = [enc.back(x.recv()) for x in (A, B, C)]
            stats = probe.stats()
        finally:
            for x in (A, B, C, probe):
                x.close()
    # the server's own arithmetic, in its order
    merge = (((a + b) - b) + b) + c_
    want = w0 - LR * merge / F32(3)
    assert all(g.tobytes() == want.tobytes() for g in got)
    assert stats["sync_rounds"] == 1 and stats["pending_sync_pushes"] == 0
    assert stats["total_pushes"] == 1 + 4    # the seed, and B's two


# --- the workload the sanitizer builds are driven with --------------------

SAN_VPK = wire.MAX_VALS_PER_KEY
#: two servers of three rows each: a default-key frame is a run of three
SAN_DIM = 2 * 3 * SAN_VPK
SAN_WORKERS = 3


def run_frame_rounds(group: ServerGroup, sync: bool, rounds: int = 5):
    """``SAN_WORKERS`` client threads against ``group`` (two servers of
    ``SAN_DIM``): fused default-key frames (runs of three rows), pushes
    and pulls of row keys with gaps, and first a push whose connection
    dies before its round completes (under BSP: rolled back out of the
    merge).  Shuts the servers down; returns each worker's last pull and
    the servers' stats from before the shutdown.  Used here on the
    standard build and by ``test_ps_tsan.py`` / ``test_sanitizer_matrix.py``
    on the sanitizer builds."""
    import threading

    from distlr_tpu.ps import KVWorker

    rng = np.random.default_rng(17)
    w0 = rng.normal(size=SAN_DIM).astype(F32)
    grads = rng.normal(size=(rounds, SAN_WORKERS, SAN_DIM)).astype(F32)
    gaps = np.array([0, 2, 5], np.uint64)     # [0, 2] of rank 0, [2] of rank 1
    with KVWorker(group.hosts, SAN_DIM, client_id=0xFC00, timeout_ms=60_000,
                  sync_group=False) as probe:
        probe.wait(probe.push_init(w0))
        with Raw(group.ports[0], 77) as doomed, \
                Raw(group.ports[0], 78) as watch:
            doomed.send(wire.OP_PUSH_PULL, np.arange(3), SAN_VPK,
                        grads[0, 0, :3 * SAN_VPK])
            if sync:
                _wait_pending(watch, 1)
            doomed.close()
            if sync:
                _wait_pending(watch, 0)
        errors, last = [], [None] * SAN_WORKERS

        def run(rank: int):
            try:
                with KVWorker(group.hosts, SAN_DIM, client_id=rank,
                              timeout_ms=60_000, sync_group=sync) as kv:
                    for rnd in range(rounds):
                        g = grads[rnd, rank]
                        kv.push_pull(g)
                        rows = g.reshape(-1, SAN_VPK)[gaps.astype(int)]
                        kv.wait(kv.push(rows.reshape(-1), keys=gaps,
                                        vals_per_key=SAN_VPK))
                        kv.pull(keys=gaps, vals_per_key=SAN_VPK)
                    kv.barrier(1)
                    last[rank] = kv.pull()
                    kv.barrier(2)
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(e)
                group.stop()

        threads = [threading.Thread(target=run, args=(r,), daemon=True)
                   for r in range(SAN_WORKERS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not errors, f"worker failed: {errors[0]!r}"
        assert not any(t.is_alive() for t in threads), "worker wedged"
        stats = [probe.stats(r) for r in range(group.num_servers)]
        probe.shutdown_servers()
    return last, stats


def check_run_frame_rounds(last, stats, sync: bool, rounds: int = 5):
    assert all(w.tobytes() == last[0].tobytes() for w in last)
    assert np.isfinite(last[0]).all()
    for rank, s in enumerate(stats):
        # the seed, the doomed push (rank 0), and a worker's two a round
        pushes = 1 + (rank == 0) + 2 * SAN_WORKERS * rounds
        assert s["total_pushes"] == pushes
        assert s["pending_sync_pushes"] == 0
        assert s["sync_rounds"] == (2 * rounds if sync else 0)
        # runs: the seed, the doomed fused frame, the fused default-key
        # frames, the last pulls; and on rank 1 the single row the
        # gapped keys leave it (one key is a run of one)
        runs = (1 + 2 * (rank == 0) + 2 * SAN_WORKERS * rounds + SAN_WORKERS
                + (rank == 1) * 2 * SAN_WORKERS * rounds)
        assert s["run_frames"] == runs, (rank, s)


@pytest.mark.parametrize("sync", [False, True], ids=["async", "sync"])
def test_the_sanitizers_workload_on_the_standard_build(sync):
    with ServerGroup(2, SAN_WORKERS, SAN_DIM, sync=sync,
                     learning_rate=0.05) as group:
        last, stats = run_frame_rounds(group, sync)
        group.wait()
        assert [p.returncode for p in group.procs] == [0, 0]
    check_run_frame_rounds(last, stats, sync)
