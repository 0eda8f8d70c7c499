"""A same-host push's values cross in a mapping the two processes share.

A client and a server on one host, connected directly, attach one sealed
memory file a connection (``kv_protocol.h`` "values in a mapping"): a
keyed frame of 64 KiB of float32 values or more then carries its header
and keys on the socket and its values in the mapping, the reply likewise.
Nothing selects the carrier but what the code can observe: through a
proxy, against a server that does not advertise, for coded, opt-state and
small frames the values stay on the socket.  These tests drive real
native servers both ways from one seed and hold the results to bit
equality; kStats ``mapped_frames`` and
``distlr_ps_payload_frames_total{op, carrier}`` say which carrier ran.
"""

import mmap
import os
import signal
import socket
import struct
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from distlr_tpu.chaos import ChaosFabric, parse_plan
from distlr_tpu.chaos.proxy import _push_vals_bytes
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.ps import KVWorker, ServerGroup, wire

from test_sanitizer_matrix import _libtsan, _run_variant, needs_toolchain

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: two servers: a slice of 32,768 values, 128 KiB, over kMappedMinBytes
DIM = 1 << 16
W, S, ROUNDS = 4, 2, 12
JOIN_S = 60.0
OPS = ("push", "push_init", "push_pull", "pull")
PASS_THROUGH = {"faults": []}


def _carried() -> dict:
    fam = get_registry().get("distlr_ps_payload_frames_total")
    return {(op, c): fam.labels(op=op, carrier=c).value
            for op in OPS for c in ("mapped", "inline")}


def _by_carrier(before: dict, after: dict) -> dict:
    out = {"mapped": 0, "inline": 0}
    for (op, c), v in after.items():
        out[c] += v - before[(op, c)]
    return out


def _exact_grads(seed: int, workers: int, rounds: int, dim: int):
    """Gradients whose every partial sum is exact in float32 (small
    integers over 64), so a round's merge and an async job's end state
    do not depend on the order the pushes arrived in."""
    rng = np.random.default_rng(seed)
    return (rng.integers(-8, 9, size=(workers, rounds, dim))
            .astype(np.float32) / 64.0)


def _segments_of(pid: int) -> int:
    """Mappings of the attach's memory file in process ``pid``."""
    with open(f"/proc/{pid}/maps") as f:
        return sum("memfd:distlr-kv" in line for line in f)


def _wait_segments(pids, want) -> None:
    """A server unmaps a connection's segment once it has seen the
    socket close: shortly after the client's ``close()`` returns."""
    deadline = time.monotonic() + 10
    while [_segments_of(p) for p in pids] != list(want):
        assert time.monotonic() < deadline, (
            [_segments_of(p) for p in pids], want)
        time.sleep(0.01)


def _memfds_of(pid: int) -> int:
    """Descriptors of the attach's memory file in process ``pid``: the
    only handle a segment has, closed at the confirm."""
    n = 0
    for fd in os.listdir(f"/proc/{pid}/fd"):
        try:
            n += "memfd:distlr-kv" in os.readlink(f"/proc/{pid}/fd/{fd}")
        except OSError:
            pass
    return n


def _run_job(sg: ServerGroup, hosts: str, *, sync: bool, seed: int,
             workers: int = W, rounds: int = ROUNDS, dim: int = DIM,
             compress: str = "none"):
    """``workers`` threads push the seeded gradients for ``rounds``
    rounds of fused push-pulls through ``hosts``.  Returns the weights
    after the job (pulled through ``hosts`` too), each worker's last
    reply, the attached connections a worker, and the servers' kStats
    rise over the rounds."""
    w0 = (np.random.default_rng(seed + 1).integers(-64, 65, size=dim)
          .astype(np.float32) / 8.0)
    grads = _exact_grads(seed, workers, rounds, dim)
    last = [None] * workers
    errors = []
    kvs = [KVWorker(hosts, dim, client_id=r, timeout_ms=30_000,
                    sync_group=sync, compress=compress)
           for r in range(workers)]
    probe = KVWorker(sg.direct_hosts, dim, client_id=0xFFF0,
                     timeout_ms=30_000, sync_group=False)
    try:
        kvs[0].push_init(w0)
        before = [probe.stats(r) for r in range(sg.num_servers)]

        def loop(r):
            try:
                for g in grads[r]:
                    last[r] = kvs[r].push_pull(g)
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(e)
                sg.stop()

        threads = [threading.Thread(target=loop, args=(r,), daemon=True)
                   for r in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(JOIN_S)
        assert not errors, errors[0]
        assert not any(t.is_alive() for t in threads), "a worker is wedged"
        after = [probe.stats(r) for r in range(sg.num_servers)]
        final = kvs[0].pull()
        attached = [kv.mapped_connections for kv in kvs]
    finally:
        for kv in kvs + [probe]:
            kv.close()
    rise = [{k: a[k] - b[k] for k in a} for b, a in zip(before, after)]
    return final, last, attached, rise, (w0, grads)


def _both_ways(*, sync: bool, seed: int, **group_kw):
    """The same seeded job direct (mapped) and through a pass-through
    proxy (inline): results, and the carriers' counts each way."""
    out = {}
    for way in ("direct", "proxy"):
        c0 = _carried()
        with ServerGroup(S, W, DIM, sync=sync, learning_rate=0.5,
                         **group_kw) as sg:
            if way == "direct":
                res = _run_job(sg, sg.direct_hosts, sync=sync, seed=seed)
            else:
                with ChaosFabric(sg.direct_hosts,
                                 parse_plan(PASS_THROUGH)) as fab:
                    res = _run_job(sg, fab.hosts, sync=sync, seed=seed)
        out[way] = res + (_by_carrier(c0, _carried()),)
    return out


def _check_carriers(out):
    """Direct: every value-carrying frame mapped, by kStats and by the
    client's counter.  Through the proxy: none."""
    _final, _last, attached, rise, _inputs, carried = out["direct"]
    assert attached == [S] * W
    for r in rise:
        ops = r["total_pushes"] + r["total_pulls"]
        assert ops == 2 * W * ROUNDS
        assert r["mapped_frames"] / ops == 1.0
        assert r["run_frames"] == ops  # a run and mapped are two things
    # W x ROUNDS push-pulls and the init and the last pull, S frames each
    assert carried == {"mapped": S * (W * ROUNDS + 2), "inline": 0}
    _final, _last, attached, rise, _inputs, carried = out["proxy"]
    assert attached == [0] * W
    for r in rise:
        assert r["total_pushes"] + r["total_pulls"] == 2 * W * ROUNDS
        assert r["mapped_frames"] == 0
    assert carried == {"mapped": 0, "inline": S * (W * ROUNDS + 2)}


@pytest.mark.parametrize("last_gradient", [False, True],
                         ids=["mean", "last_gradient"])
def test_a_lockstep_job_ends_bit_for_bit_the_same_mapped_and_inline(
        last_gradient):
    out = _both_ways(sync=True, seed=35, last_gradient=last_gradient)
    direct, proxy = out["direct"], out["proxy"]
    assert direct[0].tobytes() == proxy[0].tobytes()
    # lock-step: every worker holds the weights after the last round
    for way in (direct, proxy):
        for reply in way[1]:
            assert reply.tobytes() == way[0].tobytes()
    _check_carriers(out)
    w0, grads = direct[4]
    if not last_gradient:
        # and they are what W workers' mean gradient, round by round, gives
        want = w0.copy()
        for rnd in range(ROUNDS):
            want -= np.float32(0.5) * grads[:, rnd].sum(0) / np.float32(W)
        assert direct[0].tobytes() == want.tobytes()
    for r in direct[3]:
        assert r["sync_rounds"] == ROUNDS
        # the release's replies left side by side, into W reply areas
        assert r["release_fanned_replies"] == (W - 1) * ROUNDS
        assert r["reply_write_seconds"] > 0 and r["recv_seconds"] >= 0


def test_an_async_job_conserves_its_sum_mapped_and_inline():
    out = _both_ways(sync=False, seed=53)
    direct, proxy = out["direct"], out["proxy"]
    w0, grads = direct[4]
    want = w0 - np.float32(0.5) * grads.sum((0, 1))
    assert direct[0].tobytes() == want.tobytes()
    assert proxy[0].tobytes() == want.tobytes()
    _check_carriers(out)


def test_a_server_that_does_not_advertise_is_served_on_the_socket():
    c0 = _carried()
    with ServerGroup(S, 1, DIM, sync=False, compress=False) as sg, \
            KVWorker(sg.direct_hosts, DIM, sync_group=False,
                     timeout_ms=10_000) as kv:
        assert kv.mapped_connections == 0
        w0 = np.arange(DIM, dtype=np.float32)
        kv.push_init(w0)
        assert kv.push_pull(np.zeros(DIM, np.float32)).tobytes() == \
            w0.tobytes()
        assert [kv.stats(r)["mapped_frames"] for r in range(S)] == [0, 0]
    assert _by_carrier(c0, _carried()) == {"mapped": 0, "inline": 2 * S}


def _recv_exact(s: socket.socket, n: int) -> bytes:
    out = s.recv(n, socket.MSG_WAITALL)
    assert len(out) == n, (len(out), n)
    return out


def _frame(op: int, flags: int, aux: int, ts: int, keys=(), vals=b""):
    return (wire.HEADER_STRUCT.pack(wire.MAGIC, op, flags, aux, 7, ts,
                                    len(keys))
            + struct.pack(f"<{len(keys)}Q", *keys) + vals)


def _reply(s: socket.socket):
    """One reply frame: (flags, num_keys, payload of the socket)."""
    _m, _op, flags, _aux, _cid, _ts, n = wire.HEADER_STRUCT.unpack(
        _recv_exact(s, wire.HEADER_SIZE))
    on_socket = 0 if wire.codec_of(flags) == wire.CODEC_MAPPED else 4 * n
    return flags, n, _recv_exact(s, on_socket) if on_socket else b""


MAPPED = wire.CODEC_MAPPED << wire.CODEC_SHIFT


def _me(s: socket.socket) -> int:
    """This end of the socket as the ASK names it: IPv4 << 16 | port."""
    ip, port = s.getsockname()
    return (struct.unpack("!I", socket.inet_aton(ip))[0] << 16) | port


def _attach(s: socket.socket, vals: int):
    """The attach, spoken by hand: the mapping, or None where refused."""
    s.sendall(_frame(wire.OP_HELLO, MAPPED, wire.MAPPED_ASK, 1,
                     (_me(s), vals)))
    _flags, n, payload = _reply(s)
    if n != 6:
        return None
    pid, fd, area = struct.unpack("<3Q", payload)
    f = os.open(f"/proc/{pid}/fd/{fd}", os.O_RDWR)
    try:
        m = mmap.mmap(f, 0)
    finally:
        os.close(f)
    assert len(m) == wire.MAPPED_HEADER_BYTES + 2 * (
        -(-4 * area // wire.MAPPED_HEADER_BYTES) * wire.MAPPED_HEADER_BYTES)
    nonce, said = struct.unpack_from("<2Q", m, 0)
    assert said == area == vals and nonce != 0
    s.sendall(_frame(wire.OP_HELLO, MAPPED, wire.MAPPED_CONFIRM, 2, (nonce,)))
    _flags, n, payload = _reply(s)
    assert n == 2 and struct.unpack("<Q", payload)[0] == 1
    return m


def test_a_client_that_never_asks_is_served_on_the_socket():
    """An older client: large frames on a direct connection, no attach.
    The server reads and answers them on the socket as it always has."""
    n = DIM // 4
    with ServerGroup(1, 1, DIM, sync=False, learning_rate=1.0) as sg, \
            socket.create_connection(("127.0.0.1", sg.ports[0])) as s:
        vals = np.arange(n, dtype=np.float32)
        keys = tuple(range(n))
        s.sendall(_frame(wire.OP_PUSH, wire.FLAG_INIT_PUSH, 0, 1, keys,
                         vals.tobytes()))
        assert _reply(s)[1] == 0
        s.sendall(_frame(wire.OP_PUSH_PULL, 0, 0, 2, keys,
                         np.ones(n, np.float32).tobytes()))
        flags, got, payload = _reply(s)
        assert wire.codec_of(flags) == wire.CODEC_NONE and got == n
        assert payload == (vals - 1).tobytes()
        with KVWorker(sg.direct_hosts, DIM, sync_group=False) as kv:
            st = kv.stats(0)
        assert (st["total_pushes"], st["mapped_frames"]) == (2, 0)


def test_the_attach_by_hand_and_a_frame_the_area_cannot_hold():
    """A mapped frame that claims more values than the area's real
    length holds, and a mapped frame on a connection that never
    attached: the connection goes, the server stays."""
    area = DIM // 2
    with ServerGroup(1, 1, DIM, sync=False, learning_rate=1.0) as sg:
        addr = ("127.0.0.1", sg.ports[0])
        with socket.create_connection(addr) as s:
            m = _attach(s, area)
            assert m is not None
            # a second ask on one connection is refused: one segment each
            s.sendall(_frame(wire.OP_HELLO, MAPPED, wire.MAPPED_ASK, 3,
                             (_me(s), area)))
            assert _reply(s)[1] == 0
            # a push and a pull in the mapping, by hand
            req = np.frombuffer(m, np.float32, area, wire.MAPPED_HEADER_BYTES)
            rep = np.frombuffer(m, np.float32, area,
                                wire.MAPPED_HEADER_BYTES + 4 * area)
            req[:] = np.arange(area, dtype=np.float32)
            keys = tuple(range(area))
            s.sendall(_frame(wire.OP_PUSH, wire.FLAG_INIT_PUSH | MAPPED, 0,
                             4, keys))
            assert _reply(s)[1:] == (0, b"")
            s.sendall(_frame(wire.OP_PULL, MAPPED, 0, 5, keys))
            flags, n, payload = _reply(s)
            assert (wire.codec_of(flags), n, payload) == (
                wire.CODEC_MAPPED, area, b"")
            assert rep.tobytes() == req.tobytes()
            # one row key of 4,096 values more than the area holds
            s.sendall(_frame(wire.OP_PUSH, MAPPED, wire.MAX_VALS_PER_KEY, 6,
                             tuple(range(area // wire.MAX_VALS_PER_KEY + 1))))
            assert s.recv(1) == b""  # dropped
            del req, rep
            m.close()
        with socket.create_connection(addr) as s:
            s.sendall(_frame(wire.OP_PULL, MAPPED, 0, 1, tuple(range(area))))
            assert s.recv(1) == b""  # never attached: dropped
        with socket.create_connection(addr) as s:
            # an asker that is not the socket's own peer is refused
            s.sendall(_frame(wire.OP_HELLO, MAPPED, wire.MAPPED_ASK, 1,
                             (12345, area)))
            assert _reply(s)[1] == 0
            # and a nonce that was not read in the mapping arms nothing
            assert _attach_with_wrong_nonce(s, area)
        with KVWorker(sg.direct_hosts, DIM, sync_group=False) as kv:
            st = kv.stats(0)
            assert st["mapped_frames"] == 2 and st["initialized"] == 1
            assert kv.pull()[:area].tobytes() == np.arange(
                area, dtype=np.float32).tobytes()
        assert _memfds_of(sg.procs[0].pid) == 0
        _wait_segments([sg.procs[0].pid], [0])


def _attach_with_wrong_nonce(s: socket.socket, vals: int) -> bool:
    s.sendall(_frame(wire.OP_HELLO, MAPPED, wire.MAPPED_ASK, 11,
                     (_me(s), vals)))
    assert _reply(s)[1] == 6
    s.sendall(_frame(wire.OP_HELLO, MAPPED, wire.MAPPED_CONFIRM, 12, (1,)))
    refused = _reply(s)[1] == 0
    s.sendall(_frame(wire.OP_PULL, MAPPED, 0, 13, (0,)))
    return refused and s.recv(1) == b""


def test_which_frames_ride_the_mapping_on_an_attached_connection():
    """Scattered keyed frames over the size ride it like runs; small,
    coded and opt-state frames stay on the socket; the bytes a push
    hands over are counted whichever way they went."""
    dim = 1 << 18
    rng = np.random.default_rng(3)
    with ServerGroup(S, 1, dim, sync=False, learning_rate=0.5,
                     optimizer="ftrl") as sg:
        with KVWorker(sg.direct_hosts, dim, sync_group=False) as kv:
            assert kv.mapped_connections == S
            w0 = rng.normal(size=dim).astype(np.float32)
            kv.push_init(w0)
            every_other = np.arange(0, dim, 2, dtype=np.uint64)
            few = np.array([1, 5, dim - 1], np.uint64)
            c0, s0 = _carried(), [kv.stats(r) for r in range(S)]
            # 256 KiB a server of scattered keys: mapped, and not a run
            assert kv.pull(keys=every_other).tobytes() == \
                w0[::2].tobytes()
            assert kv._lib.kv_last_wire_sent(kv._h) == \
                S * wire.HEADER_SIZE + every_other.nbytes
            kv.push(np.zeros(every_other.size, np.float32), keys=every_other)
            assert kv._lib.kv_last_wire_sent(kv._h) == (
                S * wire.HEADER_SIZE + every_other.nbytes
                + 4 * every_other.size)
            assert _by_carrier(c0, _carried()) == {
                "mapped": 2 * S, "inline": 0}
            rise = [kv.stats(r)["mapped_frames"] - s0[r]["mapped_frames"]
                    for r in range(S)]
            assert rise == [2, 2]
            assert [kv.stats(r)["run_frames"] - s0[r]["run_frames"]
                    for r in range(S)] == [0, 0]
            # three keys: on the socket
            c0 = _carried()
            assert kv.pull(keys=few).tobytes() == w0[few].tobytes()
            assert _by_carrier(c0, _carried()) == {"mapped": 0, "inline": 2}
        # opt-state pairs stay on the socket (a handle a rank)
        lo, hi = sg.key_range(0)
        with KVWorker(f"127.0.0.1:{sg.ports[0]}", hi - lo,
                      sync_group=False) as one:
            assert one.mapped_connections == 1
            before = one.stats(0)["mapped_frames"]
            z, n = one.pull_opt_state()
            assert z.size == n.size == hi - lo
            assert one.stats(0)["mapped_frames"] == before
        # a coded push and its reply stay on the socket
        with KVWorker(sg.direct_hosts, dim, sync_group=False,
                      compress="int8") as coded:
            assert coded.mapped_connections == S
            assert coded.compress_active == "int8"
            c0 = _carried()
            s0 = [coded.stats(r)["mapped_frames"] for r in range(S)]
            coded.push_pull(rng.normal(size=dim).astype(np.float32))
            assert _by_carrier(c0, _carried()) == {"mapped": 0, "inline": S}
            assert [coded.stats(r)["mapped_frames"]
                    for r in range(S)] == s0
            # its seed is exact values, and mapped
            coded.push_init(w0, force=True)
            assert [coded.stats(r)["mapped_frames"] - s0[r]
                    for r in range(S)] == [1, 1]


def test_a_small_slice_never_asks():
    """No frame of a 64-value handle could use a mapping: the connect
    sends what it always sent (the chaos plans count those frames)."""
    with ServerGroup(1, 1, 64, sync=False) as sg, \
            ChaosFabric(sg.direct_hosts, parse_plan(PASS_THROUGH)) as fab, \
            KVWorker(fab.hosts, 64, sync_group=False) as kv:
        assert kv.mapped_connections == 0
        kv.push_init(np.ones(64, np.float32))
        assert fab.links[0]._ops == 1  # the one push, no hello before it


def test_the_proxy_frames_a_mapped_push_as_no_bytes_on_the_socket():
    assert _push_vals_bytes(MAPPED, 1 << 20) == 0
    assert wire.codec_payload_bytes(wire.CODEC_MAPPED, 1 << 20) == 0
    assert _push_vals_bytes(0, 10) == 40


_KILLED_WORKER = textwrap.dedent("""
    import sys
    import numpy as np
    from distlr_tpu.ps import KVWorker
    hosts, dim = sys.argv[1], int(sys.argv[2])
    kv = KVWorker(hosts, dim, client_id=1, timeout_ms=60_000)
    assert kv.mapped_connections == 2, kv.mapped_connections
    print("ATTACHED", flush=True)
    kv.push_pull(np.full(dim, 1000.0, np.float32))  # withheld: killed here
""")


def test_a_worker_killed_between_its_request_and_its_reply():
    """Its push, merged out of the request area and withheld at the
    barrier, rolls back out of the merge when its connection closes,
    read in place once more; the round then completes with its
    replacement and the result is the socket path's.  Nothing of the
    dead worker's segments outlives it."""
    shm0 = sorted(os.listdir("/dev/shm"))
    g0 = np.full(DIM, 0.25, np.float32)
    g1 = np.full(DIM, 0.5, np.float32)
    w0 = np.arange(DIM, dtype=np.float32)
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    with ServerGroup(S, 2, DIM, sync=True, learning_rate=1.0) as sg:
        with KVWorker(sg.direct_hosts, DIM, client_id=0,
                      timeout_ms=30_000) as kv0:
            kv0.push_init(w0)
            child = subprocess.Popen(
                [sys.executable, "-c", _KILLED_WORKER, sg.direct_hosts,
                 str(DIM)], env=env, stdout=subprocess.PIPE, text=True)
            try:
                assert child.stdout.readline().strip() == "ATTACHED"
                deadline = time.monotonic() + 30
                while any(kv0.stats(r)["pending_sync_pushes"] != 1
                          for r in range(S)):
                    assert time.monotonic() < deadline, "push never arrived"
                    time.sleep(0.01)
                pids = [p.pid for p in sg.procs]
                assert [_segments_of(p) for p in pids] == [2, 2]
                assert [_memfds_of(p) for p in pids] == [0, 0]
            finally:
                child.send_signal(signal.SIGKILL)
                child.wait()
            deadline = time.monotonic() + 30
            while any(kv0.stats(r)["pending_sync_pushes"] != 0
                      for r in range(S)):
                assert time.monotonic() < deadline, "no rollback"
                time.sleep(0.01)
            # the dead worker's segments went with its connections
            _wait_segments(pids, [1, 1])
            got = [None, None]

            def peer():
                with KVWorker(sg.direct_hosts, DIM, client_id=1,
                              timeout_ms=30_000) as kv1:
                    got[1] = kv1.push_pull(g1)

            t = threading.Thread(target=peer, daemon=True)
            t.start()
            got[0] = kv0.push_pull(g0)
            t.join(JOIN_S)
            assert not t.is_alive()
        want = w0 - np.float32(1.0) * (g0 + g1) / np.float32(2)
        assert got[0].tobytes() == got[1].tobytes() == want.tobytes()
        _wait_segments(pids, [0, 0])
    assert sorted(os.listdir("/dev/shm")) == shm0


def test_a_server_killed_and_respawned_is_attached_again():
    shm0 = sorted(os.listdir("/dev/shm"))
    w0 = np.arange(DIM, dtype=np.float32)
    with ServerGroup(S, 1, DIM, sync=False, learning_rate=1.0) as sg, \
            KVWorker(sg.direct_hosts, DIM, sync_group=False,
                     timeout_ms=5000) as kv:
        kv.push_init(w0)
        assert kv.mapped_connections == S
        sg.procs[0].kill()
        sg.procs[0].wait()
        with pytest.raises(OSError):
            kv.pull()  # the closed socket is how the client learns
        assert sorted(os.listdir("/dev/shm")) == shm0
        assert sg.respawn(0)
        kv.reconnect()
        assert kv.mapped_connections == S
        kv.push_init(w0, force=True)
        assert kv.push_pull(np.ones(DIM, np.float32)).tobytes() == \
            (w0 - 1).tobytes()
        st = kv.stats(0)
        # the respawned rank: its seed, the push and its pull half
        assert st["mapped_frames"] == st["total_pushes"] + st["total_pulls"] \
            == 3
    assert sorted(os.listdir("/dev/shm")) == shm0


def test_close_leaves_nothing_behind():
    shm0 = sorted(os.listdir("/dev/shm"))
    with ServerGroup(S, 1, DIM, sync=False) as sg:
        pids = [p.pid for p in sg.procs]
        with KVWorker(sg.direct_hosts, DIM, sync_group=False) as kv:
            assert kv.mapped_connections == S
            assert [_segments_of(p) for p in pids] == [1, 1]
            assert _segments_of(os.getpid()) == S
            # no handle but the mappings once both sides hold them
            assert [_memfds_of(p) for p in pids] == [0, 0]
            assert _memfds_of(os.getpid()) == 0
            assert sorted(os.listdir("/dev/shm")) == shm0
        assert _segments_of(os.getpid()) == 0
        _wait_segments(pids, [0, 0])


def test_an_acknowledged_push_is_logged_from_the_request_area(tmp_path):
    """The WAL record of a mapped push is written, out of the request
    area, before the reply: kill -9 after the acknowledgement loses
    nothing."""
    w0 = np.arange(DIM, dtype=np.float32)
    g = _exact_grads(7, 1, 3, DIM)[0]
    with ServerGroup(S, 1, DIM, sync=False, learning_rate=0.5,
                     store_dir=str(tmp_path), store_interval_s=60.0,
                     store_wal=True, store_wal_fsync_s=0.02) as sg, \
            KVWorker(sg.direct_hosts, DIM, sync_group=False,
                     timeout_ms=5000) as kv:
        kv.push_init(w0)
        for step in g:
            kv.push(step)
        assert kv.stats(0)["mapped_frames"] == 4
        for p in sg.procs:
            p.kill()
            p.wait()
        for r in range(S):
            assert sg.respawn(r)
        kv.reconnect()
        assert kv.pull().tobytes() == (
            w0 - np.float32(0.5) * g.sum(0)).tobytes()


#: a mapped lock-step job under the TSan client and server: the
#: release's writers copying side by side into W reply areas, the merges
#: reading W request areas in place, a stats probe beside them
_TSAN_DRIVER = textwrap.dedent(f"""
    import sys
    sys.path.insert(0, {os.path.join(REPO, "tests")!r})
    from distlr_tpu.ps import ServerGroup
    import test_ps_mapped_payload as t

    with ServerGroup(t.S, t.W, t.DIM, sync=True, learning_rate=0.5) as sg:
        final, last, attached, rise, _ = t._run_job(
            sg, sg.direct_hosts, sync=True, seed=35, rounds=6)
        assert attached == [t.S] * t.W, attached
        assert all(r["mapped_frames"] == 2 * t.W * 6 for r in rise), rise
        assert all(x.tobytes() == final.tobytes() for x in last)
    print("DRIVER_OK")
""")


@needs_toolchain
def test_a_mapped_lockstep_round_under_tsan_client_and_server(tmp_path):
    rt = _libtsan()
    if rt is None:
        pytest.skip("toolchain has no libtsan runtime")
    _run_variant("tsan", tmp_path, preload=rt, driver_src=_TSAN_DRIVER)
