"""A dense default-key op crosses the wire as row runs — and lands as the
flat keys would have.

``keys=None`` (every ``push_pull(g)``, ``pull()``, ``push_init`` of a
PS worker) addresses its values as runs of ``v`` under one u64 row key,
``v`` the largest count within the protocol's cap that divides ``dim``
and every range boundary of the handle; flat keys only where no such
``v`` exists.  The servers expand the rows at their parsing layer, so
these tests drive real native servers twice from one seed — once with
default keys, once with the explicit flat ``np.arange(dim)`` frame every
earlier round sent — and hold replies, pulls and push counts to bit
equality; the byte counts and ``distlr_ps_dense_frames_total`` say which
encoding ran.
"""

import threading

import numpy as np
import pytest

from distlr_tpu.chaos import ChaosFabric, parse_plan
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.ps import KVWorker, ServerGroup, wire
from distlr_tpu.ps.client import RetryPolicy

#: divides into whole runs for one, two and three servers (boundaries
#: 3000; 2000 and 4000): v = 3000, 3000, 2000
DIM = 6000
ROUNDS = 5
JOIN_S = 30.0


def _run_length(dim: int, servers: int) -> int:
    """The run the resolver must pick, worked out the slow way."""
    bounds = [dim * s // servers for s in range(1, servers + 1)]
    return max(v for v in range(1, min(wire.MAX_VALS_PER_KEY, dim) + 1)
               if all(b % v == 0 for b in bounds))


def _frames(op: str) -> dict[str, float]:
    fam = get_registry().get("distlr_ps_dense_frames_total")
    return {enc: fam.labels(op=op, encoding=enc).value
            for enc in ("rows", "flat")}


def _delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before}


def _wire_sent(kv: KVWorker) -> int:
    return int(kv._lib.kv_last_wire_sent(kv._h))


def _drive(servers: int, sync: bool, optimizer: str, flat_keys: bool):
    """W workers (two under BSP, so the servers really merge; one
    async) push the same seeded gradients for ROUNDS rounds.  Returns
    every fused reply, a final pull and the servers' push counts."""
    workers = 2 if sync else 1
    keys = np.arange(DIM, dtype=np.uint64) if flat_keys else None
    rng = np.random.default_rng(27)
    w0 = rng.normal(size=DIM).astype(np.float32)
    grads = rng.normal(size=(workers, ROUNDS, DIM)).astype(np.float32)
    replies = [[] for _ in range(workers)]
    errors = []
    with ServerGroup(servers, workers, DIM, sync=sync, learning_rate=0.1,
                     optimizer=optimizer) as sg:
        kvs = [KVWorker(sg.hosts, DIM, client_id=r, timeout_ms=20_000,
                        sync_group=sync) for r in range(workers)]
        try:
            kvs[0].push_init(w0, keys=keys)

            def loop(r):
                try:
                    for g in grads[r]:
                        replies[r].append(kvs[r].push_pull(g, keys=keys))
                except Exception as e:  # noqa: BLE001 — reported below
                    errors.append(e)

            threads = [threading.Thread(target=loop, args=(r,))
                       for r in range(workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(JOIN_S)
            assert not any(t.is_alive() for t in threads)
            assert not errors, errors
            final = kvs[0].pull(keys=keys)
            pushes = [h["total_pushes"] for h in sg.health()]
        finally:
            for kv in kvs:
                kv.close()
    return replies, final, pushes


@pytest.mark.parametrize("servers", [1, 2, 3])
@pytest.mark.parametrize("optimizer", ["sgd", "ftrl"])
@pytest.mark.parametrize("sync", [False, True], ids=["async", "sync"])
def test_row_runs_land_as_the_flat_keys_did(sync, optimizer, servers):
    workers = 2 if sync else 1
    before = _frames("push_pull")
    replies, final, pushes = _drive(servers, sync, optimizer, flat_keys=False)
    mid = _frames("push_pull")
    f_replies, f_final, f_pushes = _drive(servers, sync, optimizer,
                                          flat_keys=True)
    # the default keys went as rows; explicit keys are not the
    # resolver's and count as neither
    assert _delta(before, mid) == {"rows": workers * ROUNDS, "flat": 0}
    assert _delta(mid, _frames("push_pull")) == {"rows": 0, "flat": 0}
    # a fused reply is the state after that push (async) or after the
    # round both workers' pushes were merged into (BSP: float32 a + b is
    # b + a, so arrival order cannot show)
    for r in range(workers):
        assert len(replies[r]) == ROUNDS
        for got, want in zip(replies[r], f_replies[r]):
            assert got.tobytes() == want.tobytes()
    assert final.tobytes() == f_final.tobytes()
    assert pushes == f_pushes
    assert np.isfinite(final).all() and np.abs(final).max() > 0


@pytest.mark.parametrize("servers,dim", [(1, 512), (2, 1000), (3, DIM),
                                         (2, 1_000_000)])
def test_a_server_frame_is_header_row_keys_and_values(servers, dim):
    """``kv_last_wire_sent`` of a dense push: 24 + (n/v) * 8 + n * 4 a
    server, n the server's share of ``dim``."""
    v = _run_length(dim, servers)
    assert v > 1
    g = np.ones(dim, np.float32)
    with ServerGroup(servers, 1, dim, sync=False) as sg, \
            KVWorker(sg.hosts, dim, sync_group=False) as kv:
        assert kv._dense_row_encoding()[1] == v
        kv.push_init(np.zeros(dim, np.float32))
        assert _wire_sent(kv) == servers * 24 + dim // v * 8 + dim * 4
        before = _frames("push")
        kv.wait(kv.push(g))
        assert _delta(before, _frames("push")) == {"rows": 1, "flat": 0}
        sent = _wire_sent(kv)
        assert sent == sum(
            24 + n // v * 8 + n * 4
            for n in (dim * (s + 1) // servers - dim * s // servers
                      for s in range(servers)))
        # a pull sends the row keys alone
        kv.pull()
        assert _wire_sent(kv) == servers * 24 + dim // v * 8
    if dim == 1_000_000:
        assert v == 4000 and sent == 2 * (24 + 1000 + 2_000_000)


@pytest.mark.parametrize("servers,dim", [(1, 4099), (3, 10), (2, 8198)],
                         ids=["prime-over-the-cap", "three-servers-over-10",
                              "boundary-is-a-prime"])
def test_no_aligned_run_keeps_the_flat_frame(servers, dim):
    """Where only v = 1 divides ``dim`` and the boundaries the handle
    has, the frame is what it always was: a u64 key beside every
    float32, and the counter says ``flat``."""
    assert _run_length(dim, servers) == 1
    rng = np.random.default_rng(dim)
    w0 = rng.normal(size=dim).astype(np.float32)
    g = rng.normal(size=dim).astype(np.float32)
    before = {op: _frames(op) for op in ("push_init", "push_pull", "pull")}
    with ServerGroup(servers, 1, dim, sync=False, learning_rate=0.5) as sg, \
            KVWorker(sg.hosts, dim, sync_group=False) as kv:
        kv.push_init(w0)
        reply = kv.push_pull(g)
        assert _wire_sent(kv) == servers * 24 + dim * 8 + dim * 4
        np.testing.assert_array_equal(reply, w0 - np.float32(0.5) * g)
        np.testing.assert_array_equal(kv.pull(), reply)
    for op, b in before.items():
        assert _delta(b, _frames(op)) == {"rows": 0, "flat": 1}, op


@pytest.mark.parametrize("servers", [1, 2, 3])
def test_a_seed_and_a_pull_cross_as_rows(servers):
    """``push_init`` and ``pull`` take the resolver too: the seed lands
    whole, an idempotent re-send leaves it, a forced one overwrites,
    and explicit flat keys read the same bytes back."""
    rng = np.random.default_rng(servers)
    w0 = rng.normal(size=DIM).astype(np.float32)
    w1 = rng.normal(size=DIM).astype(np.float32)
    flat = np.arange(DIM, dtype=np.uint64)
    before = {op: _frames(op) for op in ("push_init", "pull")}
    with ServerGroup(servers, 1, DIM, sync=False) as sg, \
            KVWorker(sg.hosts, DIM, sync_group=False) as kv:
        kv.push_init(w0)
        assert kv.pull().tobytes() == w0.tobytes()
        assert kv.pull(keys=flat).tobytes() == w0.tobytes()
        kv.push_init(w1)  # seeded already: a no-op
        assert kv.pull().tobytes() == w0.tobytes()
        kv.push_init(w1, force=True)
        assert kv.pull().tobytes() == w1.tobytes()
        kv.push_init(w0, keys=flat, force=True)
        assert kv.pull().tobytes() == w0.tobytes()
        with pytest.raises(ValueError, match="default keys"):
            kv.push_init(w0[:-1])
    assert _delta(before["push_init"], _frames("push_init")) == {
        "rows": 3, "flat": 0}
    assert _delta(before["pull"], _frames("pull")) == {"rows": 4, "flat": 0}


def test_a_retried_op_sends_the_same_rows_and_counts_once():
    """A reset between request and reply: the pull is re-issued in
    place (idempotent), a push whose frames left is absorbed and its
    pull half re-issued — every re-issue in the row encoding, the
    counter ticking once an op that succeeded."""
    plan = parse_plan({"faults": [
        {"kind": "reset", "after_ops": n} for n in (3, 6)]})
    rng = np.random.default_rng(5)
    w0 = rng.normal(size=DIM).astype(np.float32)
    fam = get_registry()
    retries0 = fam.get("distlr_ps_retries_total").labels(op="pull").value
    unknown0 = fam.get("distlr_ps_push_outcome_unknown_total").value
    before = {op: _frames(op) for op in ("push_pull", "pull")}
    with ServerGroup(2, 1, DIM, sync=False, learning_rate=1.0) as sg, \
            ChaosFabric(sg.direct_hosts, plan) as fab, \
            KVWorker(fab.hosts, DIM, timeout_ms=5000, sync_group=False,
                     retry=RetryPolicy(attempts=5, backoff_ms=10)) as kv:
        kv.push_init(w0)
        pulls = push_pulls = 0
        for _ in range(4):
            np.testing.assert_array_equal(
                kv.pull(), kv.pull(keys=np.arange(DIM, dtype=np.uint64)))
            pulls += 1
            w = kv.push_pull(np.zeros(DIM, np.float32))
            push_pulls += 1
            assert w.tobytes() == w0.tobytes()
        assert any(e[1] == "reset" for e in fab.events())
    retried = (fam.get("distlr_ps_retries_total").labels(op="pull").value
               - retries0)
    unknown = fam.get("distlr_ps_push_outcome_unknown_total").value - unknown0
    assert retried + unknown >= 1
    got = _delta(before["push_pull"], _frames("push_pull"))
    # an absorbed push-pull resolves through a default-key pull
    assert got == {"rows": push_pulls - unknown, "flat": 0}
    assert _delta(before["pull"], _frames("pull")) == {
        "rows": pulls + unknown, "flat": 0}


def test_a_reroute_between_attempts_cuts_the_rows_again():
    """Rows are cut to the handle's boundaries: when the layout moves
    under an op, its next attempt derives them again (16-value runs
    over two servers of 16 cannot address four servers of 8)."""
    with ServerGroup(2, 1, 32, sync=False) as two, \
            ServerGroup(4, 1, 32, sync=False) as four, \
            KVWorker(two.hosts, 32, sync_group=False) as kv:
        frame = kv._resolve_keys(None, 1)
        assert (len(frame[0]), frame[1], frame[2]) == (2, 16, "rows")
        kv._apply_layout({"hosts": four.hosts, "epoch": 0})
        kv.reconnect()
        keys, vpk, dense = kv._frame_now(frame)
        assert (len(keys), vpk, dense) == (4, 8, "rows")
        w0 = np.arange(32, dtype=np.float32)
        kv.push_init(w0)
        assert kv.pull().tobytes() == w0.tobytes()
        # explicit keys are the caller's: sent as given
        mine = kv._resolve_keys(np.arange(4, dtype=np.uint64), 8)
        assert kv._frame_now(mine) is mine
