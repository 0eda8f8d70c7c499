"""What a keyed op counts as it returns, and what the counting costs.

``KVWorker``'s ``push``, ``push_pull``, ``pull`` and ``push_init`` account
for themselves in one pass after the native call (``KVWorker._keyed``):
the counts go to the handle's own shares of the series (registry cells),
the three getters keep the interpreter, the push-byte ratio is derived
when it is read and the six ``xchg_*`` spans take the tracer's lock once.
These tests hold the totals to what the series' help strings and the
client's comments define, op by op, against native servers; hold them
exact where four workers are answered at the same instant; and bound the
hand-overs of a return (a lock another worker may hold, a call that
releases the interpreter), so that a later counter on the path shows.
"""

import gc
import threading

import numpy as np
import pytest

from distlr_tpu.chaos import ChaosFabric, parse_plan
from distlr_tpu.obs.registry import family_total, get_registry
from distlr_tpu.obs.tracing import get_tracer, trace_phase
from distlr_tpu.ps import KVWorker, ServerGroup
from distlr_tpu.ps import client as ps_client
from distlr_tpu.ps.client import PSTimeoutError, RetryPolicy

DIM, SERVERS = 4096, 2
OPS = ("push", "push_pull", "pull", "push_init")
XCHG = ("xchg_enter", "xchg_send", "xchg_await", "xchg_recv", "xchg_wake",
        "xchg_account")
COUNTERS = (
    "distlr_ps_client_ops_total", "distlr_ps_client_bytes_total",
    "distlr_ps_dense_frames_total", "distlr_ps_client_key_frames_total",
    "distlr_ps_payload_frames_total",
    "distlr_ps_push_bytes_raw_total", "distlr_ps_push_bytes_wire_total",
    "distlr_ps_retries_total", "distlr_ps_push_outcome_unknown_total",
)
HEADER = 24  # bytes a frame


def _read() -> dict:
    """Every series of the client's families: ``{(family, labels): value}``,
    a histogram's observations as its value."""
    reg, got = get_registry(), {}
    for name in COUNTERS:
        for labels, child in reg.get(name).children():
            got[name, labels] = child.value
    for labels, child in reg.get("distlr_ps_client_op_seconds").children():
        got["distlr_ps_client_op_seconds", labels] = child.count
    return got


def _moved(before: dict) -> dict:
    """The series that moved since ``before``, by how much."""
    return {key: value - before.get(key, 0) for key, value in _read().items()
            if value != before.get(key, 0)}


def _ratio_reads_raw_over_wire():
    raw = family_total("distlr_ps_push_bytes_raw_total")
    wire = family_total("distlr_ps_push_bytes_wire_total")
    gauge = get_registry().get("distlr_ps_push_compress_ratio")
    # no push delivered yet in this process: the gauge stands at 0
    want = raw / wire if wire else 0.0
    assert gauge.value == pytest.approx(want, rel=1e-12)
    line, = (ln for ln in get_registry().prometheus_text().splitlines()
             if ln.startswith("distlr_ps_push_compress_ratio "))
    assert float(line.split()[1]) == pytest.approx(want, rel=1e-12)


def _call(kv: KVWorker, op: str, keys, vals):
    if op == "pull":
        return kv.pull(keys=keys)
    if op == "push_init":
        return kv.push_init(vals, keys=keys)
    return getattr(kv, op)(vals, keys=keys)


def _expected(kv: KVWorker, op: str, keyed: bool, n_vals: int,
              n_keys: int) -> dict:
    """One delivered ``op`` of ``n_vals`` values under ``n_keys`` keys as
    sent, a value frame a server, none of them in a mapping."""
    key_bytes, val_bytes = 8 * n_keys, 4 * n_vals
    wire = SERVERS * HEADER + key_bytes + val_bytes
    sent = {"push": wire, "push_pull": wire, "pull": key_bytes,
            "push_init": key_bytes + val_bytes}[op]
    want = {
        ("distlr_ps_client_ops_total", (op, "ok")): 1,
        ("distlr_ps_client_op_seconds", (op,)): 1,
        ("distlr_ps_client_bytes_total", (op, "sent")): sent,
        ("distlr_ps_payload_frames_total", (op, "inline")): SERVERS,
    }
    if op in ("push_pull", "pull"):
        want["distlr_ps_client_bytes_total", (op, "received")] = val_bytes
    if keyed:  # the caller's own array: checked by the op
        want["distlr_ps_client_key_frames_total", (op, "checked")] = 1
    else:
        want["distlr_ps_dense_frames_total", (op, "rows")] = 1
    if op in ("push", "push_pull"):
        want["distlr_ps_push_bytes_raw_total", ()] = key_bytes + val_bytes
        want["distlr_ps_push_bytes_wire_total", ()] = wire
    return want


@pytest.fixture(scope="module")
def group():
    with ServerGroup(SERVERS, 1, DIM, sync=False) as g:
        with KVWorker(g.hosts, DIM, sync_group=False) as kv:
            kv.push_init(np.zeros(DIM, np.float32))
        yield g


@pytest.mark.parametrize("keyed", [False, True], ids=["dense", "keyed"])
@pytest.mark.parametrize("op", OPS)
def test_a_delivered_op_counts_once_in_each_of_its_series(group, op, keyed):
    with KVWorker(group.hosts, DIM, sync_group=False) as kv:
        if keyed:  # keys on both servers
            keys = np.array([1, 5, DIM // 2 + 3, DIM - 2], np.uint64)
            n_keys = n_vals = keys.size
        else:
            keys, n_vals = None, DIM
            n_keys = kv._dense_row_encoding()[0].size
        vals = np.full(n_vals, 1e-3, np.float32)
        _call(kv, op, keys, vals)  # the handle's shares are bound
        before = _read()
        _call(kv, op, keys, vals)
        assert _moved(before) == _expected(kv, op, keyed, n_vals, n_keys)
        if op in ("push", "push_pull"):
            assert int(kv._lib.kv_last_wire_sent(kv._h)) == (
                SERVERS * HEADER + 8 * n_keys + 4 * n_vals)
        _ratio_reads_raw_over_wire()
        before = _read()
    del kv
    gc.collect()
    # a handle that is gone leaves its counts behind
    assert _moved(before) == {}


def _timed_out():
    with ServerGroup(1, 2, 8, sync=True) as g, \
            KVWorker(g.hosts, 8, client_id=0, timeout_ms=300) as kv:
        kv.push_init(np.zeros(8, np.float32))
        before = _read()
        with pytest.raises(PSTimeoutError):
            kv.push(np.ones(8, np.float32))  # the peer never votes
        return _moved(before), {
            ("distlr_ps_client_ops_total", ("push", "timeout")): 1}


def _through_a_reset(op, retry):
    """``op`` as the proxy's second operation, whose reply it severs."""
    plan = parse_plan({"faults": [{"kind": "reset", "after_ops": 2}]})
    with ServerGroup(1, 1, 8, sync=False) as g, \
            ChaosFabric(g.direct_hosts, plan) as fab, \
            KVWorker(fab.hosts, 8, timeout_ms=2000, sync_group=False,
                     retry=retry) as kv:
        kv.push_init(np.zeros(8, np.float32))
        before = _read()
        got = op(kv)
        moved = _moved(before)
        assert any(e[1] == "reset" for e in fab.events())
    return moved, got


def _errored():
    def op(kv):
        with pytest.raises(OSError):
            kv.pull()
    return _through_a_reset(op, None)[0], {
        ("distlr_ps_client_ops_total", ("pull", "error")): 1}


def _retried():
    moved, got = _through_a_reset(
        lambda kv: kv.pull(), RetryPolicy(attempts=4, backoff_ms=10))
    assert got.tolist() == [0.0] * 8
    # the failed attempt counts its outcome and nothing else; the re-issue
    # counts once (8 values under one row key, one server, no mapping
    # through a proxy)
    return moved, {
        ("distlr_ps_client_ops_total", ("pull", "error")): 1,
        ("distlr_ps_retries_total", ("pull",)): 1,
        ("distlr_ps_client_ops_total", ("pull", "ok")): 1,
        ("distlr_ps_client_op_seconds", ("pull",)): 1,
        ("distlr_ps_client_bytes_total", ("pull", "sent")): 8,
        ("distlr_ps_client_bytes_total", ("pull", "received")): 32,
        ("distlr_ps_dense_frames_total", ("pull", "rows")): 1,
        ("distlr_ps_payload_frames_total", ("pull", "inline")): 1,
    }


def _unknown():
    moved, got = _through_a_reset(
        lambda kv: kv.push(np.ones(8, np.float32)),
        RetryPolicy(attempts=4, backoff_ms=10))
    assert got == -1
    # its frames had left: absorbed, never re-issued, no byte accounted
    return moved, {
        ("distlr_ps_client_ops_total", ("push", "error")): 1,
        ("distlr_ps_push_outcome_unknown_total", ()): 1,
    }


@pytest.mark.parametrize("fault", ["timed_out", "errored", "retried",
                                   "unknown"])
def test_an_op_that_fails_counts_its_outcome_and_nothing_else(fault):
    moved, want = globals()["_" + fault]()
    assert moved == want


ROUNDS, WORKERS = 500, 4


@pytest.mark.parametrize("op", ["push_pull", "push"])
def test_four_workers_answered_together_lose_no_count_and_no_span(op):
    """Four threads, a handle each, meet at one barrier a round and are
    released by the servers' at the same instant, 500 times."""
    dim = 4096
    grad = np.full(dim, 1e-4, np.float32)
    gate = threading.Barrier(WORKERS)
    errors = []
    tracer = get_tracer()
    phases = get_registry().get("distlr_phase_seconds")
    with ServerGroup(SERVERS, WORKERS, dim, sync=True) as g:
        kvs = [KVWorker(g.hosts, dim, client_id=r, timeout_ms=60_000)
               for r in range(WORKERS)]
        kvs[0].push_init(np.zeros(dim, np.float32))
        for kv in kvs:  # the rows' keys as the handle sends them
            n_keys = kv._dense_row_encoding()[0].size

        def work(r):
            try:
                for k in range(ROUNDS):
                    gate.wait(60)
                    with trace_phase("push", k, r):
                        getattr(kvs[r], op)(grad)
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(e)
                gate.abort()

        before = _read()
        spans_before = {n: phases.labels(phase=n).count for n in XCHG}
        tracer.reset()
        threads = [threading.Thread(target=work, args=(r,))
                   for r in range(WORKERS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors and not any(t.is_alive() for t in threads)
        ops = ROUNDS * WORKERS
        one = _expected(kvs[0], op, False, dim, n_keys)
        want = {key: value * ops for key, value in one.items()}
        assert _moved(before) == want
        for kv in kvs:
            kv.close()
        del kvs, kv
        gc.collect()
        assert _moved(before) == want
    trace = tracer.chrome_trace()
    assert "dropped_events" not in trace["otherData"]
    events = [e for e in trace["traceEvents"] if e["name"] in XCHG]
    assert len(events) == len(XCHG) * ops
    pushes = {e["args"]["id"] for e in trace["traceEvents"]
              if e["name"] == "push"}
    assert len(pushes) == ops and all(
        e["args"]["parent"] in pushes for e in events)
    spans = tracer.breakdown()
    for name in XCHG:
        assert spans[name]["count"] == ops
        assert phases.labels(phase=name).count - spans_before[name] == ops


class _CountingLock:
    """A lock that counts its acquisitions while ``tally`` is armed."""

    def __init__(self, lock, tally):
        self._lock, self._tally = lock, tally

    def acquire(self, *a, **kw):
        if self._tally["armed"]:
            self._tally["locks"] += 1
        return self._lock.acquire(*a, **kw)

    def release(self):
        self._lock.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self._lock.release()


class _CountingLib:
    """The client library with every function counted while ``tally`` is
    armed, by whether calling it releases the interpreter (a ``CDLL``
    function does, one of a ``PyDLL`` handle does not); the native call
    of ``op`` arms the tally as it returns."""

    def __init__(self, lib, native, tally):
        self._lib, self._native, self._tally = lib, native, tally

    def __getattr__(self, name):
        fn, tally = getattr(self._lib, name), self._tally
        keeps = bool(fn._flags_ & 0x4)  # ctypes' FUNCFLAG_PYTHONAPI

        def counted(*args):
            if tally["armed"]:
                tally["keeps" if keeps else "releases"] += 1
            got = fn(*args)
            if name == self._native:
                tally["armed"] = True
            return got

        return counted


def _locks_of_the_process(tally):
    """Put a counting lock in the place of the registry's, of every
    family's and series' in it, and of the tracer's; returns the undo."""
    reg, tracer = get_registry(), get_tracer()
    owners = [reg, tracer]
    for family in list(reg._families.values()):
        owners.append(family)
        owners.extend(child for _labels, child in family.children())
    kept = [(owner, owner._lock) for owner in owners]
    for owner, lock in kept:
        owner._lock = _CountingLock(lock, tally)

    def undo():
        for owner, lock in kept:
            owner._lock = lock

    return undo


NATIVE = {"push": "kv_push_vpk", "push_pull": "kv_push_pull_vpk",
          "pull": "kv_pull_vpk", "push_init": "kv_push_init_vpk"}


@pytest.mark.parametrize("op", OPS)
def test_a_return_hands_the_interpreter_to_no_one(group, op):
    """Between the native call's return and the op's: no call that
    releases the interpreter, and at most three lock acquisitions (the
    tracer's, once for the six spans, is the one there is).  The bound is
    this test's: a counter put on the path with a lock of its own, or a
    getter on the ``CDLL`` handle, shows here."""
    vals = np.full(DIM, 1e-3, np.float32)
    tally = {"armed": False, "locks": 0, "releases": 0, "keeps": 0}
    with KVWorker(group.hosts, DIM, sync_group=False) as kv:
        with trace_phase("push", 0, 0):
            _call(kv, op, None, vals)  # shares and series are bound
        undo = _locks_of_the_process(tally)
        kv._lib = _CountingLib(ps_client._load(), NATIVE[op], tally)
        try:
            before = _read()
            spans_before = get_tracer().breakdown()["xchg_account"]["count"]
            with trace_phase("push", 1, 0):
                _call(kv, op, None, vals)
                tally["armed"] = False
        finally:
            undo()
            kv._lib = ps_client._load()
        # the op was whole: counted, and its spans recorded
        assert _moved(before)[
            "distlr_ps_client_ops_total", (op, "ok")] == 1
        assert (get_tracer().breakdown()["xchg_account"]["count"]
                == spans_before + 1)
    assert tally["releases"] == 0, tally
    assert tally["locks"] <= 3, tally
    # the getters were read, and once each
    assert tally["keeps"] == (3 if op in ("push", "push_pull") else 2), tally
