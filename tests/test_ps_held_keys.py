"""A key set the connection holds, and a reply that lands in the caller's
buffer.

``KVWorker.hold`` checks a key set once and keeps it read-only; an op that
is handed that very array makes no pass over its keys (``_resolve_keys``
looks at the object, its flag and the row space).  ``KVWorker.pull(out=)``
writes the reply into the head of a buffer the caller keeps.  These tests
hold both to what the same keys as a plain array, and a reply in an array
of its own, give: bit for bit, against native servers; and the counter
that says which way an op's keys went
(``distlr_ps_client_key_frames_total``).
"""

import sys
import threading

import numpy as np
import pytest

from distlr_tpu.chaos import ChaosFabric, parse_plan
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.ps import KVWorker, ServerGroup
from distlr_tpu.ps.client import RetryPolicy

DIM, SERVERS, LR = 4096, 2, 0.25
FRAMES = "distlr_ps_client_key_frames_total"


def _keys(vpk, n=300, seed=5):
    """Sorted unique row keys over ``DIM // vpk`` rows, on both servers."""
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(DIM // vpk, size=min(n, DIM // vpk // 2),
                              replace=False)).astype(np.uint64)


def _seed():
    return (np.random.default_rng(1).standard_normal(DIM) * 0.1).astype(
        np.float32)


def _frames() -> dict:
    fam = get_registry().get(FRAMES)
    return {labels: child.value for labels, child in fam.children()}


def _moved(before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in _frames().items()
            if v != before.get(k, 0)}


def _counted(kv) -> list:
    """Count the handle's calls of ``_validate_keys`` from here on."""
    calls, real = [], kv._validate_keys

    def counted(keys, vpk=1):
        calls.append(vpk)
        return real(keys, vpk)

    kv._validate_keys = counted
    return calls


@pytest.fixture(scope="module")
def group():
    with ServerGroup(SERVERS, 1, DIM, learning_rate=LR, sync=False) as g:
        with KVWorker(g.hosts, DIM, sync_group=False) as kv:
            kv.wait(kv.push_init(_seed()))
        yield g


@pytest.fixture
def kv(group):
    with KVWorker(group.hosts, DIM, sync_group=False) as kv:
        yield kv


# -- hold ---------------------------------------------------------------------
BAD_KEYS = {
    "unsorted": lambda rows: [5, 3, 9],
    "repeated": lambda rows: [3, 5, 5, 9],
    "descending-pair": lambda rows: [9, 3],
    "out-of-range": lambda rows: [1, 2, rows],
    "far-out-of-range": lambda rows: [1, 2 ** 40],
    "out-of-range-and-unsorted": lambda rows: [rows + 7, 2],
}


@pytest.mark.parametrize("vpk", [1, 4, 16])
@pytest.mark.parametrize("bad", sorted(BAD_KEYS))
def test_hold_refuses_what_the_ops_check_refuses_in_its_words(kv, bad, vpk):
    keys = np.array(BAD_KEYS[bad](DIM // vpk), np.uint64)
    with pytest.raises(ValueError) as want:
        kv._validate_keys(keys, vpk)
    with pytest.raises(ValueError) as got:
        kv.hold(keys, vpk)
    assert str(got.value) == str(want.value)
    with pytest.raises(ValueError) as op:
        kv.pull(keys=keys, vals_per_key=vpk)
    assert str(op.value) == str(want.value)
    assert not kv._held  # nothing is kept of a refused set


@pytest.mark.parametrize("source", ["uint64", "int64", "int32", "list",
                                    "strided", "empty"])
def test_a_held_frame_is_the_connections_own_read_only_uint64_array(
        kv, source):
    want = _keys(1)
    given = {"uint64": want.copy(), "int64": want.astype(np.int64),
             "int32": want.astype(np.int32), "list": want.tolist(),
             "strided": np.repeat(want, 2)[::2],
             "empty": np.empty(0, np.uint64)}[source]
    if source == "empty":
        want = given
    frame = kv.hold(given)
    assert type(frame) is np.ndarray and frame.dtype == np.uint64
    assert frame.flags.c_contiguous and not frame.flags.writeable
    assert frame.flags.owndata and frame.ndim == 1
    with pytest.raises(ValueError):
        frame[:1] = 0
    # what the benchmark's taps take of a round's ``keys=``
    assert np.array_equal(np.array(frame), want)
    assert np.asarray(frame) is frame and len(frame) == len(want)
    assert np.array_equal(frame, want)
    assert np.array(frame).flags.writeable
    # the caller's own array stays the caller's
    if isinstance(given, np.ndarray):
        assert given.flags.writeable and not np.shares_memory(frame, given)


@pytest.mark.parametrize("vpk", [1, 4])
def test_ops_through_a_held_frame_leave_what_plain_keys_leave_bit_for_bit(
        vpk):
    keys = _keys(vpk)
    grad = (np.random.default_rng(2).standard_normal(len(keys) * vpk)
            ).astype(np.float32)
    left = {}
    for how in ("plain", "held"):
        with ServerGroup(SERVERS, 1, DIM, learning_rate=LR, sync=False) as g, \
                KVWorker(g.hosts, DIM, sync_group=False) as kv:
            kv.wait(kv.push_init(_seed()))
            k = kv.hold(keys, vpk) if how == "held" else keys.copy()
            calls = _counted(kv)
            first = kv.pull(keys=k, vals_per_key=vpk)
            kv.wait(kv.push(grad, keys=k, vals_per_key=vpk))
            second = kv.pull(keys=k, vals_per_key=vpk)
            assert len(calls) == (0 if how == "held" else 3)
            del kv._validate_keys
            left[how] = (first, second, kv.pull())
    for plain, held in zip(left["plain"], left["held"]):
        assert plain.dtype == held.dtype == np.float32
        assert np.array_equal(plain.view(np.uint32), held.view(np.uint32))
    first, second, whole = left["held"]
    slots = (keys.astype(np.int64)[:, None] * vpk + np.arange(vpk)).reshape(-1)
    assert np.array_equal(first, _seed()[slots])
    assert np.array_equal(second, whole[slots]) and not np.array_equal(
        first, second)


def _call(kv, op, keys, vpk=1):
    if op == "pull":
        return kv.pull(keys=keys, vals_per_key=vpk)
    vals = np.zeros(len(keys) * vpk, np.float32)
    if op == "push_init":
        return kv.push_init(vals, keys=keys)
    got = getattr(kv, op)(vals, keys=keys, vals_per_key=vpk)
    if op == "push":
        kv.wait(got)
    return got


OPS = ("pull", "push", "push_pull", "push_init")


@pytest.mark.parametrize("op", OPS)
def test_a_held_frame_is_not_checked_again_and_counts_held(kv, op):
    frame = kv.hold(_keys(1))
    calls = _counted(kv)
    _call(kv, op, frame)  # the handle's shares are bound
    before = _frames()
    for _ in range(3):
        _call(kv, op, frame)
    assert calls == []
    assert _moved(before) == {(op, "held"): 3}


def _a_copy(kv, group, frame):
    return frame.copy(), 1


def _writeable_again(kv, group, frame):
    frame.flags.writeable = True
    return frame, 1


def _a_slice(kv, group, frame):
    return frame[:-1], 1


def _held_at_another_width(kv, group, frame):
    # rows of four: the same ids name other slots, under another bound
    return kv.hold(frame[frame < DIM // 4], 4), 1


def _another_handles(kv, group, frame):
    with KVWorker(group.hosts, DIM, sync_group=False) as other:
        return other.hold(frame), 1


NOT_HELD = {"a-copy": _a_copy, "writeable-again": _writeable_again,
            "a-slice": _a_slice, "held-at-another-width":
            _held_at_another_width, "another-handles": _another_handles}


@pytest.mark.parametrize("op", ["pull", "push"])
@pytest.mark.parametrize("case", sorted(NOT_HELD))
def test_anything_but_the_frame_itself_is_checked_once_an_op(
        kv, group, op, case):
    frame = kv.hold(_keys(1))
    keys, vpk = NOT_HELD[case](kv, group, frame)
    _call(kv, op, keys, vpk)
    calls = _counted(kv)
    before = _frames()
    _call(kv, op, keys, vpk)
    assert calls == [vpk]
    assert _moved(before) == {(op, "checked"): 1}


def test_a_frame_held_at_a_width_is_held_at_that_width_alone(kv):
    rows = kv.hold(_keys(4), 4)
    calls = _counted(kv)
    before = _frames()
    kv.pull(keys=rows, vals_per_key=4)
    assert calls == [] and _moved(before) == {("pull", "held"): 1}
    kv.pull(keys=rows, vals_per_key=1)
    assert calls == [1]
    assert _moved(before) == {("pull", "held"): 1, ("pull", "checked"): 1}
    # under another bound a held set can be out of range: refused as ever
    wide = kv.hold(np.array([DIM - 1], np.uint64), 1)
    with pytest.raises(ValueError, match="out of range"):
        kv.pull(keys=wide, vals_per_key=4)


@pytest.mark.parametrize("how", ["reconnect", "re-route"])
def test_a_frame_survives_a_new_handle_and_a_new_layout(kv, how):
    frame = kv.hold(_keys(1))
    want = kv.pull(keys=frame)
    if how == "re-route":
        # what ``_renegotiate_route`` does with a fetched layout: the
        # dense row encoding goes, a held frame stays
        kv._dense_row_encoding()
        kv._apply_layout({"hosts": kv._hosts, "epoch": 0, "dim": DIM})
        assert kv._dense_rows is None
    kv.reconnect()
    calls = _counted(kv)
    before = _frames()
    got = kv.pull(keys=frame)
    kv.wait(kv.push(np.zeros(len(frame), np.float32), keys=frame))
    assert calls == []
    assert _moved(before) == {("pull", "held"): 1, ("push", "held"): 1}
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))


def test_a_failed_op_counts_no_frame():
    with ServerGroup(1, 1, 64, sync=False) as g:
        kv = KVWorker(g.hosts, 64, timeout_ms=2000, sync_group=False)
        kv.wait(kv.push_init(np.zeros(64, np.float32)))
        frame = kv.hold(np.arange(0, 64, 3))
        kv.pull(keys=frame)
        own = frame.copy()
        kv.pull(keys=own)
    before = _frames()
    try:
        for keys in (frame, own):
            with pytest.raises(OSError):
                kv.pull(keys=keys)
            with pytest.raises(OSError):
                kv.push(np.zeros(len(keys), np.float32), keys=keys)
        assert _moved(before) == {}
    finally:
        kv.close()


def test_hold_is_safe_from_several_threads_at_once(kv):
    """``_place_keyed_shard`` holds a shard's windows from a pool: every
    frame held from eight threads at once is the connection's afterwards,
    and none is lost."""
    threads, each = 8, 120
    sets = [[np.unique(np.random.default_rng(1000 * t + i).integers(
        0, DIM, 50)).astype(np.uint64) for i in range(each)]
        for t in range(threads)]
    got, errors = [None] * threads, []
    gate = threading.Barrier(threads)

    def work(t):
        try:
            gate.wait(30)
            got[t] = [kv.hold(k) for k in sets[t]]
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work, args=(t,))
                for t in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in pool)
    assert len(kv._held) == threads * each
    calls = _counted(kv)
    for t in range(threads):
        for frame, want in zip(got[t], sets[t]):
            assert np.array_equal(frame, want)
            assert kv._resolve_keys(frame, 1)[2] == "held"
    assert calls == []


# -- pull(out=) ---------------------------------------------------------------
@pytest.mark.parametrize("keys", ["held", "plain", "default"])
@pytest.mark.parametrize("room", [0, 1, 777])
def test_a_reply_lands_in_the_head_of_the_callers_buffer(kv, keys, room):
    k = {"held": lambda: kv.hold(_keys(1)), "plain": lambda: _keys(1),
         "default": lambda: None}[keys]()
    want = kv.pull(keys=k)
    n = len(want)
    out = np.full(n + room, np.nan, np.float32)
    got = kv.pull(keys=k, out=out)
    assert got.base is out and got.shape == (n,)
    assert got.ctypes.data == out.ctypes.data
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    assert np.isnan(out[n:]).all()  # the tail is the caller's
    # the same buffer again: fewer keys leave the stretch between alone
    fewer = kv.hold(_keys(1)[:10])
    out[:] = 7.0
    head = kv.pull(keys=fewer, out=out)
    assert head.base is out and len(head) == 10
    assert np.array_equal(head, kv.pull(keys=fewer))
    assert (out[10:] == 7.0).all()


def test_rows_of_several_values_land_row_major(kv):
    rows = kv.hold(_keys(4), 4)
    out = np.zeros((len(rows) + 3, 4), np.float32)
    got = kv.pull(keys=rows, vals_per_key=4, out=out)
    assert got.shape == (len(rows) * 4,) and got.base is out
    assert np.array_equal(got, kv.pull(keys=rows, vals_per_key=4))
    assert not out[len(rows):].any()


BAD_BUFFERS = {
    "float64": lambda n: np.zeros(n, np.float64),
    "int32": lambda n: np.zeros(n, np.int32),
    "strided": lambda n: np.zeros(2 * n, np.float32)[::2],
    "fortran": lambda n: np.zeros((n, 2), np.float32, order="F"),
    "short": lambda n: np.zeros(n - 1, np.float32),
    "read-only": lambda n: np.broadcast_to(np.float32(0), (n,)),
    "a-list": lambda n: [0.0] * n,
}


@pytest.mark.parametrize("bad", sorted(BAD_BUFFERS))
def test_a_buffer_that_cannot_take_the_reply_is_refused_with_nothing_sent(
        kv, bad):
    frame = kv.hold(_keys(1))
    out = BAD_BUFFERS[bad](len(frame))
    pulls = [kv.stats(s)["total_pulls"] for s in range(SERVERS)]
    before = _frames()
    with pytest.raises(ValueError, match="out must be"):
        kv.pull(keys=frame, out=out)
    assert [kv.stats(s)["total_pulls"] for s in range(SERVERS)] == pulls
    assert _moved(before) == {}


def test_a_retried_pull_fills_the_same_buffer():
    """The proxy severs the reply of the pull; the re-issue writes the
    buffer the first attempt was given, and the view returned is its
    head."""
    plan = parse_plan({"faults": [{"kind": "reset", "after_ops": 2}]})
    seed = np.arange(1, 9, dtype=np.float32)
    with ServerGroup(1, 1, 8, sync=False) as g, \
            ChaosFabric(g.direct_hosts, plan) as fab, \
            KVWorker(fab.hosts, 8, timeout_ms=2000, sync_group=False,
                     retry=RetryPolicy(attempts=4, backoff_ms=10)) as kv:
        kv.wait(kv.push_init(seed))
        frame = kv.hold([1, 4, 6])
        out = np.full(5, np.nan, np.float32)
        retries = get_registry().get("distlr_ps_retries_total").labels(
            op="pull")
        before, held = retries.value, _frames()
        got = kv.pull(keys=frame, out=out)
        assert any(e[1] == "reset" for e in fab.events())
        assert retries.value == before + 1
        assert got.base is out and got.tolist() == [2.0, 5.0, 7.0]
        assert np.isnan(out[3:]).all()
        # the failed attempt counted no frame, the re-issue one
        assert _moved(held) == {("pull", "held"): 1}
