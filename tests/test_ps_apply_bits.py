"""The servers' float32 arithmetic, held to NumPy's one operation at a
time, in every bit, at whatever width the binary was built.

A native server moves its weights in three loops (``ps/native/
kv_loops.h``): the async apply ``w -= lr * g``, the lock-step merge
``m += g`` in arrival order and the release's mean step
``w -= (lr * g) / W``.  The release build packs them four lanes at a
time; the sanitizer builds keep them scalar.  Each coordinate must go
through the same correctly rounded operations in the same order either
way: a multiply fused into the subtract (one rounding for two), a
``lr * (g / W)``, a multiply by ``1 / W`` or a vector's tail handled by
another expression would each show here as a weight off by one ulp.

Every case drives a server group through ``KVWorker`` for two rounds
and compares what the workers' fused pushes returned, and a last pull
of the whole vector, with float32 NumPy: lengths round every multiple of
the lane count (1, 3, 4, 7, 8, 9, 31) and a long vector over two servers
through the mapped exchange (100,003), a run frame that starts at an odd
slot, scattered single rows and scattered rows of two and of five (a
span under four values is applied inline, not by the packed loop).  In
lock step the gradients arrive in an order the test fixes (W = 3 is
there because a divide by 1, 2 or 4 is exact, so only it tells
``(lr * g) / W`` from its rearrangements); upstream's last-gradient
shortcut and an ``--opt_segments`` group take the same step through
their own branches of the release.  The same cases run against the
``ubsan`` variant, and a few against ``asan`` and ``tsan``, where the
toolchain builds them.

The per-coordinate FTRL-Proximal step is held the same way (the second
half of this file): an asynchronous keyed push of scattered single rows
is stepped four coordinates at a time by the release build
(``kv_loops.h::FtrlStepPacked``, SSE2) and one at a time by the
sanitizer builds, and after every round the weights, ``z`` and ``n`` of
either have to be Algorithm 1's in float32 NumPy, written one operation
a line and stepped in frame order.  Frames of 1, 3, 4, 5, 7, 8, 9 and 31
keys and one of 100,003 over two servers through the mapped exchange,
two rounds each so ``n`` is not zero in the second; alpha 0.1 (a divide
that is not exact); a group with zero entries; a group in which one lane
ends under ``l1`` and three do not; a lane that goes NaN; an
``--opt_segments`` boundary inside a group of four; and raw frames,
written below ``KVWorker``'s validation, that repeat a key inside one
group or come in descending order.
"""

from __future__ import annotations

import functools
import shutil
import threading
import time

import numpy as np
import pytest

from distlr_tpu.ps import KVWorker, ServerGroup, wire
from distlr_tpu.ps.build import build_native, server_binary
from test_ps_run_frames import Raw  # frames written by hand

F32 = np.float32
LR = F32(0.3)
ROUNDS = 2
PROBE_ID = 0xFB00

pytestmark = pytest.mark.skipif(
    shutil.which("make") is None or shutil.which("g++") is None,
    reason="no native toolchain",
)

#: name -> (dim, row keys or None for the dense default set, vals a key)
FRAMES = {
    **{f"dense-{d}": (d, None, 1) for d in (1, 3, 4, 7, 8, 9, 31, 100_003)},
    "run-from-odd-slot": (96, np.arange(5, 42), 1),
    "scattered-single-rows": (64, np.array([1, 4, 6, 11, 17, 30, 31, 50, 63]), 1),
    "scattered-rows-of-two": (64, np.array([0, 3, 4, 9, 31]), 2),
    "scattered-rows-of-five": (100, np.array([0, 2, 3, 7, 11, 18]), 5),
}
#: name -> (workers, sync, last_gradient, all-SGD opt_segments)
MODES = {
    "bsp-w1": (1, True, False, False),
    "bsp-w2": (2, True, False, False),
    "bsp-w3": (3, True, False, False),
    "bsp-w4": (4, True, False, False),
    "async": (1, False, False, False),
    "bsp-w3-last-gradient": (3, True, True, False),
    "bsp-w2-segments": (2, True, False, True),
}
SANITIZER_SUBSET = [(m, f) for m in ("bsp-w4", "async")
                    for f in ("dense-9", "dense-100003", "run-from-odd-slot",
                              "scattered-single-rows")]
CASES = (
    [("", m, f) for m in MODES for f in FRAMES]
    + [("ubsan", m, f) for m in MODES for f in FRAMES]
    + [(v, m, f) for v in ("asan", "tsan") for m, f in SANITIZER_SUBSET]
)

@functools.lru_cache(maxsize=None)
def _build_error(variant: str) -> str | None:
    """None once ``variant`` is built (through the stamp rule), else the
    last line of what the toolchain said."""
    try:
        build_native(variant=variant)
    except RuntimeError as e:
        return str(e).strip().splitlines()[-1]
    return None


def _binary(variant: str) -> str:
    """The server binary of ``variant``; skips where this toolchain
    cannot build it."""
    error = _build_error(variant)
    if error is not None:
        pytest.skip(f"no {variant} build here: {error}")
    return server_binary(variant)


def _values(rng, n: int) -> np.ndarray:
    """float32 over eight decades with both signs, both zeros, a
    subnormal and a large value among them."""
    v = (rng.standard_normal(n) * 10.0 ** rng.uniform(-6, 2, n)).astype(F32)
    specials = np.array([0.0, -0.0, 1e-41, 3e30, -7e-39], F32)
    at = rng.permutation(n)[:min(n, specials.size)]
    v[at] = specials[:at.size]
    return v


def _slots(keys, vpk: int, dim: int) -> np.ndarray:
    if keys is None:
        return np.arange(dim)
    return (keys[:, None] * vpk + np.arange(vpk)[None, :]).reshape(-1)


def _expected(w0, rounds, slots, workers, sync, last_gradient):
    """The weights after each round: ``rounds[k]`` is that round's
    ``(client id, gradient)`` pairs in arrival order."""
    w, after = w0.copy(), []
    for arrivals in rounds:
        if not sync:
            for _cid, g in arrivals:
                w[slots] = w[slots] - LR * g
        else:
            m = np.zeros_like(w)
            if last_gradient:
                m[slots] = max(arrivals, key=lambda a: a[0])[1]
            else:
                for _cid, g in arrivals:
                    m[slots] = m[slots] + g
            w = w - (LR * m) / F32(workers)
        after.append(w.copy())
    return after


def _wait_pending(probe: KVWorker, servers: int, n: int):
    deadline = time.monotonic() + 30
    while any(probe.stats(s)["pending_sync_pushes"] != n
              for s in range(servers)):
        assert time.monotonic() < deadline, "a push never joined its round"
        time.sleep(0.002)


def _same_bits(got: np.ndarray, want: np.ndarray, what: str):
    off = np.flatnonzero(got.view(np.uint32) != want.view(np.uint32))
    assert off.size == 0, (
        f"{what}: {off.size} of {want.size} weights differ, first at "
        f"{off[0]}: got {got[off[0]]!r}, NumPy's float32 {want[off[0]]!r}")


@pytest.mark.parametrize("variant,mode,frame", CASES)
def test_weights_equal_numpy_float32_in_every_bit(variant, mode, frame):
    workers, sync, last_gradient, segments = MODES[mode]
    dim, keys, vpk = FRAMES[frame]
    servers = 2 if dim > 1000 else 1
    slots = _slots(keys, vpk, dim)
    rng = np.random.default_rng(4800 + dim + 7 * workers)
    w0 = _values(rng, dim)
    # round 0 arrives by rank, round 1 in the reverse order
    rounds = [[(r, _values(rng, slots.size)) for r in order]
              for order in (range(workers), reversed(range(workers)))]
    want = _expected(w0, rounds, slots, workers, sync, last_gradient)

    binary = _binary(variant)
    opt_segments = ([(end, "sgd") for end in sorted({max(dim // 2, 1), dim})]
                    if segments else None)
    replies: dict[tuple[int, int], np.ndarray] = {}
    errors = []
    with ServerGroup(servers, workers, dim, learning_rate=float(LR),
                     sync=sync, last_gradient=last_gradient, binary=binary,
                     opt_segments=opt_segments) as group, \
            KVWorker(group.hosts, dim, client_id=PROBE_ID,
                     timeout_ms=60_000, sync_group=sync) as probe:
        probe.wait(probe.push_init(w0))
        kvs = [KVWorker(group.hosts, dim, client_id=r, timeout_ms=60_000,
                        sync_group=sync) for r in range(workers)]
        try:
            def push(k: int, cid: int, g: np.ndarray):
                try:
                    replies[k, cid] = kvs[cid].push_pull(
                        g, keys=keys, vals_per_key=vpk)
                except Exception as e:  # noqa: BLE001 — reported below
                    errors.append(e)

            for k, arrivals in enumerate(rounds):
                threads = []
                for n, (cid, g) in enumerate(arrivals):
                    t = threading.Thread(target=push, args=(k, cid, g),
                                         daemon=True)
                    t.start()
                    threads.append(t)
                    if sync and n + 1 < workers:
                        _wait_pending(probe, servers, n + 1)
                    elif not sync:
                        t.join(timeout=60)
                for t in threads:
                    t.join(timeout=60)
                assert not errors, f"a push failed: {errors[0]!r}"
                assert not any(t.is_alive() for t in threads)
            final = probe.pull()
        finally:
            for kv in kvs:
                kv.close()

    for (k, cid), got in replies.items():
        _same_bits(got, want[k][slots], f"round {k}, worker {cid}'s reply")
    assert len(replies) == ROUNDS * workers
    _same_bits(final, want[-1], "the weights after two rounds")


# -- the FTRL-Proximal step ----------------------------------------------
#: alpha 0.1: neither divide by it is exact.  l1 where some |z| end
#: under it and most do not; l2 not zero, so its add is there to round.
RULE = dict(ftrl_alpha=0.1, ftrl_beta=1.0, ftrl_l1=0.05, ftrl_l2=0.01)


def _ftrl_entries(w, z, n, at, g):
    """Algorithm 1 on the distinct coordinates ``at`` of the tables, an
    entry ``g != 0`` each, one float32 operation a line in the order of
    ``kv_loops.h::FtrlStepOne``.  Changes the tables in place; returns
    which entries ended with ``|z| <= l1``."""
    alpha, beta = F32(RULE["ftrl_alpha"]), F32(RULE["ftrl_beta"])
    l1, l2 = F32(RULE["ftrl_l1"]), F32(RULE["ftrl_l2"])
    with np.errstate(all="ignore"):
        n_old = n[at]
        square = g * g
        n_new = n_old + square
        root_new = np.sqrt(n_new)
        root_old = np.sqrt(n_old)
        rise = root_new - root_old
        sigma = rise / alpha
        drag = sigma * w[at]
        step = g - drag
        z_new = z[at] + step
        under = np.abs(z_new) <= l1
        sgn = np.where(z_new > 0, F32(1.0), F32(-1.0))
        shrink = sgn * l1
        over = z_new - shrink
        minus = -over
        smoothed = beta + root_new
        rate = smoothed / alpha
        rate = rate + l2
        w_new = minus / rate
    for a in (n_new, z_new, w_new):
        assert a.dtype == F32
    n[at], z[at] = n_new, z_new
    w[at] = np.where(under, F32(0.0), w_new)
    return under


def _ftrl_frame(w, z, n, slots, g, ftrl):
    """One push in frame order: an entry 0.0 steps nothing, a slot where
    ``ftrl`` (a flag a coordinate) is False takes the SGD step, a slot
    that comes again meets what its earlier entry left.  Returns the
    steps that ended under l1, a flag an entry (False where nothing
    stepped)."""
    under = np.zeros(slots.size, bool)
    if np.unique(slots).size == slots.size and ftrl[slots].all():
        live = g != 0
        under[live] = _ftrl_entries(w, z, n, slots[live], g[live])
        return under
    for i, (k, gi) in enumerate(zip(slots, g)):
        if not ftrl[k]:
            w[k] = w[k] - LR * gi
        elif gi != 0:
            under[i] = _ftrl_entries(w, z, n, slots[i:i + 1], g[i:i + 1])[0]
    return under


def packed_steps(slots, g, segments=None) -> int:
    """What a server counts as ``ftrl_packed_steps`` for a frame of
    single-value rows it got whole: four for every group of four at
    0, 4, 8, ... of the frame whose keys strictly ascend, whose entries
    are none 0.0 and whose keys one FTRL stretch of ``segments``
    (``(end, optimizer)`` pairs; all FTRL where None) holds; nothing for
    a frame that is one run of keys (it is applied as a range) and
    nothing for the tail."""
    slots = np.asarray(slots).astype(np.int64)
    if slots.size == 0 or (slots == slots[0] + np.arange(slots.size)).all():
        return 0

    def stretch(k):
        if segments is None:
            return 0, "ftrl"
        return next((i, opt) for i, (end, opt) in enumerate(segments)
                    if k < end)

    packed = 0
    for i in range(0, slots.size - 3, 4):
        k, v = slots[i:i + 4], g[i:i + 4]
        first, last = stretch(k[0]), stretch(k[3])
        if ((np.diff(k) > 0).all() and (v != 0).all() and first == last
                and first[1] == "ftrl"):
            packed += 4
    return packed


def _ftrl_values(rng, n: int) -> np.ndarray:
    """float32 over six decades round l1 with both signs and two
    subnormals, none zero, none whose square overflows."""
    v = (rng.standard_normal(n) * 10.0 ** rng.uniform(-5, 1, n)).astype(F32)
    v[v == 0] = F32(0.25)
    specials = np.array([1e-41, -7e-39], F32)
    at = rng.permutation(n)[:min(n - 1, specials.size)]
    v[at] = specials[:at.size]
    return v


def _odd_keys(rng, dim: int, n: int) -> np.ndarray:
    """``n`` ascending keys under ``dim``, no two consecutive: no run."""
    return np.sort(rng.choice(dim // 2, size=n, replace=False)) * 2 + 1


def _ftrl_case(name: str):
    """``(dim, rounds of (slots, gradient), opt_segments)``; a case
    whose keys KVWorker would refuse is sent raw."""
    rng = np.random.default_rng(5400 + sum(map(ord, name)))
    kind, _, arg = name.partition("-")
    segments = None
    if kind == "scattered":
        n = int(arg)
        dim = 400_000 if n > 1000 else 256
        keys = _odd_keys(rng, dim, n)
        rounds = [(keys, _ftrl_values(rng, n)) for _ in range(ROUNDS)]
    elif kind == "zeros":
        # a zero of either sign in the first group, one in the tail
        dim, keys = 64, _odd_keys(rng, 64, 11)
        rounds = []
        for _ in range(ROUNDS):
            g = _ftrl_values(rng, 11)
            g[[1, 2, 9]] = (0.0, -0.0, 0.0)
            rounds.append((keys, g))
    elif kind == "under":
        # from zero tables a first step leaves z = g: the second lane of
        # the first group alone ends under l1, then a lane of the second
        dim, keys = 64, _odd_keys(rng, 64, 8)
        rounds = [
            (keys, np.array([.5, .01, -.7, .3, -.02, .9, 1.5, -.4], F32)),
            (keys, np.array([.2, .03, .1, -.6, .01, -.3, .2, .1], F32)),
        ]
    elif kind == "nan":
        # an infinite entry: n = inf, sigma = inf, z = NaN for good
        dim, keys = 64, _odd_keys(rng, 64, 8)
        rounds = []
        for _ in range(ROUNDS):
            g = _ftrl_values(rng, 8)
            g[2] = np.inf
            rounds.append((keys, g))
    elif kind == "segments":
        # the boundary at slot 20 falls inside the second group of four
        dim, keys = 64, np.array([1, 4, 6, 11, 17, 19, 22, 30, 33, 40, 41,
                                  50, 63])
        rounds = [(keys, _ftrl_values(rng, keys.size)) for _ in range(ROUNDS)]
        first, second = arg.split("|")
        segments = [(20, first), (dim, second)]
    elif kind == "raw":
        dim = 64
        keys = {
            # a key twice in the first group, three times over the
            # second and third, the tail a pair
            "repeats": [3, 9, 9, 20, 31, 40, 57, 57, 57, 60, 62, 7, 7, 7],
            "descending": [60, 52, 41, 33, 30, 22, 19, 8, 5],
        }[arg]
        keys = np.array(keys)
        rounds = [(keys, _ftrl_values(rng, keys.size)) for _ in range(ROUNDS)]
    else:
        raise AssertionError(name)
    return dim, rounds, segments


FTRL_FRAMES = (
    [f"scattered-{n}" for n in (1, 3, 4, 5, 7, 8, 9, 31, 100_003)]
    + ["zeros-in-a-group", "under-l1-one-lane", "nan-lane",
       "segments-ftrl|sgd", "segments-ftrl|ftrl", "segments-sgd|ftrl",
       "raw-repeats", "raw-descending"])
FTRL_CASES = (
    [(v, f) for v in ("", "ubsan") for f in FTRL_FRAMES]
    + [(v, f) for v in ("asan", "tsan")
       for f in ("scattered-9", "scattered-100003", "raw-repeats")])


def _same_bits_or_nan(got, want, what):
    nan = np.isnan(want)
    assert (np.isnan(got) == nan).all(), f"{what}: the NaNs stand elsewhere"
    _same_bits(np.where(nan, F32(0), got), np.where(nan, F32(0), want), what)


@pytest.mark.parametrize("variant,frame", FTRL_CASES)
def test_ftrl_tables_equal_numpy_float32_in_every_bit(variant, frame):
    dim, rounds, segments = _ftrl_case(frame)
    servers = 2 if dim > 1000 else 1
    raw = frame.startswith("raw-")
    ftrl, start = np.ones(dim, bool), 0
    for end, opt in segments or ():
        ftrl[start:end] = opt == "ftrl"
        start = end
    rng = np.random.default_rng(5400 + dim)
    # a warm start the rule forgets (none where the case counts on z = g)
    w0 = (np.zeros(dim, F32) if frame == "under-l1-one-lane"
          else _ftrl_values(rng, dim))
    w, z, n = w0.copy(), np.zeros(dim, F32), np.zeros(dim, F32)
    want, unders = [], []
    for slots, g in rounds:
        unders.append(_ftrl_frame(w, z, n, slots, g, ftrl))
        want.append(w.copy())
    if frame == "under-l1-one-lane":
        assert [u[:4].tolist() for u in unders] == [
            [False, True, False, False], [False, True, False, False]]
        assert [int(u[4:].sum()) for u in unders] == [1, 1]

    binary = _binary(variant)
    replies = []
    with ServerGroup(servers, 1, dim, learning_rate=float(LR), sync=False,
                     binary=binary, optimizer="ftrl", opt_segments=segments,
                     **RULE) as group:
        with KVWorker(group.hosts, dim, client_id=0, timeout_ms=60_000,
                      sync_group=False) as kv:
            kv.wait(kv.push_init(w0))
            if raw:
                with Raw(group.ports[0], client_id=7) as conn:
                    for slots, g in rounds:
                        replies.append(conn.call(
                            wire.OP_PUSH_PULL, keys=slots, vals=g))
            else:
                for slots, g in rounds:
                    replies.append(kv.push_pull(g, keys=slots))
            final = kv.pull()
            stats = [kv.stats(r) for r in range(servers)]
        tables = []
        ranges = [group.key_range(r) for r in range(servers)]
        for r, (lo, hi) in enumerate(ranges):
            with KVWorker(f"127.0.0.1:{group.ports[r]}", hi - lo,
                          client_id=9, sync_group=False) as one:
                tables.append(one.pull_opt_state())
    z_got = np.concatenate([t[0] for t in tables])
    n_got = np.concatenate([t[1] for t in tables])

    for k, (got, (slots, _g)) in enumerate(zip(replies, rounds)):
        _same_bits_or_nan(got, want[k][slots], f"round {k}'s reply")
    _same_bits_or_nan(final, w, "the weights after two rounds")
    _same_bits_or_nan(z_got, z, "z after two rounds")
    _same_bits_or_nan(n_got, n, "n after two rounds")
    if frame == "nan-lane":
        assert np.isnan(w).sum() == 1 and np.isnan(z).sum() == 1
    if dim > 1000:
        assert all(s["mapped_frames"] >= 2 * ROUNDS for s in stats)
    assert sum(s["ftrl_zeroed"] for s in stats) == sum(
        int(u.sum()) for u in unders)
    # either build walks the frame the same way: the width of a step is
    # the build's, what goes through it in fours is the frame's
    parts = [[(slots[(slots >= lo) & (slots < hi)],
               g[(slots >= lo) & (slots < hi)]) for slots, g in rounds]
             for lo, hi in ranges]
    assert [s["ftrl_packed_steps"] for s in stats] == [
        sum(packed_steps(k, v, segments) for k, v in part) for part in parts]
    assert [s["ftrl_steps"] for s in stats] == [
        sum(int(np.count_nonzero(v[ftrl[k]])) for k, v in part)
        for part in parts]
