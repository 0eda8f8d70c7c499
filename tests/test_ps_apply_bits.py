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
"""

from __future__ import annotations

import functools
import shutil
import threading
import time

import numpy as np
import pytest

from distlr_tpu.ps import KVWorker, ServerGroup
from distlr_tpu.ps.build import build_native, server_binary

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
