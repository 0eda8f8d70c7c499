"""ThreadSanitizer sweep of the native KV server (SURVEY.md §5.2).

The reference's only concurrency-safety argument is an unverified
"threadsafe" comment on its request handler (``src/main.cc:40``) — no
TSan/ASan anywhere (``CMakeLists.txt:4``).  Here the server's
thread-per-connection design is actually checked: build it with
``-fsanitize=thread``, hammer it with concurrent clients, and fail on
any ThreadSanitizer report.

Coverage (extended by the distlr-lint round beyond the original
sync/async sweep): the fused push_pull, FTRL with ``--opt_segments``
per-namespace updates plus concurrent opt-state snapshots, the kEpoch
fence and a live resize under concurrent clients, and codec-negotiated
(int8 / signSGD) pushes, and rounds of run frames, gapped row keys and a
push rolled back out of the merge (``test_ps_run_frames``' workload),
and the BSP release's writers with a stats probe beside them and a
shutdown racing a release.
The CLIENT library's own TSan build is
``tests/test_sanitizer_matrix.py`` (it needs the runtime preloaded).
"""

from __future__ import annotations

import glob
import os
import shutil
import threading
import time

import numpy as np
import pytest

from distlr_tpu.ps import KVWorker, MembershipCoordinator, ServerGroup
from distlr_tpu.ps.build import build_native, native_dir


def _build_tsan() -> str:
    build_native(variant="tsan")
    return os.path.join(native_dir(), "distlr_kv_server_tsan")


needs_toolchain = pytest.mark.skipif(
    shutil.which("make") is None or shutil.which("g++") is None,
    reason="no native toolchain",
)


@pytest.fixture
def tsan_env(tmp_path, monkeypatch):
    """Build the TSan server and point its reports at a scannable
    log_path; yields (binary, assert_no_reports)."""
    binary = _build_tsan()
    log_base = str(tmp_path / "tsan")
    # TSan writes each report to <log_path>.<pid>; exitcode=66 marks a
    # process that reported at least one race.
    monkeypatch.setenv("TSAN_OPTIONS", f"log_path={log_base} exitcode=66")

    def assert_no_reports(group: ServerGroup):
        group.wait()
        codes = [p.returncode for p in group.procs]
        reports = [open(f).read() for f in glob.glob(log_base + ".*")]
        assert not reports, \
            "ThreadSanitizer reports:\n" + "\n".join(reports)
        assert all(c == 0 for c in codes), \
            f"TSan server exit codes {codes} (66 = race reported)"

    return binary, assert_no_reports


def _run_threads(workers: int, fn, group: ServerGroup) -> None:
    """Run ``fn(rank)`` on ``workers`` threads, tearing the group down
    on the FIRST failure — otherwise a raising worker leaves its peers
    (and this test) parked on the sync barrier until the join timeouts
    burn out."""
    errors: list[Exception] = []

    def guarded(rank: int):
        try:
            fn(rank)
        except Exception as e:  # noqa: BLE001
            errors.append(e)
            group.stop()

    threads = [threading.Thread(target=guarded, args=(r,), daemon=True)
               for r in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not errors, f"worker failed: {errors[0]!r}"
    assert not any(t.is_alive() for t in threads), "worker thread wedged"


@needs_toolchain
@pytest.mark.parametrize("sync", [True, False], ids=["sync", "async"])
def test_server_race_free_under_tsan(tsan_env, sync):
    binary, assert_no_reports = tsan_env
    dim, workers, steps = 64, 4, 30
    group = ServerGroup(2, workers, dim, learning_rate=0.1, sync=sync,
                        binary=binary)
    with group:
        def run(rank: int):
            with KVWorker(group.hosts, dim, client_id=rank,
                          timeout_ms=60_000) as kv:
                if rank == 0:
                    kv.wait(kv.push_init(np.zeros(dim, np.float32)))
                kv.barrier(0)   # startup generation
                for i in range(steps):
                    w = kv.pull()
                    if i % 2:
                        # fused op: exercises deferred-with-payload (sync)
                        # and apply-and-reply (async) under TSan too
                        kv.push_pull(w * 0.01 + 1.0)
                    else:
                        kv.wait(kv.push(w * 0.01 + 1.0))
                kv.barrier(1)   # exit generation
                if rank == 0:
                    # stats probe runs concurrently-shaped code paths too
                    kv.stats(0), kv.stats(1)
                    kv.shutdown_servers()

        _run_threads(workers, run, group)
        assert_no_reports(group)


@needs_toolchain
def test_ftrl_opt_segments_under_tsan(tsan_env):
    """Per-namespace optimizers (--opt_segments) under concurrent
    pushes AND concurrent kOptState snapshot pulls — the PR-12 paths the
    original (pre-PR-6) sweep never covered: the FTRL z/n accumulators
    are per-coordinate server state touched by every push, and the
    supervisor's snapshot connections race the workers by design."""
    binary, assert_no_reports = tsan_env
    dim, workers, steps = 64, 3, 20
    group = ServerGroup(
        2, workers, dim, learning_rate=0.1, sync=False, binary=binary,
        opt_segments=[(32, "ftrl"), (64, "sgd")],
        ftrl_alpha=0.1, ftrl_l1=0.01)
    with group:
        stop = threading.Event()
        probe_errors: list[Exception] = []
        snapshots = [0]

        def prober():
            # per-rank opt-state snapshots concurrent with the pushes —
            # the supervisor's exact access pattern.  Failures are
            # COLLECTED and asserted after the join: a silently-dead
            # daemon probe would pass the test with the concurrent-
            # snapshot coverage it exists for quietly lost.
            from distlr_tpu.ps.client import PSRejectedError
            try:
                while not stop.is_set():
                    for rank, port in enumerate(group.ports):
                        lo, hi = group.key_range(rank)
                        try:
                            with KVWorker(f"127.0.0.1:{port}", hi - lo,
                                          client_id=0xFFFE,
                                          timeout_ms=30_000,
                                          sync_group=False) as kv:
                                kv.stats(0)
                                try:
                                    kv.pull_opt_state()
                                except PSRejectedError:
                                    pass  # rank hosting no FTRL slice
                                snapshots[0] += 1
                        except OSError:
                            return  # group shutting down
            except Exception as e:  # noqa: BLE001
                probe_errors.append(e)

        probe = threading.Thread(target=prober, daemon=True)
        probe.start()

        def run(rank: int):
            with KVWorker(group.hosts, dim, client_id=rank,
                          timeout_ms=60_000, sync_group=False) as kv:
                if rank == 0:
                    kv.push_init(np.zeros(dim, np.float32))
                kv.barrier(0)
                for i in range(steps):
                    w = kv.pull()
                    kv.push(np.sign(w) * 0.01 + (0.001 * (rank + i)))
                kv.barrier(1)
                if rank == 0:
                    kv.shutdown_servers()

        _run_threads(workers, run, group)
        stop.set()
        probe.join(timeout=30)
        assert not probe.is_alive(), "opt-state prober wedged"
        assert not probe_errors, f"prober failed: {probe_errors[0]!r}"
        assert snapshots[0] > 0, "prober took no concurrent snapshots"
        assert_no_reports(group)


@needs_toolchain
def test_epoch_fence_and_resize_under_tsan(tsan_env):
    """A live membership resize (kEpoch fence -> drain -> commit) while
    route-following clients keep pushing: the fence answers mid-stream
    on connections the handler threads share with data ops, and the
    drain's keyed pulls/forced seeds race the workers' pushes — all of
    it on the TSan server build."""
    binary, assert_no_reports = tsan_env
    dim, workers = 64, 3
    group = ServerGroup(2, workers, dim, learning_rate=0.1, sync=False,
                        binary=binary)
    with group:
        coord = MembershipCoordinator(group)
        stop = threading.Event()

        def run(rank: int):
            with KVWorker(None, dim, client_id=rank, timeout_ms=60_000,
                          sync_group=False, route=coord.layout) as kv:
                if rank == 0:
                    kv.push_init(np.zeros(dim, np.float32))
                steps = 0
                while not stop.is_set() and steps < 200:
                    w = kv.pull()
                    kv.push(w * 0.01 + 1.0)
                    steps += 1

        errors: list[Exception] = []

        def guarded(rank: int):
            try:
                run(rank)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                stop.set()

        threads = [threading.Thread(target=guarded, args=(r,), daemon=True)
                   for r in range(workers)]
        for t in threads:
            t.start()
        try:
            grow = coord.resize(4)
            shrink = coord.resize(2)
            assert grow["ok"] and shrink["ok"]
            assert coord.epoch == 3  # 1 (spawn) + two resizes
        finally:
            stop.set()
        for t in threads:
            t.join(timeout=120)
        assert not errors, f"client failed through the resize: {errors[0]!r}"
        assert not any(t.is_alive() for t in threads), "client wedged"
        # retired ranks were already reaped by commit_resize; shut down
        # the current layout and scan every rank's reports
        with KVWorker(group.hosts, dim, client_id=99,
                      timeout_ms=30_000, sync_group=False) as kv:
            kv.shutdown_servers()
        assert_no_reports(group)


@needs_toolchain
@pytest.mark.parametrize("codec", ["int8", "signsgd"])
def test_codec_pushes_under_tsan(tsan_env, codec):
    """Codec-negotiated pushes (kHello capability handshake + coded
    value payloads decoded at the parsing layer) under concurrent
    clients — int8 against SGD, 1-bit sign against the majority-vote
    kernel, both on the TSan server build."""
    binary, assert_no_reports = tsan_env
    dim, workers, steps = 64, 3, 20
    group = ServerGroup(
        2, workers, dim, learning_rate=0.01, sync=False, binary=binary,
        optimizer="signsgd" if codec == "signsgd" else "sgd")
    with group:
        def run(rank: int):
            with KVWorker(group.hosts, dim, client_id=rank,
                          timeout_ms=60_000, sync_group=False,
                          compress=codec) as kv:
                assert kv.compress_active == codec
                if rank == 0:
                    kv.push_init(np.zeros(dim, np.float32))
                kv.barrier(0)
                rng = np.random.default_rng(rank)
                for _ in range(steps):
                    g = rng.standard_normal(dim).astype(np.float32)
                    kv.push(g)
                    kv.pull()
                kv.barrier(1)
                if rank == 0:
                    kv.shutdown_servers()

        _run_threads(workers, run, group)
        assert_no_reports(group)


@needs_toolchain
@pytest.mark.parametrize("sync", [True, False], ids=["sync", "async"])
def test_run_frame_rounds_under_tsan(tsan_env, sync):
    """Frames handled as the rows they are: fused default-key frames
    (runs of three rows: apply or merge over the range, the reply out of
    ``weights_``, the values' buffer moved into the round and handed
    back), row keys with gaps, and a connection that dies with its push
    in the merge — all of it on the TSan server build."""
    from test_ps_run_frames import (
        SAN_DIM,
        SAN_WORKERS,
        check_run_frame_rounds,
        run_frame_rounds,
    )

    binary, assert_no_reports = tsan_env
    group = ServerGroup(2, SAN_WORKERS, SAN_DIM, learning_rate=0.05,
                        sync=sync, binary=binary)
    with group:
        last, stats = run_frame_rounds(group, sync)
        assert_no_reports(group)
    check_run_frame_rounds(last, stats, sync)


@needs_toolchain
def test_release_fanout_under_tsan(tsan_env):
    """The release's writers: four concurrent fused pushers through 24
    rounds against two servers (a round's four replies written side by
    side out of ``weights_``, the releasing thread holding the lock),
    a stats probe running beside them all the while, and a ``kShutdown``
    sent while the pushers are still going round, so that it races a
    release — on the TSan server build.  The writers are gone before
    the server is: exit code 0, no report."""
    binary, assert_no_reports = tsan_env
    dim, workers, rounds = 1 << 16, 4, 24
    group = ServerGroup(2, workers, dim, learning_rate=0.05, sync=True,
                        binary=binary)
    with group:
        with KVWorker(group.hosts, dim, client_id=0xFC00,
                      timeout_ms=60_000, sync_group=False) as probe:
            probe.wait(probe.push_init(np.zeros(dim, np.float32)))
            done = threading.Event()
            probed, probe_errors = [0], []

            def prober():
                try:
                    while not done.is_set():
                        probe.stats(0), probe.stats(1)
                        probed[0] += 1
                except Exception as e:  # noqa: BLE001 — asserted below
                    probe_errors.append(e)

            finished = [0] * workers

            def run(rank: int):
                grad = np.full(dim, 1e-3 * (rank + 1), np.float32)
                with KVWorker(group.hosts, dim, client_id=rank,
                              timeout_ms=60_000) as kv:
                    for _ in range(rounds):
                        kv.push_pull(grad)
                        finished[rank] += 1
                    try:    # on into the shutdown: any round may be cut
                        while True:
                            kv.push_pull(grad)
                    except OSError:
                        pass

            watcher = threading.Thread(target=prober, daemon=True)
            watcher.start()
            pushers = [threading.Thread(target=run, args=(r,), daemon=True)
                       for r in range(workers)]
            for t in pushers:
                t.start()
            deadline = time.monotonic() + 180
            while min(finished) < rounds:
                assert time.monotonic() < deadline, finished
                time.sleep(0.01)
            done.set()
            watcher.join(timeout=60)
            stats = [probe.stats(r) for r in range(2)]
            probe.shutdown_servers()
            for t in pushers:
                t.join(timeout=120)
        assert not any(t.is_alive() for t in pushers), "pusher wedged"
        assert not probe_errors, f"prober failed: {probe_errors[0]!r}"
        assert probed[0] > 0
        for s in stats:
            assert s["sync_rounds"] >= rounds
            assert s["release_fanned_replies"] == 3 * s["sync_rounds"]
        assert_no_reports(group)
