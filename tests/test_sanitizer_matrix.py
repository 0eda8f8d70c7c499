"""Native sanitizer matrix e2e (ISSUE 13 tentpole, native half).

``DISTLR_NATIVE_VARIANT={tsan,asan,ubsan}`` routes every
``ServerGroup`` spawn and (for tsan) the ctypes client itself onto
instrumented builds — so the EXISTING e2e suites run under sanitizers
unchanged.  The fast tests here drive one multi-threaded client+server
workload per variant in a subprocess (the TSan client needs the
runtime LD_PRELOADed) and fail on any report; the ``slow`` tests run
the real chaos and elastic suites under the TSan pair, which is the
acceptance criterion: zero unsuppressed reports end to end.

The reference has no sanitizer coverage at all (SURVEY.md §5.2); this
matrix already paid for itself — its first run caught the server's
per-connection zombie-thread leak (fixed in kv_server.cc's accept
loop).
"""

from __future__ import annotations

import glob
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_toolchain = pytest.mark.skipif(
    shutil.which("make") is None or shutil.which("g++") is None,
    reason="no native toolchain",
)

_OPTS_VAR = {"tsan": "TSAN_OPTIONS", "asan": "ASAN_OPTIONS",
             "ubsan": "UBSAN_OPTIONS"}


def _libtsan() -> str | None:
    """Path to the TSan runtime, or None when the toolchain lacks it."""
    if shutil.which("g++") is None:
        return None
    out = subprocess.run(["g++", "-print-file-name=libtsan.so"],
                         capture_output=True, text=True).stdout.strip()
    return out if os.path.sep in out and os.path.exists(out) else None


def _build(variant: str) -> None:
    # through the stamp rule, so the workload subprocess (which builds
    # on demand under DISTLR_NATIVE_VARIANT) finds it fresh
    from distlr_tpu.ps.build import build_native

    build_native(variant=variant)


def _host_supp() -> str:
    """HOST-process suppressions (uninstrumented jaxlib noise) — the
    native side never sees these: sanitizer_environ forces spawned
    servers onto ps/native/<variant>.supp."""
    return os.path.join(REPO, "tests", "tsan_host.supp")


#: the subprocess workload: concurrent clients (one handle per thread —
#: the documented pattern every suite uses), pushes/pulls/fused ops/
#: stats probes, plus an in-place reconnect per thread — the client
#: library's reader/retry surface under whichever sanitizer is active.
_DRIVER = textwrap.dedent("""
    import threading
    import numpy as np
    from distlr_tpu.ps import KVWorker, ServerGroup

    dim, workers, steps = 64, 3, 15
    errors = []
    with ServerGroup(2, workers, dim, learning_rate=0.1,
                     sync=False) as group:
        def run(rank):
            with KVWorker(group.hosts, dim, client_id=rank,
                          timeout_ms=60_000, sync_group=False) as kv:
                if rank == 0:
                    kv.push_init(np.zeros(dim, np.float32))
                kv.barrier(0)
                for i in range(steps):
                    w = kv.pull()
                    if i % 3 == 0:
                        kv.push_pull(w * 0.01 + 1.0)
                    else:
                        kv.push(w * 0.01 + 1.0)
                    if i == steps // 2:
                        kv.reconnect()   # retry/reroute surface
                    kv.stats(rank % 2)
                kv.barrier(1)
                if rank == 0:
                    kv.shutdown_servers()

        def guarded(rank):
            try:
                run(rank)
            except Exception as e:
                errors.append(e)
                group.stop()

        ts = [threading.Thread(target=guarded, args=(r,), daemon=True)
              for r in range(workers)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        assert not errors, errors[0]
        assert not any(t.is_alive() for t in ts), "worker wedged"
        group.wait()
        assert [p.returncode for p in group.procs] == [0, 0], \\
            [p.returncode for p in group.procs]
    print("DRIVER_OK")
""")


#: the ISSUE-20 workload: the durable store's background persistence
#: thread (snapshot writer) and WAL group-commit thread run NEXT TO the
#: op threads the whole time, with SIGUSR1-forced snapshots landing
#: mid-WAL-append, then a kill -9 + respawn so the recovery path
#: (snapshot load + WAL replay) executes under the same sanitizer.
_STORE_DRIVER = textwrap.dedent("""
    import os
    import signal
    import threading
    import numpy as np
    from distlr_tpu.ps import KVWorker, ServerGroup

    dim, workers, steps = 64, 3, 15
    store = os.path.abspath("store")
    errors = []
    with ServerGroup(2, workers, dim, learning_rate=0.1, sync=False,
                     store_dir=store, store_interval_s=0.1,
                     store_wal=True, store_wal_fsync_s=0.02) as group:
        def run(rank):
            with KVWorker(group.hosts, dim, client_id=rank,
                          timeout_ms=60_000, sync_group=False) as kv:
                if rank == 0:
                    kv.push_init(np.zeros(dim, np.float32))
                kv.barrier(0)
                for i in range(steps):
                    w = kv.pull()
                    kv.push(w * 0.01 + 1.0)
                    if i == steps // 2 and rank == 0:
                        # immediate snapshot while the WAL commit
                        # thread is appending — the cross-thread pair
                        # this test exists to race
                        for p in group.procs:
                            os.kill(p.pid, signal.SIGUSR1)
                    kv.stats(rank % 2)
                kv.barrier(1)

        def guarded(rank):
            try:
                run(rank)
            except Exception as e:
                errors.append(e)
                group.stop()

        ts = [threading.Thread(target=guarded, args=(r,), daemon=True)
              for r in range(workers)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        assert not errors, errors[0]
        assert not any(t.is_alive() for t in ts), "worker wedged"
        # power loss + cold restart: recovery runs instrumented too
        group.procs[0].kill()
        group.procs[0].wait()
        assert group.respawn(0)
        with KVWorker(group.hosts, dim, client_id=9,
                      timeout_ms=60_000, sync_group=False) as kv:
            assert kv.pull().shape == (dim,)
            kv.shutdown_servers()
        group.wait()
        assert [p.returncode for p in group.procs] == [0, 0], \\
            [p.returncode for p in group.procs]
    print("DRIVER_OK")
""")


#: ISSUE 31: the server handles a frame as the rows it is.  Rounds of
#: run frames (apply / merge over the range, the reply out of weights_,
#: the values' buffer moved into the round and handed back), row keys
#: with gaps, a push rolled back out of the merge — in both modes
#: (``tests/test_ps_run_frames.py`` has the workload and its checks).
_RUN_FRAMES_DRIVER = textwrap.dedent(f"""
    import sys
    sys.path.insert(0, {os.path.join(REPO, "tests")!r})
    from distlr_tpu.ps import ServerGroup
    from test_ps_run_frames import (
        SAN_DIM, SAN_WORKERS, check_run_frame_rounds, run_frame_rounds)

    for sync in (False, True):
        with ServerGroup(2, SAN_WORKERS, SAN_DIM, learning_rate=0.05,
                         sync=sync) as group:
            last, stats = run_frame_rounds(group, sync)
            group.wait()
            assert [p.returncode for p in group.procs] == [0, 0], \\
                [p.returncode for p in group.procs]
        check_run_frame_rounds(last, stats, sync)
    print("DRIVER_OK")
""")


def _run_variant(variant: str, tmp_path, *, preload: str | None = None,
                 timeout: int = 300, driver_src: str = _DRIVER) -> None:
    _build(variant)
    driver = tmp_path / "driver.py"
    driver.write_text(driver_src)
    log_base = str(tmp_path / f"{variant}_report")
    env = os.environ.copy()
    env.pop("LD_PRELOAD", None)
    env["DISTLR_NATIVE_VARIANT"] = variant
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # exitcode=66 marks a reporting process; log_path makes every
    # report scannable.  The host suppressions cover only audited
    # third-party noise; spawned servers get the (empty) native file
    # via ps.build, so any native report fails the run.
    opts = f"log_path={log_base} exitcode=66"
    if variant == "tsan":
        opts += f" suppressions={_host_supp()}"
    env[_OPTS_VAR[variant]] = opts
    if preload:
        env["LD_PRELOAD"] = preload
    proc = subprocess.run(
        [sys.executable, str(driver)], env=env, cwd=str(tmp_path),
        capture_output=True, text=True, timeout=timeout)
    reports = [open(f).read() for f in glob.glob(log_base + ".*")]
    assert not reports, (
        f"{variant} reports:\n" + "\n".join(reports))
    assert proc.returncode == 0 and "DRIVER_OK" in proc.stdout, (
        f"{variant} driver rc={proc.returncode}\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")


@needs_toolchain
def test_asan_server_e2e(tmp_path):
    _run_variant("asan", tmp_path)


@needs_toolchain
def test_ubsan_server_e2e(tmp_path):
    _run_variant("ubsan", tmp_path)


@needs_toolchain
@pytest.mark.parametrize("variant", ["asan", "ubsan"])
def test_run_frame_rounds_under_asan_and_ubsan(variant, tmp_path):
    _run_variant(variant, tmp_path, driver_src=_RUN_FRAMES_DRIVER)


@needs_toolchain
def test_tsan_client_and_server_e2e(tmp_path):
    """THE coverage gap this round closes: libdistlr_kv.so itself under
    TSan (the Python-side reader/retry threads had zero sanitizer
    coverage), against the TSan server, in one workload."""
    rt = _libtsan()
    if rt is None:
        pytest.skip("toolchain has no libtsan runtime")
    _run_variant("tsan", tmp_path, preload=rt)


@needs_toolchain
def test_tsan_server_store_e2e(tmp_path):
    """ISSUE 20: the durable store's snapshot + WAL threads under TSan
    — persistence armed, SIGUSR1 snapshots racing WAL appends, then a
    kill -9 respawn whose recovery (snapshot load + WAL replay) runs
    instrumented too.  Zero unsuppressed reports."""
    rt = _libtsan()
    if rt is None:
        pytest.skip("toolchain has no libtsan runtime")
    _run_variant("tsan", tmp_path, preload=rt, driver_src=_STORE_DRIVER)


@needs_toolchain
def test_tsan_client_requires_preload(monkeypatch):
    """Without the runtime preloaded the instrumented .so cannot load;
    the build layer must fail with the exact fix, not let dlopen die on
    a static-TLS error."""
    from distlr_tpu.ps import build

    monkeypatch.setenv("DISTLR_NATIVE_VARIANT", "tsan")
    monkeypatch.delenv("LD_PRELOAD", raising=False)
    with pytest.raises(RuntimeError, match="LD_PRELOAD"):
        build.client_lib()


def test_bogus_variant_rejected(monkeypatch):
    from distlr_tpu.ps import build

    monkeypatch.setenv("DISTLR_NATIVE_VARIANT", "valgrind")
    with pytest.raises(ValueError, match="DISTLR_NATIVE_VARIANT"):
        build.native_variant()


def test_sanitizer_environ_strips_host_noise(monkeypatch):
    """Caller-set options (a test's log_path/exitcode) survive, but
    host-only noise controls never reach the native processes: the
    suppressions path is FORCED to the audited native file and
    report_mutex_bugs is dropped — servers stay strictly checked even
    when the pytest host runs with relaxed options."""
    from distlr_tpu.ps import build

    monkeypatch.setenv("DISTLR_NATIVE_VARIANT", "tsan")
    monkeypatch.setenv(
        "TSAN_OPTIONS",
        "log_path=/tmp/x exitcode=66 report_mutex_bugs=0 "
        "suppressions=/tmp/host_noise.supp")
    env = build.sanitizer_environ()
    assert "log_path=/tmp/x" in env["TSAN_OPTIONS"]
    assert "exitcode=66" in env["TSAN_OPTIONS"]
    assert "report_mutex_bugs" not in env["TSAN_OPTIONS"]
    assert env["TSAN_OPTIONS"].count("suppressions=") == 1
    assert "native" in env["TSAN_OPTIONS"]  # the audited file won
    monkeypatch.delenv("DISTLR_NATIVE_VARIANT")
    assert build.sanitizer_environ() is None  # standard build: untouched


# ---------------------------------------------------------------------------
# the acceptance criterion: existing e2e suites under the TSan pair
# ---------------------------------------------------------------------------


def _run_suite_under_tsan(tmp_path, pytest_args: list[str],
                          timeout: int) -> None:
    rt = _libtsan()
    if rt is None:
        pytest.skip("toolchain has no libtsan runtime")
    _build("tsan")
    log_base = str(tmp_path / "suite_report")
    env = os.environ.copy()
    env["DISTLR_NATIVE_VARIANT"] = "tsan"
    env["LD_PRELOAD"] = rt
    env["JAX_PLATFORMS"] = "cpu"
    # report_mutex_bugs=0 is HOST-only: jaxlib/Eigen thread-pool
    # teardown (uninstrumented) false-positives "unlock of an unlocked
    # mutex" in the pytest process itself, and mutex-suppression
    # patterns cannot reach it (TSan matches report stacks, not the
    # heap-location stack that names Eigen).  ps.build.sanitizer_environ
    # STRIPS this flag for every spawned server, so the native side
    # keeps full mutex checking.
    env["TSAN_OPTIONS"] = (
        f"log_path={log_base} exitcode=66 report_mutex_bugs=0 "
        f"suppressions={_host_supp()}")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         *pytest_args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout)
    reports = [open(f).read() for f in glob.glob(log_base + ".*")]
    assert not reports, "TSan reports:\n" + "\n".join(reports)
    assert proc.returncode == 0, (
        f"suite under TSan rc={proc.returncode}\n"
        f"stdout tail:\n{proc.stdout[-4000:]}\n"
        f"stderr tail:\n{proc.stderr[-2000:]}")


@needs_toolchain
@pytest.mark.slow
def test_chaos_suite_under_tsan(tmp_path):
    """The chaos e2e suite — resets mid-op, delay windows, partitions,
    retry/reconnect storms — with BOTH native sides TSan-instrumented,
    zero unsuppressed reports (ISSUE 13 acceptance)."""
    _run_suite_under_tsan(
        tmp_path, ["tests/test_chaos.py", "-m", "not slow"], timeout=3000)


@needs_toolchain
@pytest.mark.slow
def test_elastic_suite_under_tsan(tmp_path):
    """The elastic e2e suite — kEpoch fences, live reshards, drains,
    process reuse — with both native sides TSan-instrumented, zero
    unsuppressed reports (ISSUE 13 acceptance)."""
    _run_suite_under_tsan(
        tmp_path, ["tests/test_elastic.py", "-m", "not slow"],
        timeout=3000)
