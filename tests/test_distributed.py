"""Two-process ``jax.distributed`` smoke test (VERDICT r2 #4).

The multi-host claim in ``launch.py`` (``--coordinator`` /
``--num-processes`` / ``--process-id`` bootstrapping one global mesh) is
exercised as real code: two localhost CPU processes join one
coordinator, run a sync data-parallel training job over a 2-device
global mesh (one device per process), and must (a) both exit cleanly,
(b) export bitwise-identical weights (the replicated weight vector is
the same on every process — the collective path worked), and (c) match
a single-process 2-virtual-device run of the same job to float
tolerance (process boundaries change nothing about the math).

This is the JAX analogue of the reference's multi-node-without-a-cluster
trick (``examples/local.sh:22-33``, SURVEY.md §4): cluster shape faked
on one machine, full distributed code path for real.
"""

import os
import socket
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_sync_run_agrees(tmp_path):
    d = str(tmp_path / "data")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # children set their own device counts
    gen = subprocess.run(
        [sys.executable, "-m", "distlr_tpu.launch", "gen-data",
         "--data-dir", d, "--num-samples", "1200",
         "--num-feature-dim", "24", "--num-parts", "2", "--seed", "7"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert gen.returncode == 0, gen.stderr

    port = _free_port()
    common = [
        sys.executable, "-m", "distlr_tpu.launch", "sync",
        "--data-dir", d, "--num-feature-dim", "24", "--num-iteration", "5",
        "--batch-size", "-1", "--learning-rate", "0.5", "--l2-c", "0",
        "--test-interval", "5", "--coordinator", f"127.0.0.1:{port}",
        "--num-processes", "2", "--cpu-devices", "1",
    ]
    procs = [
        subprocess.Popen(common + ["--process-id", str(i)], cwd=REPO, env=env,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True)
        for i in range(2)
    ]
    try:
        outs = [p.communicate(timeout=240)[0] for p in procs]
    finally:
        # a crashed rank leaves its peer blocked in the collective —
        # never orphan children holding the coordinator port
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out

    from distlr_tpu.train.export import load_model_text

    w0 = load_model_text(os.path.join(d, "models", "part-001"))
    w1 = load_model_text(os.path.join(d, "models", "part-002"))
    # replicated weights: every process exports the identical vector
    np.testing.assert_array_equal(w0, w1)

    # oracle: the same job in ONE process over 2 virtual devices
    # (conftest already gives this process an 8-device CPU mesh)
    from distlr_tpu import Config
    from distlr_tpu.train import Trainer

    cfg = Config(data_dir=d, num_feature_dim=24, num_iteration=5,
                 batch_size=-1, learning_rate=0.5, l2_c=0.0,
                 test_interval=0, mesh_shape={"data": 2})
    w_ref = np.asarray(Trainer(cfg).load_data().fit())
    np.testing.assert_allclose(w0, w_ref, rtol=1e-5, atol=1e-6)


def _run_split_ps(tmp_path, gen, common_cfg, rank_groups, tag="split"):
    """Shared split-deployment orchestration: one ``launch ps-server``
    subprocess (HOSTS announced via its log file), one ``launch ps``
    subprocess per rank group, every process required to exit 0.  All
    subprocess stdout goes to FILES, not pipes — a pipe nobody drains
    can fill and deadlock the job, and a blocking readline on a wedged
    server would hang the test with no timeout.  Returns
    ``(data_dir, worker_log_paths)`` for the callers' own assertions."""
    import time

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    d_split = str(tmp_path / tag)
    gen(d_split)
    srv_log = tmp_path / f"{tag}-server.log"
    srv_err = tmp_path / f"{tag}-server.err"
    # stderr gets its OWN file: the native kv_server ranks inherit the
    # ps-server process's stderr, and their "[distlr_kv_server]
    # listening" diagnostics can interleave MID-LINE with the "HOSTS ..."
    # announcement when both share one file — observed corrupting the
    # parsed host list into a connect failure (flake).
    with open(srv_log, "w") as srv_out, open(srv_err, "w") as srv_e:
        server = subprocess.Popen(
            [sys.executable, "-m", "distlr_tpu.launch", "ps-server",
             "--data-dir", d_split] + common_cfg,
            cwd=REPO, env=env, stdout=srv_out, stderr=srv_e,
            text=True,
        )
    workers = []
    w_logs = [tmp_path / f"{tag}-worker{i}.log"
              for i in range(len(rank_groups))]
    try:
        hosts = None
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            txt = srv_log.read_text()
            found = [ln for ln in txt.splitlines() if ln.startswith("HOSTS ")]
            if found:
                hosts = found[0].split(" ", 1)[1].strip()
                break
            assert server.poll() is None, (
                f"ps-server died:\n{txt}\n{srv_err.read_text()}")
            time.sleep(0.1)
        assert hosts, "ps-server never announced HOSTS"
        for i, ranks in enumerate(rank_groups):
            with open(w_logs[i], "w") as w_out:
                workers.append(subprocess.Popen(
                    [sys.executable, "-m", "distlr_tpu.launch", "ps",
                     "--data-dir", d_split, "--hosts", hosts,
                     "--worker-ranks", ranks] + common_cfg,
                    cwd=REPO, env=env, stdout=w_out,
                    stderr=subprocess.STDOUT, text=True))
        for p in workers:
            p.wait(timeout=240)
        server.wait(timeout=60)
    finally:
        for p in workers + [server]:
            if p.poll() is None:
                p.kill()
                p.wait()
    for p, log in zip(workers, w_logs):
        assert p.returncode == 0, log.read_text()
    assert server.returncode == 0, (
        srv_log.read_text() + srv_err.read_text())
    return d_split, w_logs


def test_two_process_ps_run_agrees(tmp_path):
    """Two-process PS-over-DCN smoke (VERDICT r3 #7): the multi-host PS
    deployment story in examples/README.md executed as real code — a
    KV server group hosted by one subprocess (``launch ps-server``,
    0.0.0.0 bind), worker ranks split across TWO further subprocesses
    (``launch ps --hosts ... --worker-ranks``), every process exiting
    cleanly (rank 0's shutdown_servers retires the group), and the
    final weights matching a single-process ``launch ps`` run of the
    same job to float tolerance (process boundaries change nothing
    about sync BSP math beyond gradient-arrival addition order)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)

    def gen(d):
        r = subprocess.run(
            [sys.executable, "-m", "distlr_tpu.launch", "gen-data",
             "--data-dir", d, "--num-samples", "1200",
             "--num-feature-dim", "24", "--num-parts", "4", "--seed", "7"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr

    # --cpu-devices keeps every child off the accelerator: a chip
    # belongs to one process, and these are several
    common_cfg = ["--num-feature-dim", "24", "--num-iteration", "5",
                  "--batch-size", "-1", "--learning-rate", "0.5",
                  "--l2-c", "0", "--test-interval", "0",
                  "--num-workers", "4", "--num-servers", "2",
                  "--cpu-devices", "1"]

    # --- split deployment: 1 server host + 2 worker hosts ---
    d_split, _ = _run_split_ps(tmp_path, gen, common_cfg, ("0,1", "2,3"))

    # --- oracle: identical job, single process (servers + all 4 ranks) ---
    d_one = str(tmp_path / "one")
    gen(d_one)
    one = subprocess.run(
        [sys.executable, "-m", "distlr_tpu.launch", "ps",
         "--data-dir", d_one] + common_cfg,
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240,
    )
    assert one.returncode == 0, one.stdout + one.stderr

    from distlr_tpu.train.export import load_model_text

    for part in ("part-001", "part-002", "part-003", "part-004"):
        w_split = load_model_text(os.path.join(d_split, "models", part))
        w_one = load_model_text(os.path.join(d_one, "models", part))
        np.testing.assert_allclose(w_split, w_one, rtol=1e-5, atol=1e-6)


def test_two_process_ps_blocked_vpk_agrees(tmp_path):
    """Blocked family over real process boundaries: the keyed rows ride
    the vals_per_key wire encoding (one u64 row id per R-lane row)
    between separate worker processes and a separately-hosted server
    group, and the final weights must match a single-process run of the
    same sync job to float tolerance — the multi-host deployment story
    for the row-blocked CTR path (examples/README.md), now pinned
    across the encoding boundary."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)

    def gen(d):
        r = subprocess.run(
            [sys.executable, "-m", "distlr_tpu.launch", "gen-data",
             "--data-dir", d, "--num-samples", "2000",
             "--ctr-fields", "12", "--ctr-vocab", "6", "--ctr-raw",
             "--ctr-tuples", "64", "--num-parts", "2", "--seed", "11"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr

    # D=4096 over 2 servers -> boundary 2048, R=8-aligned: the workers
    # take the vals_per_key path (supports_vals_per_key(8) is True)
    common_cfg = ["--num-feature-dim", "4096", "--model", "blocked_lr",
                  "--block-size", "8", "--num-iteration", "4",
                  "--batch-size", "256", "--learning-rate", "0.5",
                  "--l2-c", "0", "--test-interval", "0",
                  "--num-workers", "2", "--num-servers", "2",
                  "--cpu-devices", "1"]

    d_split, w_logs = _run_split_ps(tmp_path, gen, common_cfg,
                                    ("0", "1"))
    # the encoding this test exists to pin: both workers must have
    # taken the vals_per_key path, not the expanded-key fallback
    for log in w_logs:
        assert "keyed wire encoding: vals_per_key=8" in log.read_text(), (
            log.read_text())

    d_one = str(tmp_path / "one")
    gen(d_one)
    one = subprocess.run(
        [sys.executable, "-m", "distlr_tpu.launch", "ps",
         "--data-dir", d_one] + common_cfg,
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240,
    )
    assert one.returncode == 0, one.stdout + one.stderr

    from distlr_tpu.train.export import load_model_text

    for part in ("part-001", "part-002"):
        w_split = load_model_text(os.path.join(d_split, "models", part))
        w_one = load_model_text(os.path.join(d_one, "models", part))
        np.testing.assert_allclose(w_split, w_one, rtol=1e-5, atol=1e-6)
