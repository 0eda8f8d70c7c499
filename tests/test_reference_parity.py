"""Epochs-to-accuracy parity against an INDEPENDENT implementation of the
reference training protocol (VERDICT r1 #2).

``tests/oracle/reference_oracle.cc`` reimplements the reference job —
Q2 ``srand(0)`` init (src/lr.cc:92-98), Q4 L2/B gradient (src/lr.cc:40),
Q5 wraparound batches (data_iter.h:44-56), Q1 last-gradient sync merge
(src/main.cc:66-75, deterministically refined to "highest rank wins"),
async immediate-apply (src/main.cc:80-84) — in plain C++ sharing no code
with the framework.  These tests run ``compat_mode="reference"`` on the
same shards and assert the accuracy trajectory matches epoch by epoch:
tight for sync (deterministic BSP), band for async (Hogwild).  Any quirk
gate regressing (Q1/Q2/Q4/Q5) shifts the trajectory and fails here.
"""

import os
import shutil
import subprocess

import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.data.synthetic import write_synthetic_shards
from distlr_tpu.train.ps_trainer import run_ps_local

ORACLE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "oracle")


def _runs_here(path: str) -> bool:
    """Whether the binary executes on THIS machine — a committed artifact
    built against a newer glibc exists but dies at loader time, which
    `make`'s timestamp check cannot see."""
    try:
        probe = subprocess.run([path], capture_output=True, text=True)
    except OSError:
        return False
    # no args -> usage error is fine; a loader error (GLIBC_x not found)
    # surfaces as a non-zero exit with the message on stderr
    return "GLIBC" not in probe.stderr and "not found" not in probe.stderr


@pytest.fixture(scope="module")
def oracle_bin():
    return build_oracle()


def build_oracle() -> str:
    """The oracle's binary, built here; skips the caller where it cannot
    be built or does not run."""
    path = os.path.join(ORACLE_DIR, "reference_oracle")
    make_args = ["make", "-C", ORACLE_DIR, "reference_oracle"]
    r = subprocess.run(make_args, capture_output=True, text=True)
    if r.returncode == 0 and os.path.exists(path) and not _runs_here(path):
        # stale foreign-toolchain artifact: force a local rebuild
        r = subprocess.run(make_args + ["-B"], capture_output=True, text=True)
    if r.returncode != 0 or not os.path.exists(path):
        pytest.skip(f"cannot build reference_oracle: {r.stderr[-400:]}")
    if not _runs_here(path):
        pytest.skip("reference_oracle does not execute on this machine")
    return path


@pytest.fixture(scope="module")
def parity_data(tmp_path_factory):
    """1000 samples, D=24, dense; 2 train parts (500/2 - test split) so
    the same directory serves W=1 (part-001 only) and W=2 runs; shard
    sizes are NOT batch-aligned, so Q5 wraparound is exercised."""
    d = str(tmp_path_factory.mktemp("refparity") / "data")
    write_synthetic_shards(d, 1000, 24, num_parts=2, seed=3, sparsity=0.0)
    return d


def run_oracle(oracle_bin, data_dir, **kw):
    args = [oracle_bin, f"--data_dir={data_dir}"]
    args += [f"--{k}={v}" for k, v in kw.items()]
    out = subprocess.run(args, capture_output=True, text=True, check=True).stdout
    traj, weights = {}, None
    for line in out.splitlines():
        tok = line.split()
        if tok and tok[0] == "TRAJ":
            traj[int(tok[1])] = float(tok[2])
        elif tok and tok[0] == "WEIGHTS":
            weights = np.array([float(v) for v in tok[1:]], dtype=np.float32)
    assert traj and weights is not None, f"oracle output unparseable: {out[:400]}"
    return traj, weights


def run_framework(cfg):
    traj = {}
    res = run_ps_local(cfg, eval_fn=lambda e, a: traj.__setitem__(e, a), save=False)
    return traj, res[0]


BASE = dict(num_feature_dim=24, compat_mode="reference", learning_rate=0.1,
            l2_c=1.0, num_iteration=20, test_interval=5, num_servers=2)


class TestSyncTrajectoryParity:
    def test_one_worker_matches_oracle(self, oracle_bin, parity_data):
        """W=1 sync: exercises Q2 (srand(0) init), Q4 (L2/B), Q5 (wrap).
        The whole trajectory is deterministic, so tolerance is one
        boundary-sample flip of accuracy and float32 drift on weights."""
        traj_o, w_o = run_oracle(oracle_bin, parity_data, dim=24, workers=1,
                                 iters=20, batch=128, test_interval=5,
                                 lr=0.1, C=1, sync=1, seed=0)
        cfg = Config(data_dir=parity_data, sync_mode=True, num_workers=1,
                     batch_size=128, **BASE)
        traj_f, w_f = run_framework(cfg)
        assert traj_f.keys() == traj_o.keys()
        for e in traj_o:
            assert abs(traj_f[e] - traj_o[e]) <= 0.01, (e, traj_f[e], traj_o[e])
        np.testing.assert_allclose(w_f, w_o, atol=3e-3)

    def test_two_workers_match_oracle_q1(self, oracle_bin, parity_data):
        """W=2 sync: additionally exercises Q1 — only the highest-rank
        worker's gradient is applied, /W.  A regression to the correct
        mean update trains on BOTH shards and shifts the trajectory."""
        traj_o, w_o = run_oracle(oracle_bin, parity_data, dim=24, workers=2,
                                 iters=20, batch=64, test_interval=5,
                                 lr=0.1, C=1, sync=1, seed=0)
        cfg = Config(data_dir=parity_data, sync_mode=True, num_workers=2,
                     batch_size=64, **BASE)
        traj_f, w_f = run_framework(cfg)
        assert traj_f.keys() == traj_o.keys()
        for e in traj_o:
            assert abs(traj_f[e] - traj_o[e]) <= 0.01, (e, traj_f[e], traj_o[e])
        np.testing.assert_allclose(w_f, w_o, atol=3e-3)

    def test_correct_mode_diverges_from_quirk_oracle(self, oracle_bin, parity_data):
        """Sanity on the oracle's teeth: compat_mode='correct' (mean
        update, no L2/B, PRNG init) must NOT reproduce the quirk
        trajectory's weights — otherwise these tests could never catch a
        quirk-gate regression."""
        _, w_o = run_oracle(oracle_bin, parity_data, dim=24, workers=2,
                            iters=20, batch=64, test_interval=5,
                            lr=0.1, C=1, sync=1, seed=0)
        cfg = Config(data_dir=parity_data, sync_mode=True, num_workers=2,
                     batch_size=64, **{**BASE, "compat_mode": "correct"})
        _, w_f = run_framework(cfg)
        assert np.abs(w_f - w_o).max() > 0.01


class TestAsyncTrajectoryBand:
    def test_async_two_workers_within_band(self, oracle_bin, parity_data):
        """Async (Hogwild) is nondeterministic; the oracle serializes
        workers round-robin.  Ours must track that trajectory within an
        accuracy band at every test point."""
        traj_o, _ = run_oracle(oracle_bin, parity_data, dim=24, workers=2,
                               iters=20, batch=64, test_interval=5,
                               lr=0.1, C=1, sync=0, seed=0)
        cfg = Config(data_dir=parity_data, sync_mode=False, num_workers=2,
                     batch_size=64, **{**BASE, "sync_last_gradient": False})
        traj_f, _ = run_framework(cfg)
        assert traj_f.keys() == traj_o.keys()
        for e in traj_o:
            assert abs(traj_f[e] - traj_o[e]) <= 0.06, (e, traj_f[e], traj_o[e])
        # and it actually learned
        assert traj_f[max(traj_f)] >= 0.7
