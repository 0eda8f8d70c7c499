"""Experiment: row-blocked vs scalar gathers on the config-4 CTR step.

Row gathers amortize the gather unit's per-index cost over contiguous
elements.  The blocked CTR path
(data/hashing.hash_group_blocks + models.BlockedSparseLR) exploits that:
F fields grouped into ceil(F/R) blocks of R lanes -> ceil(F/R) row
gathers + scatter-adds per sample instead of F + F scalars.  This
measures the full train step (grad + SGD update, donated weights) for
the scalar layout and a sweep of block sizes (``--block-sizes 8,16,32``)
at config-4 scale (D=1M params, B=65536, 21 fields).  Bigger R = fewer
gathers but a steeper statistical trade (bench_configs.py config 4's
blocked_frontier).

Run on the chip: python benchmarks/exp_blocked.py [--block-sizes 8,16,32]
(it exits non-zero on any other platform).
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distlr_tpu.config import Config  # noqa: E402
from distlr_tpu.data.hashing import make_uniform_blocked_batch  # noqa: E402
from distlr_tpu.models import BlockedSparseLR, SparseBinaryLR  # noqa: E402
from distlr_tpu.utils.backend import start_benchmark  # noqa: E402

D, B, FIELDS, STEPS = 1_000_000, 65536, 21, 20
LR = 0.5


def timeit(name, step, w, batch, steps=STEPS):
    w1 = jax.block_until_ready(step(w, batch))
    t0 = time.perf_counter()
    for _ in range(steps):
        w1 = step(w1, batch)
    jax.block_until_ready(w1)
    dt = time.perf_counter() - t0
    assert np.isfinite(float(jnp.sum(w1)))
    rate = B * steps / dt
    print(f"{name:42s} {dt / steps * 1e3:8.2f} ms/step  {rate / 1e6:7.2f} M samples/s")
    return rate


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--block-sizes", default="8",
                    help="comma-separated R sweep, e.g. 8,16,32 (bigger R "
                    "= fewer gathers but more padded lanes AND a steeper "
                    "statistical trade: fewer, larger conjunction groups)")
    args = ap.parse_args(argv)
    try:
        r_values = [int(tok) for tok in args.block_sizes.split(",") if tok.strip()]
    except ValueError as e:
        raise SystemExit(f"--block-sizes must be comma-separated ints: {e}") from e
    if not r_values:
        raise SystemExit("--block-sizes is empty")
    bad = [r for r in r_values if r <= 0 or D % r]
    if bad:
        # the framework proper rejects non-divisible block sizes
        # (models/linear.py get_model) — don't silently bench a smaller
        # table than the model the framework would build
        raise SystemExit(f"--block-sizes must divide D={D}; bad: {bad}")
    start_benchmark("exp_blocked.py", full_size=True)

    print(f"backend={jax.default_backend()} D={D} B={B} fields={FIELDS}")
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.integers(0, 2, B), jnp.int32)
    mask = jnp.ones(B, jnp.float32)

    # --- scalar path (status quo): (B, 21) scalar gathers -------------
    cfg_s = Config(num_feature_dim=D, model="sparse_lr", l2_c=0.0)
    scalar = SparseBinaryLR(D)
    cols = jnp.asarray(rng.integers(0, D, size=(B, FIELDS)), jnp.int32)
    vals = jnp.ones((B, FIELDS), jnp.float32)

    @functools.partial(jax.jit, donate_argnums=0)
    def step_scalar(w, batch):
        g = scalar.grad(w, batch, cfg_s)
        return w - LR * g

    w0 = jnp.zeros(D, jnp.float32)
    r_scalar = timeit("scalar gathers (21 idx/sample)", step_scalar, w0,
                      (cols, vals, y, mask))

    for R in r_values:
        # --- blocked path: ceil(F/R) row gathers of R lanes/sample ----
        g_count = -(-FIELDS // R)
        nb = D // R
        cfg_b = Config(num_feature_dim=D, model="blocked_lr", block_size=R,
                       l2_c=0.0)
        blocked = BlockedSparseLR(nb, R)
        blocks_np, lane_vals_np = make_uniform_blocked_batch(rng, B, FIELDS, nb, R)
        blocks = jnp.asarray(blocks_np)
        lane_vals = jnp.asarray(lane_vals_np)

        @functools.partial(jax.jit, donate_argnums=0)
        def step_blocked(t, batch, blocked=blocked, cfg_b=cfg_b):
            g = blocked.grad(t, batch, cfg_b)
            return t - LR * g

        t0 = jnp.zeros((nb, R), jnp.float32)
        r_blocked = timeit(f"blocked rows ({g_count} idx/sample, R={R})",
                           step_blocked, t0, (blocks, lane_vals, y, mask))
        print(f"  R={R}: speedup {r_blocked / r_scalar:.2f}x vs scalar "
              f"(backend={jax.default_backend()})")


if __name__ == "__main__":
    main()
