"""Experiment: end-to-end HOST->DEVICE streaming throughput vs the
device-resident rate (config-4 scale).

Every TPU rate in ROOFLINE.md is measured on device-resident batches;
the reference's ``DataIter`` role instead streams shards from host
memory to the compute every epoch (``include/data_iter.h:16-35``).
This measures that full path through ``Trainer.fit`` — host slice +
``device_put`` + step — for the blocked CTR model at config-4 shape
(D=1M, B=65536, 21 fields), with the double-buffered prefetch
(``cfg.prefetch``) on and off, against the device-resident step rate on
identical shapes (VERDICT r3 item 3: done = e2e within ~20% of
device-resident).

Host bytes/sample (R=8): 3x4 B blocks + 3x8x4 B lane_vals + label+mask
~ 116 B -> streaming 12.5M samples/s needs ~1.5 GB/s of H2D, which is
why overlap (not bandwidth) is the thing to measure.

Run on the real chip: python benchmarks/exp_stream.py [--block-sizes 8,32]
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
from distlr_tpu.models import BlockedSparseLR  # noqa: E402
from distlr_tpu.train.trainer import GlobalShardedData, Trainer  # noqa: E402
from distlr_tpu.utils.backend import start_benchmark  # noqa: E402

D, B, FIELDS = 1_000_000, 65536, 21
N_BATCHES = 8          # host dataset = 8 steps/epoch
TIMED_EPOCHS = 3
LR = 0.5


def make_host_batch(seed: int, n: int, R: int):
    """The one batch recipe every measurement here shares: (blocks,
    lane_vals, labels, mask) as host numpy arrays.  Keeping a single
    builder guarantees the h2d ceiling's bytes/sample is exactly the
    e2e path's bytes/sample."""
    nb = D // R
    rng = np.random.default_rng(seed)
    blocks, lane_vals = make_uniform_blocked_batch(rng, n, FIELDS, nb, R)
    y = rng.integers(0, 2, n).astype(np.int32)
    mask = np.ones(n, np.float32)
    return blocks, lane_vals, y, mask


def device_resident_rate(R: int, steps: int = 20) -> float:
    """The ROOFLINE-style rate: same step, batch already in HBM."""
    nb = D // R
    cfg = Config(num_feature_dim=D, model="blocked_lr", block_size=R, l2_c=0.0)
    model = BlockedSparseLR(nb, R)
    batch = tuple(jnp.asarray(a) for a in make_host_batch(0, B, R))

    @functools.partial(jax.jit, donate_argnums=0)
    def step(t, batch):
        return t - LR * model.grad(t, batch, cfg)

    t = step(jnp.zeros((nb, R), jnp.float32), batch)
    assert np.isfinite(float(jnp.sum(t)))
    t0 = time.perf_counter()
    for _ in range(steps):
        t = step(t, batch)
    assert np.isfinite(float(jnp.sum(t)))
    return B * steps / (time.perf_counter() - t0)


def h2d_ceiling(R: int, reps: int = 12) -> tuple[float, float]:
    """Raw host->device transfer ceiling for exactly one batch's arrays:
    (samples/s if H2D were the only cost, effective GB/s).  Anything the
    e2e path loses beyond this is framework overhead; the gap between
    this and the device-resident rate is the platform's H2D link."""
    arrs = make_host_batch(2, B, R)
    nbytes = sum(a.nbytes for a in arrs)
    dev = jax.devices()[0]
    jax.block_until_ready(jax.device_put(arrs, dev))  # warm the path
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(jax.device_put(arrs, dev))
    dt = time.perf_counter() - t0
    return B * reps / dt, nbytes * reps / dt / 1e9


def streaming_rate(R: int, prefetch: int, data) -> float:
    """Full Trainer.fit path from host-resident shards.  ``data`` is the
    (blocks, lane_vals, y) triple, built once per R by the caller (the
    warmup epoch already costs seconds; don't also rebuild 50 MB of
    identical host arrays per depth)."""
    blocks, lane_vals, y = data
    n = len(y)
    cfg = Config(
        num_feature_dim=D, model="blocked_lr", block_size=R, l2_c=0.0,
        learning_rate=LR, batch_size=B, test_interval=0,
        num_iteration=TIMED_EPOCHS, prefetch=prefetch,
    )
    tr = Trainer(cfg)
    tr._train_data = GlobalShardedData([(blocks, lane_vals, y)])
    tr._test_data = None
    tr.fit(epochs=1)           # compile warmup
    tr.weights = None          # fresh weights; keeps runs comparable
    t0 = time.perf_counter()
    w = tr.fit(epochs=TIMED_EPOCHS)
    jax.block_until_ready(w)
    assert np.isfinite(float(jnp.sum(w)))
    dt = time.perf_counter() - t0
    return n * TIMED_EPOCHS / dt


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--block-sizes", default="8,32")
    ap.add_argument("--prefetch", default="1,2,4",
                    help="comma-separated prefetch depths to measure "
                         "(1 = serial, no overlap)")
    args = ap.parse_args(argv)
    r_values = [int(tok) for tok in args.block_sizes.split(",") if tok.strip()]
    depths = [int(tok) for tok in args.prefetch.split(",") if tok.strip()]
    start_benchmark("exp_stream.py", full_size=True)

    print(f"backend={jax.default_backend()} D={D} B={B} fields={FIELDS} "
          f"host_batches={N_BATCHES} epochs={TIMED_EPOCHS}")
    for R in r_values:
        resident = device_resident_rate(R)
        ceil_rate, ceil_gbs = h2d_ceiling(R)
        blocks, lane_vals, y, _ = make_host_batch(1, B * N_BATCHES, R)
        data = (blocks, lane_vals, y)
        cols = "   ".join(
            f"e2e pf={pf_depth} {rate/1e6:5.2f} M/s "
            f"({rate/resident:5.1%} resident, {rate/ceil_rate:.0%} h2d)"
            for pf_depth in depths
            for rate in (streaming_rate(R, pf_depth, data),)
        )
        print(f"R={R:3d}  device-resident {resident/1e6:7.2f} M/s   "
              f"h2d-ceiling {ceil_rate/1e6:7.2f} M/s ({ceil_gbs:.3f} GB/s)   "
              + cols)


if __name__ == "__main__":
    main()
