"""Roofline experiment: on-device generation throughput ceiling.

The headline bench (bench.py) is HBM-bound: streaming a (B, 1M) bf16
feature matrix caps the step at ~139k samples/sec measured (two passes at
~557 GB/s effective) no matter how fast the math is.  SURVEY.md section 7(d) prescribes generating features on-device
for the north-star throughput config.  This experiment measures the
ceiling of that approach on the real chip:

  A. pallas hw-RNG generation alone (prng_random_bits -> discard-ish)
  B. generation + convert to f32 + multiply-by-w + row-reduce (the
     forward matvec shape)
  C. full fwd+bwd shape: phase-0 z accumulation, phase-1 regeneration +
     outer-product accumulate (what the real kernel must do)

Prints elements/sec for each; samples/sec = elem_rate / (2*D) for C.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BT = 256      # batch rows per tile
DT = 8192     # feature cols per tile
REPS = 64     # grid steps


def _time(fn, *args):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


# --- A: generation only -----------------------------------------------------
def _kern_gen(seed_ref, out_ref, acc_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    pltpu.prng_seed(seed_ref[0], t)
    bits = pltpu.prng_random_bits((BT, DT))
    # cheap use of the bits so generation isn't dead-code-eliminated
    acc_ref[:] += bits.astype(jnp.float32)[:, :128]

    @pl.when(t == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def bench_gen():
    f = pl.pallas_call(
        _kern_gen,
        grid=(REPS,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((BT, 128), lambda t: (0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((BT, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BT, 128), jnp.float32)],
    )
    g = jax.jit(lambda s: f(s))
    dt = _time(g, jnp.array([0], jnp.int32))
    return REPS * BT * DT / dt


# --- B: generation + fwd matvec shape --------------------------------------
def _kern_fwd(seed_ref, w_ref, out_ref, z_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        z_ref[:] = jnp.zeros_like(z_ref)

    pltpu.prng_seed(seed_ref[0], t)
    bits = pltpu.prng_random_bits((BT, DT))
    x = bits.astype(jnp.float32) * (2.0 ** -31) - 1.0  # ~U[-1,1)
    z_ref[:] += jnp.sum(x * w_ref[:], axis=1, keepdims=True)

    @pl.when(t == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = z_ref[:]


def bench_fwd():
    f = pl.pallas_call(
        _kern_fwd,
        grid=(REPS,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, DT), lambda t: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((BT, 1), lambda t: (0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((BT, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BT, 1), jnp.float32)],
    )
    g = jax.jit(lambda s, w: f(s, w))
    w = jnp.ones((1, DT), jnp.float32)
    dt = _time(g, jnp.array([0], jnp.int32), w)
    return REPS * BT * DT / dt


# --- C: full fwd + regen + bwd shape ---------------------------------------
def _kern_full(seed_ref, w_ref, y_ref, g_ref, z_ref):
    t = pl.program_id(0)
    p = pl.program_id(1)  # 0 = forward, 1 = backward

    @pl.when(jnp.logical_and(t == 0, p == 0))
    def _():
        z_ref[:] = jnp.zeros_like(z_ref)

    pltpu.prng_seed(seed_ref[0], t)  # same seed both phases -> same x
    bits = pltpu.prng_random_bits((BT, DT))
    x = bits.astype(jnp.float32) * (2.0 ** -31) - 1.0

    @pl.when(p == 0)
    def _fwd():
        z_ref[:] += jnp.sum(x * w_ref[:], axis=1, keepdims=True)

    @pl.when(p == 1)
    def _bwd():
        r = jax.nn.sigmoid(z_ref[:]) - y_ref[:]
        g_ref[:] = jnp.sum(x * r, axis=0, keepdims=True)


def bench_full():
    # grid (tiles, phase): phase inner so fwd of tile t happens, then bwd?
    # NO - bwd needs z complete over ALL feature tiles. Here REPS plays the
    # role of feature tiles for ONE batch tile, so grid must be (phase,
    # tiles): all fwd tiles first, then all bwd tiles.
    f = pl.pallas_call(
        _kern_full,
        grid=(2, REPS),  # leftmost slowest: p=0 all t, then p=1 all t
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, DT), lambda p, t: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((BT, 1), lambda p, t: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, DT), lambda p, t: (0, t), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, REPS * DT), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BT, 1), jnp.float32)],
    )

    def run(s, w, y):
        return f(s, w, y)

    g = jax.jit(run)
    w = jnp.ones((1, DT), jnp.float32)
    y = jnp.zeros((BT, 1), jnp.float32)
    dt = _time(g, jnp.array([0], jnp.int32), w, y)
    elems = 2 * REPS * BT * DT  # generated twice
    return elems / dt


def main():
    ra = bench_gen()
    print(f"A gen-only:        {ra/1e9:10.2f} G elem/s")
    rb = bench_fwd()
    print(f"B gen+fwd:         {rb/1e9:10.2f} G elem/s")
    rc = bench_full()
    # rc counts generated elems: each logical element is generated twice
    # (fwd + regenerated bwd), and one sample is D = REPS*DT logical elems.
    logical_rate = rc / 2
    print(f"C full fwd+bwd:    {rc/1e9:10.2f} G gen-elem/s")
    print(f"   implied samples/sec at D=1M: {logical_rate / 1_000_000:,.0f}")


if __name__ == "__main__":
    main()
