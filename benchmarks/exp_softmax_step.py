"""Experiment: a PS worker's softmax step at news20's width, by the
layout its resident shard is held in and the precision its two products
state.

``SoftmaxRegression.grad`` over ``float32[3968, 62061]`` rows and
``float32[62061, 20]`` weights is XLA's two products with a row softmax
between them.  Two things about it had never been read on a chip:

* **the layout.**  62,061 is no multiple of 128 and 3,968 is, so the
  device's default layout for the shard has the *rows* in the lanes.
  ``default`` times the step over the shard as ``device_put`` leaves it,
  ``row_major`` over the same rows padded to 62,080 columns (then the
  columns are in the lanes), the weights padded inside the step and the
  gradient cut back, which is what a relaid resident shard would cost.
* **the precision.**  ``default`` is what a float32 ``dot`` gets on the
  TPU when nothing is stated (one bfloat16 pass), ``high`` three passes,
  ``highest`` six, ``bfloat16`` the operands cast (``compute_dtype``'s
  product default).  Each gradient is held against numpy's float64 one.

Prints a line a variant (``ms`` a step over ``--steps`` runs, the
relative error of the gradient's norm and of the gradient) and, for the
``highest`` pair, the device operations of a traced run, which is where
a transposing copy would show.  Read on the v5e (PERF.md section 6,
PR 44): 2.66-2.71 ms in all eight variants (two fusions of 1.3 ms, each
one read of the shard; no copy in either layout; the passes hide under
the stream), ``default`` and ``bfloat16`` 2.1e-3 off the float64
gradient, ``high`` 1.1e-5, ``highest`` 3.2e-7.  Exits non-zero without a
TPU (``--smoke`` runs a tiny shape anywhere and says nothing about a
time).

Run on the chip: python benchmarks/exp_softmax_step.py
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import trace_reduce  # noqa: E402

PRECISIONS = {"default": None, "high": jax.lax.Precision.HIGH,
              "highest": jax.lax.Precision.HIGHEST}


def rows(seed, n, dim, classes, nnz):
    rng = np.random.default_rng(seed)
    X = np.zeros((n, dim), np.float32)
    cols = rng.integers(0, dim, (n, nnz))
    vals = rng.random((n, nnz), np.float32) + 0.1
    X[np.arange(n)[:, None], cols] = vals
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    y = rng.integers(0, classes, n).astype(np.int32)
    W = rng.standard_normal((dim, classes), np.float32) * 0.5
    return X, y, W


def float64_gradient(X, y, W):
    z = X.astype(np.float64) @ W.astype(np.float64)
    z -= z.max(axis=1, keepdims=True)
    p = np.exp(z)
    p /= p.sum(axis=1, keepdims=True)
    p[np.arange(len(y)), y] -= 1.0
    return X.astype(np.float64).T @ p / len(y)


def _grad(W, X, y, mask, compute_dtype, precision):
    """``SoftmaxRegression.grad``'s arithmetic with ``precision`` on both
    products (None: nothing stated, what the product stated before
    PR 44)."""
    cdt = jnp.dtype(compute_dtype)
    z = jnp.dot(X.astype(cdt), W.astype(cdt), precision=precision,
                preferred_element_type=jnp.float32)
    resid = (jax.nn.softmax(z) - jax.nn.one_hot(
        y, W.shape[1], dtype=jnp.float32)) * mask[:, None]
    n = jnp.maximum(jnp.sum(mask), 1).astype(jnp.float32)
    return jnp.dot(X.astype(cdt).T, resid.astype(cdt), precision=precision,
                   preferred_element_type=jnp.float32) / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=3968)
    ap.add_argument("--dim", type=int, default=62061)
    ap.add_argument("--classes", type=int, default=20)
    ap.add_argument("--nnz", type=int, default=80)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    if args.smoke:
        args.rows, args.dim, args.steps = 128, 1000, 2
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.smoke:
        raise SystemExit(f"the experiment reads a TPU; JAX found {dev.platform}")
    n, dim, K = args.rows, args.dim, args.classes
    padded = -(-dim // 128) * 128
    X, y, W = rows(args.seed, n, dim, K, args.nnz)
    want = float64_gradient(X, y, W)
    n_want = np.linalg.norm(want)
    held = {"default": jax.device_put(X, dev)}
    held["row_major"] = jax.block_until_ready(jax.jit(
        lambda a: jnp.pad(a, ((0, 0), (0, padded - dim))))(held["default"]))
    for name, a in held.items():
        print(f"EXP layout={name} shape={a.shape} "
              f"format={getattr(a, 'format', None)}", flush=True)
    yd, md, wd = (jax.device_put(a, dev)
                  for a in (y, np.ones(n, np.float32), W))
    for layout, Xd in held.items():
        for tag, precision, cdt in (("default", "default", "float32"),
                                    ("high", "high", "float32"),
                                    ("highest", "highest", "float32"),
                                    ("bfloat16", "default", "bfloat16")):
            stated = PRECISIONS[precision]

            def step(w, X, y, mask, cdt=cdt, stated=stated, to=Xd.shape[1]):
                if to != dim:
                    w = jnp.pad(w, ((0, to - dim), (0, 0)))
                return _grad(w, X, y, mask, cdt, stated)[:dim]

            fn = jax.jit(step)
            g = jax.block_until_ready(fn(wd, Xd, yd, md))
            t = time.perf_counter()
            for _ in range(args.steps):
                g = fn(wd, Xd, yd, md)
            jax.block_until_ready(g)
            ms = 1e3 * (time.perf_counter() - t) / args.steps
            got = np.asarray(g, np.float64)
            print(f"EXP step layout={layout} precision={tag} ms={ms:.3f} "
                  f"norm_rel_gap={abs(np.linalg.norm(got) - n_want) / n_want:.3g} "
                  f"diff_rel={np.linalg.norm(got - want) / n_want:.3g}",
                  flush=True)
            if tag == "highest" and not args.smoke:
                trace_dir = tempfile.mkdtemp(prefix="exp-softmax-")
                try:
                    with jax.profiler.trace(trace_dir):
                        for _ in range(5):
                            g = fn(wd, Xd, yd, md)
                        jax.block_until_ready(g)
                    xt = trace_reduce.load_xplane(
                        trace_reduce.find_xplane(trace_dir))
                finally:
                    shutil.rmtree(trace_dir, ignore_errors=True)
                for name, s in trace_reduce.top_ops(xt, n=6):
                    print(f"EXP   op layout={layout} ms_a_step={1e3 * s / 5:.3f} "
                          f"{name[:150]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
