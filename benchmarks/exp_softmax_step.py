"""Experiment: a PS worker's softmax step at news20's width, by the
layout its resident shard is held in, the precision its two products
state, and the form its weights and gradient cross the host link in.

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

* **the form on the link** (PR 45).  The wire and the servers carry the
  weights and the gradient as one flat ``float32[D*K]`` vector; the
  device lays ``float32[62061, 20]`` out class-major in (8,128) tiles.
  ``link`` times a ``device_put`` to ready and a readback of the two
  shapes, from one thread and from four at once (the cell's four
  workers share one chip and one runtime).  ``step ... operands=``
  times the product's own step (``SoftmaxRegression.grad`` with the
  cell's L2 term, whose backward fusion reads the weights too), its
  forms interleaved over ``--rounds`` readings each: ``shaped`` is
  ``model.grad`` jitted with ``[D, K]`` operands (the program before
  PR 45), ``flat relayout=product`` is ``ps_trainer._compiled_fns``
  itself, the flat operand and result with the model's shape restored
  by two reshapes (XLA's own relayout: what ships, on every
  platform), and three forms that were tried against it and not
  built: the relayout written out through a ``[K, D]`` intermediate
  behind an optimization barrier on both sides (``transpose``), on
  the weights' side alone (``transpose_in``) and on the gradient's
  alone (``transpose_out``).  ``chain`` times a worker's round of the
  device alone, as ``PSWorker.grad_step`` enqueues it (put, program,
  readback, one wait), one worker and four at once.

* **the one-read kernel** (PR 46, ``ops/pallas_softmax.py``).
  ``kernel form=resident`` is its arithmetic alone: every panel's two
  products (six bfloat16 partial products each, X the stationary
  operand), the softmax and the parts, over ONE 128-row panel fetched
  once and held in VMEM, x 31, no HBM traffic; ``kernel form=fused`` is
  the kernel as it ships, the shard crossing HBM once; each by the
  column tiles of a block (``--block-tiles``), with its two float64
  errors (the resident form's against the float64 gradient of panel 0's
  rows under every panel's labels).  ``step operands=fused`` is
  ``ps_trainer._compiled_fns`` with the kernel's plan over the shard
  relaid row-major (what a worker runs since PR 46), interleaved with
  ``flat relayout=product`` over the default layout (what it ran
  before, and runs without a plan) over ``--rounds``.  ``--only-kernel``
  prints these alone.

Prints a line a variant (``ms`` a step over ``--steps`` runs, the
relative error of the gradient's norm and of the gradient) and, for the
``highest`` variants, the device operations of a traced run, which is
where a transposing copy or a relayout shows.  Read on the v5e (PERF.md
section 6, PR 44): 2.66-2.71 ms in all eight variants (two fusions of
1.3 ms, each one read of the shard; no copy in either layout; the passes hide under
the stream), ``default`` and ``bfloat16`` 2.1e-3 off the float64
gradient, ``high`` 1.1e-5, ``highest`` 3.2e-7.  PR 45's readings are in
PERF.md section 6 under that PR: the two reshapes cost the program
0.15 ms; ``transpose`` alone of the written-out forms reads under
``product`` (an emitter XLA picks for the backward fusion when both its
class-axis operand and result are typed ``[K, D]``, not a cheaper
relayout), by less than the cell's spread end to end, so the product
keeps the plain reshapes.  PR 46's (PERF.md section 6 under that PR):
``resident`` 1.544 ms and ``fused`` 1.546 ms at the plan's blocks of 27
tiles (the kernel is bound by its arithmetic, the one read hides under
it; blocks of 4, 8, 16 and 32 tiles read 2.25, 1.69, 1.55 and 1.59 ms
fused), 5.7e-9 and 3.1e-7 off the float64 gradient where XLA's
``highest`` step reads 1.4e-8 and 3.3e-7; the step that ships 1.708 ms
beside 2.890.  The first reading had the weights' parts split by a
conversion to bfloat16 and back under XLA, which XLA removes: 2.1e-4
off (``split3_xla`` is the cure).  Exits non-zero without a
TPU (``--smoke`` runs a tiny shape anywhere, the kernel interpreted, and
says nothing about a time).

Run on the chip: python benchmarks/exp_softmax_step.py
"""

from __future__ import annotations

import argparse
import functools
import os
import shutil
import sys
import tempfile
import threading
import time
import types

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import trace_reduce  # noqa: E402
from distlr_tpu.models.linear import SoftmaxRegression  # noqa: E402
from distlr_tpu.ops import pallas_softmax  # noqa: E402
from distlr_tpu.ops.pallas_lr import pad_columns  # noqa: E402
from distlr_tpu.train import ps_trainer  # noqa: E402

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


def _rel(got, want):
    return np.linalg.norm(got.astype(np.float64) - want) / np.linalg.norm(want)


def step_forms(model, l2_c=0.0, l2_scale_by_batch=False):
    """The product's step by the form of its operand 0 and its result:
    ``{name: (jitted step, whether it takes the weights flat)}``.
    ``product`` is ``ps_trainer._compiled_fns`` as the worker calls it;
    the others call the same ``model.grad`` with the same L2 term."""
    gcfg = types.SimpleNamespace(l2_c=l2_c, l2_scale_by_batch=l2_scale_by_batch)
    barrier = jax.lax.optimization_barrier

    def wrap(shape_in, flat_out):
        return jax.jit(lambda w, X, y, mask: flat_out(
            model.grad(shape_in(w), (X, y, mask), gcfg)))

    def in_plain(w):
        return w.reshape(model.param_shape)

    def in_written(w):
        return barrier(w.reshape(model.param_shape).T).T

    def out_plain(g):
        return g.reshape(-1)

    def out_written(g):
        return barrier(g.T).T.reshape(-1)

    return {
        "shaped": (wrap(lambda w: w, lambda g: g), False),
        "product": (ps_trainer._compiled_fns(
            model, l2_c, l2_scale_by_batch), True),
        "transpose": (wrap(in_written, out_written), True),
        "transpose_in": (wrap(in_written, out_plain), True),
        "transpose_out": (wrap(in_plain, out_written), True),
    }


def interleaved_ms(calls, steps, rounds):
    """``{name: [ms a step, a reading a round]}``: every round times each
    of ``calls`` (``name -> thunk that dispatches one step``) over
    ``steps`` dispatches waited for once, in an order that turns round
    with the round, so that a drift of the chip's falls on all alike."""
    for call in calls.values():
        jax.block_until_ready(call())
    got = {name: [] for name in calls}
    for r in range(rounds):
        for name in (list(calls) if r % 2 == 0 else list(calls)[::-1]):
            t = time.perf_counter()
            for _ in range(steps):
                g = calls[name]()
            jax.block_until_ready(g)
            got[name].append(1e3 * (time.perf_counter() - t) / steps)
    return got


def _timed(fn, steps):
    """Mean and least ms of ``fn()`` over ``steps`` calls, each waited
    for."""
    took = []
    for _ in range(steps):
        t = time.perf_counter()
        fn()
        took.append(1e3 * (time.perf_counter() - t))
    return float(np.mean(took)), float(np.min(took))


def _step_ms(fn, operands, steps):
    """``(ms a step, the last result)`` over ``steps`` dispatches waited
    for once: the device's own pace, no host between two steps."""
    g = jax.block_until_ready(fn(*operands))
    t = time.perf_counter()
    for _ in range(steps):
        g = fn(*operands)
    jax.block_until_ready(g)
    return 1e3 * (time.perf_counter() - t) / steps, g


def _at_once(fn, threads):
    """``fn()`` on ``threads`` threads at once, all joined."""
    workers = [threading.Thread(target=fn) for _ in range(threads)]
    for t in workers:
        t.start()
    for t in workers:
        t.join()


def link_times(host, dev, steps, threads=1):
    """``device_put`` to ready, and a readback, of ``host``'s shape: ms,
    mean and least, over ``threads`` threads at once (four workers share
    the cell's one chip and one runtime).  Every readback is of a fresh
    device array (an Array keeps the host copy it once made)."""
    bump = jax.jit(lambda a: a + 1.0)
    puts, backs = [], []

    def one():
        d = jax.device_put(host, dev)
        for _ in range(steps):
            t = time.perf_counter()
            jax.block_until_ready(jax.device_put(host, dev))
            puts.append(1e3 * (time.perf_counter() - t))
        for _ in range(steps):
            d = jax.block_until_ready(bump(d))
            t = time.perf_counter()
            np.asarray(d)
            backs.append(1e3 * (time.perf_counter() - t))

    _at_once(one, threads)
    return ((float(np.mean(puts)), float(np.min(puts))),
            (float(np.mean(backs)), float(np.min(backs))))


def chain_ms(fn, w_host, rest, dev, steps, threads=1):
    """A worker's device chain as ``PSWorker.grad_step`` enqueues it: the
    weights put, the program behind them, the readback behind the
    program, one wait; mean and least ms a round, over ``threads``
    workers at once on the one chip (with four, the cell's rounds less
    their exchange)."""
    def once():
        w = jax.device_put(w_host, dev)
        g = fn(w, *rest)
        g.copy_to_host_async()
        jax.block_until_ready(g)
        return np.asarray(g)
    once()
    took = []
    _at_once(lambda: took.append(_timed(once, steps)), threads)
    return (float(np.mean([m for m, _ in took])),
            float(np.min([least for _, least in took])))


def kernel_forms(X, y, W, want, dev, args, interpret):
    """The one-read kernel (``ops/pallas_softmax.py``, PR 46) at the
    shape: ``resident`` is its arithmetic alone (every panel's two
    products, softmax and parts over panel 0's bytes, fetched once: no
    HBM traffic), ``fused`` the kernel as it ships, the shard crossing
    HBM once; each by the columns of a block.  Then the step that ships
    (``_compiled_fns`` with the plan, flat in and out, the relayouts and
    the parts of the weights XLA's) beside ``flat relayout=product``
    over the default layout, interleaved."""
    n, dim = X.shape
    K = W.shape[1]
    n_want = np.linalg.norm(want)
    ones = np.ones(n, np.float32)
    yd, md, wd = (jax.device_put(a, dev) for a in (y, ones, W))
    # the resident form's own float64 gradient: panel 0's rows under
    # every panel's labels
    rows0 = pallas_softmax.PANEL_ROWS
    want0 = float64_gradient(np.tile(X[:rows0], (n // rows0, 1)), y, W)
    Xd = jax.device_put(X, dev)
    for tiles in args.block_tiles:
        plan = pallas_softmax.softmax_panel_plan(n, dim, K, block_tiles=tiles)
        if plan is None:
            print(f"EXP kernel block_tiles={tiles} no plan", flush=True)
            continue
        Xp = jax.block_until_ready(jax.jit(
            lambda a, plan=plan: pad_columns(a, plan))(Xd))
        for form, ref in (("resident", want0), ("fused", want)):
            fn = jax.jit(functools.partial(
                pallas_softmax.softmax_grad_panels, plan=plan,
                interpret=interpret, resident=form == "resident"))
            ms, g = _step_ms(fn, (wd, Xp, yd, md), args.steps)
            got = np.asarray(g, np.float64) / n
            n_ref = np.linalg.norm(ref)
            print(f"EXP kernel form={form} block_cols={plan.block_cols} "
                  f"blocks={plan.blocks} vmem_mib={plan.vmem_bytes / 2**20:.1f} "
                  f"ms={ms:.4f} "
                  f"norm_rel_gap={abs(np.linalg.norm(got) - n_ref) / n_ref:.3g} "
                  f"diff_rel={np.linalg.norm(got - ref) / n_ref:.3g}",
                  flush=True)
            if not args.smoke and tiles == args.block_tiles[0]:
                traced_ops(fn, (wd, Xp, yd, md), "row_major", f"kernel={form}")
        del Xp
    # the step that ships, beside the one it replaces
    model = SoftmaxRegression(dim, K, compute_dtype="float32")
    step = ps_trainer._compiled_fns(model, 0.0, False)
    plan = pallas_softmax.softmax_panel_plan(n, dim, K)
    flat = jax.device_put(np.ascontiguousarray(W.reshape(-1)), dev)
    Xp = jax.block_until_ready(jax.jit(
        lambda a: pad_columns(a, plan))(Xd))
    calls = {
        "flat relayout=product": lambda: step(flat, Xd, yd, md),
        "fused": lambda: step(flat, Xp, yd, md, panels=plan,
                              interpret=interpret),
    }
    took = interleaved_ms(calls, 3 * args.steps, args.rounds)
    for name, call in calls.items():
        got = np.asarray(call(), np.float64).reshape(dim, K)
        print(f"EXP step operands={name} ms={np.median(took[name]):.4f} "
              f"readings={[round(v, 4) for v in took[name]]} "
              f"norm_rel_gap={abs(np.linalg.norm(got) - n_want) / n_want:.3g} "
              f"diff_rel={np.linalg.norm(got - want) / n_want:.3g}", flush=True)
    if not args.smoke:
        traced_ops(calls["fused"], (), "row_major", "operands=fused")


def traced_ops(fn, operands, layout, tag, runs=5):
    trace_dir = tempfile.mkdtemp(prefix="exp-softmax-")
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(runs):
                g = fn(*operands)
            jax.block_until_ready(g)
        xt = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    for name, s in trace_reduce.top_ops(xt, n=8):
        print(f"EXP   op layout={layout} {tag} ms_a_step={1e3 * s / runs:.4f} "
              f"{name[:150]}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=3968)
    ap.add_argument("--dim", type=int, default=62061)
    ap.add_argument("--classes", type=int, default=20)
    ap.add_argument("--nnz", type=int, default=80)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=6,
                    help="interleaved readings of each form of the step, "
                         "3 x --steps dispatches a reading")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--block-tiles", default=[None],
                    type=lambda s: [int(v) or None for v in s.split(",")],
                    help="128-column tiles of the kernel's block, a reading "
                         "of both forms each (0: the plan's own choice)")
    ap.add_argument("--only-kernel", action="store_true",
                    help="the one-read kernel's readings alone")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    if args.smoke:
        args.rows, args.dim, args.steps, args.rounds = 256, 1000, 2, 2
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.smoke:
        raise SystemExit(f"the experiment reads a TPU; JAX found {dev.platform}")
    n, dim, K = args.rows, args.dim, args.classes
    X, y, W = rows(args.seed, n, dim, K, args.nnz)
    want = float64_gradient(X, y, W)
    n_want = np.linalg.norm(want)
    if not args.only_kernel:
        xla_forms(X, y, W, want, dev, args)
    kernel_forms(X, y, W, want, dev, args, interpret=dev.platform != "tpu")
    return 0


def xla_forms(X, y, W, want, dev, args):
    """XLA's step by layout and precision, the link, and the product's
    step by the form of its operands (PRs 44 and 45)."""
    n, dim = X.shape
    K = W.shape[1]
    padded = -(-dim // 128) * 128
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
            ms, g = _step_ms(fn, (wd, Xd, yd, md), args.steps)
            got = np.asarray(g, np.float64)
            print(f"EXP step layout={layout} precision={tag} ms={ms:.3f} "
                  f"norm_rel_gap={abs(np.linalg.norm(got) - n_want) / n_want:.3g} "
                  f"diff_rel={np.linalg.norm(got - want) / n_want:.3g}",
                  flush=True)
            if tag == "highest" and not args.smoke:
                traced_ops(fn, (wd, Xd, yd, md), layout, "operands=shaped")
    # -- the form on the host link (PR 45) --------------------------------
    flat = np.ascontiguousarray(W.reshape(-1))
    for threads in (1, 4):
        for host in (W, flat):
            put, back = link_times(host, dev, args.steps, threads)
            print(f"EXP link shape=f32{list(host.shape)} threads={threads} "
                  f"put_ms={put[0]:.3f} put_min_ms={put[1]:.3f} "
                  f"readback_ms={back[0]:.3f} readback_min_ms={back[1]:.3f}",
                  flush=True)
    rest = (held["default"], yd, md)
    forms = step_forms(SoftmaxRegression(dim, K, compute_dtype="float32"))
    on_dev = {name: jax.device_put(flat if takes_flat else W, dev)
              for name, (_, takes_flat) in forms.items()}
    took = interleaved_ms(
        {name: (lambda fn=fn, w=on_dev[name]: fn(w, *rest))
         for name, (fn, _) in forms.items()}, 3 * args.steps, args.rounds)
    base = np.asarray(forms["shaped"][0](on_dev["shaped"], *rest)).reshape(-1)
    for name, (fn, takes_flat) in forms.items():
        operands = (f"flat relayout={name}" if takes_flat else "shaped")
        got = np.asarray(fn(on_dev[name], *rest))
        print(f"EXP step layout=default precision=highest "
              f"operands={operands} ms={np.median(took[name]):.4f} "
              f"readings={[round(v, 4) for v in took[name]]} "
              f"shape={list(got.shape)} "
              f"bits_as_shaped={bool(np.array_equal(got.reshape(-1), base))} "
              f"diff_rel={_rel(got.reshape(dim, K), want):.3g}", flush=True)
        for threads in (1, 4):
            ms, least = chain_ms(fn, flat if takes_flat else W, rest, dev,
                                 args.steps, threads)
            print(f"EXP chain operands={operands} threads={threads} "
                  f"ms={ms:.3f} min_ms={least:.3f}", flush=True)
        if not args.smoke:
            traced_ops(fn, (on_dev[name], *rest), "default",
                       f"operands={operands.replace(' ', '_')}")


if __name__ == "__main__":
    sys.exit(main())
