"""Experiment: a keyed ``sparse_lr`` worker's round of the device alone,
at the size of ``criteo-ps-async-keyed-1m`` (16,384 rows x 39 slots a
window over a million columns), beside numpy's step on the host.

    chiprun --chips 1 -- python benchmarks/exp_keyed_step.py

Rows come from ``chipbench.datagen`` (``--windows`` windows of them, seed
``--seed``); the worker is a real ``PSWorker`` with no server behind it
(``load_data`` alone: localisation and the one placement), so what is
timed is what ships.  Prints, a line each:

* ``localise``: ``host_math.localise`` against ``np.unique`` on one
  window, and the worker's own ``localise`` and ``shard_put`` spans;
* ``sort``: a window's entries ordered by place on the host (a sort of
  ``place | row | value`` in one uint64, an ``argsort`` and its two
  gathers; one thread and eight) and all the worker's windows on the
  device (``_keyed_sort_program``: the first call with its compile, then
  a run), which is what ``shard_put`` holds beside the copy;
* ``numpy``: ``host_math.sparse_batch_grad`` on a window, ms;
* ``program``: ``jit_ps_keyed_grad_step`` as the worker runs it (the
  kernel on a TPU) alone (operands resident, to ready); ``kernel``: the
  lookups' call at each block size of ``--blocks``, whole and its two
  sweeps apart (``forward``: the weights looked up, ``z`` summed by row;
  ``backward``: the residual looked up, the gradient summed by place),
  and with the L2 term's fourth part; ``xla``: the same jit with no plan
  over the same sorted leaves, and the parent's program over the
  row-major window (gather, product, row sum; segment sum), each whole
  and in halves, ms a run;
* ``link``: the padded weights in, to ready, and a gradient's readback;
* ``chain``: a round of the device as ``grad_step`` enqueues it, one
  worker and four threads at once over the one resident shard, ms a
  round a thread.

``--rehearse`` runs tiny sizes anywhere (no number of it is a device's).
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _ms(call, n):
    call()
    t = time.perf_counter()
    for _ in range(n):
        call()
    return 1e3 * (time.perf_counter() - t) / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--windows", type=int, default=8)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--sort-windows", type=int, default=240,
                    help="windows the device sorts at once: a worker's "
                    "shard in the cell")
    ap.add_argument("--blocks", default="32,64,128",
                    help="lines a grid step of the kernel, to try")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from chipbench import datagen
    from distlr_tpu.config import Config
    from distlr_tpu.data.iterator import SparseDataIter, Window
    from distlr_tpu.models import host_math
    from distlr_tpu.obs.tracing import get_tracer
    from distlr_tpu.train import ps_trainer
    from distlr_tpu.utils import backend

    backend.configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"exp_keyed_step measures the TPU, found {dev.platform}")
    D, B = (65536, 512) if args.rehearse else (1_000_000, 16384)
    print(f"EXP platform={dev.platform} kind={dev.device_kind} D={D} B={B} "
          f"windows={args.windows}", flush=True)
    cols, vals, y = datagen.make_rows(
        args.seed, "train", args.windows * B, fields="criteo-kaggle",
        num_buckets=D, label_scale=0.5, label_bias=-1.0)
    slots = cols.shape[1]

    c0 = cols[:B]
    t_sort = _ms(lambda: np.unique(c0, return_inverse=True), 3)
    t_table = _ms(lambda: host_math.localise(c0, D), 3)
    keys0, place0 = host_math.localise(c0, D)

    class _NoServers:
        def supports_vals_per_key(self, vpk):
            return True

        def hold(self, keys, vals_per_key=1):
            return keys

        def close(self):
            pass

    cfg = Config(data_dir="nowhere", num_feature_dim=D, model="sparse_lr",
                 num_workers=1, num_servers=1, sync_mode=False, batch_size=B,
                 learning_rate=0.2, l2_c=0.0, test_interval=0)
    real_kv, ps_trainer.KVWorker = ps_trainer.KVWorker, lambda *a, **k: _NoServers()
    try:
        w = ps_trainer.PSWorker(cfg, 0, "none",
                                train_iter=SparseDataIter(cols, vals, y, B),
                                test_iter=SparseDataIter(cols[:B], vals[:B], y[:B], -1))
    finally:
        ps_trainer.KVWorker = real_kv
    w.load_data()
    spans = get_tracer().breakdown()
    print(f"EXP localise one_window_ms sort={t_sort:.2f} table={t_table:.2f} "
          f"keys={len(keys0)} worker_localise_s={spans['localise']['seconds']:.3f} "
          f"shard_put_s={spans['shard_put']['seconds']:.3f} "
          f"resident_bytes={sum(a.nbytes for a in w._resident)} "
          f"key_count={w._keyed_key_count} "
          f"window_keys={[len(k) for k in w._window_keys]}", flush=True)
    if w._resident is None:
        raise SystemExit("the worker kept the host path")
    bits, n = w._keyed_row_bits, B * slots
    rowid = np.arange(B, dtype=np.int32)[:, None]
    pk0 = (place0 << bits | rowid).reshape(-1)   # as the device packs it
    v0 = np.ascontiguousarray(vals[:B]).reshape(-1)

    def sort_as_uint64():
        key = np.empty(n, np.uint64)
        halves = key.view(np.uint32).reshape(n, 2)   # little-endian
        halves[:, 0], halves[:, 1] = v0.view(np.uint32), pk0.view(np.uint32)
        key.sort()
        return (np.ascontiguousarray(halves[:, 1]).view(np.int32),
                np.ascontiguousarray(halves[:, 0]).view(np.float32))

    def sort_by_argsort():
        order = np.argsort(pk0)
        return pk0[order], v0[order]

    def threads_ms(call, threads, windows=48):
        from concurrent.futures import ThreadPoolExecutor
        t = time.perf_counter()
        with ThreadPoolExecutor(threads) as pool:
            list(pool.map(lambda _: call(), range(windows)))
        return 1e3 * (time.perf_counter() - t) / windows

    print(f"EXP sort host one_window_ms uint64={_ms(sort_as_uint64, 3):.2f} "
          f"argsort={_ms(sort_by_argsort, 3):.2f} a_window_of_eight_threads="
          f"{threads_ms(sort_as_uint64, 8):.2f}", flush=True)
    sort_windows = 3 if args.rehearse else args.sort_windows
    sort = ps_trainer._keyed_sort_program(sort_windows, B, slots, bits)
    entries = lambda: tuple(  # noqa: E731
        jax.device_put(a, dev) for a in (
            np.tile((place0 << bits).reshape(-1, 128), (sort_windows, 1)),
            np.tile(v0.reshape(-1, 128), (sort_windows, 1))))
    held = jax.block_until_ready(entries())
    t = time.perf_counter()
    jax.block_until_ready(sort(*held))
    first = time.perf_counter() - t
    held = jax.block_until_ready(entries())
    t = time.perf_counter()
    jax.block_until_ready(sort(*held))
    print(f"EXP sort device windows={sort_windows} first_s={first:.3f} "
          f"run_s={time.perf_counter() - t:.3f}", flush=True)
    del held

    rng = np.random.default_rng(3)
    w_u = (rng.standard_normal(len(keys0)) * 0.05).astype(np.float32)
    mask = np.ones(B, bool)
    t_numpy = _ms(lambda: host_math.sparse_batch_grad(
        w_u, place0, vals[:B], y[:B], mask, 0.0, False), 5)
    ref = host_math.sparse_batch_grad(w_u, place0, vals[:B], y[:B], mask,
                                      0.0, False)
    got = w.grad_step(w_u, Window(0, B))
    err = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    print(f"EXP numpy step_ms={t_numpy:.3f} device_against_numpy_rel={err:.3g}",
          flush=True)

    fn = ps_trainer._compiled_keyed_fns(0.0, False)
    padded = w._keyed_key_count
    held = np.zeros(padded, np.float32)
    held[:len(w_u)] = w_u
    wd = jax.device_put(held, dev)
    j = np.int32(1)
    plan, bases = w._keyed_plan(w._train_iter), w._keyed_bases
    shape = dict(rows=B, row_bits=bits, plan=plan)
    run = lambda: jax.block_until_ready(  # noqa: E731
        fn(wd, *w._resident, bases, j, **shape))
    ready = lambda f, *a: _ms(  # noqa: E731
        lambda: jax.block_until_ready(f(*a)), args.steps)
    print(f"EXP program {w._keyed_program} ms whole={_ms(run, args.steps):.3f}",
          flush=True)
    P, V, yd, md = w._resident
    lines = P.shape[0] // bases.shape[0]
    yw, mw = yd[B:2 * B], md[B:2 * B]
    if plan is not None or args.rehearse:
        from distlr_tpu.ops import pallas_keyed

        interpret = dev.platform != "tpu"
        for block in ([16] if interpret else map(int, args.blocks.split(","))):
            at = pallas_keyed.keyed_plan(B, lines, padded, bits,
                                         block_lines=block)
            if at is None:
                print(f"EXP kernel block_lines={block} no plan", flush=True)
                continue
            sums = {d: jax.jit(lambda w_, d=d, l2=False: pallas_keyed.keyed_sums(
                w_, P, V, bases, yw, mw, j, at, direction=d, l2=l2,
                interpret=interpret)[0]) for d in (None, "forward", "backward")}
            with_l2 = jax.jit(lambda w_: pallas_keyed.keyed_sums(
                w_, P, V, bases, yw, mw, j, at, l2=True, interpret=interpret))
            print(f"EXP kernel block_lines={block} ms "
                  f"whole={ready(sums[None], wd):.3f} "
                  f"forward={ready(sums['forward'], wd):.3f} "
                  f"backward={ready(sums['backward'], wd):.3f} "
                  f"with_l2={ready(with_l2, wd):.3f}", flush=True)
    no_plan = dict(shape, plan=None)
    g_xla = fn(wd, *w._resident, bases, np.int32(0), **no_plan)
    err = float(np.linalg.norm(np.asarray(g_xla)[:len(ref)] - ref)
                / np.linalg.norm(ref))
    mask_b = (1 << bits) - 1

    def window(a):
        return jax.lax.dynamic_slice_in_dim(a, j * lines, lines).reshape(-1)

    @jax.jit
    def sorted_forward(w_u, P, V):
        p, v = window(P), window(V)
        return jax.ops.segment_sum(
            w_u.at[p >> bits].get(mode="promise_in_bounds",
                                  indices_are_sorted=True) * v,
            p & mask_b, num_segments=B, mode="promise_in_bounds")

    @jax.jit
    def sorted_backward(resid, P, V):
        p, v = window(P), window(V)
        return jax.ops.segment_sum(
            resid.at[p & mask_b].get(mode="promise_in_bounds") * v, p >> bits,
            num_segments=padded, indices_are_sorted=True,
            mode="promise_in_bounds")

    resid = jax.device_put(rng.standard_normal(B).astype(np.float32), dev)
    print(f"EXP xla sorted_leaves against_numpy_rel={err:.3g} ms "
          f"whole={ready(lambda: fn(wd, *w._resident, bases, j, **no_plan)):.3f} "
          f"forward={ready(sorted_forward, wd, P, V):.3f} "
          f"backward={ready(sorted_backward, resid, P, V):.3f}", flush=True)

    # the parent's program: the window row-major, places in the rows' order
    rows_p = jax.device_put(np.tile(place0.reshape(-1, 128), (2, 1)), dev)
    rows_v = jax.device_put(np.tile(v0.reshape(-1, 128), (2, 1)), dev)
    row_lines = B * slots // 128

    def row_major(a):
        return (jax.lax.dynamic_slice_in_dim(a, j * row_lines, row_lines)
                .reshape(B, slots))

    @jax.jit
    def forward(w_u, places, vals):
        p, v = row_major(places), row_major(vals)
        return jnp.sum(w_u.at[p].get(mode="promise_in_bounds") * v, axis=-1)

    @jax.jit
    def scatter(resid, places, vals):
        p, v = row_major(places), row_major(vals)
        return jax.ops.segment_sum((resid[:, None] * v).reshape(-1),
                                   p.reshape(-1), num_segments=padded,
                                   mode="promise_in_bounds")

    print(f"EXP xla row_major (the parent's) ms "
          f"forward={ready(forward, wd, rows_p, rows_v):.3f} "
          f"scatter={ready(scatter, resid, rows_p, rows_v):.3f}", flush=True)
    g = run()
    print(f"EXP link ms w_put_to_ready="
          f"{_ms(lambda: jax.block_until_ready(jax.device_put(held, dev)), args.steps):.3f} "
          f"readback={_ms(lambda: np.asarray(fn(wd, *w._resident, bases, j, **shape)), args.steps):.3f} "
          f"(the program inside) bytes={g.nbytes}", flush=True)

    def chain(n, out, k):
        t = time.perf_counter()
        for i in range(n):
            w.grad_step(w_u, Window((i % args.windows) * B, B))
        out[k] = 1e3 * (time.perf_counter() - t) / n

    for threads in (1, 4):
        out = [0.0] * threads
        ts = [threading.Thread(target=chain, args=(args.steps, out, k))
              for k in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        print(f"EXP chain threads={threads} ms_a_round_a_thread="
              f"{[round(v, 3) for v in out]}", flush=True)
    stats = dev.memory_stats() or {}
    print(f"EXP memory peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
