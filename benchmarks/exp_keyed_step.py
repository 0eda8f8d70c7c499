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
* ``numpy``: ``host_math.sparse_batch_grad`` on a window, ms;
* ``program``: ``jit_ps_keyed_grad_step`` alone (operands resident, to
  ready), and its two halves jitted apart (``forward``: gather, product,
  row sum; ``scatter``: the segment sum), ms a run;
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
    run = lambda: jax.block_until_ready(  # noqa: E731
        fn(wd, *w._resident, j, rows=B, slots=slots))
    lines = -(-B * slots // 128)

    @jax.jit
    def forward(w_u, places, vals, j):
        p, v = (jax.lax.dynamic_slice_in_dim(a, j * lines, lines)
                .reshape(-1)[:B * slots].reshape(B, slots)
                for a in (places, vals))
        return jnp.sum(w_u.at[p].get(mode="promise_in_bounds") * v, axis=-1)

    @jax.jit
    def scatter(resid, places, vals, j):
        p, v = (jax.lax.dynamic_slice_in_dim(a, j * lines, lines)
                .reshape(-1)[:B * slots].reshape(B, slots)
                for a in (places, vals))
        return jax.ops.segment_sum((resid[:, None] * v).reshape(-1),
                                   p.reshape(-1), num_segments=padded,
                                   mode="promise_in_bounds")

    P, V = w._resident[:2]
    resid = jax.device_put(rng.standard_normal(B).astype(np.float32), dev)
    print(f"EXP program ms whole={_ms(run, args.steps):.3f} "
          f"forward={_ms(lambda: jax.block_until_ready(forward(wd, P, V, j)), args.steps):.3f} "
          f"scatter={_ms(lambda: jax.block_until_ready(scatter(resid, P, V, j)), args.steps):.3f}",
          flush=True)
    g = run()
    print(f"EXP link ms w_put_to_ready="
          f"{_ms(lambda: jax.block_until_ready(jax.device_put(held, dev)), args.steps):.3f} "
          f"readback={_ms(lambda: np.asarray(fn(wd, *w._resident, j, rows=B, slots=slots)), args.steps):.3f} "
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
