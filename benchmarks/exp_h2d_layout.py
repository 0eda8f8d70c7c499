"""Experiment: what the runtime does with one dense batch, handed over
three ways.

The dense sync step takes a ``bf16[768, 1000000]`` batch (1.5 GB).  The
host holds it row-major; the chip's default layout for that shape is
not, so the runtime relays the whole array on its own threads
(``XlaLinearize`` -> ``Transpose::ExecuteChunk``) before the DMA
(``TransferToDevice``) starts.  This times a bare ``device_put`` +
``block_until_ready`` of such an array under a profiler trace (host
tracer on, Python tracer off) and reads, for each way, the linearize,
the transposes, the DMA and what runs on the device:

``default``     ``device_put(x, sharding)``: the runtime's own layout.
``row_major``   the layout asked for (``jax.experimental.layout``).  JAX
                0.9.0 puts the array in the default layout all the same
                and relays it on the device (``jit__identity_fn``), so
                the host's transpose stays; the step compiles for that
                layout without a copy.
``as_held``     the product's way (``distlr_tpu/parallel/feed.py``): the
                host's bytes as ``(n, 128)`` 32-bit words, in pieces, and
                ``(rows, D)``, dtype and layout restored by a small
                program on the device: the step sees what it sees today.

Each way's array is checked equal to ``default``'s, and the product's
own train step is timed on it.  Then the stream: ``--stream`` batches
back to back through one ``feed.Pacer``, as the sync trainer's producer
puts them, with ``feed.AHEAD`` at 1, 2 and 3: the
link's rate from the first put to the last batch restored says which
``AHEAD`` is the smallest at which the link never waits for the host
(``--piece-mb`` moves ``feed._PIECE_BYTES`` for the whole run).  Exits
non-zero without a TPU (``--smoke`` runs tiny shapes anywhere and says
nothing about a rate).

Run on the chip: python benchmarks/exp_h2d_layout.py [--rows 768]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental.layout import Format, Layout  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from distlr_tpu.config import Config  # noqa: E402
from distlr_tpu.models import get_model  # noqa: E402
from distlr_tpu.parallel import feed  # noqa: E402
from distlr_tpu.parallel.data_parallel import make_sync_train_step  # noqa: E402
from distlr_tpu.parallel.mesh import DATA_AXIS, make_mesh  # noqa: E402
from distlr_tpu.utils.backend import start_benchmark  # noqa: E402

#: host events read from the trace, by the name the runtime gives them
LINEARIZE = "XlaLinearize"
TRANSPOSE = "Transpose::ExecuteChunk"
DMA_DONE = "tpu::System::TransferToDevice=>IssueEvent=>Done"


def make_batch(rows: int, dim: int, seed: int) -> np.ndarray:
    """A row-major bfloat16 matrix of small integers, 39 non-zeros a
    row: the values the loader's hashed-to-dense rows hold."""
    rng = np.random.default_rng(seed)
    x = np.zeros((rows, dim), jnp.bfloat16)
    cols = rng.integers(0, dim, (rows, 39))
    x[np.arange(rows)[:, None], cols] = 1
    return x


def build_ways(mesh):
    """``{name: put(x) -> device array}``; every array has the logical
    shape, dtype and sharding ``default``'s has."""
    sharding = NamedSharding(mesh, P(DATA_AXIS))

    def put_default(x):
        return jax.device_put(x, sharding)

    row_major = Format(Layout(major_to_minor=(0, 1)), sharding)

    def put_row_major(x):
        return jax.device_put(x, row_major)

    def put_as_held(x):
        return feed.place(x, mesh)

    return {"default": put_default, "row_major": put_row_major,
            "as_held": put_as_held}


def read_trace(trace_dir: str) -> dict:
    """``{way: {...}}``: inside each way's annotated window, the host's
    linearize / transpose / DMA events and the device's program runs."""
    from jax.profiler import ProfileData  # noqa: PLC0415

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    host, modules = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                rec = (ev.name, ev.start_ns * 1e-6, ev.duration_ns * 1e-6)
                if plane.name == "/host:CPU":
                    host.append(rec)
                elif (plane.name.startswith("/device:TPU:")
                      and line.name == "XLA Modules"):
                    modules.append(rec)
    out = {}
    for name, lo, dur in host:
        if not name.startswith("way:"):
            continue
        hi = lo + dur
        inside = [(n, s, d) for n, s, d in host if lo <= s < hi]
        lin = sorted(d for n, _, d in inside if n == LINEARIZE)
        lin_at = [(s, s + d) for n, s, d in inside if n == LINEARIZE and d > 1.0]
        tr = [d for n, _, d in inside if n == TRANSPOSE]
        dma = [(s, d) for n, s, d in inside if n == DMA_DONE]
        mods = {}
        for n, s, d in modules:
            if lo <= s < hi:
                mods.setdefault(n.split("(")[0], []).append(round(d, 3))
        out.setdefault(name[4:], []).append({
            "window_ms": round(dur, 1),
            "linearize_ms": [round(d, 1) for d in lin if d > 1.0],
            "linearize_first_to_last_ms": round(
                max(e for _, e in lin_at) - min(s for s, _ in lin_at), 1)
            if lin_at else None,
            "transpose_chunks": len(tr),
            "transpose_thread_ms": round(sum(tr), 1),
            "dma_done_events": len(dma),
            "dma_first_to_last_ms": round(
                max(s + d for s, d in dma) - min(s for s, _ in dma), 1)
            if dma else None,
            "device_programs_ms": mods,
        })
    return out


def stream(mesh, x, batches: int, ahead: int) -> dict:
    """``batches`` copies of ``x`` placed back to back by one thread,
    paced ``ahead`` pieces ahead of the link, each let go when the next
    is placed."""
    shipped, feed.AHEAD = feed.AHEAD, ahead  # the constant under test
    try:
        pacer = feed.Pacer(threading.Event())
        t0, last = time.perf_counter(), None
        for _ in range(batches):
            last = feed.place(x, mesh, pacer)
        t1 = time.perf_counter()
        jax.block_until_ready(last)
        t2 = time.perf_counter()
    finally:
        feed.AHEAD = shipped
    return {"ahead": ahead, "batches": batches,
            "put_ms_a_batch": (t1 - t0) * 1e3 / batches,
            "gb_per_s": batches * x.nbytes / 1e9 / (t2 - t0)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=768)
    ap.add_argument("--dim", type=int, default=1_000_000)
    ap.add_argument("--puts", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes on any backend; no rate means anything")
    ap.add_argument("--keep-trace", default=None,
                    help="directory to keep the .xplane.pb in")
    ap.add_argument("--stream", type=int, default=8,
                    help="batches a reading of the paced stream")
    ap.add_argument("--piece-mb", type=int, default=None,
                    help="feed._PIECE_BYTES for this run, in MiB")
    args = ap.parse_args()
    if args.piece_mb:
        feed._PIECE_BYTES = args.piece_mb << 20
    dev = start_benchmark("exp_h2d_layout.py", full_size=not args.smoke)
    rows, dim = (16, 4096) if args.smoke else (args.rows, args.dim)

    mesh = make_mesh({"data": 1})
    ways = build_ways(mesh)
    cfg = Config(num_feature_dim=dim, model="binary_lr",
                 feature_dtype="bfloat16", batch_size=rows,
                 learning_rate=0.5, l2_c=0.0)
    step = make_sync_train_step(get_model(cfg), cfg, mesh)
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    y = jax.device_put(np.ones(rows, np.int32), sharding)
    mask = jax.device_put(np.ones(rows, np.float32), sharding)
    x = make_batch(rows, dim, seed=0)
    gb = x.nbytes / 1e9

    try:
        default_layout = Layout.from_pjrt_layout(
            jax.devices()[0].client.get_default_layout(
                x.dtype, x.shape, jax.devices()[0]))
    except Exception as e:  # noqa: BLE001 — reported, not fatal
        default_layout = f"unavailable: {e!r}"
    print(json.dumps({**dev, "rows": rows, "dim": dim, "gigabytes": gb,
                      "default_layout": str(default_layout)}))

    # warm: every program compiled, every array checked against default's
    ref = ways["default"](x)
    alive = {}
    for name, put in list(ways.items()):
        try:
            a = put(x)
            jax.block_until_ready(a)
            same = bool(jnp.array_equal(a, ref))
            w = jnp.zeros(dim, jnp.float32)
            w, _ = step(w, (a, y, mask))
            jax.block_until_ready(w)
            alive[name] = put
            print(json.dumps({"way": name, "format": str(a.format),
                              "equal_to_default": same,
                              "shape": a.shape, "dtype": str(a.dtype)}))
            del a, w
        except Exception as e:  # noqa: BLE001 — a way the runtime refuses
            print(json.dumps({"way": name, "refused": repr(e)[:500]}))
    del ref

    trace_dir = args.keep_trace or tempfile.mkdtemp(prefix="exp_h2d_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    walls: dict = {}
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for name, put in alive.items():
            for k in range(args.puts):
                # one at a time: put, wait, then five steps on the array
                with jax.profiler.TraceAnnotation(f"way:{name}"):
                    t0 = time.perf_counter()
                    a = put(x)
                    t1 = time.perf_counter()
                    jax.block_until_ready(a)
                    t2 = time.perf_counter()
                w = jnp.zeros(dim, jnp.float32)
                jax.block_until_ready(w)
                with jax.profiler.TraceAnnotation(f"way:{name}.steps"):
                    t3 = time.perf_counter()
                    for _ in range(5):
                        w, _ = step(w, (a, y, mask))
                    jax.block_until_ready(w)
                    t4 = time.perf_counter()
                walls.setdefault(name, []).append({
                    "dispatch_ms": (t1 - t0) * 1e3,
                    "put_to_ready_ms": (t2 - t0) * 1e3,
                    "step_ms": (t4 - t3) * 1e3 / 5})
                del a, w
            # two in flight, as an epoch of the cell has them
            with jax.profiler.TraceAnnotation(f"way:{name}.pair"):
                t0 = time.perf_counter()
                a, b = put(x), put(x)
                jax.block_until_ready(a)
                t1 = time.perf_counter()
                jax.block_until_ready(b)
                t2 = time.perf_counter()
            walls[name].append({"pair_first_ready_ms": (t1 - t0) * 1e3,
                                "pair_both_ready_ms": (t2 - t0) * 1e3})
            del a, b
    finally:
        jax.profiler.stop_trace()

    traced = read_trace(trace_dir) if dev["backend"] == "tpu" else {}
    for name in alive:
        ready = [r["put_to_ready_ms"] for r in walls[name] if "step_ms" in r]
        print(json.dumps({
            "way": name, "walls": walls[name],
            "gb_per_s_put_to_ready": gb / (min(ready) * 1e-3),
            "trace": {k: v for k, v in traced.items()
                      if k.split(".")[0] == name}}))
    # the stream: one producer, batch after batch, at each AHEAD
    plan = feed._plan(x, mesh)
    for ahead in (1, 2, 3):
        print(json.dumps({"stream": stream(mesh, x, args.stream, ahead),
                          "pieces": plan.pieces if plan else None,
                          "shipped_ahead": feed.AHEAD}))
    # the other dense feature dtypes, at the test batch's rows: the
    # product's way against the plain put, bit for bit
    for dtype in (np.int8, np.float32):
        xs = x[:rows // 3].astype(dtype)
        same = bool(jnp.array_equal(feed.place(xs, mesh),
                                    jax.device_put(xs, sharding)))
        print(json.dumps({"dtype": np.dtype(dtype).name, "rows": xs.shape[0],
                          "as_held_equal_to_default": same}))
    print(f"peak_bytes_in_use "
          f"{(jax.devices()[0].memory_stats() or {}).get('peak_bytes_in_use')}")


if __name__ == "__main__":
    main()
