"""Distributed-tracing benchmark (ISSUE 8): sampling overhead + one
banked merged trace + one flight-recorder dump.

Three measurements, one JSON line (``bench.py`` format):

* **overhead** — serve front-end requests/s with tracing unconfigured
  vs armed at the default sample rate (0.01) vs fully sampled (1.0),
  through the real ``handle_line`` path (protocol parse, microbatcher,
  jitted engine).  The acceptance bound is <5% at default sampling.
* **merged trace** — a traced closed loop (scored request -> LABEL ->
  join -> online trainer -> FTRL PS apply) is run at sample=1.0 and
  ``trace-agg``-merged; the banked artifact is a REAL cross-process
  trace (native ``distlr_kv_server`` handler spans included), the thing
  the capture window ships next to the fleet snapshot.
* **flight recorder** — the same run's ring is dumped on demand, so the
  postmortem artifact shape is banked too.

Run: ``python benchmarks/bench_trace.py [--smoke] [--out-dir DIR]``
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import socket
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from distlr_tpu.utils.backend import start_benchmark  # noqa: E402


def _make_lines(n: int, d: int, nnz: int, seed: int = 0) -> list[str]:
    import numpy as np

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        cols = np.sort(rng.choice(d, size=nnz, replace=False))
        out.append(" ".join(f"{c + 1}:1" for c in cols))
    return out


def _mk_server(d: int, max_batch: int):
    import numpy as np

    from distlr_tpu.config import Config
    from distlr_tpu.serve import ScoringEngine, ScoringServer

    cfg = Config(model="binary_lr", num_feature_dim=d, l2_c=0.0)
    engine = ScoringEngine(cfg, max_batch_size=max_batch)
    engine.set_weights(np.linspace(-1, 1, d).astype(np.float32))
    return ScoringServer(engine)


def bench_requests_per_sec(srv, lines: list[str], duration_s: float) -> float:
    # warm the jit caches out of the measured window
    for ln in lines[:8]:
        srv.handle_line(ln)
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < duration_s:
        srv.handle_line(lines[n % len(lines)])
        n += 1
    return n / (time.perf_counter() - t0)


def traced_closed_loop(run_dir: str, d: int, requests: int) -> dict:
    """Score + label ``requests`` ids at sample=1.0 through a real
    router/server/feedback/online-trainer/FTRL-group loop; returns the
    merged-trace summary."""
    import numpy as np  # noqa: F401

    from distlr_tpu.config import Config
    from distlr_tpu.feedback import FeedbackSink, OnlineTrainer
    from distlr_tpu.obs import dtrace
    from distlr_tpu.ps import ServerGroup
    from distlr_tpu.serve.router import ScoringRouter

    dtrace.configure(run_dir, "bench", 0, sample=1.0)
    cfg = Config(model="binary_lr", num_feature_dim=d, batch_size=32,
                 l2_c=0.0, sync_mode=False, ps_timeout_ms=20_000)
    tmp = os.path.join(run_dir, "feedback")
    group = ServerGroup(
        1, 1, d, sync=False, optimizer="ftrl", ftrl_alpha=1.0,
        ftrl_beta=1.0,
        trace_journal_dir=os.path.join(run_dir, "spans")).start()
    sink = FeedbackSink(os.path.join(tmp, "spool"),
                        os.path.join(tmp, "shards"),
                        model="binary_lr", window_s=30.0,
                        shard_records=max(requests // 4, 1))
    srv = _mk_server(d, 256)
    srv.feedback = sink
    srv.start()
    router = ScoringRouter([f"{srv.host}:{srv.port}"]).start()
    trainer = None
    try:
        lines = _make_lines(requests, d, nnz=8)
        with socket.create_connection((router.host, router.port),
                                      timeout=30.0) as s:
            f = s.makefile("rwb")

            def ask(line):
                f.write((line + "\n").encode())
                f.flush()
                return f.readline().decode().rstrip("\n")

            for i, ln in enumerate(lines):
                ask(f"ID bench-{i} {ln}")
                ask(f"LABEL bench-{i} {i % 2}")
        sink.joiner.flush()
        trainer = OnlineTrainer(cfg, group.hosts,
                                os.path.join(tmp, "shards"),
                                accum_start=1, poll_interval_s=0.05)
        trainer.run(idle_exit_s=2.0)
    finally:
        if trainer is not None:
            trainer.close()
        router.stop()
        srv.stop()
        sink.stop()
        dtrace.flush()
        time.sleep(0.2)
        group.stop()

    out_path = os.path.join(os.path.dirname(run_dir), "merged_trace.json")
    doc = dtrace.write_merged_trace([run_dir], out_path)
    flight = dtrace.flight_dump("bench-trace")
    dtrace.reset_for_tests()
    meta = doc["otherData"]
    return {
        "trace_path": out_path,
        "flightrec_path": flight,
        "journals": meta["journals"],
        "spans": meta["spans"],
        "trace_ids": len(meta["trace_ids"]),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (the `make -C benchmarks "
                    "trace-smoke` entry point)")
    ap.add_argument("--out-dir", default=os.path.join(
        HERE, "capture_logs", "trace"),
        help="where the merged trace + flight dump land "
        "(default benchmarks/capture_logs/trace)")
    ap.add_argument("--sample", type=float, default=0.01,
                    help="the 'default sampling' rate the overhead row "
                    "is measured at (default 0.01)")
    args = ap.parse_args()

    dev = start_benchmark("bench_trace.py", full_size=not args.smoke)

    if args.smoke:
        d, duration, loop_requests = 4096, 0.5, 8
    else:
        d, duration, loop_requests = 65536, 2.0, 64

    from distlr_tpu.obs import dtrace

    run_dir = os.path.join(args.out_dir, "run")
    if os.path.isdir(run_dir):
        shutil.rmtree(run_dir)
    os.makedirs(run_dir, exist_ok=True)

    lines = _make_lines(256, d, nnz=8)
    srv = _mk_server(d, 256)
    # INTERLEAVED rounds, medians: back-to-back one-shot windows read
    # machine drift (jit warmup, turbo decay) as tracing overhead — a
    # 2s serial A/B measured ~7% "overhead" that a second pass showed
    # was 0
    offs, defaults, fulls = [], [], []
    try:
        for _ in range(3):
            dtrace.reset_for_tests()
            offs.append(bench_requests_per_sec(srv, lines, duration))
            dtrace.configure(run_dir, "qps-default", 0, sample=args.sample)
            defaults.append(bench_requests_per_sec(srv, lines, duration))
            dtrace.configure(run_dir, "qps-full", 0, sample=1.0)
            fulls.append(bench_requests_per_sec(srv, lines, duration))
    finally:
        srv.stop()
        dtrace.reset_for_tests()
    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    qps_off, qps_default, qps_full = med(offs), med(defaults), med(fulls)
    overhead_default = 100.0 * (1.0 - qps_default / qps_off)
    overhead_full = 100.0 * (1.0 - qps_full / qps_off)

    loop = traced_closed_loop(run_dir, d, loop_requests)

    row = {
        "metric": (f"serve QPS overhead at --trace-sample {args.sample:g}, "
                   f"D={d}"),
        "value": round(overhead_default, 2),
        "unit": "percent",
        **dev,
        "D": d,
        "qps_untraced": round(qps_off, 1),
        "qps_default_sample": round(qps_default, 1),
        "qps_full_sample": round(qps_full, 1),
        "overhead_default_pct": round(overhead_default, 2),
        "overhead_full_pct": round(overhead_full, 2),
        "sample": args.sample,
        **loop,
    }
    print(json.dumps(row))
    # acceptance bound, enforced where the driver can see it: <5% at
    # default sampling (negative = measurement noise, also fine)
    if overhead_default >= 5.0:
        print(f"[bench_trace] WARNING: default-sample overhead "
              f"{overhead_default:.2f}% >= 5%", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
