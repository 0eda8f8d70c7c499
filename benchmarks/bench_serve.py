"""Serving benchmark: scoring QPS vs batch-bucket config.

Measures the online serving subsystem (``distlr_tpu/serve``) three ways:

* **engine rows/s** — the jitted bucketed scoring path fed directly, per
  bucket ladder config (the ceiling the front-end can approach);
* **end-to-end QPS** — concurrent TCP clients through the microbatcher,
  per (max_batch, max_wait) config, with the measured batch occupancy;
* **multi-engine QPS** — concurrent TCP clients through the
  :class:`~distlr_tpu.serve.router.ScoringRouter` front-end over N real
  engine replicas (the ISSUE-4 serving tier), with the router's shed /
  retry accounting in the row.

Prints ONE JSON line in ``bench.py``'s format (``metric`` / ``value`` /
``unit`` / per-config sub rows) so serving throughput joins the bench
trajectory the driver tracks.  Like bench.py, ``--quick`` runs wherever
JAX lands and names the platform in its row; the full-size run refuses
anything but a TPU.

Run: ``python benchmarks/bench_serve.py [--quick|--smoke]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from distlr_tpu.obs.tracing import get_tracer, trace_phase  # noqa: E402
from distlr_tpu.utils.backend import start_benchmark  # noqa: E402


def _profile_snapshot() -> dict:
    """Optional DISTLR_PROFILE_TOP=<N> sampler snapshot (see
    bench.profile_snapshot); empty — and the row byte-stable — when
    unset."""
    from bench import profile_snapshot  # noqa: PLC0415

    return profile_snapshot()


def _resilience() -> dict:
    """Fault-cost counter snapshot (see bench.resilience_snapshot): a
    serve bench that fought a flaky PS link records what it cost."""
    from bench import resilience_snapshot  # noqa: PLC0415

    return resilience_snapshot()


def _make_lines(n: int, d: int, nnz: int, seed: int = 0) -> list[str]:
    import numpy as np

    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(n):
        cols = np.sort(rng.choice(d, size=nnz, replace=False))
        lines.append(" ".join(f"{c + 1}:1" for c in cols))
    return lines


def bench_engine_rows(d: int, bucket: int, batches: int, *, sparse: bool,
                      nnz: int = 16) -> float:
    """Steady-state rows/s of the jitted scoring path at one bucket size
    (full buckets — the MXU-side ceiling)."""
    import numpy as np

    from distlr_tpu.config import Config
    from distlr_tpu.serve import ScoringEngine

    if sparse:
        cfg = Config(num_feature_dim=d, model="sparse_lr", l2_c=0.0)
    else:
        cfg = Config(num_feature_dim=d, model="binary_lr", l2_c=0.0)
    eng = ScoringEngine(cfg, max_batch_size=bucket, buckets=(bucket,))
    rng = np.random.default_rng(0)
    eng.set_weights(rng.standard_normal(d).astype(np.float32))
    if sparse:
        rows = (rng.integers(0, d, size=(bucket, nnz)).astype(np.int32),
                np.ones((bucket, nnz), np.float32))
    else:
        rows = (rng.standard_normal((bucket, d)).astype(np.float32),)
    with trace_phase("warmup_compile"):
        eng.score(tuple(np.array(a) for a in rows))  # compile warmup
    t0 = time.perf_counter()
    with trace_phase("engine_score"):
        for _ in range(batches):
            # fresh arrays per call: the donating jit consumes its inputs
            eng.score(tuple(np.array(a) for a in rows))
    return bucket * batches / (time.perf_counter() - t0)


def bench_e2e_qps(d: int, max_batch: int, max_wait_ms: float, *,
                  clients: int, rows_per_request: int,
                  duration_s: float) -> dict:
    """End-to-end QPS through TCP + microbatcher with concurrent clients."""
    import numpy as np

    from distlr_tpu.config import Config
    from distlr_tpu.serve import ScoringEngine, ScoringServer
    from distlr_tpu.serve.server import score_lines_over_tcp

    cfg = Config(num_feature_dim=d, model="sparse_lr", l2_c=0.0)
    eng = ScoringEngine(cfg, max_batch_size=max_batch)
    eng.set_weights(np.random.default_rng(1).standard_normal(d).astype(np.float32))
    lines = _make_lines(rows_per_request, d, 16)
    payload = json.dumps({"rows": lines})
    counts = [0] * clients
    with ScoringServer(eng, max_wait_ms=max_wait_ms) as srv:
        with trace_phase("warmup_compile"):
            score_lines_over_tcp(srv.host, srv.port, [payload])  # warmup
        stop = time.monotonic() + duration_s

        def client(i):
            import socket

            with socket.create_connection((srv.host, srv.port), timeout=30) as s:
                f = s.makefile("rwb")
                while time.monotonic() < stop:
                    f.write((payload + "\n").encode())
                    f.flush()
                    if not f.readline():
                        return
                    counts[i] += 1

        t0 = time.monotonic()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        with trace_phase("e2e_clients"):
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        elapsed = time.monotonic() - t0
        occupancy = srv.batcher.stats()["mean_occupancy"]
    reqs = sum(counts)
    return {
        "qps": round(reqs / elapsed, 1),
        "rows_per_sec": round(reqs * rows_per_request / elapsed, 1),
        "mean_occupancy": occupancy,
        "clients": clients,
        "rows_per_request": rows_per_request,
    }


def bench_router_qps(d: int, n_replicas: int, max_batch: int,
                     max_wait_ms: float, *, clients: int,
                     rows_per_request: int, duration_s: float) -> dict:
    """Multi-engine end-to-end QPS: concurrent TCP clients through the
    routing front-end over ``n_replicas`` real engine replicas."""
    import numpy as np

    from distlr_tpu.config import Config
    from distlr_tpu.serve import ScoringEngine, ScoringRouter, ScoringServer
    from distlr_tpu.serve.server import score_lines_over_tcp

    cfg = Config(num_feature_dim=d, model="sparse_lr", l2_c=0.0)
    w = np.random.default_rng(2).standard_normal(d).astype(np.float32)
    servers = []
    for _ in range(n_replicas):
        eng = ScoringEngine(cfg, max_batch_size=max_batch)
        eng.set_weights(w)
        servers.append(ScoringServer(eng, max_wait_ms=max_wait_ms).start())
    lines = _make_lines(rows_per_request, d, 16, seed=3)
    payload = json.dumps({"rows": lines})
    counts = [0] * clients
    router = ScoringRouter([f"{s.host}:{s.port}" for s in servers],
                           max_inflight=max(2 * clients, 4)).start()
    try:
        with trace_phase("warmup_compile"):
            # warm EVERY replica directly — one request through the
            # router reaches a single engine, and the others' first-use
            # jit compile would land inside the timed window
            for s in servers:
                score_lines_over_tcp(s.host, s.port, [payload])
            score_lines_over_tcp(router.host, router.port, [payload])
        stop = time.monotonic() + duration_s

        def client(i):
            import socket

            with socket.create_connection((router.host, router.port),
                                          timeout=30) as s:
                f = s.makefile("rwb")
                while time.monotonic() < stop:
                    f.write((payload + "\n").encode())
                    f.flush()
                    reply = f.readline()
                    if not reply:
                        return
                    if not reply.startswith(b"ERR"):
                        # shed/route errors are answered lines but not
                        # scored work — counting them would inflate qps
                        counts[i] += 1

        t0 = time.monotonic()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        with trace_phase("route_clients"):
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        elapsed = time.monotonic() - t0
        stats = router.stats()
    finally:
        router.stop()
        for s in servers:
            s.stop()
    reqs = sum(counts)
    return {
        "qps": round(reqs / elapsed, 1),
        "rows_per_sec": round(reqs * rows_per_request / elapsed, 1),
        "replicas": n_replicas,
        "shed": stats["shed"],
        "retries": stats["retries"],
        "clients": clients,
        "rows_per_request": rows_per_request,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny shapes (smoke/test mode)")
    ap.add_argument("--smoke", action="store_true",
                    help="alias of --quick (the `make -C benchmarks "
                    "serve-smoke` entry point)")
    args = ap.parse_args()
    if args.smoke:
        args.quick = True
    from bench import maybe_arm_profiler  # noqa: PLC0415

    maybe_arm_profiler()

    dev = start_benchmark("bench_serve.py", full_size=not args.quick)

    if args.quick:
        d, batches, duration = 4096, 3, 0.5
        buckets = (64, 256)
        e2e_cfgs = [(256, 1.0, 4, 32)]
        route_cfgs = [(2, 256, 1.0, 4, 32)]
    else:
        d, batches, duration = 1_000_000, 30, 3.0
        buckets = (64, 256, 1024, 4096)
        e2e_cfgs = [(256, 1.0, 8, 64), (1024, 2.0, 8, 64),
                    (4096, 2.0, 16, 256), (1024, 0.0, 1, 1)]
        route_cfgs = [(2, 4096, 2.0, 16, 256), (4, 4096, 2.0, 16, 256)]

    subs: dict[str, object] = {}
    for bucket in buckets:
        for name, sparse in ((f"engine_dense_b{bucket}_rows_per_sec", False),
                             (f"engine_sparse_b{bucket}_rows_per_sec", True)):
            if not sparse and d > 200_000 and bucket > 1024:
                continue  # (B, D) dense tile past HBM-reasonable size
            try:
                subs[name] = round(
                    bench_engine_rows(d, bucket, batches, sparse=sparse), 1)
            except Exception as e:  # one config must not cost the artifact
                print(f"[bench_serve] {name} failed: {e!r}", file=sys.stderr)
                subs[name] = None

    best_e2e = None
    for max_batch, wait_ms, clients, rpr in e2e_cfgs:
        key = f"e2e_mb{max_batch}_w{wait_ms:g}_c{clients}"
        try:
            r = bench_e2e_qps(d, max_batch, wait_ms, clients=clients,
                              rows_per_request=rpr, duration_s=duration)
            subs[key] = r
            if best_e2e is None or r["rows_per_sec"] > best_e2e["rows_per_sec"]:
                best_e2e = r
        except Exception as e:
            print(f"[bench_serve] {key} failed: {e!r}", file=sys.stderr)
            subs[key] = None

    best_route = None
    for n, max_batch, wait_ms, clients, rpr in route_cfgs:
        key = f"route_e2e_r{n}_mb{max_batch}_c{clients}"
        try:
            r = bench_router_qps(d, n, max_batch, wait_ms, clients=clients,
                                 rows_per_request=rpr, duration_s=duration)
            subs[key] = r
            if best_route is None or r["rows_per_sec"] > best_route["rows_per_sec"]:
                best_route = r
        except Exception as e:
            print(f"[bench_serve] {key} failed: {e!r}", file=sys.stderr)
            subs[key] = None

    engine_rates = [v for k, v in subs.items()
                    if k.startswith("engine_") and isinstance(v, float)]
    phases = get_tracer().breakdown()
    row = {
        "metric": f"serve rows/sec, sparse LR D={d}, batched jit scoring, 1 chip",
        "value": max(engine_rates) if engine_rates else None,
        "unit": "rows/sec",
        **dev,
        "D": d,
        "best_e2e": best_e2e,
        "best_route": best_route,
        # per-phase wall sums across the whole run (obs tracer).  Unlike
        # bench.py's headline breakdown, phases here OVERLAP across
        # threads (serve_score runs on the flush thread inside the
        # e2e_clients window), so the sums explain structure, not a
        # disjoint partition of wall clock.
        "phase_breakdown": {"phases": phases},
        "resilience": _resilience(),
        **_profile_snapshot(),
        **subs,
    }
    print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
