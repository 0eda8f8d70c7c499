"""Multi-tenant serving benchmark: many models behind one router.

Measures the ISSUE-10 serving shape three ways:

* **1-model baseline QPS** — concurrent TCP clients through a
  :class:`~distlr_tpu.serve.router.ScoringRouter` over a single hosted
  model (the pre-tenant topology, the comparison anchor);
* **N-model per-model QPS** — the SAME engine process hosting N model
  versions (N engines behind one :class:`ScoringServer`), clients
  ``@``-addressing models round-robin: per-model QPS and the aggregate,
  so "what does hosting N versions cost each tenant" reads off the row;
* **shadow overhead %** — primary QPS with a 10% shadow mirror to a
  candidate version ON vs OFF, interleaved A/B/A/B and compared
  pairwise (the same drift-cancelling discipline bench_prof uses), so
  the <5%-at-10% acceptance bound is measured, not assumed.

Prints ONE JSON line in ``bench.py``'s format.  Run:
``python benchmarks/bench_tenant.py [--quick|--smoke]``
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from distlr_tpu.obs.tracing import get_tracer  # noqa: E402
from distlr_tpu.utils.backend import start_benchmark  # noqa: E402


def _resilience() -> dict:
    from bench import resilience_snapshot  # noqa: PLC0415

    return resilience_snapshot()


def _mk_engines(d: int, n_models: int, max_batch: int):
    import numpy as np

    from distlr_tpu.config import Config
    from distlr_tpu.serve.engine import ScoringEngine

    cfg = Config(num_feature_dim=d, model="binary_lr", l2_c=0.0)
    engines = {}
    rng = np.random.default_rng(0)
    for i in range(n_models):
        eng = ScoringEngine(cfg, max_batch_size=max_batch)
        eng.set_weights(rng.standard_normal(d).astype(np.float32) * 0.1)
        engines[f"v{i + 1}"] = eng
    return engines


def _drive(host: str, port: int, lines: list[str], *, clients: int,
           duration_s: float) -> dict:
    """Concurrent line-protocol clients for ``duration_s``: each cycles
    its line list over one persistent connection.  Returns counts."""
    stop = threading.Event()
    counts = [0] * clients
    errors = [0] * clients

    def client(i: int) -> None:
        try:
            with socket.create_connection((host, port), timeout=30) as s:
                f = s.makefile("rwb")
                j = 0
                while not stop.is_set():
                    f.write((lines[j % len(lines)] + "\n").encode())
                    f.flush()
                    r = f.readline()
                    if not r:
                        return
                    if r.startswith(b"ERR"):
                        errors[i] += 1
                    else:
                        counts[i] += 1
                    j += 1
        except OSError:
            pass

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    wall = time.monotonic() - t0
    return {"replies": sum(counts), "errors": sum(errors),
            "qps": round(sum(counts) / wall, 1), "wall_s": round(wall, 3)}


def bench_n_models(d: int, n_models: int, *, clients: int,
                   duration_s: float, max_batch: int = 256) -> dict:
    """One server hosting ``n_models`` engines behind one router;
    clients round-robin @-addressed requests across every model."""
    import json as _json

    from distlr_tpu.serve.router import ScoringRouter
    from distlr_tpu.serve.server import ScoringServer, score_lines_over_tcp

    engines = _mk_engines(d, n_models, max_batch)
    mids = list(engines)
    srv = ScoringServer(engines=engines, max_wait_ms=1.0).start()
    addr = f"{srv.host}:{srv.port}"
    router = ScoringRouter({m: [addr] for m in mids},
                           max_inflight=max(64, clients),
                           health_interval_s=5.0, seed=0).start()
    try:
        feats = "1:1 5:1 9:1"
        lines = ([feats] if n_models == 1
                 else [f"@{m} {feats}" for m in mids])
        # warm every engine's jit cache before the measured window
        score_lines_over_tcp(router.host, router.port, lines)
        got = _drive(router.host, router.port, lines,
                     clients=clients, duration_s=duration_s)
        st = _json.loads(score_lines_over_tcp(router.host, router.port,
                                              ["STATS"])[0])
        got["per_model_qps"] = {
            m: round(st["per_model"][m]["requests"] / got["wall_s"], 1)
            for m in mids}
        got["models"] = n_models
        return got
    finally:
        router.stop()
        srv.stop()


def bench_shadow_overhead(d: int, *, clients: int, duration_s: float,
                          fraction: float = 0.1, rounds: int = 3,
                          max_batch: int = 256) -> dict:
    """Primary QPS with a ``fraction`` shadow mirror ON vs OFF —
    interleaved off/on pairs per round, overhead from the paired
    ratios (machine drift cancels within a pair)."""
    from distlr_tpu.serve.router import ScoringRouter
    from distlr_tpu.serve.server import ScoringServer, score_lines_over_tcp

    engines = _mk_engines(d, 2, max_batch)
    srv = ScoringServer(engines=engines, max_wait_ms=1.0).start()
    addr = f"{srv.host}:{srv.port}"
    router = ScoringRouter({"v1": [addr], "v2": [addr]},
                           max_inflight=max(64, clients),
                           health_interval_s=5.0, seed=0).start()
    try:
        feats = "1:1 5:1 9:1"
        # warm both engines (the mirror scores v2 off the reply path)
        score_lines_over_tcp(router.host, router.port,
                             [feats, f"@v2 {feats}"])
        ratios = []
        off_qps = on_qps = None
        for _ in range(rounds):
            score_lines_over_tcp(router.host, router.port,
                                 ["SHADOW v1 v2 0"])
            off = _drive(router.host, router.port, [feats],
                         clients=clients, duration_s=duration_s)
            score_lines_over_tcp(router.host, router.port,
                                 [f"SHADOW v1 v2 {fraction:g}"])
            on = _drive(router.host, router.port, [feats],
                        clients=clients, duration_s=duration_s)
            if off["qps"] > 0 and on["qps"] > 0:
                ratios.append(on["qps"] / off["qps"])
                off_qps, on_qps = off["qps"], on["qps"]
        ratios.sort()
        med = ratios[len(ratios) // 2] if ratios else None
        mirror = router._shadow_mirror
        return {
            "fraction": fraction,
            "qps_off": off_qps,
            "qps_on": on_qps,
            "overhead_pct": (None if med is None
                             else round(max(0.0, (1.0 - med)) * 100, 2)),
            "mirrored": mirror.mirrored if mirror else 0,
            "mirror_dropped": mirror.dropped if mirror else 0,
        }
    finally:
        router.stop()
        srv.stop()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny shapes (smoke/test mode)")
    ap.add_argument("--smoke", action="store_true",
                    help="alias of --quick (the `make -C benchmarks "
                    "tenant-smoke` entry point)")
    args = ap.parse_args()
    if args.smoke:
        args.quick = True

    dev = start_benchmark("bench_tenant.py", full_size=not args.quick)

    if args.quick:
        d, clients, duration, rounds = 4096, 4, 0.4, 2
        model_counts = (1, 2)
    else:
        d, clients, duration, rounds = 65536, 8, 2.0, 3
        model_counts = (1, 2, 4)

    subs: dict[str, object] = {}
    baseline = None
    for n in model_counts:
        key = f"models_{n}_qps"
        try:
            r = bench_n_models(d, n, clients=clients, duration_s=duration)
            subs[key] = r
            if n == 1:
                baseline = r
        except Exception as e:  # one config must not cost the artifact
            print(f"[bench_tenant] {key} failed: {e!r}", file=sys.stderr)
            subs[key] = None
    try:
        subs["shadow"] = bench_shadow_overhead(
            d, clients=clients, duration_s=duration, rounds=rounds)
    except Exception as e:
        print(f"[bench_tenant] shadow failed: {e!r}", file=sys.stderr)
        subs["shadow"] = None

    row = {
        "metric": f"multi-tenant serve QPS, binary LR D={d}, "
                  "N models one router",
        "value": baseline["qps"] if baseline else None,
        "unit": "requests/sec",
        **dev,
        "D": d,
        "phase_breakdown": {"phases": get_tracer().breakdown()},
        "resilience": _resilience(),
        **subs,
    }
    print(json.dumps(row))
    shadow = subs.get("shadow")
    if (args.quick is False and isinstance(shadow, dict)
            and shadow.get("overhead_pct") is not None
            and shadow["overhead_pct"] >= 5.0):
        # acceptance bound (ISSUE 10): <5% primary QPS overhead at a
        # 10% shadow fraction — fail loudly in full mode
        print(f"[bench_tenant] shadow overhead {shadow['overhead_pct']}% "
              ">= 5% bound", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
