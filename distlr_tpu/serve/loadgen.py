"""Open-loop diurnal load generator for the serving tier.

Drives a ``launch route`` front-end (or a single engine listener — the
line protocol is identical) with a request rate that follows one
diurnal cycle: a raised-cosine ramp from ``base_qps`` up to
``peak_qps`` and back over ``period_s``.  This is the traffic shape
the fleet autopilot is tested against (the ``test_autopilot``
acceptance e2e): a controller that can follow one synthetic day can
breathe capacity up into the peak and back down the far side.

The curve/arrival math lives in :mod:`distlr_tpu.traffic` — ONE
traffic model shared with the fleetsim discrete-event simulator
(ISSUE 19), so the simulated autopilot and the real one face the same
offered load.  This module is the socket driver around it, plus three
realism knobs:

* ``zipf_alpha`` — Zipf-skewed feature popularity (``P(k) ∝ 1/k^a``),
  the skew that makes an engine's
  :class:`~distlr_tpu.serve.hotset.HotSetTracker` working set earn its
  keep (uniform traffic has no hot set); 0 keeps the old uniform draw;
* ``tenant_mix`` — ``"v1=0.8,v2=0.2"`` per-tenant traffic mixes:
  requests pick a model by weight and ride ``MODEL``-scoped
  connections (the multi-tenant router protocol);
* ``label_frac`` + ``label_delay`` — a replayable label-delay
  distribution: that fraction of requests goes in ``ID <rid>`` mode
  and a ``LABEL <rid> <y>`` line follows after a lognormal delay
  (p50/p95-parameterized), exercising the spool/join window machinery
  with the same tape every run.

OPEN loop, deliberately: request send times are scheduled from the
curve alone, never from reply latency, so a saturated tier keeps
receiving offered load (and sheds it explicitly) instead of the
generator politely backing off and hiding the overload — the standard
closed-loop coordinated-omission trap.

Classification per reply line:

* ``OK ...``/scores — **ok** (latency recorded);
* ``ERR SHED ...`` — **shed**: explicit admission control, the signal
  the autopilot's engine band consumes.  Sheds are NOT errors;
* any other ``ERR``, a transport failure, or a dead connection —
  **err** (the acceptance bar in the e2e is err == 0).

Label lines are classified apart (``label_ok``/``label_err``) — a
fleet run without a feedback spool answers them ``ERR``, which is an
opt-in wiring gap, not a serving failure.

Deterministic for a given seed: payloads, the Zipf draws, tenant
picks, and label delays all come from seeded RNGs and the schedule is
pure arithmetic.  (Reply ordering and latency percentiles still
reflect the live fleet, of course.)

Library use::

    from distlr_tpu.serve.loadgen import run_load
    summary = run_load("127.0.0.1:7000", base_qps=20, peak_qps=120,
                       period_s=30, dim=1024, seed=7)

CLI: ``python -m distlr_tpu.serve.loadgen --addr H:P [--base-qps ...]``
prints the same summary as ONE JSON line.  The module imports no jax
(``serve/__init__`` is lazy for that reason): a sender never takes a
chip.
"""

from __future__ import annotations

import argparse
import bisect
import json
import queue
import random
import socket
import sys
import threading
import time

# the shared traffic model (re-exported: tests import `qps_at` and
# `schedule` from here)
from distlr_tpu.traffic import (
    LabelDelay,
    ZipfSampler,
    parse_tenant_mix,
    qps_at,
    schedule,
)

__all__ = ["make_payloads", "qps_at", "run_load", "schedule"]


def make_payloads(n: int, dim: int, nnz: int, rows: int, seed: int,
                  zipf_alpha: float = 0.0) -> list[str]:
    """``n`` distinct request lines (JSON ``{"rows": [...]}``) with
    seeded sparse feature rows — the engine protocol's 1-based
    ``col:val`` text format.  ``zipf_alpha > 0`` draws columns
    Zipf-skewed (popular low ids dominate — the hot set); 0 keeps the
    historical uniform draw byte-identical."""
    import numpy as np  # noqa: PLC0415

    rng = np.random.default_rng(seed)
    zipf = ZipfSampler(dim, zipf_alpha) if zipf_alpha > 0 else None
    zrng = random.Random(seed)
    payloads = []
    for _ in range(n):
        lines = []
        for _ in range(rows):
            if zipf is None:
                cols = np.sort(rng.choice(dim, size=min(nnz, dim),
                                          replace=False))
            else:
                picked: set[int] = set()
                while len(picked) < min(nnz, dim):
                    picked.add(zipf.sample(zrng))
                cols = sorted(picked)
            lines.append(" ".join(f"{int(c) + 1}:1" for c in cols))
        payloads.append(json.dumps({"rows": lines}))
    return payloads


class _Counters:
    def __init__(self):
        self.lock = threading.Lock()
        self.sent = 0
        self.ok = 0
        self.shed = 0
        self.err = 0
        self.labels_sent = 0
        self.label_ok = 0
        self.label_err = 0
        self.latencies_ms: list[float] = []


def _worker(addr: tuple[str, int], q: "queue.Queue", c: _Counters,
            timeout_s: float) -> None:
    """One sender: a persistent connection, re-dialed on failure (the
    router may churn replicas under us — that is the point).  Items are
    ``(model, line, is_label)``; a model switch re-scopes the
    connection with a ``MODEL`` line first."""
    f = None
    sock = None
    scope: str | None = None
    while True:
        item = q.get()
        if item is None:
            break
        model, payload, is_label = item
        t0 = time.monotonic()
        try:
            if f is None:
                sock = socket.create_connection(addr, timeout=timeout_s)
                f = sock.makefile("rwb")
                scope = None
            if model is not None and model != scope:
                f.write(f"MODEL {model}\n".encode())
                f.flush()
                mrep = f.readline()
                if not mrep:
                    raise ConnectionError("connection closed")
                if mrep.decode("utf-8", "replace").startswith("OK"):
                    scope = model
            f.write((payload + "\n").encode())
            f.flush()
            reply = f.readline()
            if not reply:
                raise ConnectionError("connection closed")
        except OSError:
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass
            f = sock = None
            scope = None
            with c.lock:
                if is_label:
                    c.label_err += 1
                else:
                    c.err += 1
            continue
        ms = (time.monotonic() - t0) * 1e3
        text = reply.decode("utf-8", "replace")
        with c.lock:
            if is_label:
                if text.startswith("ERR"):
                    c.label_err += 1
                else:
                    c.label_ok += 1
            elif text.startswith("ERR SHED"):
                c.shed += 1
            elif text.startswith("ERR"):
                c.err += 1
            else:
                c.ok += 1
                c.latencies_ms.append(ms)
    if sock is not None:
        try:
            sock.close()
        except OSError:
            pass


def _pct(sorted_vals: list[float], q: float) -> float | None:
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return round(sorted_vals[i], 3)


def _build_events(sends: list[float], payloads: list[str], *, seed: int,
                  tenant_mix: dict[str, float] | None, label_frac: float,
                  label_delay: LabelDelay) -> list[tuple[float, str | None,
                                                         str, bool]]:
    """The full deterministic tape: ``(t, model, line, is_label)``
    sorted by send time — labeled requests go in ``ID`` mode with
    their ``LABEL`` line scheduled ``delay`` later on the same model."""
    rng = random.Random(seed)
    models: list[str] | None = None
    cdf: list[float] = []
    if tenant_mix:
        models = list(tenant_mix)
        acc = 0.0
        for m in models:
            acc += tenant_mix[m]
            cdf.append(acc)
    events: list[tuple[float, int, str | None, str, bool]] = []
    for i, t in enumerate(sends):
        model = None
        if models:
            model = models[min(len(models) - 1,
                               bisect.bisect_left(cdf, rng.random()))]
        line = payloads[i % len(payloads)]
        if label_frac > 0 and rng.random() < label_frac:
            rid = f"lg{seed}-{i}"
            line = f"ID {rid} {line}"
            y = 1 if rng.random() < 0.5 else 0
            events.append((t + label_delay.sample(rng), i + len(sends),
                           model, f"LABEL {rid} {y}", True))
        events.append((t, i, model, line, False))
    events.sort(key=lambda e: (e[0], e[1]))
    return [(t, model, line, is_label)
            for t, _i, model, line, is_label in events]


def run_load(addr: str, *, base_qps: float = 20.0, peak_qps: float = 100.0,
             period_s: float = 30.0, duration_s: float | None = None,
             dim: int = 1024, nnz: int = 16, rows_per_request: int = 1,
             seed: int = 0, workers: int = 8, payload_pool: int = 64,
             timeout_s: float = 10.0, on_tick=None,
             zipf_alpha: float = 0.0, tenant_mix=None,
             label_frac: float = 0.0, label_delay_p50_s: float = 1.0,
             label_delay_p95_s: float = 5.0) -> dict:
    """Run one diurnal cycle (or ``duration_s``) of open-loop load
    against ``addr`` (``host:port``) and return the summary dict.
    ``on_tick(t, target_qps)`` is called about once a second — hooks
    for tests/benches that want to sample the fleet mid-ramp."""
    host, _, port = str(addr).rpartition(":")
    if not host or not port.isdigit():
        raise ValueError(f"addr must be host:port, got {addr!r}")
    if not 0.0 <= label_frac <= 1.0:
        raise ValueError(f"label_frac must be in [0, 1], got {label_frac}")
    mix = parse_tenant_mix(tenant_mix) if tenant_mix else None
    duration_s = period_s if duration_s is None else float(duration_s)
    payloads = make_payloads(payload_pool, dim, nnz, rows_per_request, seed,
                             zipf_alpha=zipf_alpha)
    sends = schedule(duration_s, base_qps, peak_qps, period_s)
    events = _build_events(
        sends, payloads, seed=seed, tenant_mix=mix, label_frac=label_frac,
        label_delay=LabelDelay(label_delay_p50_s, label_delay_p95_s))

    c = _Counters()
    q: queue.Queue = queue.Queue()
    pool = [threading.Thread(target=_worker,
                             args=((host, int(port)), q, c, timeout_s),
                             daemon=True, name=f"loadgen-{i}")
            for i in range(workers)]
    for t in pool:
        t.start()
    t0 = time.monotonic()
    next_tick = 0.0
    for offset, model, line, is_label in events:
        now = time.monotonic() - t0
        if offset > now:
            time.sleep(offset - now)
            now = offset
        if on_tick is not None and now >= next_tick:
            on_tick(now, qps_at(now, base_qps, peak_qps, period_s))
            next_tick = now + 1.0
        q.put((model, line, is_label))
        # only the pacer writes the sent counters: no lock needed
        if is_label:
            c.labels_sent += 1
        else:
            c.sent += 1
    for _ in pool:
        q.put(None)
    for t in pool:
        t.join()
    elapsed = time.monotonic() - t0
    lat = sorted(c.latencies_ms)
    summary = {
        "sent": c.sent,
        "ok": c.ok,
        "shed": c.shed,
        "err": c.err,
        "p50_ms": _pct(lat, 0.50),
        "p99_ms": _pct(lat, 0.99),
        "elapsed_s": round(elapsed, 3),
        "offered_qps": round(c.sent / elapsed, 2) if elapsed > 0 else None,
        "base_qps": base_qps,
        "peak_qps": peak_qps,
        "period_s": period_s,
        "seed": seed,
    }
    if zipf_alpha > 0:
        summary["zipf_alpha"] = zipf_alpha
    if mix:
        summary["tenant_mix"] = {m: round(w, 6) for m, w in mix.items()}
    if label_frac > 0:
        summary.update(labels_sent=c.labels_sent, label_ok=c.label_ok,
                       label_err=c.label_err,
                       label_delay_p50_s=label_delay_p50_s,
                       label_delay_p95_s=label_delay_p95_s)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="open-loop diurnal load over the serve line protocol")
    ap.add_argument("--addr", required=True,
                    help="router/engine host:port (what `launch route` "
                    "announced as ROUTING)")
    ap.add_argument("--base-qps", dest="base_qps", type=float, default=20.0)
    ap.add_argument("--peak-qps", dest="peak_qps", type=float, default=100.0)
    ap.add_argument("--period", dest="period_s", type=float, default=30.0,
                    help="seconds per diurnal cycle (default 30)")
    ap.add_argument("--duration", dest="duration_s", type=float,
                    help="seconds to run (default: one period)")
    ap.add_argument("--dim", type=int, default=1024,
                    help="feature dim of the generated rows (default 1024)")
    ap.add_argument("--nnz", type=int, default=16)
    ap.add_argument("--rows-per-request", dest="rows_per_request", type=int,
                    default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workers", type=int, default=8,
                    help="sender threads (default 8)")
    ap.add_argument("--zipf-alpha", dest="zipf_alpha", type=float,
                    default=0.0,
                    help="Zipf skew of feature popularity (0 = uniform, "
                    "the historical default; ~1.1 = realistic hot set)")
    ap.add_argument("--tenant-mix", dest="tenant_mix",
                    help="per-tenant traffic mix, e.g. v1=0.8,v2=0.2 "
                    "(requests pick a model by weight over MODEL-scoped "
                    "connections)")
    ap.add_argument("--label-frac", dest="label_frac", type=float,
                    default=0.0,
                    help="fraction of requests sent in ID mode with a "
                    "delayed LABEL line following (default 0 = no labels)")
    ap.add_argument("--label-delay-p50", dest="label_delay_p50_s",
                    type=float, default=1.0,
                    help="label-delay distribution median, seconds")
    ap.add_argument("--label-delay-p95", dest="label_delay_p95_s",
                    type=float, default=5.0,
                    help="label-delay distribution p95, seconds")
    args = ap.parse_args(argv)
    summary = run_load(args.addr, base_qps=args.base_qps,
                       peak_qps=args.peak_qps, period_s=args.period_s,
                       duration_s=args.duration_s, dim=args.dim,
                       nnz=args.nnz, rows_per_request=args.rows_per_request,
                       seed=args.seed, workers=args.workers,
                       zipf_alpha=args.zipf_alpha,
                       tenant_mix=args.tenant_mix,
                       label_frac=args.label_frac,
                       label_delay_p50_s=args.label_delay_p50_s,
                       label_delay_p95_s=args.label_delay_p95_s)
    # ONE JSON line: the CLI's scriptable contract
    print(json.dumps(summary))
    return 0 if summary["err"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
