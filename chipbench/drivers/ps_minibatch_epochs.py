"""Traffic kind ``ps_minibatch_epochs``: the asynchronous parameter-server
job with the launcher's ``BATCH_SIZE`` set: every worker keeps its shard
on the chip and walks it a window of ``B`` rows a round, in file order,
the next round's device chain running while the last round's fused
push-pull is in flight, and each epoch's end waiting for it.

Everything but the window is ``ps_epochs``'s, whose ``prepare``, ``Job``,
``in_threads``, ``record``, ``compare`` and counter readers
this module imports; that file is not edited.  What the window adds:

* a program that keeps no count of its windowed rounds cannot be held to
  them: the driver says which series it misses and leaves before a row
  is made (:func:`needs_the_windows_series`);
* ``correct`` holds each recorded gradient against the reference's
  gradient **of the window the rule gives for that round**
  (``families/dense_ps_minibatch.window``: worked out from upstream's
  rule, nothing the program says), at the weights the worker computed
  on: :func:`by_round` lays the recorded rounds out as ``ps_epochs.compare``
  reads them, one (window's rows, round) a pair;
* four rows of its own, each admitting 0 only (:func:`compare_windows`):
  ``window_rows_short``, ``resident_short``, ``lineage_broken``,
  ``two_pass_rounds``: PERF.md section 2;
* the recorded phase keeps a digest of the weights every round computed
  on and of every reply its worker's exchanges returned
  (:class:`Lineage`), which is what ``lineage_broken`` compares;
* ``train_samples_per_s`` counts the real rows of every round; the step's
  byte floor is asked for at the window's rows; the run carries ``mb``
  for the ``mb_*`` readers (:func:`mb_side`), from the tracer's events of
  the window.  A ``push`` span that is an epoch's drain carries
  ``drain`` among its stats (``obs.tracing.loop_span``); every other
  ``push`` is the wait for the exchange the round's compute failed to
  hide.

    python3 -m chipbench.drivers.ps_minibatch_epochs --workload <name> --seeds 1,2,3 [--controls 2]

reads what ``correct`` compares, seed after seed in one process: for the
program, for its control (``control.program`` in the program's place, on
the first ``--controls`` seeds) and for the reference computed in
``control.precision`` and put where the program's gradients and test
logloss stand.  ``--rehearse`` runs the tiny sizes anywhere.
"""

from __future__ import annotations

import argparse
import gc
import hashlib
import json
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import reference, trace_reduce
from chipbench.drivers import ps_epochs
from chipbench.drivers.ps_bsp_epochs import _grad_paths
from chipbench.drivers.ps_epochs import (
    STEP_PROGRAM,
    WINDOW_MARGIN,
    Job,
    _client_ops,
    _per_worker,
    _rows_of,
    _servers,
    _unacknowledged,
    in_threads,
    prepare,
)
from chipbench.drivers.train_stream import (
    _peak_bytes,
    _rss_peak_mib,
    effective_config,
)

#: the series a program has to keep for its windows to be counted
WINDOW_ROUNDS = "distlr_ps_window_rounds_total"
WINDOW_ROWS = "distlr_ps_window_rows_total"
RESIDENT = "distlr_ps_resident_bytes"
#: spans that carry rows to the device: none may open inside the window
PLACING = ("h2d", "shard_put")
#: a round's device chain, which the last round's exchange runs under
CHAIN = ("w_put", "compute", "grad_d2h")


def needs_the_windows_series() -> None:
    """A program that keeps no count of its windowed rounds streams its
    minibatches and cannot be held to the configuration's guarantees:
    leave at once, before a row is made."""
    import distlr_tpu.train.ps_trainer  # noqa: F401  (registers its series)
    from distlr_tpu.obs.registry import get_registry

    missing = [s for s in (WINDOW_ROUNDS, WINDOW_ROWS)
               if get_registry().get(s) is None]
    if missing:
        raise SystemExit(
            "chipbench ps_minibatch_epochs: this program's PSWorker keeps no "
            f"{missing}: a minibatch worker of it streams every batch from "
            "the host, so the cell's windows cannot be counted nor its shard "
            "seen on the device; the cell runs from the commit that serves a "
            "resident shard as windows")


def _digest(a: np.ndarray) -> bytes:
    return hashlib.blake2b(np.ascontiguousarray(a).view(np.uint8),
                           digest_size=16).digest()


class Lineage:
    """Stands round one worker's gradient call and its connection's
    exchanges during the recorded phase: a digest of the weights every
    round computed on, of the weights the worker held or pulled when the
    phase began, and of every reply a push-pull returned, in order."""

    def __init__(self, worker):
        self.worker, self.step = worker, worker.grad_step
        self.kv_calls = {name: getattr(worker.kv, name)
                         for name in ("pull", "push_pull")}
        held = worker._w_cache
        self.opening = None if held is None else _digest(held)
        self.rounds: list[bytes] = []
        self.replies: list[bytes] = []
        worker.grad_step = self
        worker.kv.pull = self._pull
        worker.kv.push_pull = self._push_pull

    def __call__(self, wf, batch):
        self.rounds.append(_digest(wf))
        return self.step(wf, batch)

    def _pull(self, *a, **kw):
        w = self.kv_calls["pull"](*a, **kw)
        if self.opening is None and not self.rounds:
            self.opening = _digest(w)
        return w

    def _push_pull(self, *a, **kw):
        w = self.kv_calls["push_pull"](*a, **kw)
        self.replies.append(_digest(w))
        return w

    def remove(self) -> dict:
        self.worker.grad_step = self.step
        for name in self.kv_calls:
            delattr(self.worker.kv, name)  # the class's own again
        return {"opening": self.opening, "rounds": self.rounds,
                "replies": self.replies}


def lineage_broken(lin: dict, rounds_an_epoch: int) -> int:
    """Recorded rounds whose weights differ in any bit from what the
    guarantee names: the epoch's opening weights (the reply to the last
    push of the epoch before, which its end waited for; in the first
    epoch what the worker held or pulled) at rounds 0 and 1 of an epoch,
    the reply to the worker's own push of round ``j - 2`` after: one
    push in flight, and none across an epoch's end."""
    replies, broken = lin["replies"], abs(len(lin["rounds"]) - len(lin["replies"]))
    for i, on in enumerate(lin["rounds"]):
        epoch, j = divmod(i, rounds_an_epoch)
        at = i - 2 if j >= 2 else epoch * rounds_an_epoch - 1
        want = (lin["opening"] if at < 0
                else replies[at] if at < len(replies) else None)
        broken += on != want
    return broken


def _window_counts() -> dict:
    """What the program has counted of its windows, over the ranks, and
    the bytes each rank keeps resident."""
    from distlr_tpu.obs.registry import family_total, get_registry

    held = get_registry().get(RESIDENT)
    return {"rounds": int(family_total(WINDOW_ROUNDS)),
            "rows": int(family_total(WINDOW_ROWS)),
            "two_pass": _grad_paths().get("two_pass", 0),
            "resident": {labels[0]: int(child.value)
                         for labels, child in (held.children() if held else [])}}


def _windows_short(before: dict, after: dict, rounds: int, rows: int) -> dict:
    """How far the rise of the program's window counters is from the
    rounds and the real rows the phase ran, and the rounds it counted
    under the two-pass program."""
    return {"window_rows_short": (
                abs(after["rounds"] - before["rounds"] - rounds)
                + abs(after["rows"] - before["rows"] - rows)),
            "two_pass_rounds": after["two_pass"] - before["two_pass"]}


def record(job: Job, epochs: int, keep: int, batch: int) -> dict:
    """``ps_epochs.record`` over ``epochs`` epochs with a :class:`Lineage`
    round every worker and the program's window counters read before and
    after."""
    per = reference.family("dense_ps_minibatch").rounds_an_epoch(
        job.rows_per_worker, batch)
    before = _window_counts()
    taps = [Lineage(w) for w in job.workers]
    try:
        got = ps_epochs.record(job, epochs, keep)
    finally:
        lineage = [t.remove() for t in taps]
    after = _window_counts()
    got.update(_windows_short(before, after, len(job.workers) * epochs * per,
                              len(job.workers) * epochs * job.rows_per_worker))
    got["lineage_broken"] = sum(lineage_broken(lin, per) for lin in lineage)
    got["resident"] = after["resident"]
    return got


def by_round(job_rows: dict, got: dict, batch: int) -> tuple[dict, dict]:
    """The recorded rounds as ``ps_epochs.compare`` and ``lowered`` read
    them, a worker's whole shard against its rounds, but one entry a
    (worker, round), the shard cut to the rows upstream's rule gives that
    round: the reference's gradient is then of exactly those rows."""
    window = reference.family("dense_ps_minibatch").window
    shards, first = [], []
    for shard, rounds in zip(job_rows["shards"], got["first"]):
        for k, kept in enumerate(rounds):
            at = window(k, len(shard[-1]), batch)
            shards.append(tuple(a[at] for a in shard))
            first.append([kept])
    return {**job_rows, "shards": shards}, {**got, "first": first}


def compare_windows(got: dict, shard_bytes: int, workers: int, limits: dict,
                    window: dict | None = None) -> list[dict]:
    """The window's own rows, each beside its limit (PERF.md section 2);
    ``window``: ``window_rows_short``, ``two_pass_rounds`` and ``placed``
    (spans that carry rows to the device) of a window, where one was run."""
    rows = []

    def row(name, value):
        rows.append({"name": name, "value": float(value),
                     "limit": float(limits[name]),
                     "ok": bool(np.isfinite(value) and value <= limits[name])})

    extra = window or {"window_rows_short": 0, "two_pass_rounds": 0,
                       "placed": 0}
    row("window_rows_short",
        got["window_rows_short"] + extra["window_rows_short"])
    # every worker's shard stays where load_data put it
    held = got["resident"]
    row("resident_short",
        sum(max(0, shard_bytes - held.get(str(r), 0)) for r in range(workers))
        + extra["placed"])
    row("lineage_broken", got["lineage_broken"])
    row("two_pass_rounds", got["two_pass_rounds"] + extra["two_pass_rounds"])
    return rows


def compare(job_rows: dict, got: dict, family: str, lr: float, dim: int,
            batch: int, limits: dict, window: dict | None = None) -> list[dict]:
    """The sibling's six numbers with every gradient held against its
    round's window, and the window's four."""
    rows_by_round, got_by_round = by_round(job_rows, got, batch)
    return (ps_epochs.compare(
                rows_by_round, got_by_round, family, lr, limits,
                window["unacknowledged"] if window else None)
            + compare_windows(got, len(job_rows["shards"][0][-1]) * dim * 4,
                              len(job_rows["shards"]), limits, window))


def lowered(job_rows: dict, got: dict, family: str, precision: str,
            batch: int) -> dict:
    """The recorded phase with the reference, computed in ``precision``,
    in the program's place, as ``ps_epochs.lowered`` has it: its gradient
    of each round's window at the weights the worker computed on, its
    test logloss.  What the servers and the windows did stays as
    recorded."""
    grad = reference.family(family).window_gradient
    first = [[(w, np.asarray(grad(w, *shard, k, batch, precision)))
              for k, (w, _pushed) in enumerate(rounds)]
             for shard, rounds in zip(job_rows["shards"], got["first"])]
    ll = reference.logloss(family, got["w_after"], *job_rows["test"],
                           precision=precision)
    return {**got, "first": first, "test_logloss": float(ll)}


def mb_side(events: list[dict], dropped: int) -> dict:
    """What the ``mb_*`` readers take from the tracer's events of a call
    that has just ended: a worker's ``compute`` spans start to start; its
    ``push`` spans, an epoch's drains (``drain`` among their stats) apart
    from the others; and how much of each ``wire`` span (the fused
    push-pull of round ``k`` on the comm thread) lies under the same
    worker's device chain of round ``k + 1``."""
    starts: dict = {}
    chain: dict = {}
    wires = []
    push = {"wait": [0.0, 0], "drain": [0.0, 0]}
    for e in events:
        args = e.get("args", {})
        rank, step = args.get("rank"), args.get("step")
        if rank is None or step is None:
            continue
        lo, hi = e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6
        if e["name"] in CHAIN:
            chain.setdefault((rank, step), []).append((lo, hi))
            if e["name"] == "compute":
                starts.setdefault(rank, []).append(lo)
        elif e["name"] == "wire":
            wires.append((rank, step, lo, hi))
        elif e["name"] == "push":
            side = push["drain" if args.get("drain") else "wait"]
            side[0] += hi - lo
            side[1] += 1
    gaps = [b - a for at in map(sorted, starts.values())
            for a, b in zip(at, at[1:])]
    under = sum(min(hi, e) - max(lo, s)
                for rank, step, lo, hi in wires
                for s, e in trace_reduce.union(chain.get((rank, step + 1), []))
                if min(hi, e) > max(lo, s))
    return {"round_s": sum(gaps), "rounds": len(gaps),
            "wire_s": sum(hi - lo for _r, _s, lo, hi in wires),
            "wire_under_chain_s": under, "wires": len(wires),
            "push": {k: {"seconds": s, "count": n}
                     for k, (s, n) in push.items()},
            "events_dropped": dropped}


def run(ctx) -> dict:
    """``ctx``: cell, seed, seconds, trace, rehearsal, devices, compiles,
    t_start, say.  Returns what ``chipbench.run`` prints."""
    needs_the_windows_series()
    import jax

    from distlr_tpu.obs.tracing import get_tracer

    conf = effective_config(ctx.cell, ctx.rehearsal)
    prog, traffic, family = conf["program"], ctx.cell.traffic, conf["family"]
    lr, workers = float(prog["learning_rate"]), int(prog["num_workers"])
    dim, batch = int(prog["num_feature_dim"]), int(prog["batch_size"])
    platform = ctx.devices[0].platform

    job = prepare(conf, ctx.seed, ctx.say)
    failed = True
    try:
        n = job.rows_per_worker
        per = reference.family(family).rounds_an_epoch(n, batch)
        # -- set-up: the recorded phase, then the pace ------------------
        got = record(job, int(traffic["recorded_epochs"]),
                     int(traffic["checked_rounds"]), batch)
        pace_epochs = int(traffic["pace_epochs"])
        pace = in_threads(job, lambda w: w.fit(epochs=pace_epochs)) / pace_epochs
        epochs = max(1, math.ceil(WINDOW_MARGIN * ctx.seconds / pace))
        ctx.say(f"recorded rounds={got['rounds']} acked={got['acked']} "
                f"rounds_an_epoch={per} resident_bytes="
                f"{sorted(got['resident'].values())} "
                f"epoch_pace_s={pace:.5f} window_epochs={epochs} "
                + "compiles seconds={seconds:.2f} count={count} cache_hits="
                "{hits} cache_misses={misses}".format(**ctx.compiles.snapshot()))

        # -- the window: one fit a worker, at once ----------------------
        tracer = get_tracer()
        compiled_before = ctx.compiles.snapshot()
        counted_before = [(w.timer.samples, w.timer.steps) for w in job.workers]
        ops, servers = _client_ops(), _servers(job)
        windows = _window_counts()
        tracer.reset()
        setup_s = time.perf_counter() - ctx.t_start
        window_wall = in_threads(job, lambda w: w.fit(epochs=epochs))
        spans = tracer.breakdown()
        doc = tracer.chrome_trace()
        mb = mb_side(doc["traceEvents"],
                     int(doc["otherData"].get("dropped_events", 0)))
        ops_after, servers_after = _client_ops(), _servers(job)
        windows_after = _window_counts()
        # the yardstick counts the work itself: E passes over every shard,
        # a round the real rows of its window
        rounds_done, rows_done = workers * epochs * per, workers * epochs * n
        counted = [(w.timer.samples - s, w.timer.steps - k)
                   for w, (s, k) in zip(job.workers, counted_before)]
        acked = ops_after["acked"] - ops["acked"]
        counts_agree = (counted == [(epochs * n, epochs * per)] * workers
                        and acked == rounds_done)
        bad_ops = ops_after["bad"] - ops["bad"]
        compiled_in_window = ctx.compiles.count - compiled_before["count"]
        in_window = {
            **_windows_short(windows, windows_after, rounds_done, rows_done),
            "placed": sum(spans.get(s, {"count": 0})["count"] for s in PLACING),
            "unacknowledged": _unacknowledged(servers, servers_after, acked)}
        ctx.say(f"window wall_s={window_wall:.3f} epochs={epochs} "
                f"rounds={rounds_done} rows={rows_done} "
                f"program_counted={counted} acked_pushes={acked} "
                f"failed_or_retried_ops={bad_ops} "
                f"compiles_in_window={compiled_in_window} window_rounds="
                f"{windows_after['rounds'] - windows['rounds']} window_rows="
                f"{windows_after['rows'] - windows['rows']} "
                f"two_pass_rounds={in_window['two_pass_rounds']} "
                f"placing_spans={in_window['placed']} "
                f"host_rss_peak_mib={_rss_peak_mib()}")
        ctx.say("window spans, a worker's mean ms: " + " ".join(
            f"{name}={1e3 * s['seconds'] / s['count']:.3f}"
            for name, s in sorted(spans.items()) if s["count"]))
        ctx.say("window pushes, mean ms: " + " ".join(
            f"{k}={1e3 * v['seconds'] / max(v['count'], 1):.3f} n={v['count']}"
            for k, v in mb["push"].items())
            + f" wire_under_next_chain={mb['wire_under_chain_s']:.3f}s of "
            f"{mb['wire_s']:.3f}s events_dropped={mb['events_dropped']}")

        run = {
            "cell": ctx.cell.name, "family": family, "chips": 1,
            "device_kind": ctx.devices[0].device_kind, "platform": platform,
            "setup_compile": compiled_before,
            "compiles_in_window": compiled_in_window,
            "window": {"wall_s": window_wall, "steps": rounds_done,
                       "rows": rows_done,
                       "spans": _per_worker(spans, workers)},
            # a step reads a window of the resident matrix, not the shard
            "step": {"rows": batch, "dim": dim, "nnz": batch * job.nnz_width},
            "mb": mb,
            "trace": None,
        }

        # -- a traced run: a short fit of its own under the profiler ----
        if ctx.trace:
            t_epochs = max(1, min(
                math.ceil(traffic["trace_seconds"] / (window_wall / epochs)),
                int(traffic["trace_max_epochs"])))
            trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            try:
                tracer.reset()
                host_epoch = time.perf_counter()
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                with jax.profiler.trace(trace_dir, profiler_options=options):
                    with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
                        anchor_host = time.perf_counter()
                        in_threads(job, lambda w: w.fit(epochs=t_epochs))
                traced_s = time.perf_counter() - host_epoch
                host_spans = [(e["name"], e["tid"],
                               host_epoch + e["ts"] * 1e-6, e["dur"] * 1e-6)
                              for e in tracer.chrome_trace()["traceEvents"]]
                xtrace = trace_reduce.load_xplane(
                    trace_reduce.find_xplane(trace_dir))
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
            window = trace_reduce.window_of(xtrace)
            programs = sorted({name for p in trace_reduce.device_planes(xtrace)
                               for name, _s, _d in xtrace[p].get(
                                   trace_reduce.MODULES_LINE, [])})
            ctx.say(f"traced epochs={t_epochs} fit_and_export_s={traced_s:.2f} "
                    f"programs={programs}")
            run["trace"] = {
                "xtrace": xtrace, "window": window,
                "steps": workers * t_epochs * per, "host_spans": host_spans,
                "clock_offset": window[0] - anchor_host,
                "step_program": STEP_PROGRAM,
            }

        memory_peak = _peak_bytes(ctx.devices[:1])
        # the product's own way out: final pull, exit barrier, rank 0
        # retires the group
        in_threads(job, lambda w: w.finish(save=False))
        finite = all(bool(np.isfinite(w.final_weights).all())
                     for w in job.workers)
        on_device = (len(job.pinned) == workers
                     and all(f"train -> {platform}:" in ln for ln in job.pinned))
        rows_kept = _rows_of(job)
        failed = False
    finally:
        job.close(failed)
    del job
    gc.collect()  # the shards leave the device before the reference runs

    # -- correct ---------------------------------------------------------
    t = time.perf_counter()
    rows = compare(rows_kept, got, family, lr, dim, batch, conf["limits"],
                   in_window)
    ctx.say(f"reference gradients of {workers} x {len(got['first'][0])} rounds' "
            f"windows check_s={time.perf_counter() - t:.2f}")
    for r in rows:
        ctx.say("compared {name} value={value:.6g} limit={limit:.6g} "
                "ok={ok}".format(**r))
    correct = (all(r["ok"] for r in rows) and finite and counts_agree
               and compiled_in_window == 0 and bad_ops == 0 and on_device)
    if not on_device:
        ctx.say(f"the workers' steps are not all on {platform}")

    return {
        "correct": correct,
        "attempted": rounds_done,
        "failed": rounds_done if not finite else min(bad_ops, rounds_done),
        "end_to_end": {
            "train_samples_per_s": rows_done / window_wall,
            "setup_s": setup_s,
        },
        "memory_peak_bytes": memory_peak,
        "compared": rows,
        "run": run,
    }


def main(argv=None) -> int:
    from chipbench import manifest
    from chipbench import run as harness

    ap = argparse.ArgumentParser(prog="chipbench.drivers.ps_minibatch_epochs")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    needs_the_windows_series()
    cell = manifest.Cell(manifest.load_benchmark(), args.workload)
    if not args.rehearse:
        harness.place_compile_cache()
    harness.take_devices(cell.chips, args.rehearse)
    conf = effective_config(cell, args.rehearse)
    traffic, family, prog = cell.traffic, conf["family"], conf["program"]
    lr, dim = float(prog["learning_rate"]), int(prog["num_feature_dim"])
    batch = int(prog["batch_size"])
    say = harness.Context.say
    low = conf["control"]["precision"]
    readings: dict[str, dict[str, list]] = {"program": {}, "control": {},
                                            low: {}}
    limits: dict[str, float] = {}

    def note(tag, seed, rows):
        for r in rows:
            readings[tag].setdefault(r["name"], []).append(r["value"])
            limits[r["name"]] = r["limit"]
        say(f"{tag} seed={seed} " + " ".join(
            f"{r['name']}={r['value']:.4g}" for r in rows))

    def read(tag, seed, over):
        job = prepare(conf, seed, say, program_over=over)
        failed = True
        try:
            got = record(job, int(traffic["recorded_epochs"]),
                         int(traffic["checked_rounds"]), batch)
            kept = _rows_of(job)
            failed = False
        finally:
            job.close(failed)
        del job
        gc.collect()
        note(tag, seed, compare(kept, got, family, lr, dim, batch,
                                conf["limits"]))
        if over is None:
            note(low, seed, compare(
                kept, lowered(kept, got, family, low, batch), family, lr, dim,
                batch, conf["limits"]))

    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        read("program", seed, None)
        if k < args.controls:
            read("control", seed, conf["control"]["program"])
    summary = {name: {"sound_max": max(vals),
                      "control_min": min(readings["control"].get(
                          name, [float("nan")])),
                      f"{low}_min": min(readings[low][name]),
                      "limit": limits[name]}
               for name, vals in readings["program"].items()}
    print("CONTROL " + json.dumps({"cell": cell.name, "seeds": args.seeds,
                                   "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
