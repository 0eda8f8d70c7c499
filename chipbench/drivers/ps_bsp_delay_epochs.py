"""Traffic kind ``ps_bsp_delay_epochs``: the lock-step (BSP)
parameter-server job under bounded delay, tau = 1 (``ps_max_delay``):
``sync=1`` servers and worker threads in one process, whole-shard rounds
in a closed loop, every worker's round *k* computed on the weights after
round *k* - 2 while its push of round *k* - 1 stands withheld at the
servers' barrier.

Set-up, window and traced run are the lock-step kind's
(``chipbench/drivers/ps_bsp_epochs.py``, whose ``record_rounds`` and
counter readers this module imports, and through it ``ps_epochs``'s
``prepare``, ``in_threads`` and ``lowered``); the lineage's tap is the
minibatch kind's (``ps_minibatch_epochs.Lineage``).  None of those files
is edited.  What the delay adds:

* a program with no ``Config.ps_max_delay`` and no
  ``distlr_ps_delayed_rounds_total`` cannot run the configuration: the
  driver says what it misses and leaves before a row is made
  (:func:`needs_the_delay`);
* the recorded phase is ONE ``fit`` (both ends with nothing in flight),
  with a recorder round every worker's gradient call, a
  :class:`Replies` tap round its connection (a digest of every round's
  weights and of every reply, and the first replies as arrays) and the
  program's counter and the servers read before and after;
* ``correct`` follows the DELAYED trajectory
  (``families/dense_ps_bsp_delay.rounds``): the program's first updates,
  read from the replies its pushes returned, against the reference's;
  each recorded gradient against the reference's at the weights the
  worker recorded; and three rows that admit 0 only, ``lineage_broken``,
  ``delay_miscount`` and ``in_flight_at_return``: see :func:`compare`,
  the configuration's ``guarantees`` and PERF.md section 2;
* the run carries ``dl`` for the ``dl_*`` readers (:func:`dl_side`), from
  the tracer's events of the window and the counter's rise.

    python3 -m chipbench.drivers.ps_bsp_delay_epochs --workload <name> --seeds 1,2,3 [--controls 2]

reads what ``correct`` compares, seed after seed in one process, for the
program, for its control (``control.program`` in the program's place:
the lock-step job, on the first ``--controls`` seeds) and for the
reference computed in ``control.precision`` and put where the program's
gradients stand.  ``--rehearse`` runs the tiny sizes anywhere.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import reference, trace_reduce
from chipbench.drivers.ps_bsp_epochs import (
    _grad_paths,
    _round_miscount,
    _same_bits,
    needs_the_barriers_counters,
    record_rounds,
)
from chipbench.drivers.ps_epochs import (
    STEP_PROGRAM,
    WINDOW_MARGIN,
    _client_ops,
    _per_worker,
    _rows_of,
    _servers,
    _unacknowledged,
    in_threads,
    lowered,
    prepare,
)
from chipbench.drivers.ps_minibatch_epochs import Lineage, lineage_broken
from chipbench.drivers.train_stream import (
    _peak_bytes,
    _rel_gap,
    _rss_peak_mib,
    effective_config,
)

#: the series a program has to keep for its delayed rounds to be counted
DELAYED = "distlr_ps_delayed_rounds_total"


def needs_the_delay() -> None:
    """A program that cannot be told to run a round under its push, or
    keeps no count of how stale each round's weights were, cannot run the
    configuration: leave at once, before a row is made."""
    import distlr_tpu.train.ps_trainer  # noqa: F401  (registers its series)
    from distlr_tpu import Config
    from distlr_tpu.obs.registry import get_registry

    missing = []
    if "ps_max_delay" not in {f.name for f in dataclasses.fields(Config)}:
        missing.append("Config.ps_max_delay")
    if get_registry().get(DELAYED) is None:
        missing.append(DELAYED)
    if missing:
        raise SystemExit(
            "chipbench ps_bsp_delay_epochs: this program has no "
            f"{missing}: its lock-step workers wait for every reply before "
            "the next gradient, so the configuration's bounded delay cannot "
            "be asked for nor its rounds counted; the cell runs from the "
            "commit that adds them")


class Replies(Lineage):
    """:class:`Lineage` round one worker, plus the first ``keep`` replies
    its push-pulls returned, as arrays: reply *k* is ``w_{k+1}``."""

    def __init__(self, worker, keep: int):
        self.keep = keep
        self.kept: list[np.ndarray] = []
        super().__init__(worker)

    def _push_pull(self, *a, **kw):
        w = super()._push_pull(*a, **kw)
        if len(self.kept) < self.keep:
            self.kept.append(np.array(w))
        return w


def _delayed_counts() -> dict:
    """What the program has counted of its rounds by how far behind their
    weights were, over the ranks."""
    from distlr_tpu.obs.registry import get_registry

    fam = get_registry().get(DELAYED)
    out = {"0": 0, "1": 0}
    for labels, child in fam.children() if fam else []:
        out[labels[-1]] = out.get(labels[-1], 0) + int(child.value)
    return out


def _resident_bytes() -> list[int]:
    """The bytes each rank says it keeps on its step's device."""
    from distlr_tpu.obs.registry import get_registry

    held = get_registry().get("distlr_ps_resident_bytes")
    return sorted(int(c.value) for _l, c in (held.children() if held else []))


def _delay_miscount(before: dict, after: dict, fits: int, rounds: int) -> int:
    """How far the counter's rise is from what the rounds run imply:
    ``fits`` calls of ``fit`` (one a worker) that ran ``rounds`` rounds
    between them count one round each under ``behind="0"`` and every
    other one under ``"1"``, and none elsewhere."""
    rise = {k: after.get(k, 0) - before.get(k, 0)
            for k in set(before) | set(after)}
    return (abs(rise.pop("0", 0) - fits) + abs(rise.pop("1", 0)
                                               - (rounds - fits))
            + sum(abs(v) for v in rise.values()))


def _in_flight(job) -> int:
    """Workers whose exchange still holds a future, and pushes a server
    still holds for a round it has not released."""
    return (sum(w.in_flight for w in job.workers)
            + sum(s["pending_sync_pushes"] for s in _servers(job)))


def record(job, rounds: int, keep: int) -> dict:
    """``ps_bsp_epochs.record_rounds`` as ONE ``fit`` of ``rounds`` rounds
    a worker, with a :class:`Replies` tap round every worker and the
    program's counter read before and after."""
    counted = _delayed_counts()
    taps = [Replies(w, keep) for w in job.workers]
    try:
        got = record_rounds(job, rounds, keep)
    finally:
        lineage = [t.remove() for t in taps]
    workers = len(job.workers)
    got["replies"] = [t.kept for t in taps]
    # a fit is the lineage rule's "epoch": rounds 0 and 1 on what it
    # began with, round k on the reply to the worker's own push k - 2
    got["lineage_broken"] = sum(lineage_broken(lin, rounds) for lin in lineage)
    got["delay_miscount"] = _delay_miscount(
        counted, _delayed_counts(), workers, sum(got["rounds"]))
    got["in_flight_at_return"] = _in_flight(job)
    return got


def compare(job_rows: dict, got: dict, family: str, lr: float, limits: dict,
            window: dict | None = None) -> list[dict]:
    """Each number compared, beside its limit (PERF.md section 2);
    ``window``: ``round_miscount``, ``unacknowledged``, ``delay_miscount``
    and ``in_flight_at_return`` of a window, where one was run."""
    fam = reference.family(family)
    shards, first, replies = job_rows["shards"], got["first"], got["replies"]
    workers = len(shards)
    rows = []

    def row(name, value, limit_key=None):
        limit = limits[limit_key or name]
        rows.append({"name": name, "value": float(value),
                     "limit": float(limit),
                     "ok": bool(np.isfinite(value) and value <= limit)})

    # every worker computed round k on the same weights and got the same
    # reply to its push of round k, bit for bit, and round 0 ran on what
    # the servers held before it
    lead, lead_replies = [w for w, _g in first[0]], replies[0]
    row("weights_disagree",
        sum(not all(_same_bits(w, ref) for (w, _g), ref in zip(rounds, lead))
            for rounds in first[1:])
        + sum(len(r) != len(lead_replies)
              or not all(_same_bits(a, b) for a, b in zip(r, lead_replies))
              for r in replies[1:])
        + (not _same_bits(lead[0], got["w_before"])))
    # the gradient a worker pushed, against the reference's on that
    # worker's rows at the weights it computed on: the worst worker and
    # round
    norm_gap = diff = 0.0
    for shard, rounds in zip(shards, first):
        for weights, pushed in rounds:
            ref = np.asarray(fam.gradient(weights, *shard))
            n_ref = max(float(np.linalg.norm(ref)), 1e-30)
            norm_gap = max(norm_gap, _rel_gap(np.linalg.norm(pushed), n_ref))
            diff = max(diff, float(np.linalg.norm(pushed - ref)) / n_ref)
    row("grad_norm_rel_gap", norm_gap)
    row("grad_diff_rel", diff)
    # the trajectory: the program's first updates, read from the replies
    # its pushes returned (reply k is w_{k+1}), against the reference's
    # delayed rounds from the weights the servers held before the first
    n = len(lead) - 1
    w_prog = [got["w_before"], *lead_replies[:n]]
    w_ref = [got["w_before"], *fam.rounds(got["w_before"], shards, lr, n)]
    # a reply that never came is no update at all
    norm_gap = diff = float("inf") if len(w_prog) <= n else 0.0
    for k in range(len(w_prog) - 1):
        u = w_prog[k + 1].astype(np.float64) - w_prog[k]
        u_ref = w_ref[k + 1].astype(np.float64) - w_ref[k]
        n_ref = max(float(np.linalg.norm(u_ref)), 1e-30)
        norm_gap = max(norm_gap, _rel_gap(np.linalg.norm(u), n_ref))
        diff = max(diff, float(np.linalg.norm(u - u_ref)) / n_ref)
    row("update_norm_rel_gap", norm_gap)
    row("update_diff_rel", diff)
    # conservation: over the recorded fit, both ends with nothing in
    # flight, what the servers hold moved by the mean of what was pushed
    moved = got["w_after"].astype(np.float64) - got["w_before"]
    pushed = lr / workers * got["pushed_sum"]
    row("conservation_rel", np.linalg.norm(moved + pushed)
        / max(float(np.linalg.norm(pushed)), 1e-30))
    ref_ll = reference.logloss(family, got["w_after"], *job_rows["test"])
    row("test_logloss_rel_gap", _rel_gap(got["test_logloss"], ref_ll))
    row("round_miscount_recorded", got["round_miscount"], "round_miscount")
    row("unacknowledged_recorded", got["unacknowledged"],
        "unacknowledged_pushes")
    if window is not None:
        row("round_miscount_window", window["round_miscount"],
            "round_miscount")
        row("unacknowledged_window", window["unacknowledged"],
            "unacknowledged_pushes")
    extra = window or {"delay_miscount": 0, "in_flight_at_return": 0}
    row("lineage_broken", got["lineage_broken"])
    row("delay_miscount", got["delay_miscount"] + extra["delay_miscount"])
    row("in_flight_at_return",
        got["in_flight_at_return"] + extra["in_flight_at_return"])
    return rows


def dl_side(events: list[dict], dropped: int, counted: dict) -> dict:
    """What the ``dl_*`` readers take from the tracer's events of a call
    that has just ended and from the counter's rise over it: a worker's
    ``push`` spans, the drains (``drain`` among their stats) apart from
    the waits no compute hid; its ``compute`` spans by their
    ``in_flight``; and how much of each ``wire`` span (the fused
    push-pull of round *k* on the comm thread) lies under the same
    worker's ``compute`` of round *k* + 1 that says a push was in
    flight."""
    under_flight: dict = {}
    wires = []
    push = {"wait": [0.0, 0], "drain": [0.0, 0]}
    flying = {0: 0, 1: 0}
    for e in events:
        args = e.get("args", {})
        rank, step = args.get("rank"), args.get("step")
        if rank is None:
            continue
        lo, hi = e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6
        if e["name"] == "compute" and "in_flight" in args:
            flying[int(args["in_flight"])] += 1
            # a step marker carries its step as the annotation's own
            if args["in_flight"] and step is not None:
                under_flight[(rank, step)] = (lo, hi)
        elif e["name"] == "wire" and step is not None:
            wires.append((rank, step, lo, hi))
        elif e["name"] == "push":
            side = push["drain" if args.get("drain") else "wait"]
            side[0] += hi - lo
            side[1] += 1
    under = 0.0
    for rank, step, lo, hi in wires:
        s, e = under_flight.get((rank, step + 1), (hi, hi))
        under += max(min(hi, e) - max(lo, s), 0.0)
    rounds = counted["0"] + counted["1"]
    return {"wire_s": sum(hi - lo for _r, _s, lo, hi in wires),
            "wire_under_compute_s": under, "wires": len(wires),
            "push": {k: {"seconds": s, "count": n}
                     for k, (s, n) in push.items()},
            "computes_in_flight": flying[1],
            "computes_alone": flying[0],
            "rounds_behind_sum": counted["1"], "rounds_counted": rounds,
            "events_dropped": dropped}


def run(ctx) -> dict:
    """``ctx``: cell, seed, seconds, trace, rehearsal, devices, compiles,
    t_start, say.  Returns what ``chipbench.run`` prints."""
    needs_the_barriers_counters()
    needs_the_delay()
    import jax

    from distlr_tpu.obs.tracing import get_tracer

    conf = effective_config(ctx.cell, ctx.rehearsal)
    prog, traffic, family = conf["program"], ctx.cell.traffic, conf["family"]
    lr, workers = float(prog["learning_rate"]), int(prog["num_workers"])
    platform = ctx.devices[0].platform

    job = prepare(conf, ctx.seed, ctx.say)
    failed = True
    try:
        n = job.rows_per_worker
        # -- set-up: the recorded fit, then the pace --------------------
        got = record(job, int(traffic["recorded_rounds"]),
                     int(traffic["checked_rounds"]))
        pace_rounds = int(traffic["pace_rounds"])
        pace = in_threads(job, lambda w: w.fit(epochs=pace_rounds)) / pace_rounds
        epochs = max(1, math.ceil(WINDOW_MARGIN * ctx.seconds / pace))
        ctx.say(f"recorded rounds={got['rounds']} acked={got['acked']} "
                f"lineage_broken={got['lineage_broken']} "
                f"delay_miscount={got['delay_miscount']} "
                f"in_flight_at_return={got['in_flight_at_return']} "
                f"resident_bytes={_resident_bytes()} "
                f"pace_s={pace:.5f} window_rounds={epochs} "
                "compiles seconds={seconds:.2f} count={count} cache_hits={hits} "
                "cache_misses={misses}".format(**ctx.compiles.snapshot()))

        # -- the window: one fit a worker, at once ----------------------
        tracer = get_tracer()
        compiled_before = ctx.compiles.snapshot()
        counted_before = [(w.timer.samples, w.timer.steps) for w in job.workers]
        ops, servers, paths = _client_ops(), _servers(job), _grad_paths()
        delayed = _delayed_counts()
        tracer.reset()
        setup_s = time.perf_counter() - ctx.t_start
        window_wall = in_threads(job, lambda w: w.fit(epochs=epochs))
        spans = tracer.breakdown()
        doc = tracer.chrome_trace()
        delayed_after = _delayed_counts()
        delayed_rise = {k: delayed_after[k] - delayed[k] for k in ("0", "1")}
        dl = dl_side(doc["traceEvents"],
                     int(doc["otherData"].get("dropped_events", 0)),
                     delayed_rise)
        ops_after, servers_after = _client_ops(), _servers(job)
        paths_after = _grad_paths()
        # the yardstick counts the work itself: E rounds of every shard
        rounds_done, rows_done = workers * epochs, workers * epochs * n
        counted = [(w.timer.samples - s, w.timer.steps - k)
                   for w, (s, k) in zip(job.workers, counted_before)]
        acked = ops_after["acked"] - ops["acked"]
        counts_agree = (counted == [(epochs * n, epochs)] * workers
                        and acked == rounds_done)
        bad_ops = ops_after["bad"] - ops["bad"]
        compiled_in_window = ctx.compiles.count - compiled_before["count"]
        in_window = {
            "round_miscount": _round_miscount(servers, servers_after, epochs),
            "unacknowledged": _unacknowledged(servers, servers_after, acked),
            # against the rounds the program's own timers say it ran
            "delay_miscount": _delay_miscount(
                delayed, delayed_after, workers, sum(k for _s, k in counted)),
            "in_flight_at_return": _in_flight(job)}
        ctx.say(f"window wall_s={window_wall:.3f} rounds_a_worker={epochs} "
                f"rounds={rounds_done} rows={rows_done} "
                f"program_counted={counted} acked_pushes={acked} "
                f"failed_or_retried_ops={bad_ops} "
                f"compiles_in_window={compiled_in_window} grad_rounds="
                + json.dumps({k: v - paths.get(k, 0)
                              for k, v in paths_after.items()})
                + f" delayed_rounds={json.dumps(delayed_rise)}"
                f" in_flight_at_return={in_window['in_flight_at_return']}"
                f" host_rss_peak_mib={_rss_peak_mib()}")
        ctx.say("window spans, a worker's mean ms: " + " ".join(
            f"{name}={1e3 * s['seconds'] / s['count']:.3f}"
            for name, s in sorted(spans.items()) if s["count"]))
        ctx.say("window pushes, mean ms: " + " ".join(
            f"{k}={1e3 * v['seconds'] / max(v['count'], 1):.3f} n={v['count']}"
            for k, v in dl["push"].items())
            + f" computes in_flight={dl['computes_in_flight']} "
            f"alone={dl['computes_alone']} "
            f"wire_under_next_compute={dl['wire_under_compute_s']:.3f}s of "
            f"{dl['wire_s']:.3f}s events_dropped={dl['events_dropped']}")

        def rise(stat):
            return sum(a[stat] - b[stat]
                       for b, a in zip(servers, servers_after))

        run = {
            "cell": ctx.cell.name, "family": family, "chips": 1,
            "device_kind": ctx.devices[0].device_kind, "platform": platform,
            "setup_compile": compiled_before,
            "compiles_in_window": compiled_in_window,
            "window": {"wall_s": window_wall, "steps": rounds_done,
                       "rows": rows_done,
                       "spans": _per_worker(spans, workers)},
            "step": {"rows": n, "dim": int(prog["num_feature_dim"]),
                     "nnz": n * job.nnz_width},
            "ps": {"workers": workers, "rounds_per_worker": epochs,
                   "server_pushes": rise("total_pushes"),
                   "server_push_cpu_s": rise("cpu_push_seconds")},
            "bsp": {"server_rounds": rise("sync_rounds"),
                    "hold_s": rise("sync_hold_seconds"),
                    "spread_s": rise("sync_spread_seconds"),
                    "release_cpu_s": rise("cpu_release_seconds")},
            "dl": dl,
            "trace": None,
        }

        # -- a traced run: a short fit of its own under the profiler ----
        if ctx.trace:
            t_epochs = max(1, min(
                math.ceil(traffic["trace_seconds"] / (window_wall / epochs)),
                int(traffic["trace_max_rounds"])))
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
            ctx.say(f"traced rounds_a_worker={t_epochs} "
                    f"fit_and_export_s={traced_s:.2f} programs={programs}")
            run["trace"] = {
                "xtrace": xtrace, "window": window,
                "steps": workers * t_epochs, "host_spans": host_spans,
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
    rows = compare(rows_kept, got, family, lr, conf["limits"], in_window)
    ctx.say(f"reference delayed rounds={len(got['first'][0]) - 1} and "
            f"gradients of {workers} x {len(got['first'][0])} rounds "
            f"check_s={time.perf_counter() - t:.2f}")
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

    ap = argparse.ArgumentParser(prog="chipbench.drivers.ps_bsp_delay_epochs")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    needs_the_barriers_counters()
    needs_the_delay()
    cell = manifest.Cell(manifest.load_benchmark(), args.workload)
    if not args.rehearse:
        harness.place_compile_cache()
    harness.take_devices(cell.chips, args.rehearse)
    conf = effective_config(cell, args.rehearse)
    traffic, family = cell.traffic, conf["family"]
    lr = float(conf["program"]["learning_rate"])
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
            got = record(job, int(traffic["recorded_rounds"]),
                         int(traffic["checked_rounds"]))
            kept = _rows_of(job)
            failed = False
        finally:
            job.close(failed)
        del job
        gc.collect()
        note(tag, seed, compare(kept, got, family, lr, conf["limits"]))
        if over is None:
            note(low, seed, compare(kept, lowered(kept, got, family, low),
                                    family, lr, conf["limits"]))

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
