"""Traffic kind ``ps_bsp_epochs``: the parameter-server job in lock step
(BSP), a server group with ``sync=1`` and worker threads in one process,
whole-shard rounds in a closed loop.

Set-up, window and traced run are the asynchronous kind's
(``chipbench/drivers/ps_epochs.py``, whose ``prepare``, ``record``,
``in_threads`` and counter readers this module imports): rows from the
seed, one shard a worker, the product's own ``server_group``, the
``PSWorker``s loaded (the one placement) and started, a recorded phase,
a pacing fit, then ONE ``PSWorker.fit(epochs=E)`` a worker, all at once.
The driver computes no gradient and applies no update of its own.

A BSP run has a trajectory: every worker computes round *k* on the same
weights and the servers apply one update a round, so ``correct`` follows
the plain reference (``families/dense_ps_bsp.py``) step by step through
the first rounds and holds the servers' counters to the rounds the
workers ran: see :func:`compare`, the configuration's ``guarantees`` and
PERF.md section 2.

    python3 -m chipbench.drivers.ps_bsp_epochs --workload <name> --seeds 1,2,3 [--controls 2]

reads what ``correct`` compares, seed after seed in one process, for the
program, for its control (``control.program`` in the program's place, on
the first ``--controls`` seeds) and for the reference computed in
``control.precision`` and put where the program's gradients stand: the
readings a limit is set between.  ``--rehearse`` runs the tiny sizes
anywhere.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import reference, trace_reduce
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
    record,
)
from chipbench.drivers.train_stream import (
    _peak_bytes,
    _rel_gap,
    _rss_peak_mib,
    effective_config,
)

#: what the servers count of a round (the kStats tail this kind reads)
SYNC_STATS = ("sync_rounds", "sync_hold_seconds", "sync_spread_seconds",
              "cpu_release_seconds")


def needs_the_barriers_counters() -> None:
    """A program whose servers do not count their rounds cannot be held
    to them: leave at once, before a row is made."""
    from distlr_tpu.ps.client import STATS_FIELDS

    missing = [s for s in SYNC_STATS if s not in STATS_FIELDS]
    if missing:
        raise SystemExit(
            "chipbench ps_bsp_epochs: this program's servers report no "
            f"{missing} in kStats, so the cell's rounds cannot be "
            "counted; it runs from the commit that adds them")


def _round_miscount(before: list[dict], after: list[dict], rounds: int) -> int:
    """The largest gap, over the servers, between the rounds a server
    released and the rounds a worker ran, plus the pushes a server still
    held at either end (a round begun and not released)."""
    return max(abs(a["sync_rounds"] - b["sync_rounds"] - rounds)
               + a["pending_sync_pushes"] + b["pending_sync_pushes"]
               for b, a in zip(before, after))


def record_rounds(job, rounds: int, keep: int) -> dict:
    """``ps_epochs.record`` between two readings of the servers."""
    servers = _servers(job)
    got = record(job, rounds, keep)
    got["round_miscount"] = _round_miscount(servers, _servers(job), rounds)
    return got


def _same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    return a.shape == b.shape and bool(
        (a.view(np.uint32) == b.view(np.uint32)).all())


def compare(job_rows: dict, got: dict, family: str, lr: float, limits: dict,
            window: dict | None = None) -> list[dict]:
    """Each number compared, beside its limit (PERF.md section 2);
    ``window``: ``round_miscount`` and ``unacknowledged`` of a window,
    where one was run."""
    fam = reference.family(family)
    shards, first = job_rows["shards"], got["first"]
    workers = len(shards)
    rows = []

    def row(name, value, limit_key):
        rows.append({"name": name, "value": float(value),
                     "limit": float(limits[limit_key]),
                     "ok": bool(np.isfinite(value)
                                and value <= limits[limit_key])})

    # every worker computed round k on the same weights, bit for bit, and
    # round 0 on what the servers held before it
    lead = [w for w, _g in first[0]]
    row("weights_disagree",
        sum(not all(_same_bits(w, ref) for (w, _g), ref in zip(rounds, lead))
            for rounds in first[1:])
        + (not _same_bits(lead[0], got["w_before"])),
        "weights_disagree")
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
    row("grad_norm_rel_gap", norm_gap, "grad_norm_rel_gap")
    row("grad_diff_rel", diff, "grad_diff_rel")
    # the trajectory: the program's first updates, read from what the
    # workers computed on, against the reference's rounds from the
    # weights the servers held before the first
    w_ref = got["w_before"]
    norm_gap = diff = 0.0
    for w_k, w_next in zip(lead, lead[1:]):
        w_ref_next = fam.round(w_ref, shards, lr)
        u = w_next.astype(np.float64) - w_k
        u_ref = w_ref_next.astype(np.float64) - w_ref
        n_ref = max(float(np.linalg.norm(u_ref)), 1e-30)
        norm_gap = max(norm_gap, _rel_gap(np.linalg.norm(u), n_ref))
        diff = max(diff, float(np.linalg.norm(u - u_ref)) / n_ref)
        w_ref = w_ref_next
    row("update_norm_rel_gap", norm_gap, "update_norm_rel_gap")
    row("update_diff_rel", diff, "update_diff_rel")
    # conservation: what the servers hold moved by the mean of what was
    # pushed, over every recorded round
    moved = got["w_after"].astype(np.float64) - got["w_before"]
    pushed = lr / workers * got["pushed_sum"]
    row("conservation_rel", np.linalg.norm(moved + pushed)
        / max(float(np.linalg.norm(pushed)), 1e-30), "conservation_rel")
    ref_ll = reference.logloss(family, got["w_after"], *job_rows["test"])
    row("test_logloss_rel_gap", _rel_gap(got["test_logloss"], ref_ll),
        "test_logloss_rel_gap")
    row("round_miscount_recorded", got["round_miscount"], "round_miscount")
    row("unacknowledged_recorded", got["unacknowledged"],
        "unacknowledged_pushes")
    if window is not None:
        row("round_miscount_window", window["round_miscount"],
            "round_miscount")
        row("unacknowledged_window", window["unacknowledged"],
            "unacknowledged_pushes")
    return rows


def _grad_paths() -> dict:
    from distlr_tpu.obs.registry import get_registry

    fam = get_registry().get("distlr_ps_grad_rounds_total")
    out: dict = {}
    for labels, child in fam.children() if fam else []:
        path = labels[-1]
        out[path] = out.get(path, 0) + int(child.value)
    return out


def run(ctx) -> dict:
    """``ctx``: cell, seed, seconds, trace, rehearsal, devices, compiles,
    t_start, say.  Returns what ``chipbench.run`` prints."""
    needs_the_barriers_counters()
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
        # -- set-up: the recorded phase, then the pace ------------------
        got = record_rounds(job, int(traffic["recorded_rounds"]),
                            int(traffic["checked_rounds"]))
        pace_rounds = int(traffic["pace_rounds"])
        pace = in_threads(job, lambda w: w.fit(epochs=pace_rounds)) / pace_rounds
        epochs = max(1, math.ceil(WINDOW_MARGIN * ctx.seconds / pace))
        ctx.say(f"recorded rounds={got['rounds']} acked={got['acked']} "
                f"pace_s={pace:.5f} window_rounds={epochs} "
                "compiles seconds={seconds:.2f} count={count} cache_hits={hits} "
                "cache_misses={misses}".format(**ctx.compiles.snapshot()))

        # -- the window: one fit a worker, at once ----------------------
        tracer = get_tracer()
        compiled_before = ctx.compiles.snapshot()
        counted_before = [(w.timer.samples, w.timer.steps) for w in job.workers]
        ops, servers, paths = _client_ops(), _servers(job), _grad_paths()
        tracer.reset()
        setup_s = time.perf_counter() - ctx.t_start
        window_wall = in_threads(job, lambda w: w.fit(epochs=epochs))
        spans = tracer.breakdown()
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
        ctx.say(f"window wall_s={window_wall:.3f} rounds_a_worker={epochs} "
                f"rounds={rounds_done} rows={rows_done} "
                f"program_counted={counted} acked_pushes={acked} "
                f"failed_or_retried_ops={bad_ops} "
                f"compiles_in_window={compiled_in_window} grad_rounds="
                + json.dumps({k: v - paths.get(k, 0)
                              for k, v in paths_after.items()})
                + f" host_rss_peak_mib={_rss_peak_mib()}")
        ctx.say("window spans, a worker's mean ms: " + " ".join(
            f"{name}={1e3 * s['seconds'] / s['count']:.3f}"
            for name, s in sorted(spans.items()) if s["count"]))

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
    rows = compare(rows_kept, got, family, lr, conf["limits"], {
        "round_miscount": _round_miscount(servers, servers_after, epochs),
        "unacknowledged": _unacknowledged(servers, servers_after, acked)})
    ctx.say(f"reference rounds={len(got['first'][0]) - 1} and gradients of "
            f"{workers} x {len(got['first'][0])} rounds "
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

    ap = argparse.ArgumentParser(prog="chipbench.drivers.ps_bsp_epochs")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    needs_the_barriers_counters()
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
            got = record_rounds(job, int(traffic["recorded_rounds"]),
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
