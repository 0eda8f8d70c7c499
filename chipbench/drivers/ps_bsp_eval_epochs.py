"""Traffic kind ``ps_bsp_eval_epochs``: the lock-step (BSP)
parameter-server job as the launcher ships it, with its eval inside:
``test_interval`` rounds apart rank 0 pulls the weights, evaluates the
whole test split and reports accuracy and logloss, and the other workers
stand at the next round's barrier meanwhile.

Everything but the eval is ``ps_bsp_epochs``'s, whose ``compare`` and
``record_rounds`` this module imports with ``ps_epochs``' ``prepare``,
``Job``, ``in_threads`` and counter readers; neither file is edited.
What the eval adds:

* :func:`prepare` sets the job's ``test_interval`` from the traffic
  file.  A program that does not count its evals cannot be held to
  them: the driver says which series it misses and leaves before a row
  is made (:func:`needs_the_evals_series`);
* the recorded phase keeps, with a recorder round rank 0's compiled eval
  program (:class:`EvalRecorder`, as ``GradRecorder`` stands round the
  gradient call), each eval's weights, the weights rank 0's push-pull of
  that round had returned, and the two numbers the program reported;
* ``correct`` is the sibling's comparison whole and the eval's rows
  (:func:`compare_evals`): the evals counted against the rounds, the
  rows they covered, the weights they ran on, their logloss and their
  count of right answers against ``families/dense_ps_bsp_eval.evaluate``,
  and the split resident, with nothing placed inside the window;
* ``train_samples_per_s`` counts training rows alone; the run carries
  ``eval`` for the ``eval_*`` readers: rank 0's own spans, the other
  ranks' ``push`` spans on the rounds after an eval and on the others,
  and the resident matrix's shape.

    python3 -m chipbench.drivers.ps_bsp_eval_epochs --workload <name> --seeds 1,2,3 [--controls 2]

reads what ``correct`` compares, seed after seed in one process: for the
program, for its two controls on the first ``--controls`` seeds
(``control.program`` in the program's place, upstream's shortcut; and
``control.eval``, the eval run on the weights of the eval before it:
:class:`StaleEval`) and for the reference computed in
``control.precision`` and put where the program's gradients and eval
numbers stand.  ``--rehearse`` runs the tiny sizes anywhere.
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
from chipbench.drivers import ps_epochs
from chipbench.drivers.ps_bsp_epochs import (
    _grad_paths,
    _round_miscount,
    _same_bits,
    compare,
    needs_the_barriers_counters,
    record_rounds,
)
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
    lowered,
)
from chipbench.drivers.train_stream import (
    _peak_bytes,
    _rel_gap,
    _rss_peak_mib,
    effective_config,
)

#: the series a program has to keep for its evals to be counted
EVALS = "distlr_ps_evals_total"
EVAL_ROWS = "distlr_ps_eval_rows_total"
TEST_RESIDENT = "distlr_ps_test_resident_bytes"
#: rank 0's eval program as a trace names it: ``ps_trainer._compiled_acc``
#: jits a function of this name
EVAL_PROGRAM = "jit_ps_eval"
#: spans that place rows: none may open inside the window
PLACING = ("h2d", "test_put", "shard_put")
#: a row's class is compared where the reference's logit is further from
#: 0 than this many times the sum of its terms' sizes: a float32 dot
#: product of n terms is off by at most n * 2^-24 of that sum (39
#: non-zeros a row), the program's and the reference's each
LABEL_MARGIN = 2 * 39 * 2.0 ** -24


def needs_the_evals_series() -> None:
    """A program that keeps no count of its evals cannot be held to
    them: leave at once, before a row is made."""
    import distlr_tpu.train.ps_trainer  # noqa: F401  (registers its series)
    from distlr_tpu.obs.registry import get_registry

    missing = [s for s in (EVALS, EVAL_ROWS, TEST_RESIDENT)
               if get_registry().get(s) is None]
    if missing:
        raise SystemExit(
            "chipbench ps_bsp_eval_epochs: this program's PSWorker keeps no "
            f"{missing}, so the cell's evals cannot be counted nor its test "
            "split seen on the device; the cell runs from the commit that "
            "keeps the split resident and counts its evals")


def prepare(conf: dict, seed: int, say, test_interval: int,
            program_over: dict | None = None) -> Job:
    """``ps_epochs.prepare`` with the launcher's ``TEST_INTERVAL``: that
    function builds its ``Config`` with ``test_interval=0``, and the one
    ``Config`` it hands every worker is read at each round's end, so the
    interval is set on it before any round runs."""
    job = ps_epochs.prepare(conf, seed, say, program_over=program_over)
    job.cfg.test_interval = int(test_interval)
    return job


class EvalRecorder:
    """Stands in rank 0's compiled eval program during the recorded
    phase: the same program underneath, plus the weights each call ran
    on, the weights the worker's push-pull of that round had returned,
    and the two numbers that came back."""

    def __init__(self, fn, worker):
        self.fn, self.worker = fn, worker
        self.calls: list[dict] = []

    def __call__(self, w, *batch, **how):
        got = self.fn(w, *batch, **how)
        held = self.worker._w_cache
        self.calls.append({
            "round": self.worker.rounds,
            "ran_on": np.array(w).reshape(-1),
            "after_round": None if held is None else np.array(held),
            "accuracy": float(got[0]), "logloss": float(got[1])})
        return got


class StaleEval:
    """The eval's own control: the program with its eval run on the
    weights of the eval before it (the first on its own): what an eval
    that kept its weights on the device and skipped the pull and the
    ``eval_w_put`` would report."""

    def __init__(self, fn):
        self.fn, self.last = fn, None

    def __call__(self, w, *batch, **how):
        ran_on, self.last = (w if self.last is None else self.last), w
        return self.fn(ran_on, *batch, **how)


def _eval_counts() -> dict:
    """What the program has counted of its evals: rank 0's, the other
    ranks', the rows rank 0's covered, and the bytes it keeps resident."""
    from distlr_tpu.obs.registry import get_registry

    def by_rank(name):
        fam = get_registry().get(name)
        return {labels[0]: child.value
                for labels, child in (fam.children() if fam else [])}

    evals = by_rank(EVALS)
    return {"lead": int(evals.get("0", 0)),
            "others": int(sum(v for r, v in evals.items() if r != "0")),
            "rows": int(by_rank(EVAL_ROWS).get("0", 0)),
            "resident_bytes": int(by_rank(TEST_RESIDENT).get("0", 0))}


def _due(first: int, last: int, interval: int) -> int:
    """Evals that fall due in rounds ``first + 1 .. last``."""
    return last // interval - first // interval


def _span_count(tracer, *names) -> int:
    spans = tracer.breakdown()
    return sum(spans.get(n, {"count": 0})["count"] for n in names)


def record_evals(job: Job, rounds: int, keep: int, interval: int,
                 stale: bool = False) -> dict:
    """``ps_bsp_epochs.record_rounds`` with a recorder round rank 0's eval
    program and the program's eval counts read before and after.
    ``record`` ends with one ``evaluate(w_after)`` of its own (the
    sibling's test logloss): the recorder's last call, and one eval the
    counter holds beyond those the rounds called for."""
    from distlr_tpu.obs.tracing import get_tracer

    lead = job.workers[0]
    real = lead._acc_fn
    recorder = EvalRecorder(real, lead)
    lead._acc_fn = StaleEval(recorder) if stale else recorder
    tracer = get_tracer()
    before, spans = _eval_counts(), _span_count(tracer, "eval")
    first = lead.epochs_done
    try:
        got = record_rounds(job, rounds, keep)
    finally:
        lead._acc_fn = real
    after = _eval_counts()
    due = _due(first, first + rounds, interval)
    ran = after["lead"] - before["lead"] - 1
    got["evals"] = recorder.calls[:-1]
    got["evals_miscount"] = (
        abs(ran - due) + abs(_span_count(tracer, "eval") - spans - due)
        + abs(len(got["evals"]) - due) + after["others"] - before["others"])
    got["eval_rows_short"] = abs(
        after["rows"] - before["rows"] - (ran + 1) * len(job.test[2]))
    got["resident_bytes"] = after["resident_bytes"]
    return got


def compare_evals(job_rows: dict, got: dict, family: str, dim: int,
                  limits: dict, window: dict | None = None) -> list[dict]:
    """The eval's numbers, each beside its limit (PERF.md section 2);
    ``window``: ``evals_miscount``, ``eval_rows_short`` and ``placed``
    (spans that place rows) of a window, where one was run."""
    fam = reference.family(family)
    cols, vals, y = job_rows["test"]
    rows = []

    def row(name, value, limit_key):
        rows.append({"name": name, "value": float(value),
                     "limit": float(limits[limit_key]),
                     "ok": bool(np.isfinite(value)
                                and value <= limits[limit_key])})

    row("evals_miscount_recorded", got["evals_miscount"], "evals_miscount")
    if window is not None:
        row("evals_miscount_window", window["evals_miscount"],
            "evals_miscount")
    row("eval_rows_short", got["eval_rows_short"]
        + (window["eval_rows_short"] if window else 0), "eval_rows_short")
    # an eval ran on the weights its worker's push-pull of that round had
    # returned: the bits every worker computes the next round on
    row("eval_weights_stale",
        sum(e["after_round"] is None
            or not _same_bits(e["ran_on"], e["after_round"])
            for e in got["evals"]), "eval_weights_stale")
    # the numbers it reported, against the reference's at the weights
    # after that round
    gap = flips = 0.0
    for e in got["evals"]:
        at = e["ran_on"] if e["after_round"] is None else e["after_round"]
        ref_acc, ref_ll, z = fam.evaluate(at, cols, vals, y)
        gap = max(gap, _rel_gap(e["logloss"], ref_ll))
        # the program reports a count, not rows: the fewest rows, among
        # those the reference is sure of, that answered otherwise
        unsure = int((np.abs(z) <= LABEL_MARGIN * np.abs(
            vals * np.asarray(at)[cols]).sum(axis=1)).sum())
        flips = max(flips, abs(round(e["accuracy"] * len(y))
                               - round(ref_acc * len(y))) - unsure)
    row("eval_logloss_rel_gap", gap if got["evals"] else float("nan"),
        "eval_logloss_rel_gap")
    row("eval_label_flips", max(flips, 0.0), "eval_label_flips")
    # the split stays where the first eval put it
    short = max(0, len(y) * dim * 4 - got["resident_bytes"])
    row("test_resident_short",
        short + (window["placed"] if window else 0), "test_resident_short")
    return rows


def lowered_evals(job_rows: dict, got: dict, family: str,
                  precision: str) -> dict:
    """The recorded evals with the reference, computed in ``precision``,
    in the program's place: its two numbers at the weights each eval ran
    on where the program's stood."""
    fam = reference.family(family)
    evals = []
    for e in got["evals"]:
        acc, ll, _z = fam.evaluate(e["ran_on"], *job_rows["test"],
                                   precision=precision)
        evals.append({**e, "accuracy": acc, "logloss": ll})
    return {**got, "evals": evals}


def _after_eval_pushes(events: list[dict], interval: int) -> dict:
    """The other ranks' ``push`` spans, split by whether the round
    follows one of rank 0's evals: seconds and count of each."""
    out = {"after": [0.0, 0], "other": [0.0, 0]}
    for e in events:
        args = e.get("args", {})
        if e["name"] != "push" or not args.get("rank") or not args.get("step"):
            continue
        side = out["after" if (args["step"] - 1) % interval == 0
                   and args["step"] > 1 else "other"]
        side[0] += e["dur"] * 1e-6
        side[1] += 1
    return {k: {"seconds": s, "count": n} for k, (s, n) in out.items()}


def _eval_side(job: Job, tracer, spans: dict, wall_s: float,
               interval: int) -> dict:
    """What the ``eval_*`` readers take from a call that has just ended:
    rank 0's own spans whole (``_per_worker`` spreads a span over four
    loops), the barrier's side of an eval, the resident rows' shape."""
    held = getattr(job.workers[0], "_test_resident", None)
    shape = held[0][0].shape if held else (len(job.test[2]), len(job.w0))
    return {"spans": spans, "wall_s": wall_s,
            "pushes": _after_eval_pushes(
                tracer.chrome_trace()["traceEvents"], interval),
            "rows": int(shape[0]), "dim_held": int(shape[1]),
            "program": EVAL_PROGRAM}


def run(ctx) -> dict:
    """``ctx``: cell, seed, seconds, trace, rehearsal, devices, compiles,
    t_start, say.  Returns what ``chipbench.run`` prints."""
    needs_the_barriers_counters()
    needs_the_evals_series()
    import jax

    from distlr_tpu.obs.tracing import get_tracer

    conf = effective_config(ctx.cell, ctx.rehearsal)
    prog, traffic, family = conf["program"], ctx.cell.traffic, conf["family"]
    lr, workers = float(prog["learning_rate"]), int(prog["num_workers"])
    interval, dim = int(traffic["test_interval"]), int(prog["num_feature_dim"])
    platform = ctx.devices[0].platform

    job = prepare(conf, ctx.seed, ctx.say, interval)
    failed = True
    try:
        n, n_test = job.rows_per_worker, len(job.test[2])
        lead = job.workers[0]
        # -- set-up: the recorded phase, then the pace ------------------
        got = record_evals(job, int(traffic["recorded_rounds"]),
                           int(traffic["checked_rounds"]), interval)
        pace_rounds = int(traffic["pace_rounds"])
        pace = in_threads(job, lambda w: w.fit(epochs=pace_rounds)) / pace_rounds
        epochs = max(1, math.ceil(WINDOW_MARGIN * ctx.seconds / pace))
        ctx.say(f"recorded rounds={got['rounds']} acked={got['acked']} "
                f"evals={[e['round'] for e in got['evals']]} "
                f"test_resident_bytes={got['resident_bytes']} "
                f"pace_s={pace:.5f} window_rounds={epochs} "
                "compiles seconds={seconds:.2f} count={count} cache_hits={hits} "
                "cache_misses={misses}".format(**ctx.compiles.snapshot()))
        for e in got["evals"]:
            ctx.say(f"eval round={e['round']} accuracy={e['accuracy']:.6f} "
                    f"logloss={e['logloss']:.8f}")

        # -- the window: one fit a worker, at once, its evals inside ----
        tracer = get_tracer()
        compiled_before = ctx.compiles.snapshot()
        counted_before = [(w.timer.samples, w.timer.steps) for w in job.workers]
        ops, servers, paths = _client_ops(), _servers(job), _grad_paths()
        evals_before, first = _eval_counts(), lead.epochs_done
        tracer.reset()
        setup_s = time.perf_counter() - ctx.t_start
        window_wall = in_threads(job, lambda w: w.fit(epochs=epochs))
        spans = tracer.breakdown()
        eval_side = _eval_side(job, tracer, spans, window_wall, interval)
        ops_after, servers_after = _client_ops(), _servers(job)
        paths_after, evals_after = _grad_paths(), _eval_counts()
        # the yardstick counts the work itself: E rounds of every shard;
        # an eval's rows are not samples trained
        rounds_done, rows_done = workers * epochs, workers * epochs * n
        counted = [(w.timer.samples - s, w.timer.steps - k)
                   for w, (s, k) in zip(job.workers, counted_before)]
        acked = ops_after["acked"] - ops["acked"]
        counts_agree = (counted == [(epochs * n, epochs)] * workers
                        and acked == rounds_done)
        bad_ops = ops_after["bad"] - ops["bad"]
        compiled_in_window = ctx.compiles.count - compiled_before["count"]
        due = _due(first, first + epochs, interval)
        ran = evals_after["lead"] - evals_before["lead"]
        window_evals = {
            "evals_miscount": (
                abs(ran - due) + abs(_span_count(tracer, "eval") - due)
                + evals_after["others"] - evals_before["others"]),
            "eval_rows_short": abs(evals_after["rows"] - evals_before["rows"]
                                   - ran * n_test),
            "placed": _span_count(tracer, *PLACING)}
        ctx.say(f"window wall_s={window_wall:.3f} rounds_a_worker={epochs} "
                f"rounds={rounds_done} rows={rows_done} "
                f"program_counted={counted} acked_pushes={acked} "
                f"failed_or_retried_ops={bad_ops} "
                f"compiles_in_window={compiled_in_window} "
                f"evals due={due} ran={ran} "
                f"eval_rows={evals_after['rows'] - evals_before['rows']} "
                f"placing_spans={window_evals['placed']} grad_rounds="
                + json.dumps({k: v - paths.get(k, 0)
                              for k, v in paths_after.items()})
                + f" host_rss_peak_mib={_rss_peak_mib()}")
        ctx.say("window spans, a worker's mean ms (the eval's: rank 0's): "
                + " ".join(f"{name}={1e3 * s['seconds'] / s['count']:.3f}"
                           for name, s in sorted(spans.items()) if s["count"]))
        ctx.say("window pushes of ranks 1 and up, mean ms: " + " ".join(
            f"{k}={1e3 * v['seconds'] / max(v['count'], 1):.3f} n={v['count']}"
            for k, v in eval_side["pushes"].items()))

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
            "step": {"rows": n, "dim": dim, "nnz": n * job.nnz_width},
            "ps": {"workers": workers, "rounds_per_worker": epochs,
                   "server_pushes": rise("total_pushes"),
                   "server_push_cpu_s": rise("cpu_push_seconds")},
            "bsp": {"server_rounds": rise("sync_rounds"),
                    "hold_s": rise("sync_hold_seconds"),
                    "spread_s": rise("sync_spread_seconds"),
                    "release_cpu_s": rise("cpu_release_seconds")},
            "eval": eval_side,
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
            eval_runs = trace_reduce.module_runs(xtrace, EVAL_PROGRAM, window)
            ctx.say(f"traced rounds_a_worker={t_epochs} "
                    f"fit_and_export_s={traced_s:.2f} programs={programs} "
                    f"eval_runs={len(eval_runs)}")
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
                     and all(f"train -> {platform}:" in ln for ln in job.pinned)
                     and any(f"eval -> {platform}:" in ln for ln in job.pinned))
        rows_kept = _rows_of(job)
        failed = False
    finally:
        job.close(failed)
    del job, lead
    gc.collect()  # the shards leave the device before the reference runs

    # -- correct ---------------------------------------------------------
    t = time.perf_counter()
    rows = compare(rows_kept, got, family, lr, conf["limits"], {
        "round_miscount": _round_miscount(servers, servers_after, epochs),
        "unacknowledged": _unacknowledged(servers, servers_after, acked)})
    rows += compare_evals(rows_kept, got, family, dim, conf["limits"],
                          window_evals)
    ctx.say(f"reference rounds={len(got['first'][0]) - 1}, gradients of "
            f"{workers} x {len(got['first'][0])} rounds and "
            f"{len(got['evals'])} evals check_s={time.perf_counter() - t:.2f}")
    for r in rows:
        ctx.say("compared {name} value={value:.6g} limit={limit:.6g} "
                "ok={ok}".format(**r))
    correct = (all(r["ok"] for r in rows) and finite and counts_agree
               and compiled_in_window == 0 and bad_ops == 0 and on_device)
    if not on_device:
        ctx.say(f"the workers' steps and the eval are not all on {platform}")

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

    ap = argparse.ArgumentParser(prog="chipbench.drivers.ps_bsp_eval_epochs")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    needs_the_barriers_counters()
    needs_the_evals_series()
    cell = manifest.Cell(manifest.load_benchmark(), args.workload)
    if not args.rehearse:
        harness.place_compile_cache()
    harness.take_devices(cell.chips, args.rehearse)
    conf = effective_config(cell, args.rehearse)
    traffic, family = cell.traffic, conf["family"]
    lr = float(conf["program"]["learning_rate"])
    interval = int(traffic["test_interval"])
    dim = int(conf["program"]["num_feature_dim"])
    say = harness.Context.say
    low, stale = conf["control"]["precision"], conf["control"]["eval"]
    readings: dict[str, dict[str, list]] = {"program": {}, "control": {},
                                            stale: {}, low: {}}
    limits: dict[str, float] = {}

    def note(tag, seed, rows):
        for r in rows:
            readings[tag].setdefault(r["name"], []).append(r["value"])
            limits[r["name"]] = r["limit"]
        say(f"{tag} seed={seed} " + " ".join(
            f"{r['name']}={r['value']:.4g}" for r in rows))

    def both(kept, got):
        return (compare(kept, got, family, lr, conf["limits"])
                + compare_evals(kept, got, family, dim, conf["limits"]))

    def read(tag, seed, over):
        job = prepare(conf, seed, say, interval, program_over=over)
        failed = True
        try:
            got = record_evals(job, int(traffic["recorded_rounds"]),
                               int(traffic["checked_rounds"]), interval,
                               stale=tag == stale)
            kept = _rows_of(job)
            failed = False
        finally:
            job.close(failed)
        del job
        gc.collect()
        note(tag, seed, both(kept, got))
        if tag == "program":
            note(low, seed, both(kept, lowered_evals(
                kept, lowered(kept, got, family, low), family, low)))

    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        read("program", seed, None)
        if k < args.controls:
            read("control", seed, conf["control"]["program"])
            read(stale, seed, None)
    nan = [float("nan")]
    summary = {name: {"sound_max": max(vals),
                      "control_min": min(readings["control"].get(name, nan)),
                      f"{stale}_min": min(readings[stale].get(name, nan)),
                      f"{low}_min": min(readings[low][name]),
                      "limit": limits[name]}
               for name, vals in readings["program"].items()}
    print("CONTROL " + json.dumps({"cell": cell.name, "seeds": args.seeds,
                                   "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
