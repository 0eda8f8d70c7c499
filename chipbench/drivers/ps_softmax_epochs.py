"""Traffic kind ``ps_softmax_epochs``: the asynchronous parameter-server
job of ``ps_epochs`` with a class axis: every worker keeps its float32
shard on the chip, a round pulls, computes on and pushes all ``K``
columns of every feature's row, and the test split stays on the chip
from the one eval of set-up on.

The job, its threads, the recorded phase and the counter readers are
``ps_epochs``'s (``Job``, ``in_threads``, ``record``, ``GradRecorder``
inside it, ``_client_ops``, ``_servers``, ``_unacknowledged``,
``WINDOW_MARGIN``, ``STEP_PROGRAM``), imported; that file is not edited.
What the class axis adds:

* :func:`prepare` of its own: the rows are a text collection's
  (``chipbench/newsgen.py``: tf-idf values, unit rows, class ids), not
  criteo's, written as one reference-layout shard a worker and read by
  the worker's own loader;
* a program that keeps no ``distlr_ps_step_classes`` cannot be held to
  the configuration's guarantees (its float32 products are one bfloat16
  pass on a TPU, and nothing says what its step's class axis or its
  shard's layout is): the driver says which series it misses and leaves
  before a row is made (:func:`needs_the_class_series`);
* :func:`compare`: the sibling's seven rows with the gradients and the
  test logloss held against ``families/dense_ps_softmax`` (a flat
  ``[D K]`` vector; ``reference.logloss`` is a binary model's), and two
  of its own that admit 0 only, ``resident_short`` and ``classes_short``
  (PERF.md section 2);
* the run carries ``sm`` for the ``sm_*`` readers and the step's shapes
  with ``classes``, so that ``step_hbm_roofline`` asks this family's
  floor.

    python3 -m chipbench.drivers.ps_softmax_epochs --workload <name> --seeds 1,2,3 [--controls 2]

reads what ``correct`` compares, seed after seed in one process: for the
program, for its control (``control.program`` in the program's place, on
the first ``--controls`` seeds) and for the reference computed in
``control.precision`` and put where the program's gradients and test
logloss stand.  ``--rehearse`` runs the tiny sizes anywhere.
"""

from __future__ import annotations

import argparse
import gc
import json
import logging
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import newsgen, reference, trace_reduce
from chipbench.drivers.ps_bsp_epochs import _grad_paths
from chipbench.drivers.ps_epochs import (
    PUSH_OPS,
    STEP_PROGRAM,
    WINDOW_MARGIN,
    Job,
    _client_ops,
    _Lines,
    _per_worker,
    _rows_of,
    _servers,
    _unacknowledged,
    in_threads,
    record,
)
from chipbench.drivers.train_stream import (
    _peak_bytes,
    _rel_gap,
    _rss_peak_mib,
    effective_config,
    initial_weights,
)

#: the series a program has to keep for its class axis to be held
STEP_CLASSES = "distlr_ps_step_classes"
RESIDENT_LAYOUT = "distlr_ps_resident_layout"
RESIDENT = "distlr_ps_resident_bytes"
TEST_RESIDENT = "distlr_ps_test_resident_bytes"
CLIENT_BYTES = "distlr_ps_client_bytes_total"
#: spans that carry rows to the device: none may open inside the window
PLACING = ("h2d", "shard_put", "test_put")


def needs_the_class_series() -> None:
    """A program without these series states no precision on its softmax
    products either (they came in one change): leave at once, before a
    row is made."""
    import distlr_tpu.train.ps_trainer  # noqa: F401  (registers its series)
    from distlr_tpu.obs.registry import get_registry

    missing = [s for s in (STEP_CLASSES, RESIDENT_LAYOUT)
               if get_registry().get(s) is None]
    if missing:
        raise SystemExit(
            "chipbench ps_softmax_epochs: this program's PSWorker keeps no "
            f"{missing}: nothing says what class axis its step has nor how "
            "its resident shard is held, and its float32 softmax products "
            "state no precision (one bfloat16 pass on a TPU), so the "
            "configuration's float32 cannot be held; the cell runs from "
            "the commit that states it")


def prepare(conf: dict, seed: int, say, program_over: dict | None = None) -> Job:
    """Rows from the seed as shards on disk, the server group, and
    workers that have loaded, placed and started: ``ps_epochs.prepare``
    with this configuration's generator."""
    from distlr_tpu import Config
    from distlr_tpu.data.sharding import part_name
    from distlr_tpu.ps import KVWorker
    from distlr_tpu.train import ps_trainer

    gen, prog = conf["generator"], {**conf["program"], **(program_over or {})}
    dim, workers = int(prog["num_feature_dim"]), int(prog["num_workers"])
    classes, n = int(prog["num_classes"]), int(gen["rows_per_worker"])
    rows_kw = dict(vocab=dim, classes=classes, nnz=int(gen["nnz"]))
    t = time.perf_counter()
    train = newsgen.make_rows(seed, "train", workers * n, **rows_kw)
    test = newsgen.make_rows(seed, "test", int(gen["test_rows"]), **rows_kw)
    shards = [tuple(a[r * n:(r + 1) * n] for a in train) for r in range(workers)]
    say(f"rows train={workers}x{n} test={len(test[2])} classes seen="
        f"{len(np.unique(train[2]))} made_s={time.perf_counter() - t:.2f}")

    tmp = tempfile.mkdtemp(prefix="chipbench-ps-softmax-")
    capture = _Lines()
    logger = logging.getLogger(ps_trainer.__name__)
    logger.addHandler(capture)
    group = probe = None
    made: list = []
    try:
        t = time.perf_counter()
        for r, shard in enumerate(shards):
            newsgen.write_libsvm(os.path.join(tmp, "train", part_name(r)), *shard)
        newsgen.write_libsvm(os.path.join(tmp, "test", part_name(0)), *test)
        # no eval and no checkpoint inside any fit; the iterations a call
        # runs are its own argument
        cfg = Config(data_dir=tmp, test_interval=0, **prog)
        group = ps_trainer.server_group(cfg).start()
        probe = KVWorker(group.hosts, dim * classes, client_id=0xFC00)
        w0 = initial_weights(seed, dim * classes)
        probe.wait(probe.push_init(w0))
        for r in range(workers):
            made.append(ps_trainer.PSWorker(cfg, r, group.hosts))
        for w in made:  # one after another: one shard in flight at a time
            w.load_data()
        job = Job(cfg, group, made, probe, shards, test, w0, n,
                  train[0].shape[1],
                  [ln for ln in capture.lines if "dense steps pinned" in ln])
        in_threads(job, lambda w: w.start())
        say(f"servers={cfg.num_servers} workers={workers} loaded and started "
            f"load_s={time.perf_counter() - t:.2f}")
        for ln in job.pinned:
            say(ln)
        return job
    except BaseException:
        for w in made:
            w.close(wait=False)
        if probe is not None:
            probe.close()
        if group is not None:
            group.stop()
        raise
    finally:
        logger.removeHandler(capture)
        shutil.rmtree(tmp, ignore_errors=True)


# -- what the program says of its class axis and of what it holds --------
def _by_rank(series: str) -> dict:
    from distlr_tpu.obs.registry import get_registry

    fam = get_registry().get(series)
    return {labels[0]: child.value
            for labels, child in (fam.children() if fam else [])}


def held() -> dict:
    """The program's own account: each rank's resident bytes, rank 0's
    resident test split, the class axis of each rank's step and the
    layout each rank's shard is held in."""
    from distlr_tpu.obs.registry import get_registry

    fam = get_registry().get(RESIDENT_LAYOUT)
    layouts = {labels[0]: labels[1]
               for labels, child in (fam.children() if fam else [])
               if child.value}
    return {"resident": {r: int(v) for r, v in _by_rank(RESIDENT).items()},
            "test_resident": int(_by_rank(TEST_RESIDENT).get("0", 0)),
            "classes": {r: int(v) for r, v in _by_rank(STEP_CLASSES).items()},
            "layout": layouts}


def _pushed_bytes() -> int:
    """Bytes the clients count as sent by gradient pushes (values, keys
    and headers of every frame)."""
    from distlr_tpu.obs.registry import get_registry

    fam = get_registry().get(CLIENT_BYTES)
    return int(sum(child.value for labels, child
                   in (fam.children() if fam else [])
                   if labels[0] in PUSH_OPS and labels[1] == "sent"))


def live_columns(g: np.ndarray, classes: int) -> int:
    """Class columns of a flat gradient that are not identically zero."""
    return int(np.any(g.reshape(-1, classes) != 0, axis=0).sum())


def compare(job_rows: dict, got: dict, family: str, lr: float, dim: int,
            classes: int, limits: dict, window: dict | None = None) -> list[dict]:
    """Each number compared, beside its limit (PERF.md section 2).
    ``got``: ``ps_epochs.record``'s, with ``held`` (:func:`held` after the
    recorded phase).  ``window``: ``unacknowledged``, ``placed`` (spans
    that carry rows to the device) and ``bytes_short`` of a window, where
    one was run."""
    fam = reference.family(family)
    rows = []

    def row(name, value, limit_key=None):
        limit = limits[limit_key or name]
        rows.append({"name": name, "value": float(value),
                     "limit": float(limit),
                     "ok": bool(np.isfinite(value) and value <= limit)})

    # the gradients a worker pushed, against the reference's on that
    # worker's rows at the weights it computed on: the worst worker
    norm_gap = diff = 0.0
    live = classes
    for shard, first in zip(job_rows["shards"], got["first"]):
        for weights, pushed in first:
            ref = np.asarray(fam.gradient(weights, *shard, classes))
            n_ref = max(float(np.linalg.norm(ref)), 1e-30)
            norm_gap = max(norm_gap, _rel_gap(np.linalg.norm(pushed), n_ref))
            diff = max(diff, float(np.linalg.norm(pushed - ref)) / n_ref)
            live = min(live, live_columns(pushed, classes))
    row("grad_norm_rel_gap", norm_gap)
    row("grad_diff_rel", diff)
    # conservation: what the servers hold moved by what was pushed
    moved = got["w_after"].astype(np.float64) - got["w_before"]
    pushed = lr * got["pushed_sum"]
    n_pushed = max(float(np.linalg.norm(pushed)), 1e-30)
    row("conservation_rel", np.linalg.norm(moved + pushed) / n_pushed)
    row("update_missing",
        0.0 if np.linalg.norm(moved) > 0.5 * n_pushed else 1.0)
    row("unacknowledged_recorded", got["unacknowledged"],
        "unacknowledged_pushes")
    ref_ll, _acc = fam.evaluate(got["w_after"], *job_rows["test"], classes)
    row("test_logloss_rel_gap", _rel_gap(got["test_logloss"], ref_ll))
    extra = window or {"unacknowledged": None, "placed": 0, "bytes_short": 0}
    if extra["unacknowledged"] is not None:
        row("unacknowledged_window", extra["unacknowledged"],
            "unacknowledged_pushes")
    # every shard and the split stay where set-up put them
    workers = len(job_rows["shards"])
    shard_bytes = len(job_rows["shards"][0][-1]) * dim * 4
    split_bytes = len(job_rows["test"][-1]) * dim * 4
    kept = got["held"]
    row("resident_short",
        sum(max(0, shard_bytes - kept["resident"].get(str(r), 0))
            for r in range(workers))
        + max(0, split_bytes - kept["test_resident"]) + extra["placed"])
    # the whole class axis is computed and crosses the wire
    row("classes_short", (classes - live) + extra["bytes_short"])
    return rows


def lowered(job_rows: dict, got: dict, family: str, classes: int,
            precision: str) -> dict:
    """The recorded phase with the reference, computed in ``precision``,
    in the program's place: its gradient at the weights each worker
    computed on where the pushed one stood, its test logloss where the
    product's stood.  What the servers did stays as recorded."""
    fam = reference.family(family)
    first = [[(w, np.asarray(fam.gradient(w, *shard, classes,
                                          precision=precision)))
              for w, _pushed in rounds]
             for shard, rounds in zip(job_rows["shards"], got["first"])]
    ll, _acc = fam.evaluate(got["w_after"], *job_rows["test"], classes,
                            precision=precision)
    return {**got, "first": first, "test_logloss": float(ll)}


def recorded(job: Job, traffic: dict) -> dict:
    """``ps_epochs.record`` and, after it, the program's own account of
    what it holds (the recorded phase's eval places the split)."""
    got = record(job, int(traffic["recorded_rounds"]),
                 int(traffic["checked_rounds"]))
    got["held"] = held()
    return got


def run(ctx) -> dict:
    """``ctx``: cell, seed, seconds, trace, rehearsal, devices, compiles,
    t_start, say.  Returns what ``chipbench.run`` prints."""
    needs_the_class_series()
    import jax

    from distlr_tpu.obs.tracing import get_tracer

    conf = effective_config(ctx.cell, ctx.rehearsal)
    prog, traffic, family = conf["program"], ctx.cell.traffic, conf["family"]
    lr, workers = float(prog["learning_rate"]), int(prog["num_workers"])
    dim, classes = int(prog["num_feature_dim"]), int(prog["num_classes"])
    platform = ctx.devices[0].platform
    fam = reference.family(family)

    job = prepare(conf, ctx.seed, ctx.say)
    failed = True
    try:
        n = job.rows_per_worker
        push_bytes = dim * classes * 4
        # -- set-up: the recorded phase, then the pace ------------------
        got = recorded(job, traffic)
        kept = got["held"]
        pace_rounds = int(traffic["pace_rounds"])
        pace = in_threads(job, lambda w: w.fit(epochs=pace_rounds)) / pace_rounds
        epochs = max(1, math.ceil(WINDOW_MARGIN * ctx.seconds / pace))
        ctx.say(f"recorded rounds={got['rounds']} acked={got['acked']} "
                f"pace_s={pace:.5f} window_iterations={epochs} "
                "compiles seconds={seconds:.2f} count={count} cache_hits={hits} "
                "cache_misses={misses}".format(**ctx.compiles.snapshot()))
        ctx.say(f"held path={sorted(_grad_paths())} "
                f"{STEP_CLASSES}={sorted(kept['classes'].values())} "
                f"resident_layout={sorted(set(kept['layout'].values()))} "
                f"resident_bytes={sorted(kept['resident'].values())} "
                f"test_resident_bytes={kept['test_resident']}")

        # -- the window: one fit a worker, at once ----------------------
        tracer = get_tracer()
        compiled_before = ctx.compiles.snapshot()
        counted_before = [(w.timer.samples, w.timer.steps) for w in job.workers]
        ops, servers, sent = _client_ops(), _servers(job), _pushed_bytes()
        tracer.reset()
        setup_s = time.perf_counter() - ctx.t_start
        window_wall = in_threads(job, lambda w: w.fit(epochs=epochs))
        spans = tracer.breakdown()
        ops_after, servers_after = _client_ops(), _servers(job)
        sent_after = _pushed_bytes()
        # the yardstick counts the work itself: E iterations of every shard
        rounds_done, rows_done = workers * epochs, workers * epochs * n
        counted = [(w.timer.samples - s, w.timer.steps - k)
                   for w, (s, k) in zip(job.workers, counted_before)]
        acked = ops_after["acked"] - ops["acked"]
        counts_agree = (counted == [(epochs * n, epochs)] * workers
                        and acked == rounds_done)
        bad_ops = ops_after["bad"] - ops["bad"]
        compiled_in_window = ctx.compiles.count - compiled_before["count"]
        in_window = {
            "unacknowledged": _unacknowledged(servers, servers_after, acked),
            "placed": sum(spans.get(s, {"count": 0})["count"] for s in PLACING),
            # a frame's keys and headers ride beside its values, so the
            # rise may pass what the values alone come to, and not fall
            # short of it
            "bytes_short": max(0, rounds_done * push_bytes
                               - (sent_after - sent))}
        ctx.say(f"window wall_s={window_wall:.3f} iterations={epochs} "
                f"rounds={rounds_done} rows={rows_done} "
                f"program_counted={counted} acked_pushes={acked} "
                f"failed_or_retried_ops={bad_ops} "
                f"compiles_in_window={compiled_in_window} "
                f"pushed_bytes={sent_after - sent} of "
                f"{rounds_done * push_bytes} in values "
                f"placing_spans={in_window['placed']} "
                f"host_rss_peak_mib={_rss_peak_mib()}")
        ctx.say("window spans, a worker's mean ms: " + " ".join(
            f"{name}={1e3 * s['seconds'] / s['count']:.3f}"
            for name, s in sorted(spans.items()) if s["count"]))

        step = {"rows": n, "dim": dim, "classes": classes,
                "nnz": n * job.nnz_width}
        run = {
            "cell": ctx.cell.name, "family": family, "chips": 1,
            "device_kind": ctx.devices[0].device_kind, "platform": platform,
            "setup_compile": compiled_before,
            "compiles_in_window": compiled_in_window,
            "window": {"wall_s": window_wall, "steps": rounds_done,
                       "rows": rows_done,
                       "spans": _per_worker(spans, workers)},
            "step": step,
            "sm": {"rounds_per_worker": epochs,
                   "step_flops": fam.step_flops(rows=n, dim=dim,
                                                classes=classes)},
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
            ctx.say(f"traced iterations={t_epochs} fit_and_export_s={traced_s:.2f} "
                    f"programs={programs}")
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
        axis_held = sorted(kept["classes"].values()) == [classes] * workers
        rows_kept = _rows_of(job)
        failed = False
    finally:
        job.close(failed)
    del job
    gc.collect()  # the shards leave the device before the reference runs

    # -- correct ---------------------------------------------------------
    t = time.perf_counter()
    rows = compare(rows_kept, got, family, lr, dim, classes, conf["limits"],
                   in_window)
    ctx.say(f"reference gradients of {workers} x {len(got['first'][0])} rounds "
            f"check_s={time.perf_counter() - t:.2f}")
    for r in rows:
        ctx.say("compared {name} value={value:.6g} limit={limit:.6g} "
                "ok={ok}".format(**r))
    correct = (all(r["ok"] for r in rows) and finite and counts_agree
               and compiled_in_window == 0 and bad_ops == 0 and on_device
               and axis_held)
    if not on_device:
        ctx.say(f"the workers' steps are not all on {platform}")
    if not axis_held:
        ctx.say(f"the workers' steps do not all say {classes} classes: "
                f"{kept['classes']}")

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

    ap = argparse.ArgumentParser(prog="chipbench.drivers.ps_softmax_epochs")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    needs_the_class_series()
    cell = manifest.Cell(manifest.load_benchmark(), args.workload)
    if not args.rehearse:
        harness.place_compile_cache()
    harness.take_devices(cell.chips, args.rehearse)
    conf = effective_config(cell, args.rehearse)
    traffic, family, prog = cell.traffic, conf["family"], conf["program"]
    lr, dim = float(prog["learning_rate"]), int(prog["num_feature_dim"])
    classes = int(prog["num_classes"])
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
            got = recorded(job, traffic)
            kept = _rows_of(job)
            failed = False
        finally:
            job.close(failed)
        del job
        gc.collect()
        note(tag, seed, compare(kept, got, family, lr, dim, classes,
                                conf["limits"]))
        if over is None:
            note(low, seed, compare(
                kept, lowered(kept, got, family, classes, low), family, lr,
                dim, classes, conf["limits"]))

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
