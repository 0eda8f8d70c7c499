"""Traffic kind ``ps_epochs``: the parameter-server job, a server group
and worker threads in one process, whole-shard iterations in a closed
loop.

Set-up makes the rows from the seed, writes one reference-layout libsvm
shard a worker, spawns the product's own server group
(``ps_trainer.server_group``, what ``run_ps_local`` spawns), builds the
``PSWorker``s, has each load its shard (the one placement on the
device), seeds the weights through the idempotent init, starts, and runs
a recorded phase and a pacing phase.  The window is ONE
``PSWorker.fit(epochs=E)`` a worker, the workers at once; the rate
divides the rows of those E iterations, counted here, by the wall from
the threads' start to the last one's end.  The driver computes no
gradient and applies no update of its own.

An asynchronous run has no trajectory to reproduce (arrival order
decides it), so ``correct`` holds the run to what an asynchronous PS
guarantees, on the recorded phase (the same workers, servers and
compiled program as the window, a recorder round each worker's gradient
call) and on the window's counters: see :func:`compare` and PERF.md
section 2.

    python3 -m chipbench.drivers.ps_epochs --workload <name> --seeds 1,2,3 [--controls 2]

reads what ``correct`` compares, one seed after another in one process:
for the program, for its control (the configuration's
``control.program`` in the program's place, on the first ``--controls``
seeds) and, at every seed, for the reference computed in
``control.precision`` and put where the program's gradients and test
logloss stand.  It prints the largest a sound run gave and the smallest
each control gave: the readings a limit is set between.  ``--rehearse``
runs the tiny sizes anywhere.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import logging
import math
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from chipbench import datagen, reference, trace_reduce
from chipbench.drivers.train_stream import (
    _peak_bytes,
    _rel_gap,
    _rss_peak_mib,
    effective_config,
    initial_weights,
)

PUSH_OPS = ("push", "push_pull")
STALENESS = "distlr_train_staleness_pushes"
#: the window is sized this much over --seconds from the pacing fit: on
#: the chip it ran 3% faster than that in warm processes and 12.5% faster
#: in a cold one (PERF.md section 6), and it has to last --seconds
WINDOW_MARGIN = 1.15
#: the worker's gradient program as a trace names it:
#: ``ps_trainer._compiled_fns`` jits a function of this name
STEP_PROGRAM = "jit_ps_grad_step"


class GradRecorder:
    """Stands in a worker's gradient call during the recorded phase: the
    same compiled program underneath, plus the weights and the gradient
    of the first ``keep`` rounds and a float64 sum of every gradient
    handed on to be pushed."""

    def __init__(self, step, keep: int, dim: int):
        self.step, self.keep = step, keep
        self.first: list[tuple[np.ndarray, np.ndarray]] = []
        self.total = np.zeros(dim, np.float64)
        self.rounds = 0

    def __call__(self, wf, batch):
        g = self.step(wf, batch)
        if len(self.first) < self.keep:
            self.first.append((np.array(wf), np.array(g)))
        self.total += g
        self.rounds += 1
        return g


class _Lines(logging.Handler):
    def __init__(self):
        super().__init__()
        self.lines: list[str] = []

    def emit(self, record):
        self.lines.append(record.getMessage())


@dataclasses.dataclass
class Job:
    """A started server group and its loaded workers."""
    cfg: object
    group: object
    workers: list
    probe: object           # the driver's own connection: pulls, stats
    shards: list            # a worker's rows as the generator made them
    test: tuple
    w0: np.ndarray
    rows_per_worker: int
    nnz_width: int
    pinned: list            # what each worker logged of its step's device

    def close(self, failed: bool = False) -> None:
        """Every way out: connections closed, servers reaped."""
        for w in self.workers:
            w.close(wait=not failed)
        self.workers = []
        self.probe.close()
        self.group.stop()


def in_threads(job: Job, call) -> float:
    """``call(worker)`` on every worker at once; the wall from the first
    thread's start to the last one's end.  A worker that fails takes the
    group down, so that its peers fail fast and do not wait."""
    errors: list[BaseException] = []

    def one(w):
        try:
            call(w)
        except Exception as e:  # noqa: BLE001  (re-raised below)
            errors.append(e)
            job.group.stop()

    threads = [threading.Thread(target=one, args=(w,), daemon=True,
                                name=f"ps-worker-{w.rank}")
               for w in job.workers]
    t = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t
    if errors:
        raise errors[0]
    return wall


def prepare(conf: dict, seed: int, say, program_over: dict | None = None) -> Job:
    """Rows from the seed as shards on disk, the server group, and
    workers that have loaded, placed and started."""
    from distlr_tpu import Config
    from distlr_tpu.data.sharding import part_name
    from distlr_tpu.ps import KVWorker
    from distlr_tpu.train import ps_trainer

    gen, prog = conf["generator"], {**conf["program"], **(program_over or {})}
    dim, workers = int(prog["num_feature_dim"]), int(prog["num_workers"])
    n = int(gen["rows_per_worker"])
    rows_kw = dict(fields=gen["fields"], num_buckets=dim,
                   label_scale=gen["label_scale"], label_bias=gen["label_bias"])
    t = time.perf_counter()
    train = datagen.make_rows(seed, "train", workers * n, **rows_kw)
    test = datagen.make_rows(seed, "test", int(gen["test_rows"]), **rows_kw)
    shards = [tuple(a[r * n:(r + 1) * n] for a in train) for r in range(workers)]
    say(f"rows train={workers}x{n} test={len(test[2])} "
        f"made_s={time.perf_counter() - t:.2f}")

    tmp = tempfile.mkdtemp(prefix="chipbench-ps-")
    capture = _Lines()
    logger = logging.getLogger(ps_trainer.__name__)
    logger.addHandler(capture)
    group = None
    made: list = []
    probe = None
    try:
        t = time.perf_counter()
        for r, shard in enumerate(shards):
            datagen.write_libsvm(os.path.join(tmp, "train", part_name(r)), *shard)
        datagen.write_libsvm(os.path.join(tmp, "test", part_name(0)), *test)
        # no eval and no checkpoint inside any fit; the iterations a call
        # runs are its own argument
        cfg = Config(data_dir=tmp, test_interval=0, **prog)
        group = ps_trainer.server_group(cfg).start()
        probe = KVWorker(group.hosts, dim, client_id=0xFC00)
        w0 = initial_weights(seed, dim)
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


# -- counters the program and its servers keep --------------------------
def _client_ops() -> dict:
    from distlr_tpu.obs.registry import family_total, get_registry

    ops = get_registry().get("distlr_ps_client_ops_total")
    acked = bad = 0
    for labels, child in ops.children():
        op, status = labels
        if status == "ok":
            acked += child.value if op in PUSH_OPS else 0
        else:
            bad += child.value
    return {"acked": int(acked),
            "bad": int(bad + family_total("distlr_ps_retries_total")
                       + family_total("distlr_ps_push_outcome_unknown_total"))}


def _servers(job: Job) -> list[dict]:
    return [job.probe.stats(r) for r in range(job.cfg.num_servers)]


def _staleness() -> tuple[float, int]:
    from distlr_tpu.obs.registry import get_registry

    fam = get_registry().get(STALENESS)
    series = [s for _labels, s in fam.children()] if fam else []
    return sum(s.sum for s in series), sum(s.count for s in series)


def _unacknowledged(before: list[dict], after: list[dict], acked: int) -> int:
    """The largest gap, over the servers, between the pushes a server
    applied and the gradient pushes the clients count as acknowledged:
    a dense push lands on every range, so each server's count rises by
    exactly that number."""
    return max(abs(a["total_pushes"] - b["total_pushes"] - acked)
               for b, a in zip(before, after))


def record(job: Job, rounds: int, keep: int) -> dict:
    """The recorded phase: ``rounds`` iterations a worker with a recorder
    round each gradient call, between two pulls with nothing in flight."""
    dim = len(job.w0)
    recorders = [GradRecorder(w.grad_step, keep, dim) for w in job.workers]
    for w, rec in zip(job.workers, recorders):
        w.grad_step = rec
    w_before = job.probe.pull()
    ops, servers = _client_ops(), _servers(job)
    try:
        in_threads(job, lambda w: w.fit(epochs=rounds))
    finally:
        for w, rec in zip(job.workers, recorders):
            w.grad_step = rec.step
    w_after = job.probe.pull()
    acked = _client_ops()["acked"] - ops["acked"]
    _, test_ll = job.workers[0].evaluate(w_after)
    return {
        "first": [rec.first for rec in recorders],
        "pushed_sum": sum(rec.total for rec in recorders),
        "rounds": [rec.rounds for rec in recorders],
        "w_before": w_before, "w_after": w_after,
        "acked": acked,
        "unacknowledged": _unacknowledged(servers, _servers(job), acked),
        "test_logloss": float(test_ll),
    }


def compare(job_rows: dict, got: dict, family: str, lr: float, limits: dict,
            unacknowledged_window: int | None = None) -> list[dict]:
    """Each number compared, beside its limit (PERF.md section 2);
    ``unacknowledged_window`` where a window was run."""
    fam = reference.family(family)
    rows = []

    def row(name, value, limit_key):
        rows.append({"name": name, "value": float(value),
                     "limit": float(limits[limit_key]),
                     "ok": bool(np.isfinite(value)
                                and value <= limits[limit_key])})

    # the gradients a worker pushed, against the reference's on that
    # worker's rows at the weights it computed on: the worst worker
    norm_gap = diff = 0.0
    for shard, first in zip(job_rows["shards"], got["first"]):
        for weights, pushed in first:
            ref = np.asarray(fam.gradient(weights, *shard))
            n_ref = max(float(np.linalg.norm(ref)), 1e-30)
            norm_gap = max(norm_gap, _rel_gap(np.linalg.norm(pushed), n_ref))
            diff = max(diff, float(np.linalg.norm(pushed - ref)) / n_ref)
    row("grad_norm_rel_gap", norm_gap, "grad_norm_rel_gap")
    row("grad_diff_rel", diff, "grad_diff_rel")
    # conservation: what the servers hold moved by what was pushed
    moved = got["w_after"].astype(np.float64) - got["w_before"]
    pushed = lr * got["pushed_sum"]
    n_pushed = max(float(np.linalg.norm(pushed)), 1e-30)
    row("conservation_rel", np.linalg.norm(moved + pushed) / n_pushed,
        "conservation_rel")
    row("update_missing",
        0.0 if np.linalg.norm(moved) > 0.5 * n_pushed else 1.0,
        "update_missing")
    row("unacknowledged_recorded", got["unacknowledged"],
        "unacknowledged_pushes")
    ref_ll = reference.logloss(family, got["w_after"], *job_rows["test"])
    row("test_logloss_rel_gap", _rel_gap(got["test_logloss"], ref_ll),
        "test_logloss_rel_gap")
    if unacknowledged_window is not None:
        row("unacknowledged_window", unacknowledged_window,
            "unacknowledged_pushes")
    return rows


def _rows_of(job: Job) -> dict:
    return {"shards": job.shards, "test": job.test}


def lowered(job_rows: dict, got: dict, family: str, precision: str) -> dict:
    """The recorded phase with the reference, computed in ``precision``,
    in the program's place: its gradient at the weights each worker
    computed on where the pushed one stood, its test logloss where the
    product's stood.  What the servers did stays as recorded."""
    fam = reference.family(family)
    first = [[(w, np.asarray(fam.gradient(w, *shard, precision=precision)))
              for w, _pushed in rounds]
             for shard, rounds in zip(job_rows["shards"], got["first"])]
    ll = reference.logloss(family, got["w_after"], *job_rows["test"],
                           precision=precision)
    return {**got, "first": first, "test_logloss": float(ll)}


def _per_worker(spans: dict, workers: int) -> dict:
    """The tracer's totals over four loops as one worker's means, so that
    a share of the wall stays a share and no reader divides again."""
    return {name: {"seconds": s["seconds"] / workers,
                   "count": s["count"] / workers,
                   "self_seconds": s["self_seconds"] / workers}
            for name, s in spans.items()}


def run(ctx) -> dict:
    """``ctx``: cell, seed, seconds, trace, rehearsal, devices, compiles,
    t_start, say.  Returns what ``chipbench.run`` prints."""
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
        got = record(job, int(traffic["recorded_rounds"]),
                     int(traffic["checked_rounds"]))
        # one timed fit sizes the window; WINDOW_MARGIN covers what a
        # short fit's start and end add to its rounds
        pace_rounds = int(traffic["pace_rounds"])
        pace = in_threads(job, lambda w: w.fit(epochs=pace_rounds)) / pace_rounds
        epochs = max(1, math.ceil(WINDOW_MARGIN * ctx.seconds / pace))
        ctx.say(f"recorded rounds={got['rounds']} acked={got['acked']} "
                f"pace_s={pace:.5f} window_iterations={epochs} "
                "compiles seconds={seconds:.2f} count={count} cache_hits={hits} "
                "cache_misses={misses}".format(**ctx.compiles.snapshot()))

        # -- the window: one fit a worker, at once ----------------------
        tracer = get_tracer()
        compiled_before = ctx.compiles.snapshot()
        counted_before = [(w.timer.samples, w.timer.steps) for w in job.workers]
        ops, servers = _client_ops(), _servers(job)
        stale = _staleness()
        tracer.reset()
        setup_s = time.perf_counter() - ctx.t_start
        window_wall = in_threads(job, lambda w: w.fit(epochs=epochs))
        spans = tracer.breakdown()
        ops_after, servers_after = _client_ops(), _servers(job)
        stale_after = _staleness()
        # the yardstick counts the work itself: E iterations of every shard
        rounds_done, rows_done = workers * epochs, workers * epochs * n
        counted = [(w.timer.samples - s, w.timer.steps - k)
                   for w, (s, k) in zip(job.workers, counted_before)]
        acked = ops_after["acked"] - ops["acked"]
        counts_agree = (counted == [(epochs * n, epochs)] * workers
                        and acked == rounds_done)
        bad_ops = ops_after["bad"] - ops["bad"]
        compiled_in_window = ctx.compiles.count - compiled_before["count"]
        ctx.say(f"window wall_s={window_wall:.3f} iterations={epochs} "
                f"rounds={rounds_done} rows={rows_done} "
                f"program_counted={counted} acked_pushes={acked} "
                f"failed_or_retried_ops={bad_ops} "
                f"compiles_in_window={compiled_in_window} "
                f"host_rss_peak_mib={_rss_peak_mib()}")

        ctx.say("window spans, a worker's mean ms: " + " ".join(
            f"{name}={1e3 * s['seconds'] / s['count']:.3f}"
            for name, s in sorted(spans.items()) if s["count"]))

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
            "ps": {
                "workers": workers, "rounds_per_worker": epochs,
                "server_pushes": sum(a["total_pushes"] - b["total_pushes"]
                                     for b, a in zip(servers, servers_after)),
                "server_push_cpu_s": sum(
                    a.get("cpu_push_seconds", 0.0) - b.get("cpu_push_seconds", 0.0)
                    for b, a in zip(servers, servers_after)),
                "pushes_behind_sum": stale_after[0] - stale[0],
                "pushes_behind_count": stale_after[1] - stale[1],
            },
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
        rows_kept = _rows_of(job)
        failed = False
    finally:
        job.close(failed)
    del job
    gc.collect()  # the shards leave the device before the reference runs

    # -- correct ---------------------------------------------------------
    t = time.perf_counter()
    rows = compare(rows_kept, got, family, lr, conf["limits"],
                   _unacknowledged(servers, servers_after, acked))
    ctx.say(f"reference gradients of {workers} x {len(got['first'][0])} rounds "
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

    ap = argparse.ArgumentParser(prog="chipbench.drivers.ps_epochs")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
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
