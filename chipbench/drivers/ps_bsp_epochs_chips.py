"""Traffic kind ``ps_bsp_epochs_chips``: the lock-step (BSP)
parameter-server job laid out as upstream's launcher lays it out, a
worker to a processor: worker *r*'s shard, weights, step and readback
on chip *r* of a four-chip host, the servers host processes on loopback.

Everything but the layout is ``ps_bsp_epochs``'s, whose ``compare`` and
``record_rounds`` this module imports with ``ps_epochs``' ``Job``,
``in_threads`` and counter readers; neither file is edited.  What the
layout adds:

* :func:`prepare` hands ``PSWorker`` *r* ``devices[r]``.  A program
  whose ``PSWorker`` takes no device puts every worker on the first
  chip, where four shards of this size do not fit: the driver says so
  and leaves before a row is made (:func:`needs_a_chip_a_worker`);
* ``memory_peak_bytes`` is the fullest chip's;
* ``correct`` also holds that worker *r*'s step ran on chip *r*: from
  the program's ``distlr_ps_step_device{rank}`` and, in a traced run,
  from the trace: the plane of chip *r* holds one run of the step
  program a traced round, each inside one of worker *r*'s own
  ``compute`` annotations (which carry ``rank``), and no plane else has
  any (:func:`planes_hold_their_own`);
* the run carries ``on_chips``: the devices, the servers' lock wait,
  and, traced, each worker's ``compute`` marks and its chip's plane, for
  the ``chips_*`` readers.

    python3 -m chipbench.drivers.ps_bsp_epochs_chips --workload <name> --seeds 1,2,3 [--controls 2]

reads what ``correct`` compares as ``ps_bsp_epochs``' tool does, a
worker to a chip.
"""

from __future__ import annotations

import argparse
import gc
import inspect
import json
import logging
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import datagen, trace_reduce
from chipbench.drivers.ps_bsp_epochs import (
    _grad_paths,
    _round_miscount,
    compare,
    needs_the_barriers_counters,
    record_rounds,
)
from chipbench.drivers.ps_epochs import (
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
    lowered,
)
from chipbench.drivers.train_stream import (
    _peak_bytes,
    _rss_peak_mib,
    effective_config,
    initial_weights,
)

STEP_DEVICE = "distlr_ps_step_device"


def needs_a_chip_a_worker(devices: list, workers: int) -> None:
    """A program that cannot place a worker on a chip of its own cannot
    run this layout: leave at once, before a row is made."""
    from distlr_tpu.train.ps_trainer import PSWorker

    if "device" not in inspect.signature(PSWorker.__init__).parameters:
        raise SystemExit(
            "chipbench ps_bsp_epochs_chips: this program's PSWorker takes "
            "no device: every worker's shard and step go to the first "
            f"chip, so {workers} workers cannot have a chip each; the "
            "cell runs from the commit that lets a job hand its workers "
            "their devices")
    if len(devices) < workers:
        raise SystemExit(
            f"chipbench ps_bsp_epochs_chips: {workers} workers need "
            f"{workers} devices, JAX has {len(devices)}")


def prepare(conf: dict, seed: int, say, devices: list,
            program_over: dict | None = None) -> Job:
    """``ps_epochs.prepare`` with worker *r* handed ``devices[r]``: rows
    from the seed as shards on disk, the server group, and workers that
    have loaded, placed and started."""
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
    group = probe = None
    made: list = []
    try:
        t = time.perf_counter()
        for r, shard in enumerate(shards):
            datagen.write_libsvm(os.path.join(tmp, "train", part_name(r)), *shard)
        datagen.write_libsvm(os.path.join(tmp, "test", part_name(0)), *test)
        cfg = Config(data_dir=tmp, test_interval=0, **prog)
        group = ps_trainer.server_group(cfg).start()
        probe = KVWorker(group.hosts, dim, client_id=0xFC00)
        w0 = initial_weights(seed, dim)
        probe.wait(probe.push_init(w0))
        for r in range(workers):
            made.append(ps_trainer.PSWorker(cfg, r, group.hosts,
                                            device=devices[r]))
        for w in made:  # one after another: one shard in flight at a time
            w.load_data()
        job = Job(cfg, group, made, probe, shards, test, w0, n,
                  train[0].shape[1],
                  [ln for ln in capture.lines if "dense steps pinned" in ln])
        in_threads(job, lambda w: w.start())
        say(f"servers={cfg.num_servers} workers={workers} loaded and started "
            f"load_s={time.perf_counter() - t:.2f} "
            f"host_rss_peak_mib={_rss_peak_mib()}")
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


def step_devices() -> dict[int, int]:
    """``{rank: device id}`` as the program's gauge has it."""
    from distlr_tpu.obs.registry import get_registry

    fam = get_registry().get(STEP_DEVICE)
    return {int(labels[0]): int(child.value)
            for labels, child in (fam.children() if fam else [])}


def plane_of(device) -> str:
    """The profiler's plane of a device: ``/device:TPU:<id>``."""
    return f"/device:{device.platform.upper()}:{device.id}"


def marks_by_rank(xplane_path: str, name: str = "compute") -> dict[int, list]:
    """``{rank: [(start_s, end_s), ...]}`` of the host annotations called
    ``name``, by the ``rank`` each carries (``obs.tracing.loop_span``)."""
    from jax.profiler import ProfileData

    out: dict[int, list] = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != name:
                    continue
                rank = next((v for k, v in ev.stats if k == "rank"), None)
                if rank is not None:
                    out.setdefault(int(rank), []).append(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9))
    return out


def plane_runs(xtrace: dict, plane: str, match: str, window) -> list[tuple]:
    """``trace_reduce.module_runs`` on a named plane."""
    lo, hi = window
    return [(s, s + d) for name, s, d
            in xtrace.get(plane, {}).get(trace_reduce.MODULES_LINE, [])
            if match in name and s >= lo and s + d <= hi]


def own_run(runs: list[tuple], mark: tuple, slack: float):
    """The run a ``compute`` mark waited for: the last to end inside it
    (the mark ends when its own gradient is ready)."""
    lo, hi = mark
    ended = [(e, s) for s, e in runs if lo <= e <= hi + slack]
    return max(ended)[::-1] if ended else None


def own_pairs(tr: dict, slack: float = 2e-3) -> dict[int, list]:
    """``{rank: [(mark, run), ...]}``: each of a worker's ``compute``
    marks beside its own run of the step program on its own plane."""
    out = {}
    for rank, plane in sorted(tr["plane_of_rank"].items()):
        runs = plane_runs(tr["xtrace"], plane, tr["step_program"],
                          tr["window"])
        pairs = [(m, own_run(runs, m, slack)) for m in tr["marks"].get(rank, [])]
        out[rank] = [(m, o) for m, o in pairs if o]
    return out


def launch_and_tail(tr: dict) -> dict[int, dict]:
    """A rank's means over the traced rounds, in milliseconds: ``launch``
    (the mark's start to the run's, as the two clocks read: negative
    where the device planes' clock leads the host's), ``run``, ``tail``
    (the run's end to the mark's) and ``mark``.  ``mark - run`` is two
    durations, each on its own clock, and carries no offset."""
    parts = (("launch", lambda m, o: o[0] - m[0]),
             ("run", lambda m, o: o[1] - o[0]),
             ("tail", lambda m, o: m[1] - o[1]),
             ("mark", lambda m, o: m[1] - m[0]))
    return {rank: {key: round(1e3 * sum(f(m, o) for m, o in pairs)
                              / len(pairs), 4) for key, f in parts}
            for rank, pairs in own_pairs(tr).items() if pairs}


def planes_hold_their_own(tr: dict, rounds: int, slack: float = 2e-3) -> list[str]:
    """What is wrong with where the traced step programs ran, one line a
    fault; nothing where the trace has no device plane (the CPU)."""
    xtrace, planes = tr["xtrace"], tr["plane_of_rank"]
    if not trace_reduce.device_planes(xtrace):
        return []
    faults = []
    if len(set(planes.values())) != len(planes):
        faults.append(f"two workers share a plane: {planes}")
    pairs = own_pairs(tr, slack)
    for rank, plane in sorted(planes.items()):
        runs = plane_runs(xtrace, plane, tr["step_program"], tr["window"])
        marks = tr["marks"].get(rank, [])
        owned = {run for _mark, run in pairs[rank]}
        if len(runs) != rounds or len(owned) != rounds:
            faults.append(
                f"rank {rank}: {plane} holds {len(runs)} runs of "
                f"{tr['step_program']}, {len(owned)} of them inside the "
                f"worker's own {len(marks)} compute marks, for {rounds} "
                "traced rounds")
    others = [p for p in trace_reduce.device_planes(xtrace)
              if p not in planes.values()
              and plane_runs(xtrace, p, tr["step_program"], tr["window"])]
    if others:
        faults.append(f"planes of no worker ran the step program: {others}")
    return faults


def run(ctx) -> dict:
    """``ctx``: cell, seed, seconds, trace, rehearsal, devices, compiles,
    t_start, say.  Returns what ``chipbench.run`` prints."""
    conf = effective_config(ctx.cell, ctx.rehearsal)
    prog, traffic, family = conf["program"], ctx.cell.traffic, conf["family"]
    lr, workers = float(prog["learning_rate"]), int(prog["num_workers"])
    needs_the_barriers_counters()
    needs_a_chip_a_worker(ctx.devices, workers)
    import jax

    from distlr_tpu.obs.tracing import get_tracer

    devices = list(ctx.devices[:workers])
    platform = devices[0].platform

    job = prepare(conf, ctx.seed, ctx.say, devices)
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

        # -- the window: one fit a worker, all chips at once ------------
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
            return sum(a.get(stat, 0) - b.get(stat, 0)
                       for b, a in zip(servers, servers_after))

        on_device = step_devices()
        run = {
            "cell": ctx.cell.name, "family": family, "chips": workers,
            "device_kind": devices[0].device_kind, "platform": platform,
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
            "on_chips": {"device_of_rank": on_device,
                         "lock_wait_s": rise("lock_wait_seconds")},
            "trace": None,
        }
        ctx.say(f"step devices by rank={on_device} "
                f"server lock_wait_s={run['on_chips']['lock_wait_s']:.4f}")

        # -- a traced run: a short fit of its own under the profiler ----
        plane_faults: list[str] = []
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
                xplane = trace_reduce.find_xplane(trace_dir)
                xtrace = trace_reduce.load_xplane(xplane)
                marks = marks_by_rank(xplane)
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
            window = trace_reduce.window_of(xtrace)
            run["trace"] = {
                "xtrace": xtrace, "window": window,
                "steps": workers * t_epochs, "host_spans": host_spans,
                "clock_offset": window[0] - anchor_host,
                "step_program": STEP_PROGRAM, "marks": marks,
                "plane_of_rank": {r: plane_of(d)
                                  for r, d in enumerate(devices)},
            }
            plane_faults = planes_hold_their_own(run["trace"], t_epochs)
            ctx.say(f"traced rounds_a_worker={t_epochs} "
                    f"fit_and_export_s={traced_s:.2f} step runs a plane="
                    + json.dumps({p: len(plane_runs(xtrace, p, STEP_PROGRAM,
                                                    window))
                                  for p in trace_reduce.device_planes(xtrace)})
                    + " compute marks a rank="
                    + json.dumps({r: len(m) for r, m in sorted(marks.items())})
                    + " a rank's mean ms="
                    + json.dumps(launch_and_tail(run["trace"])))

        memory_peak = _peak_bytes(devices)
        ctx.say("memory peak_bytes a chip=" + json.dumps(
            [_peak_bytes([d]) for d in devices]))
        # the product's own way out: final pull, exit barrier, rank 0
        # retires the group
        in_threads(job, lambda w: w.finish(save=False))
        finite = all(bool(np.isfinite(w.final_weights).all())
                     for w in job.workers)
        on_own_chip = (
            len(job.pinned) == workers
            and all(f"train -> {platform}:" in ln for ln in job.pinned)
            and on_device == {r: d.id for r, d in enumerate(devices)}
            and not plane_faults)
        rows_kept = _rows_of(job)
        failed = False
    finally:
        job.close(failed)
    del job
    gc.collect()  # the shards leave the chips before the reference runs

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
    if not on_own_chip:
        ctx.say("not every worker's step ran on its own chip: devices by "
                f"rank {on_device}, wanted "
                f"{ {r: d.id for r, d in enumerate(devices)} }"
                + "".join(f"; {f}" for f in plane_faults))
    correct = (all(r["ok"] for r in rows) and finite and counts_agree
               and compiled_in_window == 0 and bad_ops == 0 and on_own_chip)

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

    ap = argparse.ArgumentParser(prog="chipbench.drivers.ps_bsp_epochs_chips")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = manifest.Cell(manifest.load_benchmark(), args.workload)
    if not args.rehearse:
        harness.place_compile_cache()
    conf = effective_config(cell, args.rehearse)
    workers = int(conf["program"]["num_workers"])
    needs_the_barriers_counters()
    devices = harness.take_devices(cell.chips, args.rehearse)[:workers]
    needs_a_chip_a_worker(devices, workers)
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
        job = prepare(conf, seed, say, devices, program_over=over)
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
