"""Traffic kind ``ps_keyed_epochs``: the parameter server's own job, sparse
logistic regression with **keyed** Pull/Push.  Every worker keeps its
sparse shard on the chip, localised (each window's unique keys and each
entry's place among them worked out at load), and a round is: a keyed
pull of the window's keys, the gradient of the window on the chip, a
keyed push of the same keys; asynchronous, serialized, closed loop.

The job, the threads, the counters of acknowledged operations and the
servers' statistics are ``ps_epochs``'s, imported (``Job``,
``in_threads``, ``_client_ops``, ``_servers``, ``_unacknowledged``); that
file is not edited.  What this kind brings:

* a program with no keyed device path cannot run the cell: the driver
  asks for the series such a program keeps and leaves at once, before a
  row is made or a server spawned (:func:`needs_the_keyed_device_path`);
* set-up hands each worker its rows as arrays (``SparseDataIter``; the
  libsvm text of 15.7 M rows would be 7 GB to write and parse);
* the recorded phase taps every worker's connection (:class:`WireTap`):
  the keys and values of the first rounds' pulls and pushes as they go
  to the wire, a float64 scatter-sum of every pushed gradient into a
  D-vector, the keys every round moved; the first pulls of all workers
  are taken before any push is sent (a barrier inside the tap), so that
  they can be held to the dense pull before the phase bit for bit;
* ``correct`` (:func:`compare`; PERF.md section 2): the asynchronous
  job's rows of ``ps_epochs.compare`` with the keyed gradient held
  against ``families/sparse_ps_keyed.gradient`` of the window the rule
  gives, and ``pulled_stale``, ``keys_mismatch``, ``window_rows_short``,
  ``dense_frames``, ``resident_short``, ``host_steps``;
* the run carries ``kx`` for the ``kx_*`` readers.

    python3 -m chipbench.drivers.ps_keyed_epochs --workload <name> --seeds 1,2,3 [--controls 2]

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
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from chipbench import datagen, reference, trace_reduce
from chipbench.drivers.ps_bsp_epochs import _grad_paths
from chipbench.drivers.ps_epochs import (
    Job,
    _client_ops,
    _Lines,
    _per_worker,
    _rows_of,
    _servers,
    _unacknowledged,
    in_threads,
)
from chipbench.drivers.train_stream import (
    _peak_bytes,
    _rel_gap,
    _rss_peak_mib,
    effective_config,
    initial_weights,
)

#: the series a program has to keep for its keyed rounds to be counted
KEYED_KEYS = "distlr_ps_keyed_keys_total"
KEYED_ROWS = "distlr_ps_keyed_rows_total"
RESIDENT = "distlr_ps_resident_bytes"
CLIENT_BYTES = "distlr_ps_client_bytes_total"
#: the worker's keyed gradient program as a trace names it
STEP_PROGRAM = "jit_ps_keyed_grad_step"
DEVICE_PATH = "keyed_device"
#: spans that carry rows to the device or work a shard's keys out: none
#: may open inside the window
PLACING = ("h2d", "shard_put", "localise")
#: bytes a keyed round sends may pass its keys' and values' by this much
#: (frame headers) before ``dense_frames`` counts them
HEADER_SLACK = 0.005
LANES = 128
#: the window is sized this much over --seconds from the pacing epoch: a
#: whole epoch of 240 rounds paces the window's to 0.2% on the chip (8.546
#: s against 8.535, PERF.md section 5), where ``ps_epochs`` takes 15% for
#: its short fits; an epoch more is 8.5 s a run
WINDOW_MARGIN = 1.05


def needs_the_keyed_device_path() -> None:
    """A program that counts no keyed round has no keyed step on the
    device (its ``sparse_lr`` workers compute in numpy on the host and
    keep nothing resident): leave at once, before a row is made."""
    import distlr_tpu.train.ps_trainer  # noqa: F401  (registers its series)
    from distlr_tpu.obs.registry import get_registry

    missing = [s for s in (KEYED_KEYS, KEYED_ROWS)
               if get_registry().get(s) is None]
    if missing:
        raise SystemExit(
            "chipbench ps_keyed_epochs: this program's PSWorker keeps no "
            f"{missing}: its keyed models compute in numpy on the host and "
            "place no shard, so the cell's keyed step on the chip does not "
            "exist; the cell runs from the commit that localises a sparse_lr "
            "shard at load and runs jit_ps_keyed_grad_step")


def prepare(conf: dict, seed: int, say, program_over: dict | None = None) -> Job:
    """Rows from the seed as arrays, the server group, and workers that
    have localised, placed and started."""
    from distlr_tpu import Config
    from distlr_tpu.data.iterator import SparseDataIter
    from distlr_tpu.ps import KVWorker
    from distlr_tpu.train import ps_trainer

    gen, prog = conf["generator"], {**conf["program"], **(program_over or {})}
    dim, workers = int(prog["num_feature_dim"]), int(prog["num_workers"])
    n, batch = int(gen["rows_per_worker"]), int(prog["batch_size"])
    rows_kw = dict(fields=gen["fields"], num_buckets=dim,
                   label_scale=gen["label_scale"], label_bias=gen["label_bias"])
    t = time.perf_counter()
    train = datagen.make_rows(seed, "train", workers * n, **rows_kw)
    test = datagen.make_rows(seed, "test", int(gen["test_rows"]), **rows_kw)
    shards = [tuple(a[r * n:(r + 1) * n] for a in train) for r in range(workers)]
    say(f"rows train={workers}x{n} test={len(test[2])} "
        f"made_s={time.perf_counter() - t:.2f}")

    capture = _Lines()
    logger = logging.getLogger(ps_trainer.__name__)
    logger.addHandler(capture)
    group = None
    made: list = []
    probe = None
    try:
        t = time.perf_counter()
        # no eval and no checkpoint inside any fit; the epochs a call runs
        # are its own argument; the rows are handed in, nothing is read
        cfg = Config(data_dir="handed-in-as-arrays", test_interval=0, **prog)
        group = ps_trainer.server_group(cfg).start()
        probe = KVWorker(group.hosts, dim, client_id=0xFC00)
        w0 = initial_weights(seed, dim)
        probe.wait(probe.push_init(w0))
        for r, shard in enumerate(shards):
            made.append(ps_trainer.PSWorker(
                cfg, r, group.hosts,
                train_iter=SparseDataIter(*shard, batch),
                test_iter=SparseDataIter(*test, -1) if r == 0 else None))
        for w in made:  # one after another: one shard in flight at a time
            w.load_data()
        job = Job(cfg, group, made, probe, shards, test, w0, n,
                  train[0].shape[1],
                  [ln for ln in capture.lines if "steps pinned" in ln
                   or "run in numpy on the host" in ln])
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


def shard_bytes(rows: int, batch: int, slots: int) -> int:
    """What a worker keeps on the device of ``rows`` rows read ``batch``
    a window: a window's ``batch x slots`` places and values in whole
    lines of 128, 8 bytes an entry, and 8 bytes a row of labels and
    real-row flags."""
    windows = -(-rows // batch)
    lines = -(-batch * slots // LANES)
    return windows * (lines * LANES * 8 + batch * 8)


# -- what the program counts ---------------------------------------------
def _counts() -> dict:
    """The program's keyed counters over the ranks, its rounds by path,
    the bytes its clients sent and received in pulls and pushes, and the
    bytes each rank keeps resident."""
    from distlr_tpu.obs.registry import family_total, get_registry

    reg = get_registry()
    wire = {"sent": 0, "received": 0}
    fam = reg.get(CLIENT_BYTES)
    for (op, direction), child in (fam.children() if fam else []):
        if op in ("pull", "push"):
            wire[direction] += int(child.value)
    held = reg.get(RESIDENT)
    return {"keys": int(family_total(KEYED_KEYS)),
            "rows": int(family_total(KEYED_ROWS)),
            "paths": _grad_paths(), **wire,
            "resident": {labels[0]: int(child.value)
                         for labels, child in (held.children() if held else [])}}


def _run_frames(servers: list[dict]) -> int:
    return sum(int(s.get("run_frames", 0)) for s in servers)


def phase_counts(before: dict, after: dict, servers: list[dict],
                 servers_after: list[dict], rounds: int, rows: int,
                 keys: int) -> dict:
    """A phase (the recorded fit, the window) held to what it ran:
    ``rounds`` keyed rounds over ``rows`` real rows that moved ``keys``
    keys in all."""
    paths = {p: after["paths"].get(p, 0) - before["paths"].get(p, 0)
             for p in set(after["paths"]) | set(before["paths"])}
    on_device = paths.pop(DEVICE_PATH, 0)
    sent = after["sent"] - before["sent"]
    # a pull sends its keys, a push its keys and its float32 values
    due = keys * (8 + 8 + 4)
    return {
        "window_rows_short": (abs(after["rows"] - before["rows"] - rows)
                              + abs(after["keys"] - before["keys"] - keys)),
        "host_steps": sum(paths.values()) + max(0, rounds - on_device),
        "dense_frames": (_run_frames(servers_after) - _run_frames(servers)
                         + int(sent > due * (1 + HEADER_SLACK))),
        "sent": sent, "received": after["received"] - before["received"],
        "keys": after["keys"] - before["keys"],
    }


class WireTap:
    """Stands round one worker's connection during the recorded phase:
    the keys and values of its first ``keep`` keyed pulls and pushes as
    they go to the wire, a float64 scatter-sum of every pushed gradient,
    and the keys every round moved.  Every worker's first pull is taken
    before any worker sends a push (``first``: a barrier all the taps
    share, passed once the pull has returned)."""

    def __init__(self, worker, keep: int, dim: int, first: threading.Barrier):
        self.worker, self.keep, self.first = worker, keep, first
        self.pulls: list[tuple] = []
        self.pushes: list[tuple] = []
        self.total = np.zeros(dim, np.float64)
        self.rounds = self.keys_moved = 0
        self.calls = {name: getattr(worker.kv, name)
                      for name in ("pull", "push")}
        worker.kv.pull, worker.kv.push = self._pull, self._push

    def _pull(self, keys=None, **kw):
        got = self.calls["pull"](keys=keys, **kw)
        if len(self.pulls) < self.keep:
            self.pulls.append((None if keys is None else np.array(keys),
                               np.array(got)))
        first, self.first = self.first, None
        if first is not None:
            try:
                first.wait(timeout=120)
            except threading.BrokenBarrierError:
                pass  # a peer failed: in_threads says why
        return got

    def _push(self, vals, keys=None, **kw):
        if len(self.pushes) < self.keep:
            self.pushes.append((None if keys is None else np.array(keys),
                                np.array(vals)))
        if keys is None:
            self.total += vals
        else:
            self.total[np.asarray(keys).astype(np.int64)] += vals
            self.keys_moved += len(keys)
        self.rounds += 1
        return self.calls["push"](vals, keys=keys, **kw)

    def remove(self) -> None:
        for name in self.calls:
            delattr(self.worker.kv, name)  # the class's own again


def record(job: Job, epochs: int, keep: int) -> dict:
    """The recorded phase: ``epochs`` epochs a worker with a tap round
    each connection, between two dense pulls with nothing in flight."""
    dim, n = len(job.w0), job.rows_per_worker
    w_before = job.probe.pull()
    counts, ops, servers = _counts(), _client_ops(), _servers(job)
    first = threading.Barrier(len(job.workers))
    taps = [WireTap(w, keep, dim, first) for w in job.workers]
    try:
        in_threads(job, lambda w: w.fit(epochs=epochs))
    finally:
        for t in taps:
            t.remove()
    servers_after, counts_after = _servers(job), _counts()
    acked = _client_ops()["acked"] - ops["acked"]
    w_after = job.probe.pull()
    _, test_ll = job.workers[0].evaluate()
    rounds = sum(t.rounds for t in taps)
    keys = sum(t.keys_moved for t in taps)
    return {
        "pulls": [t.pulls for t in taps], "pushes": [t.pushes for t in taps],
        "pushed_sum": sum(t.total for t in taps),
        "rounds": [t.rounds for t in taps],
        "keys_an_epoch": [t.keys_moved // max(epochs, 1) for t in taps],
        "w_before": w_before, "w_after": w_after, "acked": acked,
        "unacknowledged": _unacknowledged(servers, servers_after, acked),
        "test_logloss": float(test_ll),
        "resident": counts_after["resident"],
        **phase_counts(counts, counts_after, servers, servers_after, rounds,
                       len(job.workers) * epochs * n, keys),
    }


def compare(job_rows: dict, got: dict, family: str, lr: float, batch: int,
            limits: dict, window: dict | None = None) -> list[dict]:
    """Each number compared, beside its limit (PERF.md section 2);
    ``window``: the window's own ``unacknowledged``, ``window_rows_short``,
    ``dense_frames``, ``host_steps`` and ``placed``, where one was run."""
    fam = reference.family(family)
    rows = []

    def row(name, value, limit_key=None):
        limit = limits[limit_key or name]
        rows.append({"name": name, "value": float(value),
                     "limit": float(limit),
                     "ok": bool(np.isfinite(value) and value <= limit)})

    extra = window or {"window_rows_short": 0, "dense_frames": 0,
                       "host_steps": 0, "placed": 0}
    # every recorded round against the reference's keys and gradient of
    # the window the rule gives that round, at the weights pulled
    norm_gap = diff = 0.0
    mismatched = stale = 0
    for shard, pulls, pushes in zip(job_rows["shards"], got["pulls"],
                                    got["pushes"]):
        cols, vals, y = shard
        mismatched += abs(len(pulls) - len(pushes))
        for k, ((pulled, w_u), (pushed_keys, g)) in enumerate(zip(pulls, pushes)):
            at = fam.window(k, len(y), batch)
            want = fam.keys(cols[at])
            same = [keys is not None and np.array_equal(keys, want)
                    for keys in (pulled, pushed_keys)]
            mismatched += 2 - sum(same)
            if k == 0 and pulled is not None:
                on = got["w_before"][np.asarray(pulled).astype(np.int64)]
                stale += int(len(on) != len(w_u) or not np.array_equal(
                    on.view(np.uint32), np.asarray(w_u).view(np.uint32)))
            if not all(same) or len(w_u) != len(want) or len(g) != len(want):
                norm_gap = diff = float("inf")  # nothing to hold it against
                continue
            ref = fam.gradient(w_u, cols[at], vals[at], y[at])
            n_ref = max(float(np.linalg.norm(ref)), 1e-30)
            norm_gap = max(norm_gap, _rel_gap(np.linalg.norm(g), n_ref))
            diff = max(diff, float(np.linalg.norm(g - ref)) / n_ref)
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
    ref_ll = reference.logloss(family, got["w_after"], *job_rows["test"])
    row("test_logloss_rel_gap", _rel_gap(got["test_logloss"], ref_ll))
    if window is not None:
        row("unacknowledged_window", window["unacknowledged"],
            "unacknowledged_pushes")
    row("pulled_stale", stale)
    row("keys_mismatch", mismatched)
    row("window_rows_short",
        got["window_rows_short"] + extra["window_rows_short"])
    row("dense_frames", got["dense_frames"] + extra["dense_frames"])
    # every worker's shard stays where load_data put it
    slots = job_rows["shards"][0][0].shape[1]
    held = got["resident"]
    row("resident_short",
        sum(max(0, shard_bytes(len(s[2]), batch, slots) - held.get(str(r), 0))
            for r, s in enumerate(job_rows["shards"])) + extra["placed"])
    row("host_steps", got["host_steps"] + extra["host_steps"])
    return rows


def lowered(job_rows: dict, got: dict, family: str, precision: str,
            batch: int) -> dict:
    """The recorded phase with the reference, computed in ``precision``,
    in the program's place: its gradient of each round's window at the
    weights the worker pulled where the pushed one stood, its test
    logloss where the program's stood.  What the servers, the keys and
    the counters did stays as recorded."""
    fam = reference.family(family)
    pushes = []
    for (cols, vals, y), pulls, pushed in zip(job_rows["shards"],
                                              got["pulls"], got["pushes"]):
        mine = []
        for k, ((_pk, w_u), (keys, _g)) in enumerate(zip(pulls, pushed)):
            at = fam.window(k, len(y), batch)
            mine.append((keys, fam.gradient(w_u, cols[at], vals[at], y[at],
                                            precision=precision)))
        pushes.append(mine)
    ll = reference.logloss(family, got["w_after"], *job_rows["test"],
                           precision=precision)
    return {**got, "pushes": pushes, "test_logloss": float(ll)}


def run(ctx) -> dict:
    """``ctx``: cell, seed, seconds, trace, rehearsal, devices, compiles,
    t_start, say.  Returns what ``chipbench.run`` prints."""
    needs_the_keyed_device_path()
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
        n, slots = job.rows_per_worker, job.nnz_width
        per = reference.family(family).rounds_an_epoch(n, batch)
        # -- set-up: the recorded epoch, then the pace ------------------
        got = record(job, int(traffic["recorded_epochs"]),
                     int(traffic["checked_rounds"]))
        pace_epochs = int(traffic["pace_epochs"])
        pace = in_threads(job, lambda w: w.fit(epochs=pace_epochs)) / pace_epochs
        epochs = max(1, math.ceil(WINDOW_MARGIN * ctx.seconds / pace))
        keys_an_epoch = sum(got["keys_an_epoch"])
        ctx.say(f"recorded rounds={got['rounds']} acked={got['acked']} "
                f"rounds_an_epoch={per} keys_a_round="
                f"{keys_an_epoch / max(workers * per, 1):.1f} resident_bytes="
                f"{sorted(got['resident'].values())} "
                f"epoch_pace_s={pace:.5f} window_epochs={epochs} "
                + "compiles seconds={seconds:.2f} count={count} cache_hits="
                "{hits} cache_misses={misses}".format(**ctx.compiles.snapshot()))

        # -- the window: one fit a worker, at once ----------------------
        tracer = get_tracer()
        compiled_before = ctx.compiles.snapshot()
        counted_before = [(w.timer.samples, w.timer.steps) for w in job.workers]
        ops, servers, counts = _client_ops(), _servers(job), _counts()
        tracer.reset()
        setup_s = time.perf_counter() - ctx.t_start
        window_wall = in_threads(job, lambda w: w.fit(epochs=epochs))
        spans = tracer.breakdown()
        ops_after, servers_after, counts_after = (
            _client_ops(), _servers(job), _counts())
        # the yardstick counts the work itself: E passes over every shard,
        # a round the real rows of its window
        rounds_done, rows_done = workers * epochs * per, workers * epochs * n
        counted = [(w.timer.samples - s, w.timer.steps - k)
                   for w, (s, k) in zip(job.workers, counted_before)]
        # a round is a pull and a push: the pushes are what is acknowledged
        acked = ops_after["acked"] - ops["acked"]
        counts_agree = (counted == [(epochs * n, epochs * per)] * workers
                        and acked == rounds_done)
        bad_ops = ops_after["bad"] - ops["bad"]
        compiled_in_window = ctx.compiles.count - compiled_before["count"]
        in_window = {
            **phase_counts(counts, counts_after, servers, servers_after,
                           rounds_done, rows_done, epochs * keys_an_epoch),
            "placed": sum(spans.get(s, {"count": 0})["count"] for s in PLACING),
            "unacknowledged": _unacknowledged(servers, servers_after, acked)}
        ctx.say(f"window wall_s={window_wall:.3f} epochs={epochs} "
                f"rounds={rounds_done} rows={rows_done} "
                f"program_counted={counted} acked_pushes={acked} "
                f"failed_or_retried_ops={bad_ops} "
                f"compiles_in_window={compiled_in_window} "
                f"keys={in_window['keys']} sent_bytes={in_window['sent']} "
                f"received_bytes={in_window['received']} "
                f"dense_frames={in_window['dense_frames']} "
                f"host_steps={in_window['host_steps']} "
                f"placing_spans={in_window['placed']} "
                f"host_rss_peak_mib={_rss_peak_mib()}")
        ctx.say("window spans, a worker's mean ms: " + " ".join(
            f"{name}={1e3 * s['seconds'] / s['count']:.3f}"
            for name, s in sorted(spans.items()) if s["count"]))

        def rise(stat):
            return sum(a.get(stat, 0.0) - b.get(stat, 0.0)
                       for b, a in zip(servers, servers_after))

        run = {
            "cell": ctx.cell.name, "family": family, "chips": 1,
            "device_kind": ctx.devices[0].device_kind, "platform": platform,
            "setup_compile": compiled_before,
            "compiles_in_window": compiled_in_window,
            "window": {"wall_s": window_wall, "steps": rounds_done,
                       "rows": rows_done,
                       "spans": _per_worker(spans, workers)},
            # a step reads a window of the resident entries and its keys
            "step": {"rows": batch, "nnz": batch * slots, "dim": dim,
                     "keys": in_window["keys"] / max(rounds_done, 1)},
            "kx": {
                "rounds_per_worker": epochs * per, "rounds": rounds_done,
                "keys": in_window["keys"], "sent_bytes": in_window["sent"],
                "received_bytes": in_window["received"],
                "dense_round_bytes": 2 * dim * 4,
                "server_pushes": rise("total_pushes"),
                "server_merge_s": rise("merge_seconds"),
                "mapped_frames": rise("mapped_frames"),
            },
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
    rows = compare(rows_kept, got, family, lr, batch, conf["limits"], in_window)
    ctx.say(f"reference keys and gradients of {workers} x "
            f"{len(got['pulls'][0])} rounds' windows "
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

    ap = argparse.ArgumentParser(prog="chipbench.drivers.ps_keyed_epochs")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    needs_the_keyed_device_path()
    cell = manifest.Cell(manifest.load_benchmark(), args.workload)
    if not args.rehearse:
        harness.place_compile_cache()
    harness.take_devices(cell.chips, args.rehearse)
    conf = effective_config(cell, args.rehearse)
    traffic, family, prog = cell.traffic, conf["family"], conf["program"]
    lr, batch = float(prog["learning_rate"]), int(prog["batch_size"])
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
                         int(traffic["checked_rounds"]))
            kept = _rows_of(job)
            failed = False
        finally:
            job.close(failed)
        del job
        gc.collect()
        note(tag, seed, compare(kept, got, family, lr, batch, conf["limits"]))
        if over is None:
            note(low, seed, compare(
                kept, lowered(kept, got, family, low, batch), family, lr,
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
