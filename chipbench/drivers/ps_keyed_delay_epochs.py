"""Traffic kind ``ps_keyed_delay_epochs``: the keyed sparse-LR job of
``ps_keyed_ftrl_epochs`` (FTRL-Proximal servers, resident localised
shards, the window's gradient on the chip) under **bounded delay, tau =
1** (``ps_max_delay: 1``): a worker's comm thread pushes round *k*'s
gradient and then pulls round *k* + 2's keys while its loop computes
round *k* + 1, so the weights under round *k* are exactly one own push
behind (``families/sparse_ps_keyed_delay.py``: the rule).

The job, its servers' state and its rows of ``correct`` are
``ps_keyed_ftrl_epochs``'s and, through it, ``ps_keyed_epochs``'s and
``ps_epochs``'s, imported; none of those files is edited.  What this
kind brings:

* a program that keeps no ``distlr_ps_keyed_pull_lineage_total`` cannot
  run a keyed round under its exchange: the driver says so and leaves at
  once, before a row is made or a server spawned
  (:func:`needs_the_keyed_delay`);
* the recorded phase's **serial prefix** is each worker's first
  ``checked_rounds`` rounds, worker after worker in rank order
  (:class:`PrefixTap`): a worker's first pull waits for the
  acknowledgement of the last prefix push of the worker before it, and
  after its own last prefix push every worker stands held while the
  servers' state is read; so every push of the prefix has one known
  place, and the pulls between them (which mutate nothing) are held to
  it by the worker's own connection;
* ``correct`` (:func:`compare`) is the sibling's every row, with
  ``replay_rel`` over the whole prefix and ``pulled_stale_rel`` replaced
  by ``pulled_lineage_rel`` (every prefix round's reply against
  ``computed_on``: own pushes through round *k* - 2 and every earlier
  rank's prefix, and none other) and ``lineage_miscount_recorded`` /
  ``_window`` (the program's own count, from its connection's op
  sequence, of pulls by how many own pushes they were behind);
* the run carries ``kd`` for the ``kd_*`` readers (:func:`kd_side`).

    python3 -m chipbench.drivers.ps_keyed_delay_epochs --workload <name> --seeds 1,2,3 [--controls 2]

reads what ``correct`` compares up to the recorded phase's end, seed
after seed in one process: for the program, for its control
(``control.program``, the serialized exchange, in the program's place on
the first ``--controls`` seeds) and for the reference computed in
``control.precision`` and put where the program's gradients stand.
``--rehearse`` runs the tiny sizes anywhere.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from chipbench import reference, trace_reduce
from chipbench.drivers.ps_epochs import (
    _client_ops,
    _per_worker,
    _rows_of,
    _servers,
    _unacknowledged,
    in_threads,
)
from chipbench.drivers.ps_keyed_epochs import (
    PLACING,
    STEP_PROGRAM,
    WINDOW_MARGIN,
    _counts,
    _run_frames,
    phase_counts,
)
from chipbench.drivers.ps_keyed_ftrl_epochs import (
    TURN_TIMEOUT_S,
    FtrlJob,
    OrderedTap,
    _change_rel,
    lowered,
    needs_the_ftrl_counters,
    prepare,
    rule_of,
    state,
    warm_up,
    zero_share,
)
from chipbench.drivers.ps_keyed_ftrl_epochs import compare as sibling_compare
from chipbench.drivers.train_stream import (
    _peak_bytes,
    _rss_peak_mib,
    effective_config,
)

#: the series a program has to keep for its delayed keyed pulls to be
#: counted by how far behind they were
LINEAGE = "distlr_ps_keyed_pull_lineage_total"
#: the spans of a worker's device chain: what the exchange rides under
CHAIN = ("w_put", "compute", "grad_d2h")
#: the comm thread's two operations a round
WIRE = ("push", "pull")


def needs_the_keyed_delay() -> None:
    """A program whose keyed exchange is the serialized one alone keeps
    no count of how far behind a keyed pull was (and its ``Config``
    refuses the configuration): leave at once, before a row is made."""
    needs_the_ftrl_counters()
    import distlr_tpu.train.ps_trainer  # noqa: F401  (registers its series)
    from distlr_tpu.obs.registry import get_registry

    if get_registry().get(LINEAGE) is None:
        raise SystemExit(
            f"chipbench ps_keyed_delay_epochs: this program keeps no {LINEAGE}"
            ": its keyed models pull, then push and wait, nothing overlapped, "
            "and ps_max_delay=1 is refused for them, so the configuration's "
            "bounded delay cannot be asked for nor a pull's lineage counted; "
            "the cell runs from the commit that adds the keyed delayed "
            "exchange to PSWorker.fit")


class PrefixTap(OrderedTap):
    """``OrderedTap`` whose serial prefix is a worker's first ``keep``
    rounds: the worker's first pull waits for its turn, the
    acknowledgement of its ``keep``-th push hands the turn on, and there
    the worker's connection stands at ``held`` until every worker's
    prefix is in and the state has been read.  Whichever thread carries
    the connection's operations is the one that waits."""

    def _wait(self, ts):
        got = self.calls["wait"](ts)
        self.acked += 1
        if self.acked == self.keep:
            nxt = self.worker.rank + 1
            if nxt < len(self.turns):
                self.turns[nxt].set()
            try:
                self.held.wait(TURN_TIMEOUT_S)
            except threading.BrokenBarrierError:
                pass  # a peer failed: in_threads says why
        return got


def _lineage() -> dict:
    """The program's count of delayed keyed pulls by how many own pushes
    they were behind, over the ranks."""
    from distlr_tpu.obs.registry import get_registry

    fam = get_registry().get(LINEAGE)
    out: dict = {}
    for labels, child in fam.children() if fam else []:
        out[labels[-1]] = out.get(labels[-1], 0) + int(child.value)
    return out


def _rise(before: dict, after: dict) -> dict:
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in set(before) | set(after)}


def lineage_miscount(rise: dict, fits: int, rounds: int) -> int:
    """How far the count's rise is from what the rounds run imply:
    ``fits`` calls of ``fit`` that ran ``rounds`` rounds between them
    count one pull each none behind, every other one behind, and none at
    any other distance."""
    rise = dict(rise)
    return (abs(rise.pop("1", 0) - (rounds - fits))
            + abs(rise.pop("0", 0) - fits)
            + sum(abs(v) for v in rise.values()))


def record(job: FtrlJob, epochs: int, keep: int) -> dict:
    """The recorded phase: ONE ``fit`` of ``epochs`` epochs a worker
    under a :class:`PrefixTap`, between two readings of the servers'
    state with nothing in flight, and a third after the serial prefix
    (each worker's first ``keep`` rounds, or the whole fit where it is
    shorter)."""
    dim, n = len(job.w0), job.rows_per_worker
    per = -(-n // int(job.cfg.batch_size))
    keep = min(keep, epochs * per)
    before = state(job)
    counts, ops, servers = _counts(), _client_ops(), _servers(job)
    lineage = _lineage()
    after_prefix: dict = {}

    def read_held():
        mine = _run_frames(_servers(job))
        after_prefix.update(state(job))
        after_prefix["run_frames"] = _run_frames(_servers(job)) - mine

    turns = [threading.Event() for _ in job.workers]
    turns[0].set()
    held = threading.Barrier(len(job.workers), action=read_held)
    taps = [PrefixTap(w, keep, dim, turns, held) for w in job.workers]
    try:
        in_threads(job, lambda w: w.fit(epochs=epochs))
    finally:
        for t in taps:
            t.remove()
    servers_after, counts_after = _servers(job), _counts()
    acked = _client_ops()["acked"] - ops["acked"]
    after = state(job)
    rounds = sum(t.rounds for t in taps)
    keys = sum(t.keys_moved for t in taps)
    counted = phase_counts(counts, counts_after, servers, servers_after,
                           rounds, len(job.workers) * epochs * n, keys)
    counted["dense_frames"] -= after_prefix.get("run_frames", 0)
    return {
        "pulls": [t.pulls for t in taps], "pushes": [t.pushes for t in taps],
        "squares": sum(t.squares for t in taps),
        "nonzero": sum(t.nonzero for t in taps),
        "steps": sum(a["ftrl_steps"] - b["ftrl_steps"]
                     for b, a in zip(servers, servers_after)),
        "rounds": [t.rounds for t in taps], "prefix": keep,
        "keys_an_epoch": [t.keys_moved // max(epochs, 1) for t in taps],
        "before": before, "after_prefix": after_prefix, "after": after,
        "acked": acked,
        "unacknowledged": _unacknowledged(servers, servers_after, acked),
        "lineage_miscount": lineage_miscount(
            _rise(lineage, _lineage()), len(job.workers), rounds),
        "in_flight_at_return": sum(w.in_flight for w in job.workers),
        "resident": counts_after["resident"], **counted,
    }


def _rel(got, want) -> float:
    if len(got) != len(want):
        return float("inf")
    return (float(np.linalg.norm(got.astype(np.float64) - want))
            / max(float(np.linalg.norm(want)), 1e-30))


def compare(job_rows: dict, got: dict, family: str, prog: dict, batch: int,
            limits: dict, window: dict | None = None) -> list[dict]:
    """Each number compared, beside its limit (PERF.md section 2): the
    sibling's rows (``ps_keyed_ftrl_epochs.compare``), with the replay
    taken over the whole serial prefix, ``pulled_stale_rel`` replaced by
    ``pulled_lineage_rel``, and the two ``lineage_miscount`` rows last.
    ``window``: the sibling's, and the window's own ``lineage_miscount``."""
    fam = reference.family(family)
    rule = rule_of(prog)
    # the sibling replays round 0 alone: its two prefix rows are made
    # here, over the prefix this kind records
    rows = sibling_compare(
        job_rows, {**got, "after_prefix": {}}, family, prog, batch,
        {**limits, "pulled_stale_rel": 0.0}, window)

    def row(name, value, limit_key=None):
        limit = limits[limit_key or name]
        return {"name": name, "value": float(value), "limit": float(limit),
                "ok": bool(np.isfinite(value) and value <= limit)}

    b, p, prefix = got["before"], got["after_prefix"], got["prefix"]
    pushes = [ps[:prefix] for ps in got["pushes"]]
    whole = (bool(p) and prefix > 0
             and all(len(ps) == prefix for ps in pushes)
             and all(len(pl) >= prefix for pl in got["pulls"])
             and all(k is not None for ps in pushes for k, _g in ps))
    replayed = lineage = float("inf")
    if whole:
        state0 = (b["w"], b["z"], b["n"])
        (w_r, z_r, n_r), _stood = fam.replay(
            [push for ps in pushes for push in ps], *state0, **rule)
        replayed = max(_change_rel(p[t], r, b[t]) for t, r in
                       (("z", z_r), ("n", n_r), ("w", w_r)))
        # every prefix round's reply against what the rule computes it on
        lineage = max(
            _rel(np.asarray(w_u), fam.computed_on(
                k, pushes, state0, rank=rank, at=pulled, **rule))
            if pulled is not None else float("inf")
            for rank, pulls in enumerate(got["pulls"])
            for k, (pulled, w_u) in enumerate(pulls[:prefix]))
    swap = {"replay_rel": [row("replay_rel", replayed)],
            "pulled_stale_rel": [row("pulled_lineage_rel", lineage)]}
    rows = [new for r in rows for new in swap.get(r["name"], [r])]
    rows.append(row("lineage_miscount_recorded", got["lineage_miscount"],
                    "lineage_miscount"))
    if window is not None:
        rows.append(row("lineage_miscount_window",
                        window["lineage_miscount"], "lineage_miscount"))
    return rows


def _covered(starts, ends):
    """``C(t)``: the seconds of the disjoint, sorted intervals that lie
    before ``t``."""
    cum = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def before(t):
        i = np.searchsorted(starts, t, side="right")
        last = np.maximum(i - 1, 0)
        part = np.clip(t - starts[last], 0.0, ends[last] - starts[last])
        return np.where(i > 0, cum[last] + part, 0.0)

    return before


def kd_side(events: list[dict], dropped: int, lineage: dict,
            rounds: int) -> dict:
    """What the ``kd_*`` readers take from the tracer's events of a call
    that has just ended and from the lineage count's rise over it: the
    comm threads' ``push`` and ``pull`` seconds, how much of them lies
    under the same worker's ``w_put``, ``compute`` and ``grad_d2h``
    spans, the loop's ``exchange_wait`` apart from its drains, and the
    mean own pushes a pull was behind."""
    chain: dict = {}
    wire: dict = {}
    wait = {"wait": [0.0, 0], "drain": [0.0, 0]}
    flying: dict = {}
    for e in events:
        args = e.get("args", {})
        rank = args.get("rank")
        if rank is None:
            continue
        lo, hi = e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6
        name = e["name"]
        if name in CHAIN:
            chain.setdefault(rank, []).append((lo, hi))
            if name == "compute" and "in_flight" in args:
                n = int(args["in_flight"])
                flying[n] = flying.get(n, 0) + 1
        elif name in WIRE:
            wire.setdefault(rank, []).append((lo, hi))
        elif name == "exchange_wait":
            side = wait["drain" if args.get("drain") else "wait"]
            side[0] += hi - lo
            side[1] += 1
    wire_s = under = 0.0
    for rank, spans in wire.items():
        ops = np.array(spans)
        wire_s += float((ops[:, 1] - ops[:, 0]).sum())
        mine = np.array(sorted(chain.get(rank, [])))
        if len(mine):
            before = _covered(mine[:, 0], mine[:, 1])
            under += float((before(ops[:, 1]) - before(ops[:, 0])).sum())
    behind = sum(int(k) * v for k, v in lineage.items())
    return {"wire_s": wire_s, "wire_under_chain_s": under,
            "wires": sum(len(s) for s in wire.values()),
            "exchange_wait": {k: {"seconds": s, "count": n}
                              for k, (s, n) in wait.items()},
            "computes_in_flight": {str(k): v
                                   for k, v in sorted(flying.items())},
            "pulls_behind_sum": behind,
            "pulls_counted": sum(lineage.values()),
            "events": len(events), "events_dropped": dropped,
            "events_a_round": (len(events) + dropped) / max(rounds, 1)}


def run(ctx) -> dict:
    """``ctx``: cell, seed, seconds, trace, rehearsal, devices, compiles,
    t_start, say.  Returns what ``chipbench.run`` prints."""
    needs_the_keyed_delay()
    import jax

    from distlr_tpu.obs.tracing import get_tracer

    conf = effective_config(ctx.cell, ctx.rehearsal)
    prog, traffic, family = conf["program"], ctx.cell.traffic, conf["family"]
    workers = int(prog["num_workers"])
    dim, batch = int(prog["num_feature_dim"]), int(prog["batch_size"])
    platform = ctx.devices[0].platform

    job = prepare(conf, ctx.seed, ctx.say)
    failed = True
    try:
        n, slots = job.rows_per_worker, job.nnz_width
        per = reference.family(family).rounds_an_epoch(n, batch)
        # -- set-up: warm-up, the recorded epoch, then the pace ----------
        warm_up(job, int(traffic["warm_epochs"]), ctx.say)
        got = record(job, int(traffic["recorded_epochs"]),
                     int(traffic["checked_rounds"]))
        pace_epochs = int(traffic["pace_epochs"])
        pace = in_threads(job, lambda w: w.fit(epochs=pace_epochs)) / pace_epochs
        epochs = max(1, math.ceil(WINDOW_MARGIN * ctx.seconds / pace))
        keys_an_epoch = sum(got["keys_an_epoch"])
        ctx.say(f"recorded rounds={got['rounds']} prefix={got['prefix']} "
                f"acked={got['acked']} rounds_an_epoch={per} keys_a_round="
                f"{keys_an_epoch / max(workers * per, 1):.1f} "
                f"ftrl_steps={got['steps']} nonzero_entries={got['nonzero']} "
                f"lineage_miscount={got['lineage_miscount']} "
                f"in_flight_at_return={got['in_flight_at_return']} "
                f"resident_bytes={sorted(got['resident'].values())} "
                f"epoch_pace_s={pace:.5f} window_epochs={epochs} "
                + "compiles seconds={seconds:.2f} count={count} cache_hits="
                "{hits} cache_misses={misses}".format(**ctx.compiles.snapshot()))

        # -- the window: one fit a worker, at once ----------------------
        tracer = get_tracer()
        compiled_before = ctx.compiles.snapshot()
        counted_before = [(w.timer.samples, w.timer.steps) for w in job.workers]
        ops, servers, counts = _client_ops(), _servers(job), _counts()
        lineage = _lineage()
        tracer.reset()
        setup_s = time.perf_counter() - ctx.t_start
        window_wall = in_threads(job, lambda w: w.fit(epochs=epochs))
        spans = tracer.breakdown()
        trace_doc = tracer.chrome_trace()
        ops_after, servers_after, counts_after = (
            _client_ops(), _servers(job), _counts())
        lineage_rise = _rise(lineage, _lineage())
        in_flight = sum(w.in_flight for w in job.workers)
        # the yardstick counts the work itself: E passes over every shard,
        # a round the real rows of its window
        rounds_done, rows_done = workers * epochs * per, workers * epochs * n
        counted = [(w.timer.samples - s, w.timer.steps - k)
                   for w, (s, k) in zip(job.workers, counted_before)]
        # a round is a pull and a push: the pushes are what is acknowledged
        acked = ops_after["acked"] - ops["acked"]
        counts_agree = (counted == [(epochs * n, epochs * per)] * workers
                        and acked == rounds_done and in_flight == 0)
        bad_ops = ops_after["bad"] - ops["bad"]
        compiled_in_window = ctx.compiles.count - compiled_before["count"]
        # what the timed path itself left, nothing in flight: the state,
        # and rank 0's keyed eval of it
        final = state(job)
        _, test_ll = job.workers[0].evaluate()
        share, stepped = zero_share(final)
        in_window = {
            **phase_counts(counts, counts_after, servers, servers_after,
                           rounds_done, rows_done, epochs * keys_an_epoch),
            "placed": sum(spans.get(s, {"count": 0})["count"] for s in PLACING),
            "unacknowledged": _unacknowledged(servers, servers_after, acked),
            "lineage_miscount": lineage_miscount(lineage_rise, workers,
                                                 rounds_done),
            "final": final, "test_logloss": float(test_ll)}

        def rise(stat):
            return sum(a.get(stat, 0.0) - b.get(stat, 0.0)
                       for b, a in zip(servers, servers_after))

        kd = {"rounds_per_worker": epochs * per,
              "server_pushes": rise("total_pushes"),
              "server_merge_s": rise("merge_seconds"),
              "lock_wait_s": rise("lock_wait_seconds"),
              "ftrl_steps": rise("ftrl_steps"),
              **kd_side(trace_doc["traceEvents"],
                        trace_doc["otherData"].get("dropped_events", 0),
                        lineage_rise, rounds_done)}
        del trace_doc
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
                f"pull_lineage={json.dumps(lineage_rise, sort_keys=True)} "
                f"in_flight_at_return={in_flight} "
                f"host_rss_peak_mib={_rss_peak_mib()}")
        ctx.say("window servers: pushes={server_pushes:.0f} "
                "merge_s={server_merge_s:.4f} lock_wait_s={lock_wait_s:.4f} "
                "ftrl_steps={ftrl_steps:.0f}".format(**kd)
                + f" keys_stepped={stepped} exact_zero_share={share:.4f}")
        ctx.say("window spans, a worker's mean ms: " + " ".join(
            f"{name}={1e3 * s['seconds'] / s['count']:.3f}"
            for name, s in sorted(spans.items()) if s["count"]))
        ctx.say("window exchange: " + " ".join(
            f"{k}={1e3 * v['seconds'] / max(v['count'], 1):.3f}ms n={v['count']}"
            for k, v in kd["exchange_wait"].items())
            + f" computes by in_flight={json.dumps(kd['computes_in_flight'])}"
            f" wire_under_chain={kd['wire_under_chain_s']:.3f}s of "
            f"{kd['wire_s']:.3f}s events={kd['events']} "
            f"events_a_round={kd['events_a_round']:.2f} "
            f"events_dropped={kd['events_dropped']}")

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
            "kd": kd,
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
    rows = compare(rows_kept, got, family, prog, batch, conf["limits"],
                   in_window)
    ctx.say(f"reference keys and gradients of {workers} x "
            f"{len(got['pulls'][0])} rounds' windows, the replay of "
            f"{workers} x {got['prefix']} pushes, each reply's lineage and "
            f"the closed form of {dim} keys twice "
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

    ap = argparse.ArgumentParser(prog="chipbench.drivers.ps_keyed_delay_epochs")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    needs_the_keyed_delay()
    cell = manifest.Cell(manifest.load_benchmark(), args.workload)
    if not args.rehearse:
        harness.place_compile_cache()
    harness.take_devices(cell.chips, args.rehearse)
    conf = effective_config(cell, args.rehearse)
    traffic, family, prog = cell.traffic, conf["family"], conf["program"]
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
            warm_up(job, int(traffic["warm_epochs"]), say)
            got = record(job, int(traffic["recorded_epochs"]),
                         int(traffic["checked_rounds"]))
            kept = _rows_of(job)
            failed = False
        finally:
            job.close(failed)
        del job
        gc.collect()
        used = {**prog, **(over or {})}
        note(tag, seed, compare(kept, got, family, used, batch,
                                conf["limits"]))
        if over is None:
            note(low, seed, compare(
                kept, lowered(kept, got, family, low, batch), family, used,
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
