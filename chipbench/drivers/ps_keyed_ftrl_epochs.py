"""Traffic kind ``ps_keyed_ftrl_epochs``: the keyed sparse-LR job of
``ps_keyed_epochs`` under the rule its users run on the servers,
per-coordinate FTRL-Proximal with L1 (``ps_optimizer: ftrl``).  The
worker's side is the sibling's (resident localised shard, keyed pull,
the window's gradient on the chip, keyed push; asynchronous, serialized,
closed loop); the servers step z, n and w of every pushed key.

The threads, the counters of acknowledged operations and the servers'
statistics are ``ps_epochs``'s, the tap, the phase's counts and the
shard's bytes ``ps_keyed_epochs``'s, imported; neither file is edited.
What this kind brings:

* a program whose servers do not count their FTRL steps cannot run the
  cell: the driver leaves at once, before a row is made or a server
  spawned (:func:`needs_the_ftrl_counters`);
* the job starts from **zero** weights, z = n = 0 (Algorithm 1's start),
  and keeps a handle a server beside the group-wide one
  (``pull_opt_state`` addresses one server a handle);
* set-up runs one free **warm-up epoch** first (the checked rounds then
  pull weights that are not all zero) and reads the share of the keys
  stepped so far whose weight is exactly 0.0;
* the recorded phase (:func:`record`) is one ``fit`` a worker under an
  :class:`OrderedTap`: round 0 of every worker in rank order, nothing
  else in flight (the **serial prefix**: a known order, which the plain
  reference replays), the state read with every worker held, then the
  epoch's other rounds free-running: the first rounds' keys, pulled
  weights and pushed gradients kept, a float64 scatter-sum of every
  pushed ``g**2`` and a count of every pushed non-zero entry;
* after the window, with nothing in flight, a dense pull and the
  ``pull_opt_state`` of every server: what the timed path itself left;
* ``correct`` (:func:`compare`; PERF.md section 2) is rebuilt on the
  configuration's guarantees: conservation of the sum of pushes is SGD's
  and does not hold here;
* the run carries ``kf`` for the ``kf_*`` readers.

    python3 -m chipbench.drivers.ps_keyed_ftrl_epochs --workload <name> --seeds 1,2,3 [--controls 2]

reads what ``correct`` compares up to the recorded phase's end, seed
after seed in one process: for the program, for its control
(``control.program`` in the program's place, on the first ``--controls``
seeds) and for the reference computed in ``control.precision`` and put
where the program's gradients stand; and, of every program run, the
quartiles of |z| over the keys stepped in the warm-up epoch (what
``ftrl_l1`` is chosen from).  ``--rehearse`` runs the tiny sizes anywhere.
"""

from __future__ import annotations

import argparse
import dataclasses
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
from chipbench.drivers.ps_keyed_epochs import (
    PLACING,
    STEP_PROGRAM,
    WINDOW_MARGIN,
    WireTap,
    _counts,
    _run_frames,
    needs_the_keyed_device_path,
    phase_counts,
    shard_bytes,
)
from chipbench.drivers.train_stream import (
    _peak_bytes,
    _rel_gap,
    _rss_peak_mib,
    effective_config,
)

#: the kStats slots the servers have to keep for their steps to be read
FTRL_STATS = ("ftrl_steps", "ftrl_zeroed")
#: a worker held at the serial prefix gives up after this long
TURN_TIMEOUT_S = 300.0


def needs_the_ftrl_counters() -> None:
    """A program whose servers count no FTRL step (kStats has no
    ``ftrl_steps``) cannot say what a step costs: leave at once, before a
    row is made."""
    needs_the_keyed_device_path()
    from distlr_tpu.ps.client import STATS_FIELDS

    missing = [s for s in FTRL_STATS if s not in STATS_FIELDS]
    if missing:
        raise SystemExit(
            "chipbench ps_keyed_ftrl_epochs: this program's servers keep no "
            f"kStats {missing}: the coordinates their FTRL-Proximal step ran "
            "on are not counted, so the cell's kf_ftrl_ns_per_step and its "
            "count of steps do not exist; the cell runs from the commit that "
            "adds the two slots to kv_protocol.h and STATS_FIELDS")


def rule_of(prog: dict) -> dict:
    """Algorithm 1's four numbers as the reference names them."""
    return {"alpha": float(prog["ftrl_alpha"]), "beta": float(prog["ftrl_beta"]),
            "l1": float(prog["ftrl_l1"]), "l2": float(prog["ftrl_l2"])}


@dataclasses.dataclass
class FtrlJob(Job):
    """``ps_epochs.Job`` with a handle a server: ``pull_opt_state``
    addresses one server a handle."""
    ranks: list = dataclasses.field(default_factory=list)

    def close(self, failed: bool = False) -> None:
        for h in self.ranks:
            h.close()
        self.ranks = []
        super().close(failed)


def prepare(conf: dict, seed: int, say, program_over: dict | None = None
            ) -> FtrlJob:
    """Rows from the seed as arrays, the server group under the
    configuration's rule, zero weights, and workers that have localised,
    placed and started."""
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
    handles: list = []
    try:
        t = time.perf_counter()
        # no eval and no checkpoint inside any fit; the epochs a call runs
        # are its own argument; the rows are handed in, nothing is read
        cfg = Config(data_dir="handed-in-as-arrays", test_interval=0, **prog)
        group = ps_trainer.server_group(cfg).start()
        handles.append(KVWorker(group.hosts, dim, client_id=0xFC00))
        # Algorithm 1's start: the idempotent init seeds zeros before a
        # worker starts (rank 0's own init is then a no-op); z and n are
        # zero in a server that has stepped nothing
        w0 = np.zeros(dim, np.float32)
        handles[0].wait(handles[0].push_init(w0))
        for r in range(cfg.num_servers):
            lo, hi = group.key_range(r)
            handles.append(KVWorker(f"127.0.0.1:{group.ports[r]}", hi - lo,
                                    client_id=0xFC01 + r, sync_group=False))
        for r, shard in enumerate(shards):
            made.append(ps_trainer.PSWorker(
                cfg, r, group.hosts,
                train_iter=SparseDataIter(*shard, batch),
                test_iter=SparseDataIter(*test, -1) if r == 0 else None))
        for w in made:  # one after another: one shard in flight at a time
            w.load_data()
        job = FtrlJob(cfg, group, made, handles[0], shards, test, w0, n,
                      train[0].shape[1],
                      [ln for ln in capture.lines if "steps pinned" in ln
                       or "run in numpy on the host" in ln],
                      ranks=handles[1:])
        in_threads(job, lambda w: w.start())
        say(f"servers={cfg.num_servers} optimizer={cfg.ps_optimizer} "
            f"workers={workers} loaded and started "
            f"load_s={time.perf_counter() - t:.2f}")
        for ln in job.pinned:
            say(ln)
        return job
    except BaseException:
        for w in made:
            w.close(wait=False)
        for h in handles:
            h.close()
        if group is not None:
            group.stop()
        raise
    finally:
        logger.removeHandler(capture)


def state(job: FtrlJob) -> dict:
    """What the servers hold, with nothing of the workers' in flight: the
    weights by a dense pull, z and n by every server's ``pull_opt_state``
    (zeros, and ``no_opt_state`` set, where a server says it keeps none:
    it runs another rule)."""
    from distlr_tpu.ps.client import PSRejectedError

    w = np.array(job.probe.pull())
    z, n, none = [], [], 0
    for h in job.ranks:
        try:
            zr, nr = h.pull_opt_state()
        except PSRejectedError:
            zr = nr = np.zeros(h.dim, np.float32)
            none += 1
        z.append(zr)
        n.append(nr)
    return {"w": w, "z": np.concatenate(z), "n": np.concatenate(n),
            "no_opt_state": none}


def zero_share(st: dict) -> tuple[float, int]:
    """Of the keys stepped so far (n > 0), the share whose weight is
    exactly 0.0, and their count."""
    stepped = st["n"] > 0
    count = int(stepped.sum())
    return (float((st["w"][stepped] == 0).sum()) / max(count, 1), count)


class OrderedTap(WireTap):
    """``WireTap`` that also holds round 0 to rank order (the serial
    prefix): a worker's first pull waits for the acknowledgement of the
    worker before it, and after its own every worker stands at ``held``,
    whose action reads the servers' state while nothing is in flight.
    Beside the tap's own sums: a float64 scatter-sum of every pushed
    ``g**2`` and the count of pushed entries that are not zero."""

    def __init__(self, worker, keep: int, dim: int, turns: list,
                 held: threading.Barrier):
        super().__init__(worker, keep, dim, None)
        self.turns, self.held = turns, held
        self.squares = np.zeros(dim, np.float64)
        self.nonzero = self.acked = 0
        self.calls["wait"] = worker.kv.wait
        worker.kv.wait = self._wait

    def _pull(self, keys=None, **kw):
        if self.rounds == 0:
            if not self.turns[self.worker.rank].wait(TURN_TIMEOUT_S):
                raise TimeoutError(
                    f"rank {self.worker.rank}: the worker before it never "
                    "finished its round of the serial prefix")
        return super()._pull(keys=keys, **kw)

    def _push(self, vals, keys=None, **kw):
        g = np.asarray(vals, np.float64)
        at = (slice(None) if keys is None
              else np.asarray(keys).astype(np.int64))
        self.squares[at] += g * g
        self.nonzero += int(np.count_nonzero(g))
        return super()._push(vals, keys=keys, **kw)

    def _wait(self, ts):
        got = self.calls["wait"](ts)
        self.acked += 1
        if self.acked == 1:
            nxt = self.worker.rank + 1
            if nxt < len(self.turns):
                self.turns[nxt].set()
            try:
                self.held.wait(TURN_TIMEOUT_S)
            except threading.BrokenBarrierError:
                pass  # a peer failed: in_threads says why
        return got


def record(job: FtrlJob, epochs: int, keep: int) -> dict:
    """The recorded phase: ``epochs`` epochs a worker under an
    :class:`OrderedTap`, between two readings of the servers' state with
    nothing in flight, and a third after the serial prefix."""
    dim, n = len(job.w0), job.rows_per_worker
    before = state(job)
    counts, ops, servers = _counts(), _client_ops(), _servers(job)
    after_prefix: dict = {}

    def read_held():
        # every worker stands at the barrier: the run frames that rise
        # here are this reading's own (a dense pull and a pull of the
        # whole opt state a server), not the job's
        mine = _run_frames(_servers(job))
        after_prefix.update(state(job))
        after_prefix["run_frames"] = _run_frames(_servers(job)) - mine

    turns = [threading.Event() for _ in job.workers]
    turns[0].set()
    held = threading.Barrier(len(job.workers), action=read_held)
    taps = [OrderedTap(w, keep, dim, turns, held) for w in job.workers]
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
        "rounds": [t.rounds for t in taps],
        "keys_an_epoch": [t.keys_moved // max(epochs, 1) for t in taps],
        "before": before, "after_prefix": after_prefix, "after": after,
        "acked": acked,
        "unacknowledged": _unacknowledged(servers, servers_after, acked),
        "resident": counts_after["resident"], **counted,
    }


def _change_rel(got, ref, start) -> float:
    """How far ``got`` is from ``ref``, in units of the change the
    reference made from ``start``: 0 the same, 1 a state left as it was."""
    moved = max(float(np.linalg.norm(ref.astype(np.float64) - start)), 1e-30)
    return float(np.linalg.norm(got.astype(np.float64) - ref)) / moved


def _per_key_rel(rose, squares, n_after) -> float:
    """Guarantee 2 key by key: over the keys a phase pushed a non-zero
    entry for or whose n moved, the root mean square of ``|rise of n -
    sum of g**2|`` in units of the key's own n after the phase (float32
    rounds each of a key's additions to an ulp of n, so that is the scale
    of a sound server's error; a key pushed for that holds no n reads
    1)."""
    touched = (squares > 0) | (rose != 0)
    if not touched.any():
        return float("inf")
    scale = np.maximum(np.maximum(n_after.astype(np.float64), squares),
                       1e-300)
    rel = np.abs(rose - squares)[touched] / scale[touched]
    return float(np.sqrt(np.mean(rel * rel)))


def held_to_the_rule(st: dict, fam, rule: dict) -> dict:
    """Guarantee 3 over every key of a state read with nothing in flight:
    w against the closed form of (z, n) over the keys stepped (n > 0),
    the exact zeros where |z| <= l1 and nowhere else, and a key never
    stepped left at z = n = w = 0."""
    w, z, n = st["w"], st["z"], st["n"]
    stepped = n > 0
    want = fam.closed_form(z, n, **rule)
    norm = max(float(np.linalg.norm(want[stepped])), 1e-30)
    return {
        "closed_form_rel":
            float(np.linalg.norm((w - want)[stepped].astype(np.float64)))
            / norm if stepped.any() else float("inf"),
        "zeros_mismatch": int(((w == 0) != (np.abs(z) <= np.float32(
            rule["l1"])))[stepped].sum()),
        "untouched_moved": int(((w != 0) | (z != 0))[~stepped].sum()),
    }


def compare(job_rows: dict, got: dict, family: str, prog: dict, batch: int,
            limits: dict, window: dict | None = None) -> list[dict]:
    """Each number compared, beside its limit (PERF.md section 2), by the
    configuration's guarantees.  ``window``: the window's own
    ``unacknowledged``, ``window_rows_short``, ``dense_frames``,
    ``host_steps``, ``placed``, the state after it (``final``) and rank
    0's test logloss there, where one was run."""
    fam = reference.family(family)
    rule = rule_of(prog)
    rows = []

    def row(name, value, limit_key=None):
        limit = limits[limit_key or name]
        rows.append({"name": name, "value": float(value),
                     "limit": float(limit),
                     "ok": bool(np.isfinite(value) and value <= limit)})

    extra = window or {"window_rows_short": 0, "dense_frames": 0,
                       "host_steps": 0, "placed": 0}
    # (6)-(7) every recorded round against the reference's keys and
    # gradient of the window the rule gives that round, at the weights
    # pulled; ``grads``: gradients put in the pushed ones' place
    norm_gap = diff = 0.0
    mismatched = 0
    grads = got.get("grads", got["pushes"])
    for shard, pulls, pushes, mine in zip(job_rows["shards"], got["pulls"],
                                          got["pushes"], grads):
        cols, vals, y = shard
        mismatched += abs(len(pulls) - len(pushes))
        for k, ((pulled, w_u), (pushed_keys, _g), (_k, g)) in enumerate(
                zip(pulls, pushes, mine)):
            at = fam.window(k, len(y), batch)
            want = fam.keys(cols[at])
            same = [keys is not None and np.array_equal(keys, want)
                    for keys in (pulled, pushed_keys)]
            mismatched += 2 - sum(same)
            if not all(same) or len(w_u) != len(want) or len(g) != len(want):
                norm_gap = diff = float("inf")  # nothing to hold it against
                continue
            ref = fam.gradient(w_u, cols[at], vals[at], y[at])
            n_ref = max(float(np.linalg.norm(ref)), 1e-30)
            norm_gap = max(norm_gap, _rel_gap(np.linalg.norm(g), n_ref))
            diff = max(diff, float(np.linalg.norm(g - ref)) / n_ref)
    row("grad_norm_rel_gap", norm_gap)
    row("grad_diff_rel", diff)

    # (4)-(5) the serial prefix: round 0 of every worker, in rank order,
    # replayed by the reference from the state before; a worker's pull
    # held to the weights with every earlier push applied
    b, p = got["before"], got["after_prefix"]
    prefix = [pushes[0] for pushes in got["pushes"] if pushes]
    whole = (len(prefix) == len(got["pushes"]) and bool(p)
             and all(k is not None for k, _g in prefix))
    if whole:
        (w_r, z_r, n_r), stood = fam.replay(prefix, b["w"], b["z"], b["n"],
                                            **rule)
        stale = 0.0
        for pulls, on in zip(got["pulls"], stood):
            _keys, w_u = pulls[0]
            stale = max(stale, float("inf") if len(w_u) != len(on) else
                        float(np.linalg.norm(w_u.astype(np.float64) - on))
                        / max(float(np.linalg.norm(on)), 1e-30))
        # the worst of the three tables, each in units of its own change
        row("replay_rel", max(_change_rel(p[t], r, b[t]) for t, r in
                              (("z", z_r), ("n", n_r), ("w", w_r))))
        row("pulled_stale_rel", stale)
    else:
        row("replay_rel", float("inf"))
        row("pulled_stale_rel", float("inf"))

    # (1)-(2) over the recorded phase, nothing in flight at either end:
    # n rose by the squares of what was pushed, a step a non-zero entry
    a = got["after"]
    rose, squares = a["n"].astype(np.float64) - b["n"], got["squares"]
    row("n_conservation_rel", _per_key_rel(rose, squares, a["n"]))
    row("update_missing", 0.0 if np.linalg.norm(rose) > 0.5 * max(
        float(np.linalg.norm(squares)), 1e-30) else 1.0)
    row("steps_miscount", abs(got["steps"] - got["nonzero"]))
    row("unacknowledged_recorded", got["unacknowledged"],
        "unacknowledged_pushes")

    # (3) every key of a state with nothing in flight: after the recorded
    # phase and, where a window ran, after it (what the timed path left)
    states = [a] + ([window["final"]] if window is not None else [])
    held = [held_to_the_rule(s, fam, rule) for s in states]
    for name in ("closed_form_rel", "zeros_mismatch", "untouched_moved"):
        row(name, max(h[name] for h in held))
    row("no_opt_state", sum(s["no_opt_state"] for s in [b, a] + states[1:]))
    if window is not None:
        ref_ll = reference.logloss(family, window["final"]["w"],
                                   *job_rows["test"])
        row("test_logloss_rel_gap", _rel_gap(window["test_logloss"], ref_ll))
        row("unacknowledged_window", window["unacknowledged"],
            "unacknowledged_pushes")
    row("keys_mismatch", mismatched)
    row("window_rows_short",
        got["window_rows_short"] + extra["window_rows_short"])
    row("dense_frames", got["dense_frames"] + extra["dense_frames"])
    # every worker's shard stays where load_data put it
    slots = job_rows["shards"][0][0].shape[1]
    held_bytes = got["resident"]
    row("resident_short",
        sum(max(0, shard_bytes(len(s[2]), batch, slots)
                - held_bytes.get(str(r), 0))
            for r, s in enumerate(job_rows["shards"])) + extra["placed"])
    row("host_steps", got["host_steps"] + extra["host_steps"])
    return rows


def lowered(job_rows: dict, got: dict, family: str, precision: str,
            batch: int) -> dict:
    """The recorded phase with the reference, computed in ``precision``,
    in the program's place for the gradient rows: its gradient of each
    round's window at the weights the worker pulled where the pushed one
    stood.  What the servers, the keys and the counters did stays as
    recorded."""
    fam = reference.family(family)
    grads = []
    for (cols, vals, y), pulls, pushed in zip(job_rows["shards"],
                                              got["pulls"], got["pushes"]):
        mine = []
        for k, ((_pk, w_u), (keys, _g)) in enumerate(zip(pulls, pushed)):
            at = fam.window(k, len(y), batch)
            mine.append((keys, fam.gradient(w_u, cols[at], vals[at], y[at],
                                            precision=precision)))
        grads.append(mine)
    return {**got, "grads": grads}


def warm_up(job: FtrlJob, epochs: int, say) -> dict:
    """``epochs`` free epochs a worker from Algorithm 1's start; the state
    after them, and what L1 has made of the keys stepped so far."""
    wall = in_threads(job, lambda w: w.fit(epochs=epochs))
    st = state(job)
    share, stepped = zero_share(st)
    say(f"warm-up epochs={epochs} wall_s={wall:.3f} keys_stepped={stepped} "
        f"exact_zero_share={share:.4f}")
    return {"state": st, "zero_share": share, "stepped": stepped,
            "wall_s": wall}


def run(ctx) -> dict:
    """``ctx``: cell, seed, seconds, trace, rehearsal, devices, compiles,
    t_start, say.  Returns what ``chipbench.run`` prints."""
    needs_the_ftrl_counters()
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
        ctx.say(f"recorded rounds={got['rounds']} acked={got['acked']} "
                f"rounds_an_epoch={per} keys_a_round="
                f"{keys_an_epoch / max(workers * per, 1):.1f} "
                f"ftrl_steps={got['steps']} nonzero_entries={got['nonzero']} "
                f"resident_bytes={sorted(got['resident'].values())} "
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
            "final": final, "test_logloss": float(test_ll)}

        def rise(stat):
            return sum(a.get(stat, 0.0) - b.get(stat, 0.0)
                       for b, a in zip(servers, servers_after))

        kf = {"rounds_per_worker": epochs * per,
              "server_pushes": rise("total_pushes"),
              "server_merge_s": rise("merge_seconds"),
              "lock_wait_s": rise("lock_wait_seconds"),
              "ftrl_steps": rise("ftrl_steps"),
              "ftrl_zeroed": rise("ftrl_zeroed")}
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
        ctx.say("window servers: pushes={server_pushes:.0f} "
                "merge_s={server_merge_s:.4f} lock_wait_s={lock_wait_s:.4f} "
                "ftrl_steps={ftrl_steps:.0f} ftrl_zeroed={ftrl_zeroed:.0f}"
                .format(**kf)
                + f" keys_stepped={stepped} exact_zero_share={share:.4f}")
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
            # a step reads a window of the resident entries and its keys
            "step": {"rows": batch, "nnz": batch * slots, "dim": dim,
                     "keys": in_window["keys"] / max(rounds_done, 1)},
            "kf": kf,
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
            f"{workers} pushes and the closed form of {dim} keys twice "
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

    ap = argparse.ArgumentParser(prog="chipbench.drivers.ps_keyed_ftrl_epochs")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--l1", type=float, default=None,
                    help="ftrl_l1 in the configuration's place: a reading "
                         "for choosing it")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    needs_the_ftrl_counters()
    cell = manifest.Cell(manifest.load_benchmark(), args.workload)
    if not args.rehearse:
        harness.place_compile_cache()
    harness.take_devices(cell.chips, args.rehearse)
    conf = effective_config(cell, args.rehearse)
    if args.l1 is not None:
        conf = {**conf, "program": {**conf["program"], "ftrl_l1": args.l1}}
    traffic, family, prog = cell.traffic, conf["family"], conf["program"]
    batch = int(prog["batch_size"])
    say = harness.Context.say
    low = conf["control"]["precision"]
    readings: dict[str, dict[str, list]] = {"program": {}, "control": {},
                                            low: {}}
    limits: dict[str, float] = {}
    shares = []

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
            warm = warm_up(job, int(traffic["warm_epochs"]), say)
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
            st = warm["state"]
            z = np.abs(st["z"][st["n"] > 0])
            quartiles = [float(q) for q in np.quantile(
                z, (0.25, 0.5, 0.75))] if len(z) else []
            shares.append({"seed": seed, "ftrl_l1": prog["ftrl_l1"],
                           "keys_stepped": warm["stepped"],
                           "exact_zero_share": warm["zero_share"],
                           "abs_z_quartiles": quartiles,
                           "exact_zero_share_after_2_epochs":
                               zero_share(got["after"])[0]})
            say(f"program seed={seed} after the warm-up epoch: "
                + json.dumps(shares[-1]))
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
                                   "summary": summary, "l1": shares}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
