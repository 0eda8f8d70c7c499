"""Traffic kind ``train_stream``: the sync trainer fed from host shards.

Set-up makes the rows from the seed and hands them to the program the
way the configuration's generator says under ``train_via``: as
reference-layout text shards through ``Trainer.load_data()`` and its
parser, which densifies (``libsvm``), or as the padded-COO arrays that
parser returns (``arrays``, for splits whose text would take minutes to
write and parse in every run; ``tests/chipbench`` holds the parser to
these arrays).  It gives the trainer weights made from the seed and
drives whole warm epochs of ``Trainer.fit``.  The window is ONE further
``fit(epochs=E)`` on that same trainer; the rate divides the rows of
those E epochs, counted here, by the call's own wall, waits for data
included.  After the window the reference follows the first steps from
the generator's own rows and ``correct`` compares (see PERF.md).
"""

from __future__ import annotations

import dataclasses
import math
import os
import resource
import shutil
import tempfile
import time

import numpy as np

from chipbench import datagen, reference, trace_reduce

WEIGHT_SCALE = 0.01
HEAVY_ONE_IN, HEAVY_FACTOR = 1024, 64.0


def initial_weights(seed: int, dim: int) -> np.ndarray:
    """Weights from the seed, for the program and the reference alike: a
    partly trained model's shape, small everywhere and large on a few
    buckets (one in 1,024, 64 times the rest), as a CTR model's weights
    are."""
    rng = np.random.default_rng([int(seed), 0x1717])
    w = rng.standard_normal(dim, np.float32) * np.float32(WEIGHT_SCALE)
    heavy = rng.random(dim) < 1.0 / HEAVY_ONE_IN
    w[heavy] *= np.float32(HEAVY_FACTOR)
    return w


class StepRecorder:
    """Stands in the trainer's step during the warm epochs: same compiled
    step underneath, plus a copy of what the first steps produced."""

    def __init__(self, step, keep: int):
        self.step, self.keep = step, keep
        self.losses, self.weights, self.entered = [], [], []

    def __call__(self, w, batch):
        import jax.numpy as jnp

        self.entered.append(time.perf_counter())
        w_new, metrics = self.step(w, batch)
        if len(self.losses) < self.keep:
            self.losses.append(metrics["loss"])
            self.weights.append(jnp.copy(w_new))  # w_new is donated next step
        return w_new, metrics


def _rel_gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def compare(w0, prog_losses, prog_w, ref_losses, ref_w, prog_test_ll,
            ref_test_ll, lr: float, limits: dict):
    """Each number compared, beside its limit.  One leaf (the weight
    vector), so the worst leaf is that leaf."""
    rows = []

    def row(name, value, limit_key):
        rows.append({"name": name, "value": float(value),
                     "limit": float(limits[limit_key]),
                     "ok": bool(np.isfinite(value)
                                and value <= limits[limit_key])})

    for k, (lp, lr_) in enumerate(zip(prog_losses, ref_losses), 1):
        row(f"loss_step{k}_rel_gap", _rel_gap(lp, lr_), "loss_rel_gap")
    g_p = np.linalg.norm((w0 - prog_w[0]) / lr)
    g_r = np.linalg.norm((w0 - ref_w[0]) / lr)
    row("grad1_norm_rel_gap", _rel_gap(g_p, g_r), "grad_norm_rel_gap")
    d_p, d_r = prog_w[-1] - w0, ref_w[-1] - w0
    n_r = np.linalg.norm(d_r)
    row("update_norm_rel_gap", _rel_gap(np.linalg.norm(d_p), n_r),
        "update_norm_rel_gap")
    row("update_diff_rel", np.linalg.norm(d_p - d_r) / max(n_r, 1e-30),
        "update_diff_rel")
    row("test_logloss_rel_gap", _rel_gap(prog_test_ll, ref_test_ll),
        "test_logloss_rel_gap")
    # the fault each of these is there to catch: a step that returns its
    # state unchanged moves nothing
    row("update_missing", 0.0 if np.linalg.norm(d_p) > 0.5 * n_r else 1.0,
        "update_missing")
    return rows


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) and isinstance(
            base.get(k), dict) else v
    return out


def _rows_of_step(leaves, k: int, global_batch: int):
    """Rows ``[k * B, (k + 1) * B)`` of the split out of per-shard leaves
    ``(shards, rows, ...)`` in which shard ``i`` holds rows ``i::shards``."""
    out = []
    for leaf in leaves:
        b = global_batch // leaf.shape[0]
        part = leaf[:, k * b:(k + 1) * b]
        out.append(np.ascontiguousarray(
            np.moveaxis(part, 0, 1).reshape((global_batch,) + leaf.shape[2:])))
    return tuple(out)


def _rss_peak_mib() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


@dataclasses.dataclass
class Prepared:
    """One trainer with its rows, ready for its first steps."""
    trainer: object
    ref_source: tuple       # per-shard leaves of the generator's train rows
    test: tuple
    w0: np.ndarray
    global_batch: int
    train_rows: int
    steps_per_epoch: int
    nnz_width: int


def sizes(conf: dict, chips: int) -> tuple[int, int, int]:
    gen = conf["generator"]
    batch, n_train = int(gen["global_batch"]), int(gen["train_rows"])
    if n_train % batch or batch % chips:
        raise ValueError("train_rows must be whole steps of global_batch, "
                         "and global_batch a multiple of the chips")
    return batch, n_train, n_train // batch


def _held_rows(data, mine, chips: int) -> tuple:
    """The program's own per-shard arrays of the train split, shown to be
    the generator's rows (shard ``i`` holds rows ``i::chips``).  The
    reference reads these, so that no second whole-split copy sits
    beside the program's."""
    theirs = (*data._feats, data.y)
    for a, b in zip(mine, theirs):
        for i in range(chips):
            if not np.array_equal(a[i::chips], b[i]):
                raise AssertionError(
                    "the trainer does not hold the generator's rows")
    return theirs


def prepare(conf: dict, chips: int, seed: int, say,
            program_over: dict | None = None) -> Prepared:
    """Rows from the seed, a ``Trainer`` that has loaded them, and
    weights from the seed in it.  ``program_over`` switches on a path of
    the program's own (a control)."""
    from distlr_tpu import Config
    from distlr_tpu.train.trainer import GlobalShardedData, Trainer

    gen, prog = conf["generator"], {**conf["program"], **(program_over or {})}
    dim = int(prog["num_feature_dim"])
    global_batch, n_train, steps_per_epoch = sizes(conf, chips)
    rows_kw = dict(fields=gen["fields"], num_buckets=dim,
                   label_scale=gen["label_scale"], label_bias=gen["label_bias"])
    t = time.perf_counter()
    train = datagen.make_rows(seed, "train", n_train, **rows_kw)
    test = datagen.make_rows(seed, "test", int(gen["test_rows"]), **rows_kw)
    say(f"rows train={n_train} test={len(test[2])} "
        f"made_s={time.perf_counter() - t:.2f}")
    t = time.perf_counter()
    kw = dict(mesh_shape={"data": chips}, batch_size=global_batch // chips,
              test_interval=0, **prog)
    nnz_width = train[0].shape[1]
    if gen.get("train_via", "arrays") == "libsvm":
        if chips != 1:
            raise ValueError("text shards are written for one chip")
        tmp = tempfile.mkdtemp(prefix="chipbench-shards-")
        try:
            for split, rows in (("train", train), ("test", test)):
                datagen.write_libsvm(os.path.join(tmp, split, "part-001"), *rows)
            trainer = Trainer(Config(data_dir=tmp, **kw))
            trainer.load_data()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        ref_source = tuple(a[None] for a in train)  # COO rows: small
    else:
        trainer = Trainer(Config(**kw))
        trainer.load_data(train=GlobalShardedData._from_parts([train], chips),
                          test=GlobalShardedData._from_parts([test], chips))
        ref_source = _held_rows(trainer._train_data, train, chips)
    del train
    say(f"splits loaded load_s={time.perf_counter() - t:.2f}")
    w0 = initial_weights(seed, dim)
    trainer.weights = trainer._shard_weights(w0)
    return Prepared(trainer, ref_source, test, w0, global_batch, n_train,
                    steps_per_epoch, nnz_width)


def first_steps(p: Prepared, keep: int, warm_steps: int = 0) -> dict:
    """Whole warm epochs of ``fit`` until ``keep`` steps (and
    ``warm_steps``, where the mix asks for more) have run, through the
    trainer's own loop and feed; what the first ``keep`` produced, and
    the pace."""
    trainer = p.trainer
    step = trainer.train_step
    recorder = trainer.train_step = StepRecorder(step, keep)
    t = time.perf_counter()
    trainer.fit(epochs=max(1, math.ceil(max(keep, warm_steps)
                                        / p.steps_per_epoch)))
    wall = time.perf_counter() - t
    trainer.train_step = step
    test_batch = trainer._shard_batch(trainer._test_data.full_batch())
    test_ll = float(
        trainer.eval_step(recorder.weights[-1], test_batch)["logloss"])
    # a step's pace without the first one, which compiles on a cold run
    gaps = np.diff(recorder.entered)[1:]
    pace = float(np.mean(gaps)) if len(gaps) else wall / len(recorder.entered)
    # an epoch's wall, its end included: the shortest of the warm epochs
    # after the first, so that a window sized from it is not cut short by
    # warm epochs that ran slow; from the steps' pace where there are
    # under three
    starts = np.diff(recorder.entered[::p.steps_per_epoch])[1:]
    epoch_wall = float(starts.min()) if len(starts) else pace * p.steps_per_epoch
    return {
        "losses": [float(v) for v in recorder.losses],
        "weights": [np.asarray(w) for w in recorder.weights],
        "test_logloss": test_ll,
        "steps": len(recorder.entered),
        "wall_s": wall,
        "pace_s": pace,
        "epoch_wall_s": epoch_wall,
    }


def reference_steps(p: Prepared, family: str, keep: int, lr: float, l2: float,
                    precision: str = "float32") -> dict:
    """The reference (or, in a lower ``precision``, the control) through
    the same first steps, from the generator's rows."""
    batches = []
    for k in range(keep):  # a short split comes round again, as an epoch does
        batches.append(_rows_of_step(p.ref_source, k % p.steps_per_epoch,
                                     p.global_batch))
    losses, weights = reference.follow_steps(
        family, p.w0, batches, lr=lr, l2=l2, precision=precision)
    return {"losses": losses, "weights": weights,
            "test_logloss": reference.logloss(family, weights[-1], *p.test,
                                              precision=precision)}


def compare_runs(p: Prepared, got: dict, ref: dict, lr: float, limits: dict):
    return compare(p.w0, got["losses"], got["weights"], ref["losses"],
                   ref["weights"], got["test_logloss"], ref["test_logloss"],
                   lr, limits)


def effective_config(cell, rehearsal: bool) -> dict:
    conf = cell.config
    return _merge(conf, conf["rehearsal"]) if rehearsal else conf


def run(ctx) -> dict:
    """``ctx``: cell, seed, seconds, trace, rehearsal, devices, compiles,
    t_start, say.  Returns what ``chipbench.run`` prints."""
    import jax

    from distlr_tpu.obs.tracing import get_tracer

    conf = effective_config(ctx.cell, ctx.rehearsal)
    prog, traffic = conf["program"], ctx.cell.traffic
    family, chips = conf["family"], ctx.cell.chips
    keep = int(traffic["checked_steps"])

    # -- set-up: rows, trainer, warm epochs -----------------------------
    p = prepare(conf, chips, ctx.seed, ctx.say)
    trainer, steps_per_epoch = p.trainer, p.steps_per_epoch
    got = first_steps(p, keep, int(traffic.get("warm_steps", 0)))
    epochs = max(1, math.ceil(ctx.seconds / got["epoch_wall_s"]))
    ctx.say(f"warm steps={got['steps']} wall_s={got['wall_s']:.2f} "
            f"pace_s={got['pace_s']:.4f} epoch_wall_s={got['epoch_wall_s']:.4f} "
            f"window_epochs={epochs} "
            "compiles seconds={seconds:.2f} count={count} cache_hits={hits} "
            "cache_misses={misses}".format(**ctx.compiles.snapshot()))

    # -- the window: one fit call ---------------------------------------
    tracer = get_tracer()
    compiled_before = ctx.compiles.snapshot()
    samples_before, steps_before = trainer.timer.samples, trainer.timer.steps
    tracer.reset()
    setup_s = time.perf_counter() - ctx.t_start
    t = time.perf_counter()
    trainer.fit(epochs=epochs)
    window_wall = time.perf_counter() - t
    spans = tracer.breakdown()
    # the yardstick counts the work itself: E whole epochs of the split
    rows_done, steps_done = epochs * p.train_rows, epochs * steps_per_epoch
    counted = (trainer.timer.samples - samples_before,
               trainer.timer.steps - steps_before)
    counts_agree = counted == (rows_done, steps_done)
    compiled_in_window = ctx.compiles.count - compiled_before["count"]
    finite = bool(np.isfinite(np.asarray(trainer.weights)).all())
    ctx.say(f"window wall_s={window_wall:.3f} epochs={epochs} "
            f"steps={steps_done} rows={rows_done} "
            f"program_counted_rows={counted[0]} steps={counted[1]} "
            f"compiles_in_window={compiled_in_window} "
            f"host_rss_peak_mib={_rss_peak_mib()}")

    run = {
        "cell": ctx.cell.name, "family": family, "chips": chips,
        "device_kind": ctx.devices[0].device_kind,
        "platform": ctx.devices[0].platform,
        "setup_compile": compiled_before,
        "compiles_in_window": compiled_in_window,
        "window": {"wall_s": window_wall, "steps": steps_done,
                   "rows": rows_done, "spans": spans},
        "step": {"rows": p.global_batch // chips,
                 "dim": int(prog["num_feature_dim"]),
                 "nnz": (p.global_batch // chips) * p.nnz_width},
        "trace": None,
    }

    # -- a traced run: a short fit of its own under the profiler --------
    if ctx.trace:
        epoch_wall = window_wall / epochs
        t_epochs = max(1, min(
            math.ceil(traffic["trace_seconds"] / epoch_wall),
            traffic["trace_max_steps"] // steps_per_epoch))
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            tracer.reset()
            host_epoch = time.perf_counter()
            # device operations and the program's own spans are what the
            # reduction reads: no Python call events, which are most of a
            # trace's bulk and of the profiler's drag on the host
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            with jax.profiler.trace(trace_dir, profiler_options=options):
                with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
                    anchor_host = time.perf_counter()
                    trainer.fit(epochs=t_epochs)
            traced_s = time.perf_counter() - host_epoch
            host_spans = [(e["name"], e["tid"],
                           host_epoch + e["ts"] * 1e-6, e["dur"] * 1e-6)
                          for e in tracer.chrome_trace()["traceEvents"]]
            xtrace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
            ctx.say(f"traced epochs={t_epochs} fit_and_export_s={traced_s:.2f} "
                    f"read_s={time.perf_counter() - host_epoch - traced_s:.2f}")
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        window = trace_reduce.window_of(xtrace)
        run["trace"] = {
            "xtrace": xtrace, "window": window,
            "steps": t_epochs * steps_per_epoch,
            "host_spans": host_spans,
            "clock_offset": window[0] - anchor_host,
            "step_program": "step",
        }

    memory_peak = _peak_bytes(ctx.devices[:chips])

    # -- correct: the reference follows the first steps, once the
    # program's state is freed --------------------------------------------
    lr, l2 = float(prog["learning_rate"]), float(prog["l2_c"])
    p.trainer = trainer = None
    t = time.perf_counter()
    ref = reference_steps(p, family, keep, lr, l2)
    rows = compare_runs(p, got, ref, lr, conf["limits"])
    ctx.say(f"reference followed {keep} steps check_s={time.perf_counter() - t:.2f}")
    for r in rows:
        ctx.say("compared {name} value={value:.6g} limit={limit:.6g} "
                "ok={ok}".format(**r))
    ctx.say("losses program=" + ",".join(f"{v:.7f}" for v in got["losses"])
            + " reference=" + ",".join(f"{v:.7f}" for v in ref["losses"])
            + f" test_logloss program={got['test_logloss']:.7f} "
            f"reference={ref['test_logloss']:.7f}")
    correct = (all(r["ok"] for r in rows) and finite and counts_agree
               and compiled_in_window == 0)

    return {
        "correct": correct,
        "attempted": steps_done,
        "failed": 0 if finite else steps_done,
        "end_to_end": {
            "train_samples_per_s": rows_done / window_wall,
            "setup_s": setup_s,
        },
        "memory_peak_bytes": memory_peak,
        "compared": rows,
        "run": run,
    }
