#!/usr/bin/env python3
"""Proof that the system still starts on the chip.

Drives the three execution planes once, through the entry points a user
reaches from ``python -m distlr_tpu.launch``, at D = 1,000,000 on the
TPU this process finds: the SPMD trainer (dense and sparse), the
parameter-server plane (native servers; Hogwild workers, then lock-step
ones held to the benchmark's plain reference), the scoring
server, and the Pallas kernel; on a host with two or more chips also the
lock-step job a worker to a chip (``ps-bsp-chips``; skipped, by name, on
one), and with four or more the ``data`` x ``model`` mesh.  One process
— it holds the chip — and the only children are the native KV servers.

    python chip_smoke.py                 # on the chip: the check
    python chip_smoke.py --rehearse-cpu  # anywhere: tiny sizes, says
                                         # REHEARSAL, never PASS

Each leg prints one ``SMOKE <leg> ok ...`` line; a failed leg prints
``CHIP_SMOKE FAIL leg=<leg>`` and ends the run non-zero before any later
leg starts.  A full run ends ``CHIP_SMOKE PASS`` and, as the last line of
stdout, one JSON object naming the device as JAX reports it.  Without a
TPU the script exits 2 before any leg.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import math
import os
import resource
import shutil
import sys
import tempfile
import time


@dataclasses.dataclass(frozen=True)
class Sizes:
    d: int                 # feature width of every training/serving leg
    dense_samples: int     # gen-data rows (80% train in 2 parts, 20% test)
    dense_batch: int       # global rows per sync-dense step
    dense_epochs: int
    sparse_samples: int
    sparse_batch: int
    sparse_epochs: int
    ps_batch: int
    ps_epochs: int
    bsp_rows: int          # a BSP worker's whole resident shard
    kernel_rows: int       # of the row-panel kernel's shard, D = d wide
    kernel_interpret: bool
    timing_steps: int


FULL = Sizes(
    d=1_000_000,
    dense_samples=1280, dense_batch=256, dense_epochs=10,
    sparse_samples=163_840, sparse_batch=65_536, sparse_epochs=8,
    # 1e6 * 64 = 6.4e7 >= ps_trainer._PS_AUTO_CPU_THRESHOLD (2**25), so
    # ps_compute_device must put the step on the accelerator
    ps_batch=64, ps_epochs=2,
    bsp_rows=128,          # x 1e6 is over the same threshold
    kernel_rows=384,       # a worker's shard in dense-ps-async-1chip
    kernel_interpret=False,
    timing_steps=20,
)
REHEARSAL = Sizes(
    d=8192,
    dense_samples=640, dense_batch=64, dense_epochs=6,
    sparse_samples=2560, sparse_batch=1024, sparse_epochs=4,
    ps_batch=16, ps_epochs=2,
    bsp_rows=16,
    kernel_rows=16,
    kernel_interpret=True,
    timing_steps=5,
)

# Hashed one-hot CTR rows give one weight a mean gradient of about
# (rows of the batch that hold its feature) / batch, so a step size that
# learns in a few dozen steps is large.  A small raw vocabulary makes the
# rows outnumber the features, so the loss falls because the model
# learns, test accuracy rises, and the served labels are of both classes.
DENSE_FIELDS, DENSE_VOCAB, DENSE_LR = 39, 8, 2.0
SPARSE_FIELDS, SPARSE_VOCAB, SPARSE_LR = 21, 1000, 100.0
# The 1-device / 4-device comparison runs 8 steps at a step size that
# leaves every sigmoid saturated (logits stay above ~10), so the bf16
# rounding of the residual cannot differ between layouts and float32
# reduction order is the only difference; the tolerances are
# __graft_entry__.dryrun_multichip's.
MESH_LR, MESH_EPOCHS, MESH_RTOL, MESH_ATOL = 0.2, 2, 2e-5, 1e-5


class _LogCapture(logging.Handler):
    def __init__(self):
        super().__init__(logging.INFO)
        self.lines: list[str] = []

    def emit(self, record):
        self.lines.append(record.getMessage())


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.startswith("."))
    except FileNotFoundError:
        return 0


def _rss_mib() -> tuple[int, int]:
    """(current, peak) resident set of this process in MiB."""
    with open("/proc/self/status") as f:
        now = next(int(ln.split()[1]) for ln in f if ln.startswith("VmRSS:"))
    # ru_maxrss is KiB on Linux
    return now // 1024, resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class Smoke:
    def __init__(self, sizes: Sizes, tmp: str, dev: dict):
        import jax

        from distlr_tpu.obs import jaxrt

        # compile seconds and cache hits, from the program's own registry
        # (distlr_jax_compile_seconds_total, distlr_jax_compile_cache_total)
        self.compile_totals = jaxrt.compile_totals
        self.s = sizes
        self.tmp = tmp
        self.dev = dev  # backend.device_summary()
        self.on_tpu = dev["platform"] == "tpu"
        self.devices = jax.devices()
        # shared between legs
        self.dense_dir = os.path.join(tmp, "dense")
        self.dense_trainer = None
        self.dense_model_path = None

    # -- plumbing -----------------------------------------------------------
    def run_leg(self, name: str, fn) -> None:
        c0, t0 = self.compile_totals()["seconds"], time.perf_counter()
        try:
            fields = fn()
        except BaseException:
            print(f"CHIP_SMOKE FAIL leg={name}", flush=True)
            raise
        head = {
            "platform": self.dev["platform"],
            "device_kind": json.dumps(self.dev["kind"]),
            "devices": self.dev["count"],
            "steps": fields.pop("steps", "na"),
            "loss_first": fields.pop("loss_first", "na"),
            "loss_last": fields.pop("loss_last", "na"),
            "compile_s": f"{self.compile_totals()['seconds'] - c0:.2f}",
            "wall_s": f"{time.perf_counter() - t0:.2f}",
            "host_rss_mib": "{}(peak {})".format(*_rss_mib()),
        }
        print(f"SMOKE {name} ok "
              + " ".join(f"{k}={v}" for k, v in {**head, **fields}.items()),
              flush=True)

    def gen_data(self, data_dir: str, samples: int, fields: int, vocab: int,
                 parts: int) -> None:
        from distlr_tpu import launch

        rc = launch.main([
            "gen-data", "--data-dir", data_dir, "--num-samples", str(samples),
            "--num-feature-dim", str(self.s.d), "--num-parts", str(parts),
            "--seed", "1", "--ctr-fields", str(fields),
            "--ctr-vocab", str(vocab)])
        _check(rc == 0, f"gen-data exited {rc}")

    def fit(self, cfg):
        """What ``launch sync`` runs: load, fit, save, evaluate."""
        import numpy as np

        from distlr_tpu.train import Trainer
        from distlr_tpu.train.export import load_model_text

        trainer = Trainer(cfg).load_data()
        trainer.fit()
        path = trainer.save_model()
        records = trainer.metrics.records
        losses = [r["loss"] for r in records]
        _check(len(records) == cfg.num_iteration, "an eval line per epoch")
        _check(all(np.isfinite(v) for v in losses), f"finite loss: {losses}")
        _check(losses[-1] < losses[0], f"falling loss: {losses}")
        _check(all(np.isfinite(r["test_logloss"]) for r in records),
               "finite test logloss")
        w = trainer.weights
        _check({d.platform for d in w.devices()} == {self.dev["platform"]},
               f"weights live on {w.devices()}")
        saved = load_model_text(path)
        _check(saved.shape == (self.s.d,), f"{path} holds {saved.shape}")
        _check(np.isfinite(saved).all(), "saved weights finite")
        return trainer, path, {
            "steps": trainer.timer.steps,
            "loss_first": f"{losses[0]:.4f}", "loss_last": f"{losses[-1]:.4f}",
            "acc": f"{records[-1]['accuracy']:.4f}",
            "model": os.path.relpath(path, cfg.data_dir),
        }

    def dense_cfg(self, data_dir: str, **over):
        from distlr_tpu import Config

        n_data = over.get("mesh_shape", {}).get("data", len(self.devices))
        base = dict(
            data_dir=data_dir, num_feature_dim=self.s.d, model="binary_lr",
            feature_dtype="bfloat16", batch_size=self.s.dense_batch // n_data,
            num_iteration=self.s.dense_epochs, test_interval=1,
            learning_rate=DENSE_LR, l2_c=0.0)
        return Config(**{**base, **over})

    # -- legs ---------------------------------------------------------------
    def sync_dense(self) -> dict:
        import jax
        import jax.numpy as jnp

        self.gen_data(self.dense_dir, self.s.dense_samples, DENSE_FIELDS,
                      DENSE_VOCAB, parts=2)
        cfg = self.dense_cfg(self.dense_dir)
        trainer, path, fields = self.fit(cfg)
        self.dense_trainer, self.dense_model_path = trainer, path

        # T2: one warmed step timed both ways.  The step donates its
        # weights, so they are threaded through.
        batch = trainer._shard_batch(
            next(iter(trainer._train_data.batches(cfg.batch_size))))
        w = trainer.weights
        w, _ = trainer.train_step(w, batch)
        float(jnp.sum(w))  # compiles the readback's reduction
        n = self.s.timing_steps
        ms = {}
        for how in ("block_until_ready", "readback"):
            t0 = time.perf_counter()
            for _ in range(n):
                w, _ = trainer.train_step(w, batch)
            if how == "readback":
                float(jnp.sum(w))
            else:
                jax.block_until_ready(w)
            ms[how] = (time.perf_counter() - t0) / n * 1e3
        trainer.weights = w
        fields["t2_block_until_ready_ms"] = f"{ms['block_until_ready']:.3f}"
        fields["t2_readback_ms"] = f"{ms['readback']:.3f}"
        return fields

    def sync_sparse(self) -> dict:
        from distlr_tpu import Config

        data_dir = os.path.join(self.tmp, "sparse")
        self.gen_data(data_dir, self.s.sparse_samples, SPARSE_FIELDS,
                      SPARSE_VOCAB, parts=1)
        cfg = Config(
            data_dir=data_dir, num_feature_dim=self.s.d, model="sparse_lr",
            batch_size=self.s.sparse_batch // len(self.devices),
            num_iteration=self.s.sparse_epochs, test_interval=1,
            learning_rate=SPARSE_LR, l2_c=0.0)
        _, _, fields = self.fit(cfg)
        return fields

    def ps_async(self) -> dict:
        import jax
        import numpy as np

        from distlr_tpu import Config
        from distlr_tpu.obs.registry import family_total, get_registry
        from distlr_tpu.obs.tracing import get_tracer
        from distlr_tpu.train import ps_trainer
        from distlr_tpu.utils.logging import log_eval_line

        data_dir = os.path.join(self.tmp, "ps")  # `launch ps` saves models too
        self.gen_data(data_dir, self.s.dense_samples, DENSE_FIELDS,
                      DENSE_VOCAB, parts=2)
        cfg = Config(
            data_dir=data_dir, num_feature_dim=self.s.d, model="binary_lr",
            sync_mode=False, num_workers=2, num_servers=2,
            batch_size=self.s.ps_batch, num_iteration=self.s.ps_epochs,
            test_interval=1, learning_rate=DENSE_LR, l2_c=0.0)

        ops = get_registry().get("distlr_ps_client_ops_total")

        def acked_pushes() -> int:
            return int(sum(ops.labels(op=op, status="ok").value
                           for op in ("push", "push_pull")))

        capture = _LogCapture()
        logger = logging.getLogger(ps_trainer.__name__)
        logger.addHandler(capture)
        accs: list[float] = []

        def on_eval(epoch, acc):
            accs.append(acc)
            log_eval_line(epoch, acc)

        tracer = get_tracer()
        def count(span: str) -> int:
            return tracer.breakdown().get(span, {"count": 0})["count"]

        spans_before = {s: count(s) for s in ("wire", "shard_put", "h2d")}
        windows_before = family_total("distlr_ps_window_rounds_total")
        before = acked_pushes()
        try:
            weights = ps_trainer.run_ps_local(cfg, eval_fn=on_eval, save=True)
        finally:
            logger.removeHandler(capture)
        pushes = acked_pushes() - before
        spans = tracer.breakdown()

        pinned = [ln for ln in capture.lines if "dense steps pinned" in ln]
        _check(len(pinned) == cfg.num_workers, f"one device line per worker: {pinned}")
        if self.on_tpu:
            # the product's own selection, and what the workers logged
            _check(ps_trainer.ps_compute_device(cfg, cfg.batch_size) is None
                   and jax.default_backend() == "tpu",
                   "auto picks the default backend, which is the TPU")
            for ln in pinned:
                _check("train -> tpu:" in ln, ln)
            _check(any("eval -> tpu:" in ln for ln in pinned), f"{pinned}")
        rows_per_worker = self.s.dense_samples * 4 // 5 // cfg.num_workers
        steps_per_worker = self.s.ps_epochs * math.ceil(
            rows_per_worker / cfg.batch_size)
        # every acknowledged dense push ticks the group's push clock by one
        _check(pushes >= cfg.num_workers * steps_per_worker,
               f"push clock advanced by {pushes}")
        # a pipelined exchange a step on the comm thread; on the chip
        # minibatch workers keep their shard on the device and read a
        # window of it a round: one placement a worker, no batch streamed
        # (the rehearsal's tiny steps are numpy's, which places nothing)
        rounds = cfg.num_workers * steps_per_worker
        _check(count("wire") - spans_before["wire"] == rounds,
               f"wire spans {spans['wire']}")
        resident = self.on_tpu
        _check(count("shard_put") - spans_before["shard_put"]
               == resident * cfg.num_workers,
               "a minibatch worker places its shard once")
        _check(count("h2d") == spans_before["h2d"],
               "a minibatch worker streamed a batch")
        _check(family_total("distlr_ps_window_rounds_total") - windows_before
               == resident * rounds,
               "every round read a window of the resident shard")
        w = np.asarray(weights[0])
        _check(w.shape == (self.s.d,) and np.isfinite(w).all(), "pulled weights finite")
        _check(np.count_nonzero(w) > 0, "pulled weights non-zero")
        _check(len(accs) == cfg.num_iteration, "an eval per epoch")
        return {
            "steps": cfg.num_workers * steps_per_worker,
            "push_clock": f"+{pushes}", "acc": f"{accs[-1]:.4f}",
            "wire_ms": f"{1e3 * spans['wire']['seconds'] / spans['wire']['count']:.2f}",
            "step_device": json.dumps(
                max(pinned, key=len).split("pinned: ")[1]),
        }

    def ps_bsp(self, devices=None) -> dict:
        """The lock-step mode through the benchmark's own driver pieces:
        two workers, two servers (``sync=1``), two whole-shard rounds;
        every worker computes a round on the same weights, and the
        weights after follow ``families/dense_ps_bsp.round``.  With
        ``devices``, worker *r* is handed ``devices[r]`` (the four-chip
        cell's ``prepare``) and has to compute there."""
        import numpy as np

        from chipbench import manifest
        from chipbench.drivers import ps_bsp_epochs, ps_bsp_epochs_chips, ps_epochs
        from chipbench.families import dense_ps_bsp

        cell = manifest.Cell(manifest.load_benchmark(), "dense-ps-bsp-1chip")
        conf = ps_bsp_epochs.effective_config(cell, not self.on_tpu)
        # a worker to a chip: 128 rows keep the step on a jax device at the
        # rehearsal's width too (under 2**20 elements "auto" takes numpy)
        rows = self.s.bsp_rows if devices is None else max(self.s.bsp_rows, 128)
        conf = {**conf,
                "generator": {**conf["generator"],
                              "rows_per_worker": rows,
                              "test_rows": 64},
                "program": {**conf["program"], "num_workers": 2,
                            "num_feature_dim": self.s.d}}
        rounds, lr = 2, conf["program"]["learning_rate"]

        def say(text):
            print(f"  {text}")

        job = (ps_epochs.prepare(conf, 20260928, say) if devices is None
               else ps_bsp_epochs_chips.prepare(conf, 20260928, say, devices))
        failed = True
        try:
            got = ps_bsp_epochs.record_rounds(job, rounds, rounds)
            pinned, shards = job.pinned, job.shards
            kept = {"shards": shards, "test": job.test}
            ps_epochs.in_threads(job, lambda w: w.finish(save=False))
            failed = False
        finally:
            job.close(failed)
        if self.on_tpu:
            for ln in pinned:
                _check("train -> tpu:" in ln, ln)
        on = {}
        if devices is not None:
            on = ps_bsp_epochs_chips.step_devices()
            _check(on == {r: d.id for r, d in enumerate(devices)},
                   f"worker r's step on device r: {on}")
            for d, ln in zip(devices, sorted(pinned)):
                _check(f"(id {d.id})" in ln, ln)
        compared = ps_bsp_epochs.compare(kept, got, conf["family"], lr,
                                         conf["limits"])
        bad = [r for r in compared if not r["ok"]]
        _check(not bad, f"the rounds left the reference: {bad}")
        want = got["w_before"]
        for _ in range(rounds):
            want = dense_ps_bsp.round(want, shards, lr)
        moved = np.linalg.norm(got["w_after"] - got["w_before"])
        off = np.linalg.norm(got["w_after"] - want) / moved
        _check(off <= conf["limits"]["update_diff_rel"],
               f"weights after {rounds} rounds are {off:.3g} of the move "
               "off the reference")
        by_name = {r["name"]: r["value"] for r in compared}
        return {
            "steps": sum(got["rounds"]),
            "weights_disagree": int(by_name["weights_disagree"]),
            "update_diff_rel": f"{by_name['update_diff_rel']:.2e}",
            "after_rounds_off": f"{off:.2e}",
            "round_miscount": int(by_name["round_miscount_recorded"]),
            **({"step_devices": json.dumps(on, separators=(",", ":"))}
               if on else {}),
        }

    def ps_bsp_chips(self) -> dict:
        """The lock-step job a worker to a chip: two workers on the first
        two chips, each shard, step and readback on its own."""
        return self.ps_bsp(self.devices[:2])

    def serve(self) -> dict:
        import numpy as np

        from distlr_tpu import Config
        from distlr_tpu.serve import ScoringEngine, ScoringServer
        from distlr_tpu.serve.server import score_lines_over_tcp
        from distlr_tpu.train.export import load_model_text, load_weights

        trainer, path = self.dense_trainer, self.dense_model_path
        # the reference: what `launch eval --model-file` computes — the
        # trainer's own eval step on the SAVED weights (the text format
        # keeps 6 digits, so the in-memory weights are not the reference)
        trainer.weights = trainer._shard_weights(
            load_model_text(path, shape=trainer.model.param_shape))
        ref_acc = trainer.evaluate()
        test = trainer._test_data
        X, y, _ = trainer._shard_batch(test.full_batch())
        _check(test.num_samples == y.shape[0], "no padded test rows")
        # the W data shards hold the file's rows round-robin: position
        # (shard i, row j) of the full batch is line j*W + i of the file
        file_row = np.arange(len(y)).reshape(-1, test.num_shards).T.reshape(-1)

        def in_file_order(a):
            out = np.empty_like(np.asarray(a))
            out[file_row] = np.asarray(a)
            return out

        ref_labels = in_file_order(trainer.model.predict(trainer.weights, X))
        ref_scores = in_file_order(trainer.model.proba(trainer.weights, X))
        y = in_file_order(y)

        with open(os.path.join(self.dense_dir, "test", "part-001")) as f:
            lines = [ln.strip() for ln in f if ln.strip()]
        _check(64 < len(lines) <= 256 and len(lines) == len(y),
               "the test split fills the 256 bucket")

        cfg = Config(num_feature_dim=self.s.d, model="binary_lr")
        engine = ScoringEngine(cfg, max_batch_size=256)  # buckets 64, 256
        engine.set_weights(load_weights(path, shape=engine.model.param_shape))
        with ScoringServer(engine, port=0) as server:
            replies = score_lines_over_tcp(
                server.host, server.port,
                lines[:3] + [json.dumps({"rows": lines}), "STATS"],
                timeout_s=900)
        single = [r.split() for r in replies[:3]]
        _check(all(len(p) == 2 and p[0] in ("0", "1")
                   and 0.0 <= float(p[1]) <= 1.0 for p in single),
               f"'label score' per line: {replies[:3]}")
        batch = json.loads(replies[3])
        labels = np.asarray(batch["labels"])
        scores = np.asarray(batch["scores"], np.float32)
        _check(labels.shape == y.shape and np.isfinite(scores).all(),
               "one finite score per row")
        stats = json.loads(replies[4])
        _check(stats["engine"]["weights_version"] >= 1, f"STATS: {stats['engine']}")
        hits = {int(k) for k in stats["engine"]["bucket_hits"]}
        _check(hits == {64, 256}, f"buckets hit: {hits}")
        # Served == offline to bf16 tolerance.  The server holds float32
        # rows and the trainer bfloat16 ones; on the TPU, XLA keeps the
        # weights' excess precision in one program and rounds them to
        # bf16 in the other, which moves a logit by up to ~2**-8 of its
        # terms.  Labels must agree wherever the score is off the
        # threshold by more than that.
        tol = 1e-2
        score_diff = float(np.abs(scores - ref_scores).max())
        _check(score_diff < tol, f"served scores differ by {score_diff}")
        decided = np.abs(ref_scores - 0.5) > tol
        agree = int((labels == ref_labels)[decided].sum())
        _check(agree == int(decided.sum()) and decided.mean() > 0.9,
               f"served labels agree on {agree}/{int(decided.sum())} rows")
        for p, lab, sc, dec in zip(single, ref_labels, ref_scores, decided):
            _check(abs(float(p[1]) - sc) < tol and (int(p[0]) == lab or not dec),
                   f"a row scored alone: {p} vs offline {lab} {sc}")
        served_acc = float((labels == y).mean())
        _check(abs(served_acc - ref_acc) <= (~decided).mean() + 1e-6,
               f"served accuracy {served_acc} vs offline {ref_acc}")
        _check(0 < labels.sum() < len(labels), "both classes predicted")
        return {
            "steps": 2, "rows": len(lines) + 3, "buckets": "64,256",
            "weights_version": stats["engine"]["weights_version"],
            "labels_agree": f"{agree}/{int(decided.sum())}",
            "max_score_diff": f"{score_diff:.1e}",
            "acc_served": f"{served_acc:.4f}", "acc_offline": f"{ref_acc:.4f}",
        }

    def kernel(self) -> dict:
        """The row-panel kernel (``ops/pallas_lr.py``) at a PS worker's
        shard: one read of X against ``BinaryLR.grad``'s two."""
        import jax
        import jax.numpy as jnp

        from distlr_tpu import Config
        from distlr_tpu.models import BinaryLR
        from distlr_tpu.ops import pad_columns, panel_plan

        rows, d = self.s.kernel_rows, self.s.d
        plan = panel_plan(rows, d)
        _check(plan is not None, f"a plan for {rows}x{d}")
        cfg = Config(num_feature_dim=d, l2_c=0.5)
        # float32 matmuls on both sides: on the CPU XLA rounds the default
        # bfloat16 operands, on the chip it keeps float32 either way
        model = BinaryLR(d, compute_dtype="float32")
        kx, km, ky, kw = jax.random.split(jax.random.PRNGKey(0), 4)
        # about 40 non-zeros a row, made on the device
        X = jax.jit(lambda: jax.random.normal(kx, (rows, d), jnp.float32)
                    * (jax.random.uniform(km, (rows, d)) < 40 / d))()
        y = (jax.random.uniform(ky, (rows,)) < 0.3).astype(jnp.int32)
        mask = jnp.ones(rows, jnp.float32).at[-3:].set(0)
        w = 0.05 * jax.random.normal(kw, (d,), jnp.float32)
        Xp = jax.jit(lambda X: pad_columns(X, plan))(X)
        two = jax.jit(lambda w, X: model.grad(w, (X, y, mask), cfg))
        one = jax.jit(lambda w, Xp: model.grad_panels(
            w, (Xp, y, mask), cfg, plan, interpret=self.s.kernel_interpret))

        def timed(fn, *args):
            out = jax.block_until_ready(fn(*args))   # compiles
            t0 = time.perf_counter()
            for _ in range(self.s.timing_steps):
                out = fn(*args)
            jax.block_until_ready(out)
            return out, 1e3 * (time.perf_counter() - t0) / self.s.timing_steps

        ref, two_ms = timed(two, w, X)
        g, one_ms = timed(one, w, Xp)
        _check(g.shape == (d,) and bool(jnp.isfinite(g).all()), "finite")
        err = float(jnp.linalg.norm(g - ref) / jnp.linalg.norm(ref))
        _check(err <= 5e-6, f"{rows}x{d}: rel err {err:.3g}")
        return {
            "steps": 2 * (1 + self.s.timing_steps),
            "shape": f"{rows}x{d}", "padded_to": plan.dim_padded,
            "vmem_limit_bytes": plan.vmem_limit,
            "f": f"{plan.held_share:.3f}", "chunks": plan.chunks,
            "ahead": plan.ahead,
            "interpret": self.s.kernel_interpret, "rel_err": f"{err:.2e}",
            "one_pass_ms": f"{one_ms:.3f}", "two_pass_ms": f"{two_ms:.3f}",
        }

    def mesh(self) -> dict:
        import numpy as np

        import __graft_entry__
        from distlr_tpu.train import Trainer

        __graft_entry__.dryrun_multichip(4)
        four = set(self.devices[:4])
        finals = {}
        for name, shape in (("1", {"data": 1}), ("4", {"data": 4}),
                            ("2x2", {"data": 2, "model": 2})):
            cfg = self.dense_cfg(self.dense_dir, mesh_shape=shape,
                                 learning_rate=MESH_LR,
                                 num_iteration=MESH_EPOCHS, test_interval=0)
            trainer = Trainer(cfg).load_data()
            if name != "1":
                X = trainer._shard_batch(
                    next(iter(trainer._train_data.batches(cfg.batch_size))))[0]
                _check({s.device for s in X.addressable_shards} == four,
                       f"mesh {name}: the batch is on every device")
            w = trainer.fit()
            if name == "2x2":
                _check({s.device for s in w.addressable_shards} == four
                       and w.addressable_shards[0].data.shape == (self.s.d // 2,),
                       "mesh 2x2: the weights are sharded over every device")
            finals[name] = np.asarray(w)
        for name in ("4", "2x2"):
            np.testing.assert_allclose(finals[name], finals["1"],
                                       rtol=MESH_RTOL, atol=MESH_ATOL)
        steps = MESH_EPOCHS * (self.s.dense_samples * 4 // 5 // self.s.dense_batch)
        return {"steps": 3 * steps, "meshes": "1,4,2x2",
                "max_abs_diff": f"{max(np.abs(finals[n] - finals['1']).max() for n in ('4', '2x2')):.2e}"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on whatever backend JAX lands on; "
                    "prints REHEARSAL, never PASS — not the chip check")
    args = ap.parse_args(argv)

    import jax

    from distlr_tpu.data import libsvm
    from distlr_tpu.data._native import _SO as parser_so
    from distlr_tpu.ps.build import build_native, server_binary
    from distlr_tpu.utils import backend
    from distlr_tpu.utils.native_build import read_stamp

    cache_dir = backend.configure_compile_cache()
    # the smoke wants every program it compiles in the cache, so that a
    # second run shows what a warm cache is worth; JAX's default skips
    # programs that compile in under a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = backend.device_summary()
    if dev["platform"] != "tpu" and not args.rehearse_cpu:
        print(f"chip_smoke: no TPU — JAX reports platform={dev['platform']} "
              f"device_kind={dev['kind']} devices={dev['count']}; nothing was "
              "run", file=sys.stderr)
        return 2
    entries_before = _cache_entries(cache_dir)
    print(f"SMOKE setup platform={dev['platform']} "
          f"device_kind={json.dumps(dev['kind'])} devices={dev['count']} "
          f"cache_dir={cache_dir} cache_entries={entries_before}", flush=True)

    build_native()
    _check(libsvm.native_available(), "the native libsvm parser builds")
    print(f"SMOKE native parser=native parser_stamp={read_stamp(parser_so)[:12]} "
          f"kv_server_stamp={read_stamp(server_binary())[:12]}", flush=True)

    t0 = time.perf_counter()
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        smoke = Smoke(REHEARSAL if args.rehearse_cpu else FULL, tmp, dev)
        legs = [("sync-dense", smoke.sync_dense),
                ("sync-sparse", smoke.sync_sparse),
                ("ps-async", smoke.ps_async),
                ("ps-bsp", smoke.ps_bsp),
                ("serve", smoke.serve),
                ("kernel", smoke.kernel)]
        if dev["count"] >= 2:
            legs.append(("ps-bsp-chips", smoke.ps_bsp_chips))
        else:
            print("SMOKE ps-bsp-chips skipped devices=1 (a worker to a chip "
                  "needs two)", flush=True)
        if dev["count"] >= 4:
            legs.append(("mesh", smoke.mesh))
        for name, fn in legs:
            smoke.run_leg(name, fn)
        _check(libsvm._NATIVE is not None,
               "the pure-Python parser fallback was taken")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    compiled = smoke.compile_totals()
    print(f"SMOKE total wall_s={time.perf_counter() - t0:.1f} "
          f"compile_s={compiled['seconds']:.1f} "
          f"cache_hits={compiled['hits']} cache_misses={compiled['misses']} "
          f"cache_entries_before={entries_before} "
          f"cache_entries_after={_cache_entries(cache_dir)} "
          f"host_rss_peak_mib={_rss_mib()[1]}", flush=True)
    if args.rehearse_cpu:
        print("CHIP_SMOKE REHEARSAL complete (tiny sizes; not a chip result)",
              flush=True)
        return 0
    print("CHIP_SMOKE PASS", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
