"""Per-config benchmarks for the five BASELINE.json workloads.

BASELINE.json names five parity configs (none with published numbers —
SURVEY.md §6); this script measures this framework on each and writes
``BENCH_CONFIGS.json``:

1. dense binary LR, synthetic gen-data layout, 1 worker / 1 server
2. 4-worker async-SGD dense LR (native C++ PS servers, Hogwild)
3. Criteo-style CTR hashed-to-dense (north-star D, MXU dense path)
4. sparse one-hot LR (Avazu-style, segment_sum gradients)
5. multinomial softmax regression (MNIST-shaped: D=784, K=10; plus a
   north-star-D HBM-stress sub-row)
6. row-blocked CTR over the keyed native PS plane (beyond BASELINE.json:
   the deployment-shaped row VERDICT r4 #5 asked for)

Each row reports steady-state training ``samples_per_sec`` and a
convergence metric (final accuracy, plus logloss where meaningful) so
perf claims stay tied to statistical quality.  ``--quick`` shrinks every
workload for CPU / smoke runs (this is what CI exercises); the full sizes
are TPU-scale.

Run: ``python benchmarks/bench_configs.py [--quick] [--configs 1,3,5]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from distlr_tpu.utils.backend import start_benchmark  # noqa: E402


def _steady_state_sps(step, w, batch, steps: int, batch_samples: int) -> float:
    """samples/sec of ``w = step(w, batch)`` iterated ``steps`` times.

    One warmup call compiles; timing ends on ``block_until_ready``."""
    import jax

    w = jax.block_until_ready(step(w, batch))
    t0 = time.perf_counter()
    for _ in range(steps):
        w = step(w, batch)
    jax.block_until_ready(w)
    dt = time.perf_counter() - t0
    return batch_samples * steps / dt


def _scan_step(model, cfg):
    """Plain SGD step (no mesh): the 1-chip hot path."""
    import jax

    @jax.jit
    def step(w, batch):
        g = model.grad(w, batch, cfg)
        return jax.tree.map(lambda p, t: p - cfg.learning_rate * t, w, g)

    return step


def bench_config_1(quick: bool) -> dict:
    """Dense binary LR on gen-data-layout synthetic shards, single chip
    (the reference's ``local.sh 1 1`` workload, ``examples/local.sh:6-9``)."""
    import tempfile

    from distlr_tpu import Config
    from distlr_tpu.data import write_synthetic_shards
    from distlr_tpu.train import Trainer

    n, d, epochs = (4000, 123, 40) if quick else (100_000, 123, 100)
    with tempfile.TemporaryDirectory() as tmp:
        write_synthetic_shards(tmp, n, d, num_parts=1, seed=42)
        cfg = Config(
            data_dir=tmp, num_feature_dim=d, num_iteration=epochs,
            learning_rate=0.5, l2_c=0.0, test_interval=epochs,
        )
        tr = Trainer(cfg).load_data()
        tr.fit(eval_fn=lambda *_: None)
        em = tr.evaluate_metrics()
        sps = tr.timer.samples_per_sec
    return {
        "config": 1,
        "name": "dense binary LR, synthetic gen-data, 1W/1S sync",
        "samples_per_sec": round(sps, 1),
        "accuracy": round(em["accuracy"], 4),
        "test_logloss": round(em["logloss"], 5),
    }


def bench_config_2(quick: bool) -> dict:
    """4-worker asynchronous (Hogwild) dense LR against native C++ KV
    servers — the reference's ``SYNC_MODE=0`` path (``src/main.cc:79-84``)."""
    import tempfile

    from distlr_tpu import Config
    from distlr_tpu.data import write_synthetic_shards
    from distlr_tpu.ps import build_native
    from distlr_tpu.train.ps_trainer import run_ps_local

    n, d, epochs = (4000, 123, 15) if quick else (100_000, 123, 60)
    with tempfile.TemporaryDirectory() as tmp:
        write_synthetic_shards(tmp, n, d, num_parts=4, seed=42)
        build_native()  # outside the timer, like every config's compile
        cfg = Config(
            data_dir=tmp, num_feature_dim=d, num_iteration=epochs,
            learning_rate=0.1, l2_c=0.0, test_interval=epochs,
            sync_mode=False, num_workers=4, num_servers=2, batch_size=256,
        )
        # Warmup run compiles the gradient AND accuracy steps; the jit
        # cache transfers to the timed run (ps_trainer._compiled_fns is
        # shared across PSWorker instances). test_interval=1 so the
        # epoch-1 eval actually compiles the accuracy fn.
        run_ps_local(cfg.replace(num_iteration=1, test_interval=1),
                     eval_fn=lambda *_: None)
        accs: list[float] = []
        t0 = time.perf_counter()
        ws = run_ps_local(cfg, eval_fn=lambda _epoch, a: accs.append(a))
        dt = time.perf_counter() - t0
        # test logloss of the final weights (the driver parity metric,
        # BASELINE.json epochs-to-logloss), on the written test shard
        from distlr_tpu.data import parse_libsvm_file
        Xt, yt = parse_libsvm_file(os.path.join(tmp, "test", "part-001"), d)
        z = Xt @ np.asarray(ws[0], np.float64)
        test_ll = float(np.mean(np.logaddexp(0.0, z) - yt * z))
    n_train = int(n * 0.8)
    return {
        "config": 2,
        "name": "4-worker async-SGD dense LR (native PS, Hogwild)",
        "samples_per_sec": round(n_train * epochs / dt, 1),
        "accuracy": round(accs[-1], 4) if accs else None,
        "test_logloss": round(test_ll, 5),
    }


def bench_config_3(quick: bool) -> dict:
    """Criteo-style hashed-to-dense CTR at north-star width: dense MXU
    path, device-resident one-hot-ish features (BASELINE.json config 3)."""
    import jax
    import jax.numpy as jnp

    from distlr_tpu import Config
    from distlr_tpu.models import BinaryLR

    d, b, steps = (1 << 14, 512, 6) if quick else (1_000_000, 2048, 20)
    cfg = Config(num_feature_dim=d, learning_rate=0.2, l2_c=0.0)
    model = BinaryLR(d)

    @jax.jit
    def make(key):
        # hashed-to-dense CTR: F active buckets per row; dense bf16 layout
        kcols, ky = jax.random.split(key)
        cols = jax.random.randint(kcols, (b, 39), 0, d)
        X = jnp.zeros((b, d), jnp.bfloat16)
        X = jax.vmap(lambda row, c: row.at[c].set(1))(X, cols)
        y = jax.random.bernoulli(ky, 0.5, (b,)).astype(jnp.int32)
        return X, y, jnp.ones((b,), jnp.float32)

    batch = jax.block_until_ready(make(jax.random.PRNGKey(0)))
    step = _scan_step(model, cfg)
    w = jnp.zeros(d, jnp.float32)
    sps = _steady_state_sps(step, w, batch, steps, b)

    # feature_dtype="int8_dot" variant: int8-resident X and the native
    # int8 x int8 -> int32 MXU contraction (the shipped formulation that
    # beat the bf16-convert wall in exp_int8_dot.py).  One-hot features
    # quantize exactly: scale = 1/127, lanes {0, 127}.
    import dataclasses

    from distlr_tpu.models import get_model

    cfg_q = Config(num_feature_dim=d, learning_rate=0.2, l2_c=0.0,
                   feature_dtype="int8_dot")
    model_q = dataclasses.replace(get_model(cfg_q), feature_scale=1.0 / 127.0)
    batch_q = ((batch[0].astype(jnp.float32) * 127).astype(jnp.int8),
               batch[1], batch[2])
    sps_q = _steady_state_sps(_scan_step(model_q, cfg_q),
                              jnp.zeros(d, jnp.float32), batch_q, steps, b)

    # Quality column (VERDICT r4 #3: config 3 never had one) — same
    # recipe as config 4's convergence block, on the DENSE encoding this
    # config benchmarks: recover a hashed ground-truth signal to
    # near-oracle held-out accuracy.  The int8_dot variant trains on the
    # same problem: one-hot rows quantize exactly (scale 1/127, lanes
    # {0,127}), so any accuracy gap vs the f32 path would expose int8
    # gradient-quantization error, not data loss.
    from distlr_tpu.data.hashing import make_ctr_dataset

    dc, nc, n_te = 512, 6000, 1500
    raw, cols_q, vals_q, cy, w_true = make_ctr_dataset(
        nc + n_te, 8, 5000, dc, seed=1)
    # dense encoding built by scatter-add from the dataset's OWN hashed
    # COO (not a re-hash, which would silently desync if the dataset's
    # encoder ever changed)
    Xd = np.zeros((nc + n_te, dc), np.float32)
    np.add.at(Xd, (np.repeat(np.arange(nc + n_te), cols_q.shape[1]),
                   cols_q.reshape(-1)), vals_q.reshape(-1))
    oracle = float(((np.sum(w_true[cols_q[:n_te]] * vals_q[:n_te], -1) > 0
                     ).astype(int) == cy[:n_te]).mean())
    ccfg = Config(num_feature_dim=dc, learning_rate=1.0, l2_c=0.0)
    cmodel = BinaryLR(dc)
    ctr_b = (jnp.asarray(Xd[n_te:]), jnp.asarray(cy[n_te:]),
             jnp.ones(nc, jnp.float32))
    cte_b = (jnp.asarray(Xd[:n_te]), jnp.asarray(cy[:n_te]),
             jnp.ones(n_te, jnp.float32))
    acc, test_ll = _fit_and_eval(cmodel, ccfg, ctr_b, cte_b, 1000, dc)
    ccfg_q = Config(num_feature_dim=dc, learning_rate=1.0, l2_c=0.0,
                    feature_dtype="int8_dot")
    # scale = max/127 (same recipe as config 5): intra-row hash
    # collisions sum to 2.0 in the dense encoding, and those lanes must
    # survive quantization, not clip to 1
    q_scale = float(np.abs(Xd).max()) / 127.0
    cmodel_q = dataclasses.replace(get_model(ccfg_q), feature_scale=q_scale)
    Xq = np.clip(np.rint(Xd / q_scale), -127, 127).astype(np.int8)
    q_tr = (Xq[n_te:], ctr_b[1], ctr_b[2])
    q_te = (Xq[:n_te], cte_b[1], cte_b[2])
    acc_q, _llq = _fit_and_eval(
        cmodel_q, ccfg_q,
        tuple(jnp.asarray(a) for a in q_tr),
        tuple(jnp.asarray(a) for a in q_te), 1000, dc)
    return {
        "config": 3,
        "name": f"Criteo-style hashed-to-dense CTR, D={d}, dense MXU path",
        "samples_per_sec": round(sps, 1),
        "int8_dot_samples_per_sec": round(sps_q, 1),
        "accuracy": round(acc, 4),
        "test_logloss": round(test_ll, 5),
        "int8_dot_accuracy": round(acc_q, 4),
        "oracle_accuracy": round(oracle, 4),
        "quality_note": (
            "held-out accuracy after 1000 full-batch steps on a small "
            "hashed-CTR problem (dc=512, same recipe as config 4's "
            "convergence block) — the dense-encoding path this config "
            "rates; int8_dot_accuracy trains the same problem through "
            "the native int8 MXU contraction (one-hot rows quantize "
            "exactly, so a gap would be int8 gradient error)"),
    }


def bench_config_4(quick: bool) -> dict:
    """Avazu-style sparse one-hot LR: padded-COO batches, gather forward,
    segment_sum gradient (BASELINE.json config 4).  Also reports
    convergence on a small hashed-CTR problem."""
    import jax.numpy as jnp

    from distlr_tpu import Config
    from distlr_tpu.data.hashing import make_ctr_dataset
    from distlr_tpu.models import SparseBinaryLR

    # throughput at scale: D=1M buckets, 21 fields (Avazu's feature count)
    d, b, fields, steps = (1 << 14, 2048, 21, 8) if quick else (1_000_000, 65536, 21, 20)
    cfg = Config(num_feature_dim=d, learning_rate=0.5, l2_c=0.0, model="sparse_lr")
    model = SparseBinaryLR(d)
    _, cols, vals, y, _w = make_ctr_dataset(b, fields, 10_000_000, d, seed=0)
    batch = (jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(y), jnp.ones(b, jnp.float32))
    step = _scan_step(model, cfg)
    sps = _steady_state_sps(step, jnp.zeros(d, jnp.float32), batch, steps, b)

    # row-blocked variants of the same workload shape (the trainable
    # blocked_lr path; the statistical trade: bigger R = fewer gathers
    # but coarser conjunction groups)
    from distlr_tpu.data.hashing import make_uniform_blocked_batch
    from distlr_tpu.models import BlockedSparseLR

    blocked_sps = {}
    rng_b = np.random.default_rng(1)
    for r in (8, 16, 32):
        nb = d // r
        cfg_b = Config(num_feature_dim=d, model="blocked_lr", block_size=r,
                       learning_rate=0.5, l2_c=0.0)
        bmodel = BlockedSparseLR(nb, r)
        blocks_np, lv = make_uniform_blocked_batch(rng_b, b, fields, nb, r)
        bbatch = (jnp.asarray(blocks_np), jnp.asarray(lv), jnp.asarray(y),
                  jnp.ones(b, jnp.float32))
        bstep = _scan_step(bmodel, cfg_b)
        blocked_sps[r] = round(_steady_state_sps(
            bstep, jnp.zeros((nb, r), jnp.float32), bbatch, steps, b), 1)

    # convergence (small): recover hashed signal to near-oracle accuracy;
    # metrics are HELD-OUT (first n_te rows never trained on).
    #
    # Oracle-gap accounting (measured r4, on-chip probe): at the round-3
    # protocol (120 steps) test acc was 0.7967 vs oracle 0.8427 — 1.7pt
    # of that was under-convergence (1000 steps reaches 0.8133, train
    # acc 0.859) and the rest is finite-sample estimation error (512
    # params fit on 6000 Bernoulli rows): the same model on 4x the
    # train rows reaches 0.8507, ABOVE the oracle draw.  Collisions
    # cost nothing here by construction — the ground truth lives in
    # bucket space, so the learner sees the exact feature map the
    # labels were generated from.
    dc, nc, n_te = 512, 6000, 1500
    _, ccols, cvals, cy, w_true = make_ctr_dataset(nc + n_te, 8, 5000, dc, seed=1)
    oracle = float(((np.sum(w_true[ccols[:n_te]] * cvals[:n_te], -1) > 0
                     ).astype(int) == cy[:n_te]).mean())
    ccfg = Config(num_feature_dim=dc, learning_rate=1.0, l2_c=0.0, model="sparse_lr")
    cmodel = SparseBinaryLR(dc)
    cstep = _scan_step(cmodel, ccfg)
    cbatch = (jnp.asarray(ccols[n_te:]), jnp.asarray(cvals[n_te:]),
              jnp.asarray(cy[n_te:]), jnp.ones(nc, jnp.float32))
    tbatch = (jnp.asarray(ccols[:n_te]), jnp.asarray(cvals[:n_te]),
              jnp.asarray(cy[:n_te]), jnp.ones(n_te, jnp.float32))
    w = jnp.zeros(dc, jnp.float32)
    for _ in range(1000):
        w = cstep(w, cbatch)
    acc = float(cmodel.accuracy(w, tbatch))
    test_ll = float(cmodel.logloss(w, tbatch))
    return {
        "config": 4,
        "name": f"sparse one-hot LR (Avazu-style), D={d}, {fields} fields, segment_sum",
        "samples_per_sec": round(sps, 1),
        "blocked_samples_per_sec": blocked_sps,
        "accuracy": round(acc, 4),
        "test_logloss": round(test_ll, 5),
        "oracle_accuracy": round(oracle, 4),
        "oracle_gap_note": "remaining gap is finite-sample estimation "
                           "error (512 params / 6000 train rows; 4x rows "
                           "reaches 0.851, above the oracle draw) — see "
                           "the measured decomposition in bench_config_4",
        "blocked_frontier": _blocked_frontier(quick, blocked_sps, sps),
    }


def _blocked_frontier(quick: bool, blocked_sps: dict, scalar_sps: float) -> dict:
    """Rate-vs-quality frontier for the row-blocked hashing path.

    A blocked rate is only a real training-throughput claim if a model
    at that R still LEARNS — at
    R=32 all 21 fields form one conjunction group, so rows are trained
    per exact value tuple and the scheme degrades to tuple memorization
    when tuples don't recur.  This sweeps
    R in {8, 16, 32} against scalar hashing on three data regimes at
    EQUAL parameter count (blocked table nb = D/R rows of R lanes):

      high_card_iid      vocab 10M, fields i.i.d. — tuples never recur
      low_card_iid       vocab 2, fields i.i.d. — R=8 group tuples
                         (2^8 = 256) recur ~190x at full scale; R=16
                         (65k) and R=32 (2^21) essentially do not
      correlated_tuples  512 distinct field tuples (one latent factor,
                         e.g. device model, fixes all fields) — every
                         group tuple recurs ~96x at any R

    Labels are mean-centered (``center_logits``) so the class marginal
    stays near 0.5 — at low vocab an uncentered logistic model hands
    every predictor a ~90% majority-class accuracy and the comparison
    measures nothing.

    Each regime row reports held-out accuracy/logloss per R, the scalar
    baseline, and ``largest_r_within_1pt`` — the biggest R whose
    accuracy is within 1pt of scalar (None if none is), i.e. the R at
    which the measured blocked rate is claimable for that regime.
    """
    import jax.numpy as jnp

    from distlr_tpu import Config
    from distlr_tpu.data.hashing import encode_blocked, make_ctr_dataset
    from distlr_tpu.models import BlockedSparseLR, SparseBinaryLR

    fields = 21
    dc, n_tr, n_te, steps_cv = ((1024, 4000, 1000, 120) if quick
                                else (16384, 49152, 8192, 250))
    lr = 1.0
    r_values = (8, 16, 32)
    regimes = {
        "high_card_iid": dict(vocab_size=10_000_000),
        "low_card_iid": dict(vocab_size=2),
        "correlated_tuples": dict(vocab_size=50, num_distinct_tuples=512),
    }
    out = {}
    for name, kw in regimes.items():
        raw, cols, vals, y, _w = make_ctr_dataset(
            n_tr + n_te, fields, num_buckets=dc, seed=7,
            center_logits=True, **kw)
        # scalar baseline (SparseBinaryLR over dc buckets)
        cfg_s = Config(num_feature_dim=dc, learning_rate=lr, l2_c=0.0,
                       model="sparse_lr")
        smodel = SparseBinaryLR(dc)
        tr_b = (jnp.asarray(cols[n_te:]), jnp.asarray(vals[n_te:]),
                jnp.asarray(y[n_te:]), jnp.ones(n_tr, jnp.float32))
        te_b = (jnp.asarray(cols[:n_te]), jnp.asarray(vals[:n_te]),
                jnp.asarray(y[:n_te]), jnp.ones(n_te, jnp.float32))
        acc_s, ll_s = _fit_and_eval(smodel, cfg_s, tr_b, te_b, steps_cv, dc)
        row = {
            "scalar": {"accuracy": round(acc_s, 4),
                       "test_logloss": round(ll_s, 5),
                       "samples_per_sec": round(scalar_sps, 1)},
        }
        largest_ok = None
        for r in r_values:
            nb = dc // r
            blocks, lane_vals = encode_blocked(raw, nb, r, seed=7)
            cfg_b = Config(num_feature_dim=dc, model="blocked_lr",
                           block_size=r, learning_rate=lr, l2_c=0.0)
            bmodel = BlockedSparseLR(nb, r)
            btr = (jnp.asarray(blocks[n_te:]), jnp.asarray(lane_vals[n_te:]),
                   jnp.asarray(y[n_te:]), jnp.ones(n_tr, jnp.float32))
            bte = (jnp.asarray(blocks[:n_te]), jnp.asarray(lane_vals[:n_te]),
                   jnp.asarray(y[:n_te]), jnp.ones(n_te, jnp.float32))
            acc_r, ll_r = _fit_and_eval(bmodel, cfg_b, btr, bte, steps_cv,
                                        (nb, r))
            row[f"r{r}"] = {
                "accuracy": round(acc_r, 4),
                "test_logloss": round(ll_r, 5),
                "delta_vs_scalar_pts": round((acc_r - acc_s) * 100, 2),
                "samples_per_sec": blocked_sps.get(r),
            }
            if acc_r >= acc_s - 0.01:
                largest_ok = r
        row["largest_r_within_1pt"] = largest_ok
        out[name] = row
    out["operating_point"] = _operating_point_sweep(quick)
    return out


def _fit_and_eval(model, cfg, train_batch, test_batch, steps: int,
                  param_shape) -> tuple[float, float]:
    """Shared quality-measurement core for the frontier sweeps: fit
    ``steps`` full-batch SGD steps from zeros, return held-out
    ``(accuracy, logloss)``.  Both ``_blocked_frontier`` and
    ``_operating_point_sweep`` must measure through THIS function so the
    protocol (init, step count, metrics) cannot silently diverge between
    the two sweeps that bench.py's quality gate compares."""
    import jax.numpy as jnp

    step = _scan_step(model, cfg)
    w = jnp.zeros(param_shape, jnp.float32)
    for _ in range(steps):
        w = step(w, train_batch)
    return float(model.accuracy(w, test_batch)), float(model.logloss(w, test_batch))


def _split_groups(num_fields: int, g: int, r: int) -> np.ndarray:
    """``g`` near-equal field groups padded to ``r`` lanes — now the
    shipped ``hashing.split_field_groups`` (``cfg.block_groups`` end to
    end); kept as a thin adapter for the sweep's (fields, G, R) call
    order."""
    from distlr_tpu.data.hashing import split_field_groups

    return split_field_groups(num_fields, r, g)


def _operating_point_sweep(quick: bool) -> dict:
    """Blocked quality at the rates' ACTUAL load factor (VERDICT r4 #1).

    The equal-param frontier above shrinks the table to dc=16384, which
    puts R=32 at row load 1.0 (512 correlated tuples into 512 rows) —
    but every blocked RATE in this repo is measured at D=1M, where the
    same 512 tuples land in 31250 rows (load 0.016).  Quality and rate
    were being measured at different collision regimes.  This sweep
    holds the data regimes fixed and scales the table toward the
    north-star operating point, adding the intermediate groupings the
    r4 frontier never tried (G=2/G=3 conjunction groups at R=32,
    ``_split_groups``).  The verdict that matters for the headline:
    ``valid_default_rs`` — default-grouping R values within 1pt of the
    SAME-dc scalar baseline at the largest dc measured.
    """
    import jax.numpy as jnp

    from distlr_tpu import Config
    from distlr_tpu.data.hashing import (
        HashedFeatureEncoder,
        default_field_groups,
        hash_group_blocks,
        make_ctr_dataset,
    )
    from distlr_tpu.models import BlockedSparseLR, SparseBinaryLR

    fields = 21
    n_tr, n_te, steps_cv = (4000, 1000, 120) if quick else (49152, 8192, 250)
    dc_ops = (4096,) if quick else (65536, 1_048_576)
    lr = 1.0
    regimes = {
        "low_card_iid": dict(vocab_size=2),
        "correlated_tuples": dict(vocab_size=50, num_distinct_tuples=512),
    }
    # (label, R, field_groups builder) — None = default consecutive chunks
    variants = [
        ("r8", 8, None),
        ("r16", 16, None),
        ("r32", 32, None),
        ("r32_g2", 32, lambda: _split_groups(fields, 2, 32)),
        ("r32_g3", 32, lambda: _split_groups(fields, 3, 32)),
    ]
    out: dict = {"note": (
        "quality at matched load: same regimes as the equal-param "
        "frontier, table scaled toward the D=1M operating point where "
        "the blocked rates were measured"),
        "shapes": {"fields": fields, "n_train": n_tr, "n_test": n_te,
                   "steps": steps_cv, "dc_values": list(dc_ops)},
        "regimes": {}}
    for name, kw in regimes.items():
        raw, _cols, _vals, y, _w = make_ctr_dataset(
            n_tr + n_te, fields, num_buckets=max(dc_ops), seed=7,
            center_logits=True, **kw)
        reg_rows: dict = {}
        for dc in dc_ops:
            # scalar baseline at THIS dc (cols must be rehashed per dc)
            enc = HashedFeatureEncoder(dc, seed=7)
            field_ids = np.broadcast_to(np.arange(fields), raw.shape)
            c_dc, v_dc = enc.encode_coo(field_ids, raw)
            cfg_s = Config(num_feature_dim=dc, learning_rate=lr, l2_c=0.0,
                           model="sparse_lr")
            smodel = SparseBinaryLR(dc)
            tr_b = (jnp.asarray(c_dc[n_te:].astype(np.int32)),
                    jnp.asarray(v_dc[n_te:]),
                    jnp.asarray(y[n_te:]), jnp.ones(n_tr, jnp.float32))
            te_b = (jnp.asarray(c_dc[:n_te].astype(np.int32)),
                    jnp.asarray(v_dc[:n_te]),
                    jnp.asarray(y[:n_te]), jnp.ones(n_te, jnp.float32))
            acc_s, ll_s = _fit_and_eval(smodel, cfg_s, tr_b, te_b,
                                        steps_cv, dc)
            cell: dict = {"scalar": {
                "accuracy": round(acc_s, 4),
                "test_logloss": round(ll_s, 5)}}
            for label, r, mk_groups in variants:
                nb = dc // r
                groups = (default_field_groups(fields, r) if mk_groups is None
                          else mk_groups())
                blocks64, lane_vals = hash_group_blocks(raw, groups, nb, seed=7)
                blocks = blocks64.astype(np.int32)
                # collision/recurrence diagnostics on the actual groups
                distinct = [len(np.unique(raw[:, g[g >= 0]], axis=0))
                            for g in groups]
                cfg_b = Config(num_feature_dim=dc, model="blocked_lr",
                               block_size=r, learning_rate=lr, l2_c=0.0)
                bmodel = BlockedSparseLR(nb, r)
                btr = (jnp.asarray(blocks[n_te:]),
                       jnp.asarray(lane_vals[n_te:]),
                       jnp.asarray(y[n_te:]), jnp.ones(n_tr, jnp.float32))
                bte = (jnp.asarray(blocks[:n_te]),
                       jnp.asarray(lane_vals[:n_te]),
                       jnp.asarray(y[:n_te]), jnp.ones(n_te, jnp.float32))
                acc_r, ll_r = _fit_and_eval(bmodel, cfg_b, btr, bte,
                                            steps_cv, (nb, r))
                cell[label] = {
                    "accuracy": round(acc_r, 4),
                    "test_logloss": round(ll_r, 5),
                    "delta_vs_scalar_pts": round((acc_r - acc_s) * 100, 2),
                    "groups": len(groups),
                    "row_load": round(sum(distinct) / nb, 4),
                    "min_recurrence": round(
                        (n_tr + n_te) / max(distinct), 1),
                }
            reg_rows[f"dc{dc}"] = cell
        out["regimes"][name] = reg_rows
    # Headline verdict: which DEFAULT-grouping R values hold within 1pt
    # of same-dc scalar at the largest (most operating-point-like) dc in
    # at least one regime — this is what bench.py's quality gate reads.
    top = f"dc{max(dc_ops)}"
    valid_default: set[int] = set()
    valid_variants: set[str] = set()
    for label, r, mk_groups in variants:
        held = any(reg[top][label]["delta_vs_scalar_pts"] >= -1.0
                   for reg in out["regimes"].values())
        if held:
            valid_variants.add(label)
            if mk_groups is None:
                valid_default.add(r)
    out["valid_default_rs"] = sorted(valid_default)
    out["valid_variants"] = sorted(valid_variants)
    out["at_dc"] = max(dc_ops)
    return out


def bench_config_5(quick: bool) -> dict:
    """Multinomial softmax regression, MNIST-shaped (D=784, K=10), on
    synthetic 10-class data (zero-egress environment: no MNIST download;
    same shapes and math as BASELINE.json config 5)."""
    import jax.numpy as jnp

    from distlr_tpu import Config
    from distlr_tpu.data import make_synthetic_dataset
    from distlr_tpu.models import SoftmaxRegression

    d, k, n = 784, 10, (4096 if quick else 60_000)
    n_te = max(n // 5, 512)
    steps = 10 if quick else 30
    X, y, w_true = make_synthetic_dataset(n + n_te, d, seed=0, num_classes=k)
    # Quality ceilings for this workload: the generator's own weights
    # (Bayes-style oracle — labels carry Gumbel noise, so < 1.0), and a
    # train-to-convergence run of the same model (the reachable ceiling).
    # (argmax is scale-invariant, so the generator's 3.0 logit
    # temperature doesn't enter the oracle prediction)
    oracle = float((np.argmax(X[:n_te] @ w_true, axis=1) == y[:n_te]).mean())
    cfg = Config(num_feature_dim=d, num_classes=k, model="softmax",
                 learning_rate=0.3, l2_c=0.0)
    model = SoftmaxRegression(d, k)
    batch = (jnp.asarray(X[n_te:]), jnp.asarray(y[n_te:]), jnp.ones(n, jnp.float32))
    tbatch = (jnp.asarray(X[:n_te]), jnp.asarray(y[:n_te]), jnp.ones(n_te, jnp.float32))
    step = _scan_step(model, cfg)
    W = jnp.zeros((d, k), jnp.float32)
    sps = _steady_state_sps(step, W, batch, steps, n)

    # int8_dot variant (r4: the native int8 MXU contraction covers the
    # softmax family too): int8-resident X, same step protocol
    import dataclasses

    from distlr_tpu.models import get_model

    scale = float(np.abs(X[n_te:]).max()) / 127.0
    Xq = np.clip(np.rint(X[n_te:] / scale), -127, 127).astype(np.int8)
    cfg_q = Config(num_feature_dim=d, num_classes=k, model="softmax",
                   learning_rate=0.3, l2_c=0.0, feature_dtype="int8_dot")
    # via get_model so int8_dot/compute_dtype derive from the Config
    # exactly as the Trainer builds it (same pattern as config 3)
    model_q = dataclasses.replace(get_model(cfg_q), feature_scale=scale)
    batch_q = (jnp.asarray(Xq), batch[1], batch[2])
    sps_q = _steady_state_sps(_scan_step(model_q, cfg_q),
                              jnp.zeros((d, k), jnp.float32),
                              batch_q, steps, n)

    for _ in range(60):
        W = step(W, batch)
    acc = float(model.accuracy(W, tbatch))
    test_ll = float(model.logloss(W, tbatch))
    conv_steps = 100 if quick else 1500
    for _ in range(conv_steps - 60):
        W = step(W, batch)
    conv_acc = float(model.accuracy(W, tbatch))
    conv_ll = float(model.logloss(W, tbatch))
    return {
        "config": 5,
        "name": "multinomial softmax regression, D=784 K=10 (MNIST-shaped)",
        "samples_per_sec": round(sps, 1),
        "int8_dot_samples_per_sec": round(sps_q, 1),
        "accuracy": round(acc, 4),
        "test_logloss": round(test_ll, 5),
        "converged_accuracy": round(conv_acc, 4),
        "converged_test_logloss": round(conv_ll, 5),
        "converged_steps": conv_steps,
        "oracle_accuracy": round(oracle, 4),
        "large_d": _softmax_large_d(quick),
    }


def _softmax_large_d(quick: bool) -> dict:
    """Softmax at north-star D (VERDICT r4 #5): D>=100k is where the
    (D, K) table and the int8_dot grid actually stress HBM — config 5's
    MNIST shape (D=784) never does.  Single-chip rates; the multi-chip
    feature-sharded correctness of the same family is driver-validated
    by ``__graft_entry__.dryrun_multichip`` (softmax sweep, r5) and
    ``tests/test_feature_parallel.py``."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from distlr_tpu import Config
    from distlr_tpu.models import SoftmaxRegression, get_model

    d, k, b, steps = (1 << 14, 10, 256, 3) if quick else (1_000_000, 10, 2048, 10)
    cfg = Config(num_feature_dim=d, num_classes=k, model="softmax",
                 learning_rate=0.1, l2_c=0.0)
    model = SoftmaxRegression(d, k)

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(key)
        X = jax.random.normal(kx, (b, d), dtype=jnp.bfloat16)
        y = jax.random.randint(ky, (b,), 0, k)
        return X, y, jnp.ones((b,), jnp.float32)

    batch = jax.block_until_ready(make(jax.random.PRNGKey(0)))
    sps = _steady_state_sps(_scan_step(model, cfg),
                            jnp.zeros((d, k), jnp.float32), batch, steps, b)

    cfg_q = Config(num_feature_dim=d, num_classes=k, model="softmax",
                   learning_rate=0.1, l2_c=0.0, feature_dtype="int8_dot")
    model_q = dataclasses.replace(get_model(cfg_q), feature_scale=1.0 / 127.0)
    batch_q = (jnp.clip(jnp.rint(batch[0].astype(jnp.float32) * 42.0),
                        -127, 127).astype(jnp.int8), batch[1], batch[2])
    sps_q = _steady_state_sps(_scan_step(model_q, cfg_q),
                              jnp.zeros((d, k), jnp.float32),
                              batch_q, steps, b)
    return {
        "D": d, "K": k, "B": b,
        "samples_per_sec": round(sps, 1),
        "int8_dot_samples_per_sec": round(sps_q, 1),
    }


def bench_config_6(quick: bool) -> dict:
    """Row-blocked CTR over the KEYED native PS plane (VERDICT r4 #5):
    the K8s-style deployment the README advertises — table rows travel
    as R-wide key ranges over TCP, only the batch's touched rows move
    (ps-lite's sliced-key capability the reference app itself never
    exercises, ``src/main.cc:98-101``).  Rate is end-to-end async
    (pull -> host grad -> keyed push) through real sockets."""
    import tempfile

    from distlr_tpu import Config
    from distlr_tpu.data.hashing import write_raw_ctr_shards
    from distlr_tpu.ps import build_native
    from distlr_tpu.train.ps_trainer import run_ps_local

    if quick:
        d, n, fields, r, workers, servers, epochs, bs = (
            4096, 2000, 21, 8, 2, 1, 3, 256)
    else:
        d, n, fields, r, workers, servers, epochs, bs = (
            1_048_576, 100_000, 21, 32, 4, 2, 3, 4096)
    with tempfile.TemporaryDirectory() as tmp:
        # tuple-recurrent data (512 distinct field tuples): the regime
        # the blocked path learns on — i.i.d. fields would pin accuracy
        # at 0.5 by construction and make the row's quality column
        # meaningless
        write_raw_ctr_shards(tmp, n, fields, 50, num_parts=workers, seed=3,
                             num_distinct_tuples=64 if quick else 512)
        build_native()
        cfg = Config(
            data_dir=tmp, num_feature_dim=d, num_iteration=epochs,
            learning_rate=0.5, l2_c=0.0, test_interval=epochs,
            model="blocked_lr", block_size=r,
            sync_mode=False, num_workers=workers, num_servers=servers,
            batch_size=bs, ps_timeout_ms=60_000,
        )
        accs: list[float] = []
        # warmup run: jit caches for the keyed grad/eval compile outside
        # the timed window (same protocol as config 2)
        run_ps_local(cfg.replace(num_iteration=1, test_interval=1),
                     eval_fn=lambda *_: None)
        t0 = time.perf_counter()
        run_ps_local(cfg, eval_fn=lambda _e, a: accs.append(a))
        dt = time.perf_counter() - t0
    n_train = int(n * 0.8)
    g = -(-fields // r)
    return {
        "config": 6,
        "name": (f"blocked CTR over keyed native PS, D={d} R={r}, "
                 f"{workers}W/{servers}S async"),
        "samples_per_sec": round(n_train * epochs / dt, 1),
        "accuracy": round(accs[-1], 4) if accs else None,
        "keyed_bytes_per_pull_note": (
            "only touched R-wide rows travel per batch, as one u64 row "
            "id per R vals (vals_per_key wire encoding, ps-lite "
            f"KVPairs.lens-style): <= {bs} samples x {g} groups x "
            f"({r} lanes x 4B + 8B key) per direction vs {d * 4} B for "
            "a full-vector pull; measured r5: the encoding halves "
            "per-op pull latency vs expanded per-lane keys (~2.8x "
            "fewer keyed bytes) with ~3% end-to-end gain on localhost "
            "(loop is gradient/GIL-bound there) — the byte cut is "
            "sized for DCN deployments"),
    }


BENCHES = {1: bench_config_1, 2: bench_config_2, 3: bench_config_3,
           4: bench_config_4, 5: bench_config_5, 6: bench_config_6}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small shapes (CPU/CI)")
    ap.add_argument("--configs", default="1,2,3,4,5,6",
                    help="comma-separated subset, e.g. 1,3,5")
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_CONFIGS.json"))
    ap.add_argument("--isolate", action="store_true",
                    help="run each config in its own subprocess so device "
                         "memory is fully released between configs (the "
                         "full-size suite can otherwise accumulate HBM "
                         "across configs and die RESOURCE_EXHAUSTED)")
    args = ap.parse_args(argv)
    default_out = os.path.join(REPO, "BENCH_CONFIGS.json")
    if args.quick and os.path.abspath(args.out) == default_out:
        # A quick probe must never clobber the canonical full-size
        # artifact (it did once — r4 review finding); quick results
        # always go to a sibling scratch file.
        args.out = os.path.join(REPO, "BENCH_CONFIGS_quick.json")
        print(f"[bench_configs] --quick: writing to {args.out}",
              file=sys.stderr)

    if not args.isolate:
        dev = start_benchmark("bench_configs.py", full_size=not args.quick)

    rows = []
    if args.isolate:
        import subprocess
        import tempfile
        for i in (int(s) for s in args.configs.split(",")):
            with tempfile.NamedTemporaryFile(suffix=".json") as tmp:
                cmd = [sys.executable, os.path.abspath(__file__),
                       "--configs", str(i), "--out", tmp.name]
                if args.quick:
                    cmd.append("--quick")
                proc = subprocess.run(cmd)
                if proc.returncode != 0:
                    # Abort WITHOUT writing: a partial row set silently
                    # replacing the canonical artifact would drop whole
                    # configs from the headline results (r4 review
                    # finding — same protect-the-artifact rule as the
                    # --quick divert above).
                    print(f"[bench_configs] config {i} failed "
                          f"(rc={proc.returncode}); aborting without "
                          f"writing {args.out}", file=sys.stderr)
                    return 1
                with open(tmp.name) as f:
                    rows.extend(json.load(f)["rows"])
    else:
        for i in (int(s) for s in args.configs.split(",")):
            row = BENCHES[i](args.quick)
            rows.append(row)
            print(json.dumps(row))
    if args.isolate:
        # only now: the parent stays off the backend until its children,
        # each of which made the full-size check, are done (one process
        # per chip)
        dev = start_benchmark("bench_configs.py", full_size=not args.quick)
    payload = {
        **dev,
        "quick": args.quick,
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    _maybe_refresh_frontier_artifact(payload, args.out, default_out)
    return 0


def _maybe_refresh_frontier_artifact(payload: dict, out_path: str,
                                     canonical_path: str) -> None:
    """Keep ``benchmarks/FRONTIER_TPU.json`` (the standalone frontier
    artifact that bench.py's quality gate reads) in lockstep with the
    canonical run: one full-size on-chip bench_configs invocation
    refreshes both.  Quick runs never touch it — the artifact must
    stay on-chip evidence only.  Neither do runs writing anywhere but
    the canonical BENCH_CONFIGS.json: in ``--isolate`` mode the per-
    config children write to temp files, and only the parent's final
    aggregate write may refresh — a child refreshing on its own would
    strand a new frontier beside an aborted/old BENCH_CONFIGS.json."""
    if payload.get("quick"):
        return
    if os.path.abspath(out_path) != canonical_path:
        return
    row4 = next((r for r in payload["rows"] if r.get("config") == 4), None)
    if row4 is None or "blocked_frontier" not in row4:
        return
    import datetime

    art = {
        "what": ("blocked rate-vs-quality frontier measured on-chip by "
                 "bench_configs.bench_config_4 — regenerated automatically "
                 "with the canonical BENCH_CONFIGS.json run"),
        "backend": payload["backend"],
        "timestamp": datetime.datetime.now(datetime.timezone.utc)
        .isoformat(timespec="seconds"),
        "samples_per_sec_scalar": row4.get("samples_per_sec"),
        "blocked_samples_per_sec": row4.get("blocked_samples_per_sec"),
        "frontier": row4["blocked_frontier"],
    }
    # fold in the standalone seed-replication evidence so regeneration
    # can't silently orphan the docs that cite it (exp_op_seed_check.py)
    try:
        with open(os.path.join(HERE, "OP_SEED_CHECK.json")) as f:
            sc = json.load(f)
        op = art["frontier"].get("operating_point")
        if isinstance(op, dict):
            op["seed_replication"] = {
                "deltas_pts_r32_vs_scalar": [r["delta_pts"]
                                             for r in sc["rows"]],
                "seeds": [r["seed"] for r in sc["rows"]],
                "claim_holds_all_seeds": sc["claim_holds_all_seeds"],
                "source": "benchmarks/OP_SEED_CHECK.json (exp_op_seed_check.py)",
            }
    except (OSError, ValueError, KeyError):
        pass
    path = os.path.join(HERE, "FRONTIER_TPU.json")
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
    print(f"[bench_configs] refreshed {path}", file=sys.stderr)


if __name__ == "__main__":
    raise SystemExit(main())
