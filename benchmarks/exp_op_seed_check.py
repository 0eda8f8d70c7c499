"""Seed-robustness check for the operating-point R=32 quality claim.

The headline finding of the operating-point sweep — single-group R=32
holds scalar accuracy in the correlated-tuples regime at dc=1M (load
0.016) — was measured on one data draw (seed 7).  The held-out split is
8192 rows, so a single accuracy delta has ~0.5pt of sampling noise;
this replicates the scalar-vs-R=32 comparison over several independent
draws so the artifact can state the claim with a spread, not a point.

Quality statistics are backend-independent (deterministic math), so
this runs anywhere; writes ``benchmarks/OP_SEED_CHECK.json``.

Run: python benchmarks/exp_op_seed_check.py
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# the replication must measure through the SAME fit/eval core as the
# sweep it replicates (bench_configs._fit_and_eval centralizes the
# protocol precisely so it cannot silently diverge)
from bench_configs import _fit_and_eval  # noqa: E402

from distlr_tpu import Config  # noqa: E402
from distlr_tpu.data.hashing import (  # noqa: E402
    default_field_groups,
    hash_group_blocks,
    make_ctr_dataset,
)
from distlr_tpu.models import BlockedSparseLR, SparseBinaryLR  # noqa: E402

FIELDS, DC, N_TR, N_TE, STEPS, LR = 21, 1_048_576, 49_152, 8_192, 250, 1.0
SEEDS = (7, 11, 23)


def one_seed(seed: int) -> dict:
    # make_ctr_dataset already returns the scalar hashed-COO encoding
    # at num_buckets=DC with this seed — use it directly
    raw, cols, vals, y, _w = make_ctr_dataset(
        N_TR + N_TE, FIELDS, vocab_size=50, num_buckets=DC, seed=seed,
        center_logits=True, num_distinct_tuples=512)
    ones_tr = jnp.ones(N_TR, jnp.float32)
    ones_te = jnp.ones(N_TE, jnp.float32)
    acc_s, _ll = _fit_and_eval(
        SparseBinaryLR(DC),
        Config(num_feature_dim=DC, model="sparse_lr", learning_rate=LR,
               l2_c=0.0),
        (jnp.asarray(cols[N_TE:]), jnp.asarray(vals[N_TE:]),
         jnp.asarray(y[N_TE:]), ones_tr),
        (jnp.asarray(cols[:N_TE]), jnp.asarray(vals[:N_TE]),
         jnp.asarray(y[:N_TE]), ones_te),
        STEPS, DC)
    nb = DC // 32
    blocks, lv = hash_group_blocks(
        raw, default_field_groups(FIELDS, 32), nb, seed=seed)
    blocks = blocks.astype(np.int32)
    acc_b, _ll = _fit_and_eval(
        BlockedSparseLR(nb, 32),
        Config(num_feature_dim=DC, model="blocked_lr", block_size=32,
               learning_rate=LR, l2_c=0.0),
        (jnp.asarray(blocks[N_TE:]), jnp.asarray(lv[N_TE:]),
         jnp.asarray(y[N_TE:]), ones_tr),
        (jnp.asarray(blocks[:N_TE]), jnp.asarray(lv[:N_TE]),
         jnp.asarray(y[:N_TE]), ones_te),
        STEPS, (nb, 32))
    return {"seed": seed, "scalar": round(acc_s, 4), "r32": round(acc_b, 4),
            "delta_pts": round((acc_b - acc_s) * 100, 2)}


def main() -> int:
    rows = []
    for s in SEEDS:
        row = one_seed(s)
        rows.append(row)
        print(row)
    deltas = [r["delta_pts"] for r in rows]
    art = {
        "what": ("seed replication of the operating-point claim: "
                 "single-group R=32 vs scalar hashing, correlated-tuples "
                 "regime (512 tuples), dc=1M (row load 0.016)"),
        # an accuracy replication, not a device measurement: it runs on
        # the default backend and names it
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "shapes": {"fields": FIELDS, "dc": DC, "n_train": N_TR,
                   "n_test": N_TE, "steps": STEPS},
        "rows": rows,
        "delta_pts_min": min(deltas),
        "delta_pts_max": max(deltas),
        "claim_holds_all_seeds": all(d >= -1.0 for d in deltas),
    }
    out = os.path.join(HERE, "OP_SEED_CHECK.json")
    with open(out, "w") as f:
        json.dump(art, f, indent=1)
    print("wrote", out, "deltas", deltas)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
