"""Row-blocked (group-hashed) CTR path: hashing, model, accuracy gate.

The blocked layout trades per-field bucket weights for per-(conjunction,
field) row lanes so one R-wide row gather replaces R scalar gathers
(row gathers amortize the per-index cost).  These tests pin the semantics and the
statistical gate: on low-cardinality fields (recurring tuples) the
blocked model must recover the oracle signal as well as the scalar-hash
sparse path does.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from distlr_tpu.config import Config
from distlr_tpu.data.hashing import hash_buckets, hash_group_blocks
from distlr_tpu.models import BlockedSparseLR, SparseBinaryLR, get_model


class TestHashGroupBlocks:
    def test_shapes_and_determinism(self):
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 50, size=(100, 8))
        groups = np.array([[0, 1, 2, 3], [4, 5, 6, 7]])
        b1, v1 = hash_group_blocks(ids, groups, 4096, seed=7)
        b2, v2 = hash_group_blocks(ids, groups, 4096, seed=7)
        assert b1.shape == (100, 2) and v1.shape == (100, 2, 4)
        np.testing.assert_array_equal(b1, b2)
        assert (b1 >= 0).all() and (b1 < 4096).all()
        assert (v1 == 1.0).all()
        b3, _ = hash_group_blocks(ids, groups, 4096, seed=8)
        assert (b1 != b3).any()

    def test_block_depends_on_every_member_value(self):
        ids = np.zeros((1, 4), np.int64)
        groups = np.array([[0, 1, 2, 3]])
        base, _ = hash_group_blocks(ids, groups, 1 << 20)
        for f in range(4):
            mod = ids.copy()
            mod[0, f] = 1
            b, _ = hash_group_blocks(mod, groups, 1 << 20)
            assert b[0, 0] != base[0, 0], f"field {f} ignored by block hash"

    def test_tuple_not_multiset(self):
        # same values in different field positions must key differently
        a, _ = hash_group_blocks(np.array([[3, 9]]), np.array([[0, 1]]), 1 << 20)
        b, _ = hash_group_blocks(np.array([[9, 3]]), np.array([[0, 1]]), 1 << 20)
        assert a[0, 0] != b[0, 0]

    def test_padded_lane_contributes_zero(self):
        ids = np.arange(6).reshape(2, 3)
        groups = np.array([[0, 1, 2, -1]])
        b, v = hash_group_blocks(ids, groups, 1024)
        assert v.shape == (2, 1, 4)
        assert (v[:, :, 3] == 0.0).all() and (v[:, :, :3] == 1.0).all()
        # and the pad lane must not alter the key vs a fixed convention
        assert (b >= 0).all()

    def test_raw_vals_flow_to_lanes(self):
        ids = np.array([[5, 6]])
        vals = np.array([[2.5, -1.0]], np.float32)
        _, v = hash_group_blocks(ids, np.array([[0, 1]]), 64, raw_vals=vals)
        np.testing.assert_allclose(v[0, 0], [2.5, -1.0])


class TestBlockedSparseLR:
    def _batch(self, n=64, g=2, r=4, nb=256, seed=0):
        rng = np.random.default_rng(seed)
        blocks = jnp.asarray(rng.integers(0, nb, size=(n, g)), jnp.int32)
        lane_vals = jnp.asarray(rng.standard_normal((n, g, r)), jnp.float32)
        y = jnp.asarray(rng.integers(0, 2, n), jnp.int32)
        mask = jnp.ones(n, jnp.float32)
        return blocks, lane_vals, y, mask

    def test_grad_matches_autodiff(self):
        cfg = Config(num_feature_dim=1024, model="blocked_lr", block_size=4,
                     l2_c=0.3)
        model = get_model(cfg)
        assert isinstance(model, BlockedSparseLR)
        batch = self._batch(nb=model.num_blocks)
        t = jnp.asarray(np.random.default_rng(1).standard_normal(
            (model.num_blocks, 4)), jnp.float32)
        g_closed = model.grad(t, batch, cfg)
        g_auto = jax.grad(lambda p: model.loss(p, batch, cfg))(t)
        np.testing.assert_allclose(np.asarray(g_closed), np.asarray(g_auto),
                                   rtol=1e-4, atol=1e-5)

    def test_block_size_divisibility_checked(self):
        with pytest.raises(ValueError, match="multiple"):
            get_model(Config(num_feature_dim=1001, model="blocked_lr",
                             block_size=8))

    def test_blocked_matches_scalar_when_groups_are_singletons(self):
        """R=1 blocked is exactly scalar sparse LR (same table, same
        gather semantics) — the layouts only diverge in grouping."""
        cfg = Config(num_feature_dim=512, model="blocked_lr", block_size=1,
                     l2_c=0.0)
        blocked = get_model(cfg)
        scalar = SparseBinaryLR(512)
        rng = np.random.default_rng(3)
        cols = jnp.asarray(rng.integers(0, 512, size=(32, 5)), jnp.int32)
        vals = jnp.asarray(rng.standard_normal((32, 5)), jnp.float32)
        y = jnp.asarray(rng.integers(0, 2, 32), jnp.int32)
        mask = jnp.ones(32, jnp.float32)
        w = jnp.asarray(rng.standard_normal(512), jnp.float32)
        zb = blocked.logits(w[:, None], cols, vals[..., None])
        zs = scalar.logits(w, cols, vals)
        np.testing.assert_allclose(np.asarray(zb), np.asarray(zs), rtol=1e-6)
        gb = blocked.grad(w[:, None], (cols, vals[..., None], y, mask), cfg)
        gs = scalar.grad(w, (cols, vals, y, mask), cfg)
        np.testing.assert_allclose(np.asarray(gb)[:, 0], np.asarray(gs),
                                   rtol=1e-5, atol=1e-6)


def _train_eval(model, cfg, batch_tr, batch_te, steps=500, lr=0.5):
    t = model.init(cfg)
    grad = jax.jit(lambda p: model.grad(p, batch_tr, cfg))
    for _ in range(steps):
        t = t - lr * grad(t)
    return float(model.accuracy(t, batch_te))


class TestBlockedAccuracyGate:
    """The collision/accuracy gate (VERDICT r1 #3).

    Blocked rows are keyed per conjunction, so each row trains on
    n / |tuple space| samples where the scalar path gets n / vocab per
    bucket — a sample-efficiency trade (measured ~4pt on this synthetic
    config, shrinking as tuple recurrence grows) bought for ~R-fold fewer
    gather indices.  Documented in data/hashing.py; these tests pin BOTH
    sides: the bounded loss on purely-additive data, and the capacity WIN
    on interaction data that the scalar path cannot represent at all.
    """

    N_TRAIN, N_TEST, F, VOCAB = 6000, 1500, 8, 4
    GROUPS = np.array([[0, 1, 2, 3], [4, 5, 6, 7]])

    def _ids(self, rng):
        return rng.integers(0, self.VOCAB, size=(self.N_TRAIN + self.N_TEST, self.F))

    def _split(self, a):
        return a[: self.N_TRAIN], a[self.N_TRAIN:]

    def _accs(self, ids, y):
        y_tr, y_te = self._split(y)
        ones = np.ones(self.N_TRAIN, np.float32)
        ones_te = np.ones(self.N_TEST, np.float32)

        cfg_s = Config(num_feature_dim=1024, model="sparse_lr", l2_c=0.0)
        field_ids = np.broadcast_to(np.arange(self.F), ids.shape)
        cols, _ = hash_buckets(ids, 1024, seed=5, field_ids=field_ids)
        cols_tr, cols_te = self._split(cols.astype(np.int32))
        vals = np.ones_like(cols, np.float32)
        vals_tr, vals_te = self._split(vals)
        acc_scalar = _train_eval(
            SparseBinaryLR(1024), cfg_s,
            (jnp.asarray(cols_tr), jnp.asarray(vals_tr), jnp.asarray(y_tr), jnp.asarray(ones)),
            (jnp.asarray(cols_te), jnp.asarray(vals_te), jnp.asarray(y_te), jnp.asarray(ones_te)),
        )

        # blocked: 2 groups of 4; 4096 rows so block collisions are rare
        # (512 live tuples) and the comparison isolates the conjunction
        # parameterization itself
        blocks, lane_vals = hash_group_blocks(ids, self.GROUPS, 4096, seed=5)
        blk_tr, blk_te = self._split(blocks.astype(np.int32))
        lv_tr, lv_te = self._split(lane_vals)
        cfg_b = Config(num_feature_dim=4 * 4096, model="blocked_lr",
                       block_size=4, l2_c=0.0)
        acc_blocked = _train_eval(
            get_model(cfg_b), cfg_b,
            (jnp.asarray(blk_tr), jnp.asarray(lv_tr), jnp.asarray(y_tr), jnp.asarray(ones)),
            (jnp.asarray(blk_te), jnp.asarray(lv_te), jnp.asarray(y_te), jnp.asarray(ones_te)),
        )
        return acc_scalar, acc_blocked

    def test_additive_signal_loss_is_bounded(self):
        """Purely per-field ground truth (scalar hashing's best case):
        the blocked path's sample-efficiency cost must stay within the
        documented band, and it must still clearly learn."""
        rng = np.random.default_rng(42)
        ids = self._ids(rng)
        w_true = (rng.standard_normal((self.F, self.VOCAB)) * 1.5).astype(np.float32)
        logits = w_true[np.arange(self.F)[None, :], ids].sum(-1)
        y = (rng.random(len(ids)) < 1 / (1 + np.exp(-logits))).astype(np.int32)
        oracle_acc = float(((logits > 0) == y).mean())

        acc_scalar, acc_blocked = self._accs(ids, y)
        assert acc_blocked >= acc_scalar - 0.07, (acc_blocked, acc_scalar)
        assert acc_blocked >= oracle_acc - 0.08, (acc_blocked, oracle_acc)
        assert acc_blocked >= 0.75  # far above chance

    def test_interaction_signal_is_a_capacity_win(self):
        """Per-tuple (conjunction) ground truth — the data regime the
        blocked layout exists for: a unigram scalar hash CANNOT represent
        it, the blocked table represents it exactly."""
        rng = np.random.default_rng(7)
        ids = self._ids(rng)
        # one independent weight per (group, value-tuple)
        radix = self.VOCAB ** np.arange(4)
        w_g = (rng.standard_normal((2, self.VOCAB ** 4)) * 2.0).astype(np.float32)
        tuple_ids = np.stack(
            [ids[:, g] @ radix for g in (slice(0, 4), slice(4, 8))], axis=1
        )
        logits = w_g[0, tuple_ids[:, 0]] + w_g[1, tuple_ids[:, 1]]
        y = (rng.random(len(ids)) < 1 / (1 + np.exp(-logits))).astype(np.int32)

        acc_scalar, acc_blocked = self._accs(ids, y)
        assert acc_blocked >= acc_scalar + 0.05, (acc_blocked, acc_scalar)


class TestRawCtrShards:
    """Raw-CTR on-disk format: hash-scheme-agnostic shards + manifest
    (the blocked_lr load path; VERDICT r2 next-round item 2)."""

    def test_write_read_roundtrip(self, tmp_path):
        from distlr_tpu.data.hashing import (
            read_ctr_meta,
            read_raw_ctr_file,
            resolve_ctr_fields,
            write_raw_ctr_shards,
        )

        d = str(tmp_path)
        m = write_raw_ctr_shards(d, 500, 6, 40, 2, seed=9)
        assert m["meta"]["num_fields"] == 6
        assert read_ctr_meta(d)["seed"] == 9
        # provenance: i.i.d. draws record no tuple table
        assert read_ctr_meta(d)["num_distinct_tuples"] is None
        assert resolve_ctr_fields(d, 0) == 6
        assert resolve_ctr_fields(d, 6) == 6  # explicit cfg, agreeing
        # an explicit cfg.ctr_fields that CONTRADICTS the manifest is a
        # config error, surfaced here — not a downstream per-row parse
        # failure (ADVICE r3)
        with pytest.raises(ValueError, match="conflicts with"):
            resolve_ctr_fields(d, 11)
        # without a manifest the explicit value is the only source: wins
        assert resolve_ctr_fields(str(tmp_path / "nometa"), 11) == 11
        ids, y = read_raw_ctr_file(m["train_parts"][0], 6)
        assert ids.shape[1] == 6 and ids.dtype == np.int64
        assert (ids >= 0).all() and (ids < 40).all()
        assert set(np.unique(y)) <= {0, 1}
        # deterministic: rewrite produces identical bytes
        d2 = str(tmp_path / "again")
        m2 = write_raw_ctr_shards(d2, 500, 6, 40, 2, seed=9)
        with open(m["train_parts"][0]) as f1, open(m2["train_parts"][0]) as f2:
            assert f1.read() == f2.read()

    def test_missing_manifest_and_field_mismatch_reject(self, tmp_path):
        from distlr_tpu.data.hashing import (
            read_raw_ctr_file,
            resolve_ctr_fields,
            write_raw_ctr_shards,
        )

        with pytest.raises(FileNotFoundError, match="ctr_meta"):
            resolve_ctr_fields(str(tmp_path), 0)
        m = write_raw_ctr_shards(str(tmp_path), 100, 5, 10, 1)
        with pytest.raises(ValueError, match="fields"):
            read_raw_ctr_file(m["train_parts"][0], 7)
        # too FEW expected fields must also reject (the parser's column
        # filter must not silently truncate a 5-field shard to 3)
        with pytest.raises(ValueError, match="5 fields, expected 3"):
            read_raw_ctr_file(m["train_parts"][0], 3)
        # out-of-range field number with the right row length
        bad = tmp_path / "range"
        bad.write_text("1 1:3 2:4 9:7\n")
        with pytest.raises(ValueError, match="field number 9"):
            read_raw_ctr_file(str(bad), 3)

    def test_malformed_rows_reject(self, tmp_path):
        from distlr_tpu.data.hashing import read_raw_ctr_file

        dup = tmp_path / "dup"
        dup.write_text("1 1:3 1:4 3:7\n")  # field 1 twice, field 2 missing
        with pytest.raises(ValueError, match="repeats a field"):
            read_raw_ctr_file(str(dup), 3)
        neg = tmp_path / "neg"
        neg.write_text("1 1:3 2:-4 3:7\n")
        with pytest.raises(ValueError, match="non-negative"):
            read_raw_ctr_file(str(neg), 3)
        frac = tmp_path / "frac"
        frac.write_text("1 1:3.7 2:4 3:7\n")
        with pytest.raises(ValueError, match="integers"):
            read_raw_ctr_file(str(frac), 3)
        # ids at/above 2^24 were already rounded in the float32 value
        # slot — the reader must mirror the writer's bound (ADVICE r3)
        big = tmp_path / "big"
        big.write_text(f"1 1:3 2:4 3:{1 << 24}\n")
        with pytest.raises(ValueError, match="exact-integer range"):
            read_raw_ctr_file(str(big), 3)
        ok = tmp_path / "ok"
        ok.write_text(f"1 1:3 2:4 3:{(1 << 24) - 1}\n")
        ids, _ = read_raw_ctr_file(str(ok), 3)
        assert ids[0, 2] == (1 << 24) - 1

    def test_negative_hash_seed_rejected_at_config(self):
        with pytest.raises(ValueError, match="hash_seed"):
            Config(hash_seed=-1)

    def test_vocab_beyond_float32_exact_range_rejects(self, tmp_path):
        from distlr_tpu.data.hashing import write_raw_ctr_shards

        with pytest.raises(ValueError, match="2\\^24"):
            write_raw_ctr_shards(str(tmp_path), 10, 2, 1 << 24, 1)

    def test_blocked_quantization_rejected(self):
        with pytest.raises(ValueError, match="dense models only"):
            Config(model="blocked_lr", feature_dtype="int8")


def _gen_blocked_dir(tmp_path, n=4000, parts=2, seed=1):
    from distlr_tpu.data.hashing import write_raw_ctr_shards

    d = str(tmp_path / "data")
    # vocab 4, groups of 4 -> 256 tuples: high recurrence, blocked learns
    write_raw_ctr_shards(d, n, 8, 4, parts, seed=seed)
    return d


def _blocked_cfg(d, **kw):
    kw.setdefault("num_iteration", 12)
    kw.setdefault("batch_size", 256)
    kw.setdefault("test_interval", 6)
    return Config(model="blocked_lr", num_feature_dim=4096, block_size=4,
                  data_dir=d, learning_rate=0.5, l2_c=0.0, **kw)


class TestBlockedEndToEnd:
    """blocked_lr trainable from shards on disk, in every mode."""

    def test_sync_trainer_from_disk(self, tmp_path):
        from distlr_tpu.train import Trainer

        tr = Trainer(_blocked_cfg(_gen_blocked_dir(tmp_path))).load_data()
        tr.fit()
        assert tr.evaluate() >= 0.70
        path = tr.save_model()
        from distlr_tpu.train.export import load_model_text

        w = load_model_text(path)
        assert w.size == 4096

    def test_ps_sync_matches_sync_trainer(self, tmp_path):
        """Keyed row Push/Pull (2 workers x 2 servers) reproduces the
        SPMD trainer's trajectory: same shards, full-batch, l2=0."""
        from distlr_tpu.train import Trainer
        from distlr_tpu.train.ps_trainer import run_ps_local

        d = _gen_blocked_dir(tmp_path, n=1200, parts=2)
        cfg = _blocked_cfg(d, num_iteration=4, batch_size=-1,
                           num_workers=2, num_servers=2, test_interval=0)
        ws = run_ps_local(cfg, save=False)
        assert all(np.array_equal(ws[0], w) for w in ws)

        tr = Trainer(cfg.replace(mesh_shape={"data": 2})).load_data()
        w_sync = np.asarray(tr.fit()).reshape(-1)
        np.testing.assert_allclose(ws[0], w_sync, rtol=2e-4, atol=2e-5)

    def test_ps_async_converges(self, tmp_path):
        from distlr_tpu.train.ps_trainer import run_ps_local

        d = _gen_blocked_dir(tmp_path, n=2400, parts=2)
        evals = []
        cfg = _blocked_cfg(d, sync_mode=False, num_workers=2, num_servers=2,
                           num_iteration=10, test_interval=5)
        run_ps_local(cfg, save=False,
                     eval_fn=lambda ep, acc: evals.append((ep, acc)))
        assert evals and evals[-1][1] >= 0.65

    def test_launch_cli_gen_and_sync(self, tmp_path):
        from distlr_tpu import launch

        d = str(tmp_path / "cli")
        rc = launch.main([
            "gen-data", "--data-dir", d, "--num-samples", "1500",
            "--ctr-fields", "8", "--ctr-vocab", "4", "--ctr-raw",
            "--num-parts", "2", "--seed", "3",
        ])
        assert rc == 0
        rc = launch.main([
            "sync", "--data-dir", d, "--model", "blocked_lr",
            "--num-feature-dim", "4096", "--block-size", "4",
            "--num-iteration", "6", "--batch-size", "256",
            "--learning-rate", "0.5", "--l2-c", "0", "--test-interval", "3",
        ])
        assert rc == 0

    def test_ctr_raw_requires_fields(self, capsys):
        from distlr_tpu import launch

        rc = launch.main(["gen-data", "--data-dir", "/tmp/x", "--ctr-raw"])
        assert rc == 2


class TestSuggestBlockSize:
    """The data-driven advisor distilled from the frontier an earlier capture
    measured (its script is gone): every case below is
    one of the frontier's regimes, asserted to land where the
    measurement said quality lands."""

    def _regime(self, n, seed=7, **kw):
        from distlr_tpu.data.hashing import make_ctr_dataset

        raw, *_ = make_ctr_dataset(n, 21, num_buckets=64, seed=seed, **kw)
        return raw

    def test_high_cardinality_iid_gets_scalar(self):
        from distlr_tpu.data.hashing import suggest_block_size

        raw = self._regime(50_000, vocab_size=10_000_000)
        assert suggest_block_size(raw, 1_000_000) == 1  # tuples never recur

    def test_correlated_tuples_at_frontier_buckets_gets_16(self):
        """The exact measured shape: 512 tuples, dc=16384 — R=32 lost
        9pt there (single-group collisions at row load 1.0), R=16 held
        within 0.4pt; the advisor must split them the same way."""
        from distlr_tpu.data.hashing import suggest_block_size

        raw = self._regime(49_152, vocab_size=50, num_distinct_tuples=512)
        assert suggest_block_size(raw, 16384) == 16

    def test_correlated_tuples_with_room_gets_32(self):
        """Same recurrence but a 1M-bucket table: 512 tuples into
        31250 rows is load ~0.016 — the single-group failure mode is
        gone and the fastest R wins."""
        from distlr_tpu.data.hashing import suggest_block_size

        raw = self._regime(49_152, vocab_size=50, num_distinct_tuples=512)
        assert suggest_block_size(raw, 1_000_000) == 32

    def test_single_group_needs_near_zero_load(self):
        """The r5 operating-point anchor: 512 correlated tuples at
        dc=65536 put single-group R=32 at row load 0.25, where it
        measured -3.8pt (no redundancy to absorb collisions at G=1) —
        the advisor must step down to R=16 (G=2, measured +0.5pt
        there); only ~zero load (dc=1M, 0.016, measured +0.2pt)
        green-lights the single group."""
        from distlr_tpu.data.hashing import suggest_block_size

        raw = self._regime(49_152, vocab_size=50, num_distinct_tuples=512)
        assert suggest_block_size(raw, 65536) == 16

    def test_sparse_recurrence_rejected(self):
        """~2 samples/tuple (the quick-mode frontier that degraded
        everywhere): recurrence below threshold at every R."""
        from distlr_tpu.data.hashing import suggest_block_size

        raw = self._regime(1_000, vocab_size=50, num_distinct_tuples=512)
        assert suggest_block_size(raw, 1_000_000) == 1

    def test_thresholds_are_overridable(self):
        from distlr_tpu.data.hashing import suggest_block_size

        raw = self._regime(1_000, vocab_size=50, num_distinct_tuples=512)
        assert suggest_block_size(raw, 1_000_000, min_recurrence=1.0) == 32

    def test_block_size_auto_cli_end_to_end(self, tmp_path):
        """--block-size auto: low-vocab raw shards (two 8-field groups,
        2^8 tuples each recurring ~78x at 20k rows) resolve to R=8 and
        train through the normal sync path; the single-group R=16/32
        candidates are rejected (2^16 tuples never recur, and G=1 needs
        row load <= 0.1 per the measured operating-point anchors).
        Config forbids unresolved 0 elsewhere."""
        import pytest

        from distlr_tpu import Config, launch
        from distlr_tpu.data.hashing import resolve_auto_block_size

        d = str(tmp_path / "auto")
        rc = launch.main([
            "gen-data", "--data-dir", d, "--num-samples", "20000",
            "--ctr-fields", "16", "--ctr-vocab", "2", "--ctr-raw",
            "--num-parts", "1", "--seed", "5",
        ])
        assert rc == 0
        assert resolve_auto_block_size(d, 0, 4096) == (8, 0)
        rc = launch.main([
            "sync", "--data-dir", d, "--model", "blocked_lr",
            "--num-feature-dim", "4096", "--block-size", "auto",
            "--num-iteration", "3", "--batch-size", "512",
            "--learning-rate", "0.5", "--l2-c", "0", "--test-interval", "0",
        ])
        assert rc == 0
        with pytest.raises(ValueError, match="auto"):
            Config(model="sparse_lr", num_feature_dim=64, block_size=0)
        with pytest.raises(ValueError, match="resolved"):
            from distlr_tpu.models import get_model
            get_model(Config(model="blocked_lr", num_feature_dim=4096,
                             block_size=0))

    def test_block_size_auto_ps_mode(self, tmp_path):
        """PS mode resolves --block-size auto too (same helper, applied
        in cmd_ps); the keyed blocked path then trains end to end."""
        from distlr_tpu import launch

        d = str(tmp_path / "auto_ps")
        rc = launch.main([
            "gen-data", "--data-dir", d, "--num-samples", "20000",
            "--ctr-fields", "16", "--ctr-vocab", "2", "--ctr-raw",
            "--num-parts", "2", "--seed", "5",
        ])
        assert rc == 0
        rc = launch.main([
            "ps", "--data-dir", d, "--model", "blocked_lr",
            "--num-feature-dim", "4096", "--block-size", "auto",
            "--num-iteration", "2", "--batch-size", "1024",
            "--learning-rate", "0.5", "--l2-c", "0", "--test-interval", "0",
            "--num-workers", "2", "--num-servers", "1",
        ])
        assert rc == 0


class TestBlockGroups:
    """cfg.block_groups / --block-groups: explicit conjunction-group
    counts (r5).  The motivation is an operating-point sweep of the
    earlier capture; these tests pin the layout, the statistical
    direction, and the end-to-end plumbing."""

    def test_split_field_groups_layouts(self):
        import numpy as np

        from distlr_tpu.data.hashing import (
            default_field_groups,
            split_field_groups,
        )

        # num_groups=0 is bit-identical to the historical default, so
        # existing data hashes identically
        np.testing.assert_array_equal(
            split_field_groups(21, 16, 0), default_field_groups(21, 16))
        # ... and so is num_groups == ceil(F/R): one canonical layout
        # per (F, R, G) triple, so the advisor's G->0 normalization and
        # an explicit --block-groups ceil(F/R) hash identically
        np.testing.assert_array_equal(
            split_field_groups(21, 8, 3), default_field_groups(21, 8))
        np.testing.assert_array_equal(
            split_field_groups(21, 16, 2), default_field_groups(21, 16))
        g3 = split_field_groups(21, 32, 3)
        assert g3.shape == (3, 32)
        members = [g[g >= 0] for g in g3]
        assert [len(m) for m in members] == [7, 7, 7]
        np.testing.assert_array_equal(np.concatenate(members), np.arange(21))
        import pytest

        with pytest.raises(ValueError, match="outside"):
            split_field_groups(21, 32, -1)
        with pytest.raises(ValueError, match="outside"):
            split_field_groups(21, 8, 2)  # 2 groups can't hold 21 fields at R=8
        with pytest.raises(ValueError, match="outside"):
            split_field_groups(21, 32, 22)  # more groups than fields

    def test_g3_rescues_low_card_iid_direction(self):
        """Statistical direction at small scale (mirrors the quick
        operating-point sweep): on low-cardinality i.i.d. fields the
        3-group R=32 layout must clearly beat the single-group one
        (tuple spaces 2^7 recur; 2^21 never do)."""
        import jax.numpy as jnp
        import numpy as np

        from distlr_tpu import Config
        from distlr_tpu.data.hashing import (
            hash_group_blocks,
            make_ctr_dataset,
            split_field_groups,
        )
        from distlr_tpu.models import BlockedSparseLR

        dc, n_tr, n_te, steps = 4096, 6000, 1500, 120
        raw, _c, _v, y, _w = make_ctr_dataset(
            n_tr + n_te, 21, vocab_size=2, num_buckets=dc, seed=7,
            center_logits=True)
        accs = {}
        for g in (1, 3):
            nb = dc // 32
            groups = split_field_groups(21, 32, g)
            blocks, lv = hash_group_blocks(raw, groups, nb, seed=7)
            cfg = Config(num_feature_dim=dc, model="blocked_lr",
                         block_size=32, learning_rate=1.0, l2_c=0.0)
            m = BlockedSparseLR(nb, 32)
            import jax

            @jax.jit
            def step(t, b):
                return t - 1.0 * m.grad(t, b, cfg)

            tr = (jnp.asarray(blocks[n_te:].astype(np.int32)),
                  jnp.asarray(lv[n_te:]), jnp.asarray(y[n_te:]),
                  jnp.ones(n_tr, jnp.float32))
            te = (jnp.asarray(blocks[:n_te].astype(np.int32)),
                  jnp.asarray(lv[:n_te]), jnp.asarray(y[:n_te]),
                  jnp.ones(n_te, jnp.float32))
            t = jnp.zeros((nb, 32), jnp.float32)
            for _ in range(steps):
                t = step(t, tr)
            accs[g] = float(m.accuracy(t, te))
        # measured at these shapes: g1 ~0.47 (memorizing never-recurring
        # 21-field tuples), g3 ~0.65; wide margin so seed drift can't flake
        assert accs[3] > accs[1] + 0.05, accs

    def test_cli_block_groups_end_to_end(self, tmp_path):
        """gen-data --ctr-tuples writes tuple-recurrent raw shards; sync
        and PS runs train blocked_lr with --block-groups 3 end to end."""
        from distlr_tpu import launch

        d = str(tmp_path / "bg")
        rc = launch.main([
            "gen-data", "--data-dir", d, "--num-samples", "6000",
            "--ctr-fields", "21", "--ctr-vocab", "50", "--ctr-raw",
            "--ctr-tuples", "64", "--num-parts", "2", "--seed", "5",
        ])
        assert rc == 0
        rc = launch.main([
            "sync", "--data-dir", d, "--model", "blocked_lr",
            "--num-feature-dim", "4096", "--block-size", "32",
            "--block-groups", "3", "--num-iteration", "3",
            "--batch-size", "512", "--learning-rate", "0.5", "--l2-c", "0",
            "--test-interval", "0",
        ])
        assert rc == 0
        rc = launch.main([
            "ps", "--data-dir", d, "--model", "blocked_lr",
            "--num-feature-dim", "4096", "--block-size", "32",
            "--block-groups", "3", "--num-iteration", "2",
            "--batch-size", "512", "--learning-rate", "0.5", "--l2-c", "0",
            "--test-interval", "2", "--num-workers", "2", "--num-servers", "1",
        ])
        assert rc == 0

    def test_config_rejects_block_groups_off_family(self):
        import pytest

        from distlr_tpu import Config

        with pytest.raises(ValueError, match="block_groups"):
            Config(model="binary_lr", num_feature_dim=64, block_groups=2)
        with pytest.raises(ValueError, match="block_groups"):
            Config(model="blocked_lr", num_feature_dim=64, block_size=8,
                   block_groups=-1)

    def test_gen_data_tuples_requires_raw(self, capsys):
        from distlr_tpu import launch

        rc = launch.main([
            "gen-data", "--data-dir", "/tmp/nope", "--num-samples", "100",
            "--ctr-fields", "8", "--ctr-tuples", "16",
        ])
        assert rc == 2


class TestSuggestBlocking:
    """Joint (R, G) advisor: same measured gates as suggest_block_size,
    candidates ordered by gather cost (fewest groups, then fewest
    lanes), evaluated on the grouping actually trained."""

    def _regime(self, n, seed=7, **kw):
        from distlr_tpu.data.hashing import make_ctr_dataset

        raw, *_ = make_ctr_dataset(n, 21, num_buckets=64, seed=seed, **kw)
        return raw

    def test_matches_default_advisor_where_defaults_win(self):
        from distlr_tpu.data.hashing import suggest_blocking

        # correlated tuples with a 1M-row table: single-group R=32 at
        # ~zero load, same as suggest_block_size
        raw = self._regime(49_152, vocab_size=50, num_distinct_tuples=512)
        assert suggest_blocking(raw, 1_000_000) == (32, 0)
        # at dc=65536 the single group fails its load gate; the G=2
        # layouts pass and R=16 fetches fewer lanes than R=32
        assert suggest_blocking(raw, 65536) == (16, 0)

    def test_finds_multi_group_layout_default_advisor_finds(self):
        from distlr_tpu.data.hashing import (
            suggest_block_size,
            suggest_blocking,
        )

        # low-cardinality iid fields: only 3-group layouts recur (2^7
        # tuples); cheapest is R=8 = the default ceil(21/8)=3 chunking
        raw = self._regime(49_152, vocab_size=2)
        assert suggest_blocking(raw, 1_000_000) == (8, 0)
        assert suggest_block_size(raw, 1_000_000) == 8  # agreement

    def test_pinned_groups_searches_r_only(self):
        from distlr_tpu.data.hashing import suggest_blocking

        raw = self._regime(49_152, vocab_size=2)
        # G pinned to 3: R=8's default grouping IS 3 groups -> normalized
        r, g = suggest_blocking(raw, 1_000_000, num_groups=3)
        assert (r, g) == (8, 0)
        # G pinned to 1: no single 21-field conjunction recurs -> scalar
        assert suggest_blocking(raw, 1_000_000, num_groups=1) == (1, 0)

    def test_scalar_fallback_on_hostile_data(self):
        from distlr_tpu.data.hashing import suggest_blocking

        raw = self._regime(50_000, vocab_size=10_000_000)
        assert suggest_blocking(raw, 1_000_000) == (1, 0)

    def test_wide_field_default_layouts_always_searched(self):
        """max_groups bounds only the EXTRA gathers: with 40 fields the
        R=8 default chunking is 5 groups (> max_groups=4), and it is
        the only layout whose tuple spaces (2^8) recur on vocab-2 data
        — auto must find it, not silently fall back to scalar (r5
        review finding)."""
        from distlr_tpu.data.hashing import make_ctr_dataset, suggest_blocking

        raw, *_ = make_ctr_dataset(20_000, 40, vocab_size=2,
                                   num_buckets=64, seed=7)
        assert suggest_blocking(raw, 1_000_000) == (8, 0)

    def test_infeasible_pinned_groups_raise(self):
        """A pinned G no candidate R can realize is a config error, not
        a data statistic — it must raise, not silently train scalar."""
        from distlr_tpu.data.hashing import suggest_blocking

        raw = self._regime(5_000, vocab_size=50, num_distinct_tuples=64)
        with pytest.raises(ValueError, match="infeasible"):
            suggest_blocking(raw, 1_000_000, num_groups=25)  # > 21 fields

    def test_auto_with_pinned_groups_cli(self, tmp_path):
        """--block-size auto --block-groups G resolves through the
        grouping actually trained (r5 review finding: auto used to
        validate the default grouping and could then crash on an
        incompatible pinned G)."""
        from distlr_tpu import launch

        d = str(tmp_path / "autog")
        rc = launch.main([
            "gen-data", "--data-dir", d, "--num-samples", "20000",
            "--ctr-fields", "21", "--ctr-vocab", "2", "--ctr-raw",
            "--num-parts", "1", "--seed", "5",
        ])
        assert rc == 0
        rc = launch.main([
            "sync", "--data-dir", d, "--model", "blocked_lr",
            "--num-feature-dim", "4096", "--block-size", "auto",
            "--block-groups", "3", "--num-iteration", "2",
            "--batch-size", "512", "--learning-rate", "0.5", "--l2-c", "0",
            "--test-interval", "0",
        ])
        assert rc == 0
