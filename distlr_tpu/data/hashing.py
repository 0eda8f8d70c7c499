"""Feature hashing (the "hashing trick") for CTR-scale workloads.

The reference caps out at dense feature vectors whose dimension is fixed by
``NUM_FEATURE_DIM`` (``examples/local.sh:14``) — its north-star scaling
path, per BASELINE.json configs 3-4 (Criteo hashed-to-dense 1M features,
Avazu sparse one-hot), needs categorical features of unbounded vocabulary
hashed into a fixed bucket space.  This module provides:

* a vectorized 64-bit mixer (splitmix64) — deterministic, seed-parameterized,
  numpy-only, no Python-object hashing (``hash()`` is salted per process);
* CSR -> hashed padded-COO / hashed dense conversion, feeding either the
  ``SparseBinaryLR`` segment_sum path or the dense MXU path;
* a deterministic synthetic CTR generator (fields x vocab -> one active
  value per field) with ground-truth weights *in bucket space*, so
  convergence tests can assert signal recovery after hashing collisions;
* a reference-layout shard writer (one-hot libsvm rows over bucket ids),
  so the whole existing libsvm pipeline (native parser, sharding,
  trainer) runs unchanged on hashed CTR data.

Sign hashing (Weinberger et al.'s +/-1 trick) is supported to de-bias
collision noise: ``val = sign(h') * raw_val``.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np

__all__ = [
    "splitmix64",
    "hash_buckets",
    "hash_group_blocks",
    "default_field_groups",
    "split_field_groups",
    "encode_blocked",
    "suggest_block_size",
    "suggest_blocking",
    "resolve_auto_block_size",
    "HashedFeatureEncoder",
    "csr_to_padded_coo",
    "make_ctr_dataset",
    "write_ctr_shards",
    "write_raw_ctr_shards",
    "read_raw_ctr_file",
    "read_ctr_meta",
]

_U64 = np.uint64


def splitmix64(x: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 finalizer: uint64 array -> uint64 array.

    Full-avalanche integer mixer (each input bit flips ~half the output
    bits) — the standard seed-expander of the xoshiro family.
    """
    x = x.astype(_U64, copy=True)
    with np.errstate(over="ignore"):
        x += _U64(0x9E3779B97F4A7C15)
        z = x
        z = (z ^ (z >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> _U64(27))) * _U64(0x94D049BB133111EB)
        z = z ^ (z >> _U64(31))
    return z


def hash_buckets(ids: np.ndarray, num_buckets: int, *, seed: int = 0, field_ids=None):
    """Hash integer feature ids into ``[0, num_buckets)``.

    ``field_ids`` (same shape or broadcastable) namespaces ids per
    categorical field so value 7 in field 0 and value 7 in field 1 land in
    independent buckets.  Returns ``(buckets, signs)`` where ``signs`` is
    the +/-1 sign-hash (float32) derived from an independent bit of the
    same mix.
    """
    h = np.asarray(ids, dtype=np.int64).astype(_U64)
    if field_ids is not None:
        with np.errstate(over="ignore"):
            h = h + splitmix64(np.asarray(field_ids, dtype=np.int64).astype(_U64) + _U64(0x51))
    with np.errstate(over="ignore"):
        h = splitmix64(h + splitmix64(np.full_like(h, _U64(seed))))
    buckets = (h % _U64(num_buckets)).astype(np.int64)
    # bit 63 is independent of the modulus for num_buckets << 2^63
    signs = np.where((h >> _U64(63)).astype(bool), np.float32(1.0), np.float32(-1.0))
    return buckets, signs


def hash_group_blocks(raw_ids, field_groups, num_blocks: int, *, seed: int = 0,
                      raw_vals=None):
    """Row-aligned ("blocked") hashing: field groups -> block-row ids.

    TPU gathers amortize their per-index cost over contiguous elements,
    but that only pays off if the fetched lanes are all used —
    which requires co-locating several of a sample's features in ONE
    table row.  Per-field buckets cannot co-locate (each field's value
    picks an independent bucket), so this scheme hashes a GROUP of R
    fields jointly: the group's value tuple selects the block row, and
    lane j holds the learned weight of member field j under that
    conjunction.  One R-wide row gather then replaces R scalar gathers.

    The statistical trade (documented, opt-in): weights are per
    (conjunction, field) instead of per field — rows are trained only
    when their exact value tuple recurs, so group LOW-CARDINALITY fields
    (tuple space small enough to recur in training data) and keep
    high-cardinality fields on the scalar `hash_buckets` path.

    Args:
      raw_ids: (N, F) integer categorical values.
      field_groups: sequence of equal-length field-index tuples; use -1
        to pad a short group (its lane contributes value 0).
      num_blocks: table rows; total params = num_blocks * R.
      raw_vals: optional (N, F) float values (default one-hot 1.0).

    Returns ``(blocks, lane_vals)``: (N, G) int64 block ids and
    (N, G, R) float32 per-lane values.
    """
    raw_ids = np.asarray(raw_ids, dtype=np.int64)
    groups = np.asarray(field_groups, dtype=np.int64)
    if groups.ndim != 2:
        raise ValueError("field_groups must be a (G, R) array of field indices")
    n, _ = raw_ids.shape
    g_count, r = groups.shape
    pad = groups < 0
    safe = np.where(pad, 0, groups)
    vals_f = (np.ones_like(raw_ids, dtype=np.float32) if raw_vals is None
              else np.asarray(raw_vals, dtype=np.float32))
    member_ids = raw_ids[:, safe.reshape(-1)].reshape(n, g_count, r)
    lane_vals = vals_f[:, safe.reshape(-1)].reshape(n, g_count, r).copy()
    lane_vals[:, pad] = 0.0

    # Conjunction key: fold member (field, value) mixes in lane order so
    # the tuple (not the multiset) is keyed; padded lanes fold a constant.
    key = np.full((n, g_count), _U64(seed), dtype=_U64)
    with np.errstate(over="ignore"):
        key = splitmix64(key)
        for j in range(r):
            fj = np.where(pad[:, j], _U64(0xD1F), safe[:, j].astype(_U64))
            vj = np.where(pad[None, :, j], _U64(0), member_ids[:, :, j].astype(_U64))
            key = splitmix64(key ^ splitmix64(vj + splitmix64(fj + _U64(0x9E))))
    blocks = (key % _U64(num_blocks)).astype(np.int64)
    return blocks, lane_vals


def default_field_groups(num_fields: int, block_size: int) -> np.ndarray:
    """Consecutive grouping: fields 0..F-1 chunked into ceil(F/R) groups
    of R, the last padded with -1.

    The grouping is a statistical knob (co-hashed fields share a
    conjunction key — see :func:`hash_group_blocks`); consecutive chunks
    are the neutral default when no field-cardinality information exists.
    """
    g_count = -(-num_fields // block_size)
    groups = np.full((g_count, block_size), -1, dtype=np.int64)
    flat = groups.reshape(-1)
    flat[:num_fields] = np.arange(num_fields)
    return groups


def split_field_groups(num_fields: int, block_size: int,
                       num_groups: int = 0) -> np.ndarray:
    """Field grouping with an explicit group count.

    ``num_groups=0`` (the default everywhere) keeps the historical
    :func:`default_field_groups` layout — consecutive R-sized chunks —
    so existing data hashes identically.  ``num_groups == ceil(F/R)``
    returns that SAME default layout (one canonical grouping per
    (F, R, G) triple — the advisor's normalization of G to 0 and an
    explicit ``--block-groups ceil(F/R)`` must hash identically, or a
    model trained one way and evaluated the other silently scores
    garbage).  Larger ``num_groups=G`` splits the fields into G
    near-equal consecutive groups, each padded to R lanes: the
    intermediate groupings between ceil(F/R) chunks and one all-fields
    conjunction.  Motivation (an operating-point sweep of the earlier
    capture, not measured since): on low-cardinality i.i.d. fields the single-group R=32
    layout loses accuracy (21-field tuples never recur) while the SAME R
    at G=3 stays close to scalar hashing — extra groups trade one extra
    row gather per sample for tuple spaces small enough to recur.
    """
    g_min = -(-num_fields // block_size)
    if num_groups in (0, None) or num_groups == g_min:
        return default_field_groups(num_fields, block_size)
    g = int(num_groups)
    if g < g_min or g > num_fields:
        raise ValueError(
            f"num_groups={g} outside [{g_min}, {num_fields}] for "
            f"{num_fields} fields at block_size={block_size} (each group "
            f"holds at most {block_size} fields, at least 1)"
        )
    groups = np.full((g, block_size), -1, dtype=np.int64)
    bounds = np.linspace(0, num_fields, g + 1).astype(int)
    for i in range(g):
        m = bounds[i + 1] - bounds[i]
        groups[i, :m] = np.arange(bounds[i], bounds[i + 1])
    return groups


def suggest_block_size(raw_ids, num_buckets: int,
                       candidates: tuple[int, ...] = (32, 16, 8),
                       *,
                       min_recurrence: float = 32.0,
                       max_row_load: float = 0.5,
                       max_row_load_single: float = 0.1) -> int:
    """Data-driven block-size advisor: the largest candidate R whose
    conjunction groups would actually TRAIN on this data, else 1
    (scalar hashing).

    Row-blocked hashing (:func:`hash_group_blocks`) keys table rows per
    (field-group, value-tuple), so it only learns where tuples recur
    and rows don't collide.  The frontier an earlier capture
    measured (its script and records are gone; not measured since): at 512
    distinct tuples recurring ~96x, R=16 holds accuracy within 0.4pt
    of scalar hashing at 3.4x its throughput, while R=32 loses ~9pt
    because 512 tuples into D/32 rows is load factor 1 (birthday
    collisions) — and on high-cardinality i.i.d. fields every R fails
    (tuples never recur).  This function checks exactly those two
    failure modes on a sample of real rows:

      recurrence  min over groups of  N / distinct(group tuples)
                  must be >= ``min_recurrence`` (rows are trained per
                  tuple; each needs enough label observations)
      collision   total distinct tuples / (D/R table rows), discounted
      exposure    by the group count G, must be <= ``max_row_load``
                  when G >= 2, and <= ``max_row_load_single`` when the
                  candidate puts ALL fields in one group.  A colliding
                  row averages unrelated conjunctions, but with G >= 2
                  the other groups' rows partially compensate, so
                  corruption scales well below 1/G; at G=1 the row IS
                  the whole logit and there is no redundancy to absorb
                  it.  Measured anchors (equal-param frontier + r5
                  operating-point sweep, correlated-tuples regime):
                  G=2 at row load 1.0 held within 0.4pt, while G=1
                  lost 9.5pt at load 1.0, still lost 3.8pt at load
                  0.25, and only reached parity (+0.2pt) at load
                  0.016 — hence the much stricter single-group bound.

    Recurrence is necessary, not sufficient: purely additive signal
    with no field interactions can still favor scalar hashing by a
    point or two (the low-cardinality i.i.d. row of the frontier held
    R=8 at -2.3pt despite 192x recurrence), so treat the suggestion as
    a starting point and validate with eval metrics.  Pass a
    representative sample (1e5 rows is plenty — both statistics
    concentrate); N below is the sample size, so thresholds are
    computed against the sample, not the full dataset.
    """
    raw_ids = np.asarray(raw_ids, dtype=np.int64)
    n, num_fields = raw_ids.shape
    if n == 0:
        raise ValueError(
            "suggest_block_size needs a non-empty sample of raw rows"
        )
    for r in sorted(candidates, reverse=True):
        groups = default_field_groups(num_fields, r)
        if _grouping_passes(n, _distinct_group_tuples(raw_ids, groups),
                            num_buckets, r, min_recurrence, max_row_load,
                            max_row_load_single):
            return r
    return 1


def _distinct_group_tuples(raw_ids, groups) -> list[int]:
    """Distinct value-tuple count per group (the advisor's raw stat)."""
    return [len(np.unique(raw_ids[:, g[g >= 0]], axis=0)) for g in groups]


def _grouping_passes(n: int, distinct: list[int], num_buckets: int, r: int,
                     min_recurrence: float, max_row_load: float,
                     max_row_load_single: float) -> bool:
    """The advisor's two statistical gates, evaluated on an explicit
    grouping's distinct-tuple counts (shared by
    :func:`suggest_block_size` and :func:`suggest_blocking` so the
    measured thresholds live once)."""
    recurrence = n / max(distinct)
    load = sum(distinct) / max(num_buckets // r, 1)
    load_ok = (load <= max_row_load_single if len(distinct) == 1
               else load / len(distinct) <= max_row_load)
    return recurrence >= min_recurrence and load_ok


def suggest_blocking(raw_ids, num_buckets: int,
                     r_candidates: tuple[int, ...] = (32, 16, 8),
                     *,
                     num_groups: int = 0,
                     max_groups: int = 4,
                     min_recurrence: float = 32.0,
                     max_row_load: float = 0.5,
                     max_row_load_single: float = 0.1) -> tuple[int, int]:
    """Joint (block_size, block_groups) advisor: the cheapest layout
    whose conjunction groups would actually train, else ``(1, 0)``
    (scalar hashing).

    Generalizes :func:`suggest_block_size` over explicit group counts
    (:func:`split_field_groups`): candidates are ordered by gather cost
    — fewest groups first (each group is one row gather per sample,
    the dominant cost on the measured gather-bound step), then smallest
    fitting R (fewer lanes fetched).  Each candidate is gated by the
    same recurrence/row-load thresholds as :func:`suggest_block_size`,
    evaluated on the grouping ACTUALLY trained — this is what lets the
    advisor find e.g. (R=8, 3 default groups) on low-cardinality
    i.i.d. fields where every single-group layout fails, or step down
    to more groups when a wide single group would collide.

    ``num_groups > 0`` pins the user's group count and only searches R
    (the ``--block-size auto --block-groups G`` path).  ``max_groups``
    bounds the EXTRA groups the unpinned search will spend; the default
    ceil(F/R) chunking of every candidate R is always searched
    regardless, so wide-field data never loses a layout the plain
    :func:`suggest_block_size` would have tried.  The returned group
    count is normalized to 0 when it equals the default ceil(F/R)
    chunking, keeping resolved configs canonical.
    """
    raw_ids = np.asarray(raw_ids, dtype=np.int64)
    n, num_fields = raw_ids.shape
    if n == 0:
        raise ValueError("suggest_blocking needs a non-empty sample of raw rows")
    rs = sorted(r_candidates)
    if num_groups:
        g_values = [int(num_groups)]
    else:
        # 1..max_groups bounds the EXTRA gathers auto may spend, but the
        # default ceil(F/R) chunking of every candidate R must always be
        # searched — otherwise wide-field data (F > max_groups * min R)
        # would silently lose layouts the plain R advisor always tried
        g_values = sorted(
            set(range(1, min(max_groups, num_fields) + 1))
            | {-(-num_fields // r) for r in rs}
        )
    # distinct-tuple counts depend only on group MEMBERSHIP, which many
    # (r, g) candidates share — memoize so the np.unique sorts (the
    # advisor's entire cost on a 100k-row sample) run once per layout
    # key must include the shape: a (2, 8) and a (1, 16) grouping over
    # fields 0..15 serialize to identical bytes
    memo: dict[tuple, list[int]] = {}

    def distinct_of(groups) -> list[int]:
        key = (groups.shape, groups.tobytes())
        if key not in memo:
            memo[key] = _distinct_group_tuples(raw_ids, groups)
        return memo[key]

    any_feasible = False
    for g in g_values:
        for r in rs:
            if r * g < num_fields or g > num_fields:
                continue  # G groups of <= R lanes cannot hold every field
            any_feasible = True
            groups = split_field_groups(num_fields, r, g)
            if _grouping_passes(n, distinct_of(groups), num_buckets, r,
                                min_recurrence, max_row_load,
                                max_row_load_single):
                return r, (0 if g == -(-num_fields // r) else g)
    if num_groups and not any_feasible:
        # A pinned G that no candidate R can realize is a config error,
        # not a data statistic — raise like split_field_groups would,
        # instead of silently training scalar with a misleading log.
        raise ValueError(
            f"block_groups={int(num_groups)} is infeasible for "
            f"{num_fields} fields with block-size candidates {tuple(rs)} "
            f"(need ceil(fields/G) <= R and G <= fields)"
        )
    return 1, 0


def resolve_auto_block_size(data_dir: str, ctr_fields: int, num_buckets: int,
                            *, sample_rows: int = 100_000,
                            num_groups: int = 0) -> tuple[int, int]:
    """Resolve ``block_size=0`` ("auto") for a raw-CTR data dir: run
    :func:`suggest_blocking` on a sample of the first train shard and
    return ``(block_size, block_groups)`` (``block_groups`` 0 = default
    ceil(F/R) chunking; ``(1, 0)`` = scalar fallback).  ``num_groups``
    pins an explicit ``--block-groups`` so the advisor validates the
    grouping actually trained.  Requires raw shards on disk — auto
    cannot work on pre-encoded or injected data (the raw categorical
    ids are gone by then)."""
    from distlr_tpu.data.sharding import part_name  # noqa: PLC0415

    path = os.path.join(data_dir, "train", part_name(0))
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"block_size=0 (auto) needs raw-CTR shards to sample; no "
            f"{path} — pass an explicit --block-size instead"
        )
    num_fields = resolve_ctr_fields(data_dir, ctr_fields)
    # Representative sample: stride line reads across the WHOLE shard
    # (row count estimated from file size) instead of taking the head —
    # time-/user-ordered CTR logs cluster identical tuples, so a head
    # sample over-counts recurrence and can green-light exactly the
    # too-wide R the advisor exists to reject.  Striding parses only
    # ~sample_rows rows regardless of shard size.
    import itertools  # noqa: PLC0415

    with open(path, "rb") as f:
        probe = list(itertools.islice(f, 200))
    if not probe:
        raise ValueError(
            f"{path} is empty; cannot sample for block_size auto"
        )
    avg_line = sum(len(ln) for ln in probe) / len(probe)
    approx_rows = max(1, int(os.path.getsize(path) / avg_line))
    # CEIL division: a floor stride of 1 on a shard just over
    # sample_rows would keep only the head — the bias this whole path
    # exists to avoid; ceil guarantees the kept lines span the file.
    stride = max(1, -(-approx_rows // sample_rows))
    raw_ids, _ = read_raw_ctr_file(path, num_fields,
                                   max_rows=sample_rows, stride=stride)
    # only Rs that divide the table (get_model requires it; 1M-style
    # power-of-two bucket counts keep every candidate)
    candidates = tuple(r for r in (32, 16, 8) if num_buckets % r == 0)
    return suggest_blocking(raw_ids, num_buckets, candidates,
                            num_groups=num_groups)


def encode_blocked(raw_ids, num_blocks: int, block_size: int, *, seed: int = 0,
                   raw_vals=None, field_groups=None, num_groups: int = 0):
    """Raw ``(N, F)`` categorical ids -> ``BlockedSparseLR`` batch leaves
    ``(blocks, lane_vals)``.

    The one load-time call sites use; keeps the train/test splits of a
    run hashing identically as long as they share ``seed``, shape, and
    grouping.  ``num_groups=0`` keeps the default consecutive chunking;
    ``num_groups=G`` selects the near-equal G-way split
    (:func:`split_field_groups` — ``cfg.block_groups`` end to end).
    Returns ``(blocks (N, G) int32, lane_vals (N, G, R) float32)``.
    """
    raw_ids = np.asarray(raw_ids, dtype=np.int64)
    if field_groups is None:
        field_groups = split_field_groups(raw_ids.shape[1], block_size,
                                          num_groups)
    blocks, lane_vals = hash_group_blocks(
        raw_ids, field_groups, num_blocks, seed=seed, raw_vals=raw_vals
    )
    return blocks.astype(np.int32), lane_vals


@dataclasses.dataclass(frozen=True)
class HashedFeatureEncoder:
    """Stateless encoder from raw (field, id, value) features to a fixed
    ``num_buckets``-dimensional space.

    The TPU-native successor of the reference's fixed ``NUM_FEATURE_DIM``
    contract (``src/main.cc:130-131``): instead of requiring the data to
    already live in ``[0, D)``, any 64-bit id space is folded into
    ``[0, num_buckets)`` deterministically.
    """

    num_buckets: int
    seed: int = 0
    signed: bool = False

    def encode_coo(self, field_ids, raw_ids, raw_vals=None):
        """(..., F) raw ids -> (cols, vals) in bucket space, same shape."""
        cols, signs = hash_buckets(
            raw_ids, self.num_buckets, seed=self.seed, field_ids=field_ids
        )
        vals = np.ones(cols.shape, np.float32) if raw_vals is None else np.asarray(
            raw_vals, np.float32
        )
        if self.signed:
            vals = vals * signs
        return cols, vals

    def encode_dense(self, field_ids, raw_ids, raw_vals=None):
        """(B, F) raw ids -> dense (B, num_buckets) float32 (scatter-add)."""
        cols, vals = self.encode_coo(field_ids, raw_ids, raw_vals)
        B = cols.shape[0]
        X = np.zeros((B, self.num_buckets), np.float32)
        rows = np.repeat(np.arange(B), cols.shape[1])
        np.add.at(X, (rows, cols.reshape(-1)), vals.reshape(-1))
        return X

    def encode_csr(self, row_ptr, cols, vals):
        """Rehash CSR column ids (no field namespacing) into bucket space;
        returns CSR with the same row_ptr."""
        new_cols, signs = hash_buckets(cols, self.num_buckets, seed=self.seed)
        new_vals = np.asarray(vals, np.float32)
        if self.signed:
            new_vals = new_vals * signs
        return row_ptr, new_cols, new_vals


def csr_to_padded_coo(row_ptr, cols, vals, *, nnz_max: int | None = None):
    """CSR arrays -> static-shape padded COO ``(cols, vals)`` of shape
    ``(B, nnz_max)`` (pad col = 0, pad val = 0) — the ``SparseBinaryLR``
    batch layout (static shapes; XLA compiles one program per NNZ_MAX).

    Rows longer than ``nnz_max`` are truncated (keeping the first entries);
    callers wanting losslessness pass ``nnz_max=None`` (= longest row).
    """
    row_ptr = np.asarray(row_ptr)
    n = len(row_ptr) - 1
    lengths = np.diff(row_ptr)
    if nnz_max is None:
        nnz_max = int(lengths.max()) if n else 0
    nnz_max = max(int(nnz_max), 1)
    out_cols = np.zeros((n, nnz_max), np.int32)
    out_vals = np.zeros((n, nnz_max), np.float32)
    # vectorized gather: entry (i, j) reads CSR slot row_ptr[i] + j while
    # j < min(len_i, nnz_max) (startup-path hot loop for CTR-scale shards)
    j = np.arange(nnz_max)[None, :]
    valid = j < np.minimum(lengths, nnz_max)[:, None]
    src = row_ptr[:-1, None] + j
    out_cols[valid] = cols[src[valid]]
    out_vals[valid] = vals[src[valid]]
    return out_cols, out_vals


def make_ctr_dataset(
    num_samples: int,
    num_fields: int,
    vocab_size: int,
    num_buckets: int,
    *,
    seed: int = 0,
    signed: bool = False,
    noise: float = 0.0,
    num_distinct_tuples: int | None = None,
    center_logits: bool = False,
):
    """Deterministic synthetic CTR data: ``num_fields`` categorical fields,
    each drawing one value from ``vocab_size``, labels from a logistic
    model over the *hashed* one-hot encoding.

    Ground truth lives in bucket space (``w_true`` shape
    ``(num_buckets,)``), so the learnable signal survives hash collisions
    by construction and convergence tests can assert recovery.

    ``num_distinct_tuples`` models correlated fields (real CTR fields are
    rarely independent — e.g. one device model fixes many of them): rows
    are drawn uniformly from a fixed table of that many distinct (F,)
    value tuples, so every tuple recurs ~N/T times regardless of
    ``vocab_size``.  This is the recurrence regime the row-blocked
    hashing path (:func:`hash_group_blocks`) needs; ``None`` keeps the
    fields i.i.d. (tuples essentially never recur at realistic vocab).

    ``center_logits`` subtracts the mean logit before sampling labels.
    At low vocab the handful of occupied buckets gives the logit a
    random O(1) mean offset, which can push the class marginal to 90%+
    and let a majority-class predictor fake high accuracy; centering
    keeps the base rate near 0.5 so accuracy comparisons measure signal.

    Returns ``(raw_ids, cols, vals, y, w_true)`` where ``raw_ids`` is the
    ``(N, F)`` categorical draw, ``(cols, vals)`` its ``(N, F)`` hashed
    padded-COO encoding, and ``y`` in {0,1}.
    """
    rng = np.random.default_rng(seed)
    if num_distinct_tuples is not None:
        table = rng.integers(
            0, vocab_size, size=(num_distinct_tuples, num_fields))
        raw_ids = table[rng.integers(0, num_distinct_tuples, size=num_samples)]
    else:
        raw_ids = rng.integers(0, vocab_size, size=(num_samples, num_fields))
    field_ids = np.broadcast_to(np.arange(num_fields), raw_ids.shape)
    enc = HashedFeatureEncoder(num_buckets, seed=seed, signed=signed)
    cols, vals = enc.encode_coo(field_ids, raw_ids)
    w_true = (rng.standard_normal(num_buckets) * (3.0 / np.sqrt(num_fields))).astype(
        np.float32
    )
    logits = np.sum(w_true[cols] * vals, axis=-1)
    if center_logits:
        logits = logits - logits.mean()
    if noise > 0.0:
        logits += noise * rng.standard_normal(num_samples)
    p = 1.0 / (1.0 + np.exp(-logits))
    y = (rng.random(num_samples) < p).astype(np.int32)
    return raw_ids, cols.astype(np.int32), vals, y, w_true


def write_ctr_shards(
    data_dir: str,
    num_samples: int,
    num_fields: int,
    vocab_size: int,
    num_buckets: int,
    num_parts: int,
    *,
    seed: int = 0,
    test_fraction: float = 0.2,
) -> dict:
    """Write hashed one-hot CTR data as reference-layout libsvm shards
    (``train/part-001..``, ``test/part-001``, ``models/``), rows being
    ``label idx:1 idx:1 ...`` over 1-based bucket ids — byte-compatible
    with the reference's data contract (``include/data_iter.h:19-34``) at
    ``NUM_FEATURE_DIM = num_buckets``."""
    from distlr_tpu.data.sharding import part_name  # noqa: PLC0415

    _, cols, vals, y, w_true = make_ctr_dataset(
        num_samples, num_fields, vocab_size, num_buckets, seed=seed
    )
    n_test = int(num_samples * test_fraction)
    os.makedirs(os.path.join(data_dir, "train"), exist_ok=True)
    os.makedirs(os.path.join(data_dir, "test"), exist_ok=True)
    os.makedirs(os.path.join(data_dir, "models"), exist_ok=True)

    def _write(path, c, v, labels):
        with open(path, "w") as f:
            for i in range(len(labels)):
                toks = [str(2 * int(labels[i]) - 1)]  # +/-1 labels like a9a
                # merge intra-row hash collisions (sum values per bucket) —
                # libsvm indices must be unique & ascending, and the dense
                # parse path assigns rather than accumulates duplicates
                uniq, inv = np.unique(c[i], return_inverse=True)
                summed = np.zeros(len(uniq), np.float32)
                np.add.at(summed, inv, v[i])
                toks += [
                    f"{int(uc) + 1}:{sv:g}" for uc, sv in zip(uniq, summed) if sv != 0
                ]
                f.write(" ".join(toks) + "\n")

    ctr, cte = cols[n_test:], cols[:n_test]
    vtr, vte = vals[n_test:], vals[:n_test]
    ytr, yte = y[n_test:], y[:n_test]
    parts = []
    for i in range(num_parts):
        sl = slice(i * len(ytr) // num_parts, (i + 1) * len(ytr) // num_parts)
        path = os.path.join(data_dir, "train", part_name(i))
        _write(path, ctr[sl], vtr[sl], ytr[sl])
        parts.append(path)
    test_path = os.path.join(data_dir, "test", part_name(0))
    _write(test_path, cte, vte, yte)
    w_path = os.path.join(data_dir, "w_true.npy")
    np.save(w_path, w_true)
    return {"train_parts": parts, "test_path": test_path, "w_true_path": w_path}


_CTR_META = "ctr_meta.json"


def read_ctr_meta(data_dir: str) -> dict | None:
    """The raw-CTR manifest written by :func:`write_raw_ctr_shards`
    (None when the dir holds plain libsvm / hashed shards instead)."""
    import json  # noqa: PLC0415

    path = os.path.join(data_dir, _CTR_META)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def make_uniform_blocked_batch(rng, n: int, num_fields: int,
                               num_blocks: int, block_size: int):
    """Uniform-random one-hot blocked batch ``(blocks, lane_vals)`` for
    benchmarks/tests: ``ceil(F/R)`` groups with the last group's padded
    lanes zeroed — the layout ``default_field_groups`` +
    ``hash_group_blocks`` produce for one-hot data, without the hashing
    (bench workloads want uniform row access, not a data distribution)."""
    g_count = -(-num_fields // block_size)
    blocks = rng.integers(0, num_blocks, size=(n, g_count)).astype(np.int32)
    lane_vals = np.ones((n, g_count, block_size), np.float32)
    pad = g_count * block_size - num_fields
    if pad:
        lane_vals[:, -1, block_size - pad:] = 0.0
    return blocks, lane_vals


def resolve_ctr_fields(data_dir: str, ctr_fields: int) -> int:
    """The raw field count for blocked loading: from the data dir's
    manifest, or from an explicit ``cfg.ctr_fields`` when there is no
    manifest.  When BOTH exist they must agree — a conflict raises here
    (config error) rather than surfacing later as a per-row parse
    failure."""
    meta = read_ctr_meta(data_dir)
    if ctr_fields:
        if meta is not None and int(meta["num_fields"]) != int(ctr_fields):
            # Surface the config-vs-manifest conflict here, where both
            # sources are visible — not later as a baffling per-row
            # "row has N fields, expected M" parse error.
            raise ValueError(
                f"cfg.ctr_fields={int(ctr_fields)} conflicts with "
                f"{os.path.join(data_dir, _CTR_META)} num_fields="
                f"{int(meta['num_fields'])} — drop ctr_fields to trust the "
                "manifest, or regenerate the shards"
            )
        return int(ctr_fields)
    if meta is None:
        raise FileNotFoundError(
            f"{data_dir} has no {_CTR_META} manifest and cfg.ctr_fields is 0 "
            "— blocked_lr needs the raw field count (write shards with "
            "write_raw_ctr_shards / `launch gen-data --ctr-fields F "
            "--ctr-raw`, or set ctr_fields)"
        )
    return int(meta["num_fields"])


def write_raw_ctr_shards(
    data_dir: str,
    num_samples: int,
    num_fields: int,
    vocab_size: int,
    num_parts: int,
    *,
    seed: int = 0,
    test_fraction: float = 0.2,
    num_distinct_tuples: int | None = None,
) -> dict:
    """Write RAW categorical CTR shards: reference-layout parts whose rows
    are ``±1 field:id ...`` with 1-based field numbers and the raw
    categorical id as the "value".

    Unlike :func:`write_ctr_shards` (which bakes scalar bucket hashing
    into the bytes on disk), this format is **hash-scheme agnostic**: the
    same shard trains the scalar one-hot path (`hash_buckets` at load
    time) or the row-blocked path (`hash_group_blocks`) — the hashing is
    a load-time choice.  Labels come
    from the same hashed-ground-truth logistic model as
    :func:`make_ctr_dataset`, so signal recovery stays assertable.

    A ``ctr_meta.json`` manifest records ``num_fields``/``vocab``/``seed``
    so loaders need no side-channel configuration.  Raw ids ride the
    libsvm float value slot; float32 is exact below 2**24, enforced here.
    """
    import json  # noqa: PLC0415

    from distlr_tpu.data.sharding import part_name  # noqa: PLC0415

    if vocab_size >= 1 << 24:
        raise ValueError(
            f"vocab_size {vocab_size} exceeds float32's exact-integer range "
            "(2^24); raw ids would corrupt in the libsvm value slot"
        )
    # num_distinct_tuples models correlated fields (see make_ctr_dataset)
    # — the tuple-recurrent regime the blocked path needs to learn
    raw_ids, _, _, y, w_true = make_ctr_dataset(
        num_samples, num_fields, vocab_size, max(num_fields * 64, 1024),
        seed=seed, num_distinct_tuples=num_distinct_tuples,
    )
    n_test = int(num_samples * test_fraction)
    os.makedirs(os.path.join(data_dir, "train"), exist_ok=True)
    os.makedirs(os.path.join(data_dir, "test"), exist_ok=True)
    os.makedirs(os.path.join(data_dir, "models"), exist_ok=True)

    def _write(path, ids, labels):
        with open(path, "w") as f:
            for i in range(len(labels)):
                toks = [str(2 * int(labels[i]) - 1)]
                toks += [f"{j + 1}:{int(ids[i, j])}" for j in range(num_fields)]
                f.write(" ".join(toks) + "\n")

    itr, ite = raw_ids[n_test:], raw_ids[:n_test]
    ytr, yte = y[n_test:], y[:n_test]
    parts = []
    for i in range(num_parts):
        sl = slice(i * len(ytr) // num_parts, (i + 1) * len(ytr) // num_parts)
        path = os.path.join(data_dir, "train", part_name(i))
        _write(path, itr[sl], ytr[sl])
        parts.append(path)
    test_path = os.path.join(data_dir, "test", part_name(0))
    _write(test_path, ite, yte)
    meta = {
        "format": "raw_ctr",
        "num_fields": num_fields,
        "vocab_size": vocab_size,
        "seed": seed,
        # provenance only: loaders never read this (the block-size
        # advisor measures recurrence empirically), but a human auditing
        # a data dir should see whether rows were drawn from a fixed
        # tuple table (correlated fields) or i.i.d.
        "num_distinct_tuples": num_distinct_tuples,
    }
    with open(os.path.join(data_dir, _CTR_META), "w") as f:
        json.dump(meta, f)
    w_path = os.path.join(data_dir, "w_true.npy")
    np.save(w_path, w_true)
    return {"train_parts": parts, "test_path": test_path,
            "w_true_path": w_path, "meta": meta}


def csr_to_raw_ids(row_ptr, cols, vals, num_fields: int, *,
                   origin: str = "input") -> np.ndarray:
    """Validated CSR -> raw ``(N, F) int64`` id matrix — THE raw-CTR row
    assembly, shared by the shard reader and the serving front-end so
    training and serving parse (and REJECT) identically.

    ``cols`` give the 0-based field slot, in any order; ``vals`` are the
    raw categorical ids riding the libsvm value slot.  Rejects: a row
    with a missing/extra field, a field number outside ``1..F``, a
    negative / fractional / >= 2^24 id (the float32 value slot has
    already corrupted larger ids), and a duplicated field number (which
    passes the length check but leaves its partner slot unwritten).
    ``origin`` names the source (file path, "request") in errors.
    """
    row_ptr = np.asarray(row_ptr)
    cols = np.asarray(cols)
    vals = np.asarray(vals)
    n = len(row_ptr) - 1
    lengths = np.diff(row_ptr)
    if n and not (lengths == num_fields).all():
        bad = int(np.argmax(lengths != num_fields))
        raise ValueError(
            f"{origin}: row {bad} has {int(lengths[bad])} fields, expected "
            f"{num_fields} (raw-CTR rows carry every field)"
        )
    if n and ((cols < 0).any() or (cols >= num_fields).any()):
        bad = int(cols[(cols < 0) | (cols >= num_fields)][0]) + 1
        raise ValueError(
            f"{origin}: field number {bad} outside 1..{num_fields}"
        )
    if (vals < 0).any():
        raise ValueError(f"{origin}: raw-CTR ids must be non-negative")
    if (vals != np.floor(vals)).any():
        raise ValueError(
            f"{origin}: raw-CTR ids must be integers (found fractional value)"
        )
    if (vals >= float(1 << 24)).any():
        # Mirror write_raw_ctr_shards' bound: an id >= 2^24 has already
        # been rounded in the float32 value slot, so casting it to int64
        # would yield a silently-corrupted id, not the one on disk.
        raise ValueError(
            f"{origin}: raw-CTR id exceeds float32's exact-integer range "
            "(2^24); the id was already corrupted when it was encoded"
        )
    raw_ids = np.full((n, num_fields), -1, np.int64)
    raw_ids[np.repeat(np.arange(n), num_fields), cols] = vals.astype(np.int64)
    if (raw_ids < 0).any():
        bad = int(np.argmax((raw_ids < 0).any(axis=1)))
        raise ValueError(
            f"{origin}: row {bad} repeats a field number (every field must "
            "appear exactly once)"
        )
    return raw_ids


def read_raw_ctr_file(path: str, num_fields: int, *,
                      max_rows: int | None = None, stride: int = 1):
    """Parse one raw-CTR shard -> ``(raw_ids (N, F) int64, y (N,) int32)``.

    Rides the existing libsvm parser (native fast path included): field
    numbers arrive as CSR columns, raw ids as float32 values (exact below
    2^24 by the writer's contract).  Every row must carry all F fields —
    raw-CTR is a dense-fields format, unlike one-hot libsvm.

    ``max_rows``/``stride`` select a row subsample at the LINE level
    (every ``stride``-th line, at most ``max_rows`` of them) without
    parsing or materializing the rest of the shard — the advisor's
    sampling path (:func:`resolve_auto_block_size`).
    """
    from distlr_tpu.data.libsvm import (  # noqa: PLC0415
        parse_libsvm_file,
        parse_libsvm_lines,
    )

    # num_features=None: keep ALL columns, so a shard with MORE fields
    # than expected fails the checks below instead of being silently
    # truncated to a passing width by the parser's column filter.
    if max_rows is None and stride == 1:
        (row_ptr, cols, vals), y = parse_libsvm_file(path, None, dense=False)
    else:
        import itertools  # noqa: PLC0415

        stop = None if max_rows is None else max_rows * stride
        with open(path) as f:  # text mode: the line parser wants str
            lines = list(itertools.islice(f, 0, stop, stride))
        (row_ptr, cols, vals), y = parse_libsvm_lines(lines, None, dense=False)
    return csr_to_raw_ids(row_ptr, cols, vals, num_fields, origin=path), y
