"""The keyed step's kernel (``ops/pallas_keyed.py``), interpreted on the
CPU over windows laid out here in numpy (sorted by place, packed, a base
row a chunk), against ``host_math.sparse_batch_grad`` and float64: the
shapes of a click log that stress a lookup by one-hot products (a hot
key, keys met once, pads, a short window, a column twice in a row), the
three L2 forms, and what the plan refuses.  Its compile for a described
v5e is in ``tests/test_ops.py``."""

import jax.numpy as jnp
import numpy as np
import pytest

from distlr_tpu.models import host_math
from distlr_tpu.ops import pallas_keyed
from distlr_tpu.ops.pallas_keyed import (
    BLOCK_LINES,
    CHUNK_LINES,
    TABLE_ROWS,
    chunk_base,
    keyed_plan,
    keyed_sums,
)

DIM, BATCH, SLOTS = 1 << 16, 256, 39
ROW_BITS = 8
LINES = -(-BATCH * SLOTS // (128 * BLOCK_LINES)) * BLOCK_LINES
KEYS = 16384
CHUNK = CHUNK_LINES * 128


def _window(cols, vals):
    """One window as the worker lays it out, by numpy alone: its keys,
    each entry's place (row-major, for the references) and the sorted
    leaves."""
    keys, place = np.unique(cols, return_inverse=True)
    place = place.reshape(cols.shape).astype(np.int32)
    row = np.arange(len(cols), dtype=np.int32)[:, None]
    packed = np.zeros(LINES * 128, np.int32)
    values = np.zeros(LINES * 128, np.float32)
    packed[:cols.size] = (place << ROW_BITS | row).reshape(-1)
    values[:cols.size] = vals.reshape(-1)
    order = np.argsort(packed, kind="stable")
    packed, values = packed[order], values[order]
    bases = chunk_base(packed[::CHUNK] >> ROW_BITS).astype(np.int32)
    return keys, place, packed, values, bases


def _case(name):
    """``(cols, vals, y, real)`` of one window: ``real`` rows of ``BATCH``
    carry entries, the rest are the short window's masked ones."""
    rng = np.random.default_rng(sum(map(ord, name)))
    real = BATCH - 56 if name == "short-window" else BATCH
    cols = rng.integers(1, DIM, (real, SLOTS))
    vals = rng.standard_normal((real, SLOTS)).astype(np.float32)
    if name in ("hot-key", "a-chunk-inside-one-run"):
        # one place on thousands of entries: with 4 columns of every row
        # naming it, 1,024 of them in a row and whole chunks inside the run
        cols[:, :8 if name == "hot-key" else 16] = 77
    elif name == "all-singletons":
        cols = rng.permutation(DIM - 1)[:real * SLOTS].reshape(real, SLOTS) + 1
    elif name == "pads-inside-a-line":
        gone = rng.random(cols.shape) < 0.2
        cols[gone], vals[gone] = 0, 0.0
    elif name == "a-column-twice-in-a-row":
        cols[:, 5], cols[:, 9] = cols[:, 3], cols[:, 3]
    y = (rng.random(real) < 0.3).astype(np.float32)
    return cols, vals, y, real


def _float64(w_u, place, vals, y, l2_c, by_batch):
    w = w_u.astype(np.float64)
    z = (w[place] * vals).sum(-1)
    n = max(len(y), 1)
    g = np.bincount(place.ravel(), minlength=len(w), weights=(
        (1 / (1 + np.exp(-z)) - y)[:, None] * vals).ravel()) / n
    if l2_c:
        active = np.bincount(place.ravel(), weights=(vals != 0).ravel(),
                             minlength=len(w)) > 0
        g += l2_c * w * active / (n if by_batch else 1)
    return g


def _kernel_grad(w_u, packed, values, bases, y, real, l2, windows=1, j=0,
                 **shape):
    plan = keyed_plan(BATCH, LINES, KEYS, ROW_BITS, **shape)
    held = np.zeros(KEYS, np.float32)
    held[:len(w_u)] = w_u
    labels, mask = np.zeros(BATCH, np.float32), np.zeros(BATCH, np.float32)
    labels[:real], mask[:real] = y, 1.0
    l2_c, by_batch = l2
    sums, entries = keyed_sums(
        jnp.asarray(held), jnp.asarray(packed.reshape(windows * LINES, 128)),
        jnp.asarray(values.reshape(windows * LINES, 128)),
        jnp.asarray(bases.reshape(windows, -1)), jnp.asarray(labels),
        jnp.asarray(mask), j, plan, l2=bool(l2_c), interpret=True)
    g = np.asarray(sums) / real
    if l2_c:
        g = g + l2_c * held * (np.asarray(entries) > 0) / (
            real if by_batch else 1)
    assert not g[len(w_u):].any()       # no key beyond the window's
    return g[:len(w_u)]


@pytest.mark.parametrize("l2", [(0.0, False), (0.5, False), (0.5, True)],
                         ids=["no-l2", "l2", "l2-by-batch"])
@pytest.mark.parametrize("name", [
    "hot-key", "all-singletons", "pads-inside-a-line", "short-window",
    "a-column-twice-in-a-row", "a-chunk-inside-one-run"])
def test_the_kernel_is_numpys_step_and_float64s(name, l2):
    cols, vals, y, real = _case(name)
    keys, place, packed, values, bases = _window(cols, vals)
    if name == "a-chunk-inside-one-run":
        first, last = packed[::CHUNK] >> ROW_BITS, packed[CHUNK - 1::CHUNK] >> ROW_BITS
        assert (first == last).any()
    if name == "hot-key":
        assert np.bincount(place.ravel()).max() >= 2000
    if name == "all-singletons":
        assert len(keys) == real * SLOTS
    w_u = (np.random.default_rng(1).standard_normal(len(keys)) * 0.1).astype(
        np.float32)
    got = _kernel_grad(w_u, packed, values, bases, y, real, l2)
    want = _float64(w_u, place, vals, y, *l2)
    assert np.linalg.norm(got - want) <= 2e-6 * np.linalg.norm(want)
    numpys = host_math.sparse_batch_grad(w_u, place, vals, y,
                                         np.ones(real, bool), *l2)
    assert np.linalg.norm(got - numpys) <= 2e-6 * np.linalg.norm(want)


@pytest.mark.parametrize("shape", [
    dict(block_lines=8), dict(block_lines=32), dict(block_lines=128)],
    ids=lambda s: "-".join(f"{k}-{v}" for k, v in s.items()))
def test_any_whole_blocks_of_chunks_give_the_same_sums(shape):
    cols, vals, y, real = _case("pads-inside-a-line")
    keys, place, packed, values, bases = _window(cols, vals)
    w_u = (np.random.default_rng(2).standard_normal(len(keys)) * 0.1).astype(
        np.float32)
    got = _kernel_grad(w_u, packed, values, bases, y, real, (0.0, False),
                       **shape)
    want = _float64(w_u, place, vals, y, 0.0, False)
    assert np.linalg.norm(got - want) <= 2e-6 * np.linalg.norm(want)


def test_the_window_index_picks_the_windows_own_entries_and_bases():
    laid = [_window(*_case(name)[:2]) for name in ("hot-key", "short-window")]
    packed, values, bases = (np.concatenate([w[i] for w in laid])
                             for i in (2, 3, 4))
    cols, vals, y, real = _case("short-window")
    keys, place = laid[1][:2]
    w_u = (np.random.default_rng(3).standard_normal(len(keys)) * 0.1).astype(
        np.float32)
    got = _kernel_grad(w_u, packed, values, bases, y, real, (0.0, False),
                       windows=2, j=1)
    want = _float64(w_u, place, vals, y, 0.0, False)
    assert np.linalg.norm(got - want) <= 2e-6 * np.linalg.norm(want)


def test_a_chunk_reads_no_row_beyond_its_sixteen():
    """Sorted entries of a window whose every place has one span at most
    1,024 places a chunk: from a base in whole sublane groups, 16 rows."""
    for name in ("all-singletons", "hot-key", "pads-inside-a-line"):
        _keys, _place, packed, _values, bases = _window(*_case(name)[:2])
        places = (packed >> ROW_BITS).reshape(-1, CHUNK)
        assert (np.diff(packed >> ROW_BITS) >= 0).all()
        assert (bases % 8 == 0).all()
        assert (places >= bases[:, None] * 128).all()
        assert (places < (bases[:, None] + TABLE_ROWS) * 128).all()


@pytest.mark.parametrize("over,why", [
    (dict(lines=LINES + 8), "lines that are not whole blocks"),
    (dict(keys=KEYS + 128), "table rows that are not whole sublane groups"),
    (dict(rows=BATCH + 1), "a row its bits do not hold"),
    (dict(keys=1 << 24), "a place that does not share an int32 with the row"),
    (dict(vmem_limit=8 << 20), "tables VMEM does not hold"),
    (dict(block_lines=12), "a block that is not whole chunks"),
])
def test_the_plan_refuses(over, why):
    kw = dict(rows=BATCH, lines=LINES, keys=KEYS, row_bits=ROW_BITS)
    assert keyed_plan(**kw) is not None
    assert keyed_plan(**{**kw, **over}) is None, why


def test_leaves_that_are_not_the_plans_are_refused():
    plan = keyed_plan(BATCH, LINES, KEYS, ROW_BITS)
    z = jnp.zeros
    with pytest.raises(ValueError, match="the plan is for windows of"):
        keyed_sums(z(KEYS), z((LINES + 8, 128), jnp.int32), z((LINES + 8, 128)),
                   z((1, LINES // 8), jnp.int32), z(BATCH), z(BATCH), 0, plan,
                   interpret=True)


def test_the_row_side_table_is_whole_tiles_of_lanes():
    assert keyed_plan(16384, 4992, 90112, 14).row_tiles == 128
    assert keyed_plan(BATCH, LINES, KEYS, ROW_BITS).row_tiles == 128
    assert keyed_plan(1 << 15, 9984, 1 << 16, 15).row_tiles == 256
    assert pallas_keyed.keyed_plan(16384, 4992, 90112, 14).vmem_bytes < 64 << 20
