"""Pallas TPU kernel: the keyed sparse-LR step as lookups in tables that
stay in VMEM, over a window whose entries lie **sorted by place**.

The step (``host_math.sparse_batch_grad``) is two irregular passes over a
window's ``N`` entries ``(place p, row r, value v)``: the forward
``z_r = sum w_u[p] v`` (a gather by place, a sum by row) and the backward
``g_u = sum r_row v`` (a gather by row, a sum by place).  A TPU has no
vector gather from HBM, so XLA lowers both to one address at a time: 7 ns
an entry each way, 8.9 ms a step of 638,976 entries whose bytes the HBM
moves in 7 us (PERF.md section 5, PR 51).  Neither table is large (the
pulled weights 352 KB, a window's residuals 64 KB), and a lookup in a
table held as ``[n / 128, 128]`` is a **one-hot product on the MXU and a
pick on the VPU**: for an index ``i = 128 hi + lo``, the product of the
table with ``onehot(lo)`` (or ``onehot(hi)``) gives each entry a column
of 128 candidates, and a mask on the other half of the index picks one.
A sum by index is the same product the other way round.  Such a lookup
costs ``N x n`` multiply-adds, so *which table is looked into decides
the cost*, and sorted entries make the key side local: every place of a
window has an entry, so 1,024 consecutive entries span at most 1,024
consecutive places, 16 rows of the weights' table from a base that is
worked out at load (:data:`CHUNK_LINES`, :data:`TABLE_ROWS`).  Only the
row side is a lookup in a whole table.

**The arithmetic is float32.**  A one-hot factor is exact in bfloat16;
the float32 side of every product goes in as its three bfloat16 parts
(``pallas_softmax.split3``: ``hi + mid + lo`` is the float32) stacked
down the rows of the streamed operand, accumulated in float32 and summed
small parts first.  What is rounded is what float32 rounds: the
products ``w v`` and ``r v`` on the VPU and the sums' order.

The layout (``PSWorker._place_keyed_shard``): a window's entries in
``lines`` lines of 128, sorted by place; ``packed = place << row_bits |
row`` (int32) and the values (float32), ``[windows * lines, 128]``; and
for every chunk of :data:`CHUNK_LINES` lines its base row
(``[windows, lines / 8]`` int32, a window's row of it in SMEM).  Pad
entries (place 0, value 0) add nothing.

The kernel walks the window twice, a grid step a block of
``block_lines`` lines fetched by the pipeline, with the tables resident:

* sweep 0, a chunk: the 16 local rows of ``w`` as parts ``[48, 128]``
  against ``onehot(place lo) [128, T]`` gives ``[48, T]``; the parts
  summed and the row ``place hi`` picked: ``w_u[p]``; ``t = w_u[p] v``;
  ``t``'s parts placed at ``row lo`` ``[384, T]`` against ``onehot(row
  hi)^T [T, R]``: ``z^T`` by parts, ``[384, R]``, added up in VMEM;
* between the sweeps: ``r^T = (sigmoid(z^T) - y^T) mask^T`` as parts;
* sweep 1, a chunk: ``r^T``'s parts ``[384, R]`` against ``onehot(row
  hi) [R, T]``, summed and picked at ``row lo``: ``r[row]``; ``c =
  r[row] v``; ``c``'s parts placed at ``place hi`` ``[48, T]`` against
  ``onehot(place lo)^T [T, 128]``: the chunk's 16 rows of the gradient,
  added where they lie.  With L2 a fourth part counts the real entries
  of a key (the lazy term's active keys).

The mean, the L2 term and the reshapes are plain ``jnp`` round the call
(:func:`keyed_sums`), in the caller's jitted function.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distlr_tpu.ops.pallas_lr import VMEM_LIMIT_BYTES, _LANES, _VMEM_SLACK
from distlr_tpu.ops.pallas_softmax import split3

#: lines of 128 entries that share a base row: one int32 tile
CHUNK_LINES = 8
#: rows of the weights' table a chunk looks into.  1,024 sorted entries
#: span at most 1,024 consecutive places: 9 rows of 128 from the first
#: entry's, and up to 7 more before it for a base in whole sublane groups
TABLE_ROWS = 16
#: Lines of a grid step.  Read on the v5e at 16,384 x 39 entries: 32, 64
#: and 128 lines gave a call of 1.622, 1.620 and 1.621 ms, and a
#: chunk's lookups cut into products of 1, 2 or 4 lines 1.623, 1.632 and
#: 1.639 (``benchmarks/exp_keyed_step.py``; PERF.md section 6, PR 52):
#: the call is bound by a chunk's own vector work, not by the steps.
BLOCK_LINES = 64
_NT = (((1,), (1,)), ((), ()))   # A . B^T


def chunk_base(first_place):
    """The base row of a chunk whose first (smallest) place is
    ``first_place``: that place's row of the ``[n / 128, 128]`` table,
    down to a whole sublane group."""
    return first_place // (_LANES * 8) * 8


@dataclasses.dataclass(frozen=True)
class KeyedPlan:
    """How a window of ``lines`` lines of sorted entries over ``rows``
    rows and ``keys`` (padded) keys is walked."""

    rows: int
    lines: int
    keys: int
    row_bits: int
    block_lines: int
    vmem_limit: int

    @property
    def key_rows(self) -> int:
        return self.keys // _LANES

    @property
    def row_tiles(self) -> int:
        """``R``: the rows' table is ``[128, R]`` (a row's low seven bits
        in the sublanes, the rest in the lanes), whole tiles of lanes."""
        return pl.cdiv(pl.cdiv(self.rows, _LANES), _LANES) * _LANES

    @property
    def blocks(self) -> int:
        return self.lines // self.block_lines

    @property
    def chunks(self) -> int:
        return self.lines // CHUNK_LINES

    @property
    def vmem_bytes(self) -> int:
        """The tables (weights, gradient, active counts), the rows' sums
        and residual parts, two blocks of entries twice, a chunk's
        one-hots and products, and ``pallas_lr``'s slack."""
        t = CHUNK_LINES * _LANES
        tables = 3 * (self.key_rows + TABLE_ROWS) * _LANES * 4
        rows = 3 * _LANES * self.row_tiles * (4 + 2 + 4)
        chunk = t * (4 * _LANES * 4 + 3 * self.row_tiles * 4)
        return (tables + rows + 4 * self.block_lines * _LANES * 8 + 4 * chunk
                + _VMEM_SLACK)


def keyed_plan(rows: int, lines: int, keys: int, row_bits: int, *,
               block_lines: int = BLOCK_LINES,
               vmem_limit: int = VMEM_LIMIT_BYTES) -> KeyedPlan | None:
    """The plan, or None where the kernel cannot run: lines that are not
    whole blocks, a key count that is not whole sublane groups of table
    rows, a row that does not fit its bits, or tables VMEM does not hold.
    ``block_lines`` is the tests' and the instrument's."""
    if (rows <= 0 or lines <= 0 or keys <= 0 or lines % block_lines
            or block_lines % CHUNK_LINES or keys % (8 * _LANES)
            or rows > 1 << row_bits or (keys - 1) >> (31 - row_bits)):
        return None
    plan = KeyedPlan(rows, lines, keys, row_bits, block_lines, vmem_limit)
    return plan if plan.vmem_bytes <= vmem_limit else None


def _wide(x):
    """A chunk's ``[8, 128]`` tile as ``[1, 1024]``: the entries in the
    lanes, line after line."""
    return jnp.concatenate([x[i:i + 1] for i in range(CHUNK_LINES)], axis=1)


def _onehot(n, idx):
    """``bf16[n, T]``: 1 where the sublane is the entry's index."""
    t = idx.shape[1]
    return jnp.where(lax.broadcasted_iota(jnp.int32, (n, t), 0) == idx,
                     1.0, 0.0).astype(jnp.bfloat16)


def _placed(n, idx, x, more=()):
    """``bf16[(3 + len(more)) n, T]``: the three parts of ``x`` (``f32[1,
    T]``), then ``more``'s rows (exact in bfloat16), each at sublane
    ``idx`` of its own ``n`` rows, zero elsewhere."""
    at = lax.broadcasted_iota(jnp.int32, (n, idx.shape[1]), 0) == idx
    return jnp.concatenate(
        [jnp.where(at, p.astype(jnp.float32), 0.0).astype(jnp.bfloat16)
         for p in (*split3(x), *more)], axis=0)


def _parts_sum(x, n):
    """Rows ``[0, n)``, ``[n, 2n)``, ``[2n, 3n)`` of ``x`` (hi, mid, lo)
    summed small parts first."""
    return (x[2 * n:3 * n] + x[n:2 * n]) + x[:n]


def _picked(idx, x):
    """``f32[1, T]``: of ``x`` (``f32[n, T]``) the sublane ``idx``."""
    at = lax.broadcasted_iota(jnp.int32, x.shape, 0) == idx
    return jnp.sum(jnp.where(at, x, 0.0), axis=0, keepdims=True)


def _kernel(plan: KeyedPlan, l2: bool, direction, j_ref, base_ref, pk_ref,
            v_ref, w_ref, yt_ref, mt_ref, g_ref, *rest):
    """``j_ref``: ``i32[1]``, the window (the entries' blocks are
    addressed by it); ``base_ref``: ``i32[chunks]``, the window's base
    rows; ``pk_ref``, ``v_ref``: a block of the window's packed indices
    and values, ``[block_lines, 128]``; ``w_ref``: ``f32[key_rows + 16,
    128]``; ``yt_ref``, ``mt_ref``: ``f32[128, R]``, labels and real-row
    flags, row ``128 hi + lo`` at ``[lo, hi]``; ``g_ref``: the summed
    gradient as ``w_ref`` lies; with ``l2``, ``act_ref``: a key's count of
    real entries, the same way; ``zt_ref``: ``f32[384, R]``, ``z^T`` by
    parts; ``rt_ref``: ``bf16[384, R]``, the residual's.  ``direction``:
    the instrument's, None for the step: ``"forward"`` walks sweep 0
    alone (and leaves what ``g_ref``'s first rows hold of ``z^T``),
    ``"backward"`` sweep 1 alone over the labels as the residual."""
    act_ref = rest[0] if l2 else None
    zt_ref, rt_ref = rest[-2:]
    sweep, blk = pl.program_id(0), pl.program_id(1)
    mask = (1 << plan.row_bits) - 1
    per_block = plan.block_lines // CHUNK_LINES
    backward_only = direction == "backward"

    @pl.when((sweep == 0) & (blk == 0))
    def _():
        zt_ref[...] = jnp.zeros_like(zt_ref)
        g_ref[...] = jnp.zeros_like(g_ref)
        if l2:
            act_ref[...] = jnp.zeros_like(act_ref)

    def chunk(c):
        at = pl.ds(pl.multiple_of(c * CHUNK_LINES, CHUNK_LINES), CHUNK_LINES)
        base = pl.multiple_of(base_ref[blk * per_block + c], 8)
        pk, v = _wide(pk_ref[at, :]), _wide(v_ref[at, :])
        row = pk & mask
        place = (pk >> plan.row_bits) - base * _LANES
        return (base, v, row >> 7, row & (_LANES - 1), place >> 7,
                place & (_LANES - 1))

    def looked_up(n, table, contracted, picked):
        """``f32[1, T]``: ``table`` (parts ``bf16[3 n, .]``) at the index
        whose contracted half is ``contracted`` and whose other half, a
        sublane of the ``n``, is ``picked``."""
        got = jnp.dot(table, _onehot(table.shape[1], contracted),
                      preferred_element_type=jnp.float32)
        return _picked(picked, _parts_sum(got, n))

    def forward(c):
        base, v, r_hi, r_lo, p_hi, p_lo = chunk(c)
        w = jnp.concatenate(split3(w_ref[pl.ds(base, TABLE_ROWS), :]), axis=0)
        t = looked_up(TABLE_ROWS, w, p_lo, p_hi) * v
        zt_ref[...] += lax.dot_general(
            _placed(_LANES, r_lo, t), _onehot(plan.row_tiles, r_hi), _NT,
            preferred_element_type=jnp.float32)

    def backward(c):
        base, v, r_hi, r_lo, p_hi, p_lo = chunk(c)
        cv = looked_up(_LANES, rt_ref[...], r_hi, r_lo) * v
        more = ((v != 0).astype(jnp.bfloat16),) if l2 else ()
        sums = lax.dot_general(
            _placed(TABLE_ROWS, p_hi, cv, more), _onehot(_LANES, p_lo), _NT,
            preferred_element_type=jnp.float32)
        rows = pl.ds(base, TABLE_ROWS)
        g_ref[rows, :] += _parts_sum(sums, TABLE_ROWS)
        if l2:
            act_ref[rows, :] += sums[3 * TABLE_ROWS:]

    def walk(body):
        def step(c, carry):
            body(c)
            return carry

        lax.fori_loop(0, per_block, step, 0)

    if not backward_only:
        pl.when(sweep == 0)(lambda: walk(forward))

    @pl.when((sweep == 1) & (blk == 0))
    def _():
        if backward_only:
            resid = yt_ref[...]
        else:
            z = _parts_sum(zt_ref[...], _LANES)
            resid = (jax.nn.sigmoid(z) - yt_ref[...]) * mt_ref[...]
        rt_ref[...] = jnp.concatenate(split3(resid), axis=0)

    if direction == "forward":
        @pl.when((sweep == 1) & (blk == 0))
        def _():
            n = min(_LANES, g_ref.shape[0])
            g_ref[:n, :] = _parts_sum(zt_ref[...], _LANES)[:n, :_LANES]
    else:
        pl.when(sweep == 1)(lambda: walk(backward))


def _lanes_of_rows(x, plan: KeyedPlan):
    """A window's ``f32[rows]`` as the kernel reads the rows' tables:
    ``[128, R]``, row ``128 hi + lo`` at ``[lo, hi]``, zero beyond."""
    r = plan.row_tiles
    return jnp.pad(x, (0, r * _LANES - plan.rows)).reshape(r, _LANES).T


def keyed_sums(w_u, packed, vals, bases, y, mask, j, plan: KeyedPlan, *,
               l2: bool = False, interpret: bool = False, direction=None):
    """The window's ``sum r_row v`` by key, ``f32[keys]``, not yet
    divided by the count of real rows, and with ``l2`` each key's count
    of real entries (else None).  ``w_u``: ``f32[keys]``; ``packed``,
    ``vals``: ``[windows * lines, 128]``; ``bases``: ``i32[windows,
    chunks]``; ``y``, ``mask``: the window's own ``f32[rows]``; ``j``:
    the window, traced."""
    rows = plan.key_rows + TABLE_ROWS
    if (packed.shape != vals.shape or packed.shape[1] != _LANES
            or packed.shape[0] % plan.lines
            or bases.shape != (packed.shape[0] // plan.lines, plan.chunks)
            or w_u.shape != (plan.keys,)):
        raise ValueError(
            f"the plan is for windows of {plan.lines} lines of 128 entries, "
            f"{plan.chunks} bases a window and {plan.keys} keys, not "
            f"{list(packed.shape)}, {list(bases.shape)}, {list(w_u.shape)}")
    j = jnp.asarray(j, jnp.int32).reshape(1)
    base = lax.dynamic_slice_in_dim(bases, j[0], 1)[0]
    w = jnp.pad(w_u.reshape(plan.key_rows, _LANES), ((0, TABLE_ROWS), (0, 0)))
    entries = pl.BlockSpec(
        (plan.block_lines, _LANES),
        lambda s, b, j_ref, base_ref: (j_ref[0] * plan.blocks + b, 0))
    whole = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda s, b, j_ref, base_ref: (0, 0))
    r = plan.row_tiles
    table = jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_kernel, plan, l2, direction),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(2, plan.blocks),
            in_specs=[entries, entries, whole((rows, _LANES)),
                      whole((_LANES, r)), whole((_LANES, r))],
            out_specs=[whole((rows, _LANES))] * (2 if l2 else 1),
            scratch_shapes=[pltpu.VMEM((3 * _LANES, r), jnp.float32),
                            pltpu.VMEM((3 * _LANES, r), jnp.bfloat16)]),
        out_shape=[table] * (2 if l2 else 1),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=plan.vmem_limit),
        name="keyed_grad_lookups",
        interpret=interpret,
    )(j, base, packed, vals, w, _lanes_of_rows(y, plan),
      _lanes_of_rows(mask, plan))
    if direction == "forward":
        return out[0][:_LANES], None
    sums = out[0][:plan.key_rows].reshape(-1)
    return sums, (out[1][:plan.key_rows].reshape(-1) if l2 else None)
