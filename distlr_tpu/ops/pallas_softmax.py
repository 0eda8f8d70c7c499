"""Pallas TPU kernel: the softmax-regression gradient from one HBM read
of X, both products float32 by six bfloat16 partial products.

XLA computes ``G = X^T ((softmax(X W) - onehot(y)) * mask)`` as two
fusions, ``X W`` with the row softmax and ``X^T R``, and each streams the
resident shard out of HBM (``float32[3968, 62061]``: 985 MB, 1.34 and
1.41 ms of a 2.89 ms step on the v5e; PERF.md section 5, PR 45).  As in
``ops/pallas_lr.py`` the forward only has to finish for *a row* before
that row's backward can run, so the shard is walked in **row panels**,
here the 128 rows one MXU tile contracts over: 31.8 MB at news20's
width, two of which VMEM holds.

The layout is ``pallas_lr``'s: ``float32[rows, Dp]``, the columns in the
lanes (:func:`~distlr_tpu.ops.pallas_lr.pad_columns`), relaid once by
whoever keeps the shard on the device.  A panel is fetched as ``blocks``
column blocks of ``block_tiles`` tiles (1,024 columns: 512 KB) by
``make_async_copy`` into one of **two banks** of block slots; sweep 2
starts a block's fetch for the panel after next as it leaves the slot, so
a whole panel's fetches are in flight whenever the arithmetic between
the sweeps runs (what ``pallas_lr``'s look-ahead slots are for).

**The arithmetic.**  A class axis makes both products the MXU's, and the
MXU multiplies bfloat16.  A float32 product there is what
``Precision.HIGHEST`` is on this chip: each operand split into three
bfloat16 parts ``hi + mid + lo`` (:func:`split3`; exact, the parts
rounded to nearest), and of the nine partial products the **six** that
matter, accumulated in float32 and summed small terms first
(:data:`SIX`): ``lo.hi, mid.mid, hi.lo, mid.hi, hi.mid, hi.hi``.  With
``3 K`` no more than a tile's 128, the small operand's three parts stand
side by side (``[W_hi; W_mid; W_lo]``, ``Kp`` rows each, ``Kp`` = K in
whole bfloat16 sublane groups of 16), so the six are **three** MXU
products, one a part of X, each against a prefix of that stack:
``X_hi`` meets all three, ``X_mid`` the first two, ``X_lo`` the first.
X is the stationary operand of all of them (a 128 x 128 tile latched,
the small operand's ``3 Kp``, ``2 Kp`` or ``Kp`` rows streamed through):

* sweep 1, a block: ``Z^T[j Kp, 128] += Wt[:j Kp, block] . X_part^T``
  (the transposed right-hand side is the MXU's own load, no XLU
  transpose), so the logits stand classes in the sublanes, rows in the
  lanes;
* between the sweeps, on a ``[Kp, 128]`` tile (nothing): the six
  summed, the softmax down the class axis, ``R^T = (P - onehot(y)) *
  mask``, split into its three parts and stacked;
* sweep 2, a block: ``G^T[j Kp, block] = Rt[:j Kp] . X_part``, the six
  summed and added to the gradient's block, which stays in VMEM
  (``float32[Kp, Dp]``, 8 MB) across the panels and leaves once.

The weights come as ``float32[D, K]`` and the gradient leaves as that;
the stack of the weights' parts, the cut to ``D`` and the transposes are
plain ``jnp`` round the call, as are the mean, the L2 term and
``feature_scale`` (:meth:`SoftmaxRegression.grad_panels`).

A **window** is ``pallas_lr``'s: a first row in SMEM, added where a
panel's fetch is addressed.
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

#: rows of a panel: what one MXU tile contracts over in ``X^T R``
PANEL_ROWS = 128
#: Column tiles of a block, the grain of a fetch and of a product: the
#: count in this range that pads the fewest columns on (the larger of
#: two that tie).  Read on the v5e at 3,968 x 62,061 x 20 (PERF.md
#: section 6, PR 46): blocks of 4, 8 and 16 tiles gave a kernel of 2.25,
#: 1.69 and 1.56 ms, what is fixed a block (the loop, the DMA's issue,
#: the products' fill and drain) being that much of it; past 32 tiles a
#: block's parts and products outgrow the VMEM left beside two banks.
_BLOCK_TILES = range(16, 33)
#: rows of a bfloat16 sublane group: a part's rows in a stack
_PART_ROWS = 16
#: The six partial products of a float32 product, as (part of X, part of
#: the small operand), 0 = hi: small terms first, the order they are
#: summed in.  ``Precision.HIGHEST`` leaves out the same three
#: (``mid.lo, lo.mid, lo.lo``: 2^-24 of the product and less).
SIX = ((2, 0), (1, 1), (0, 2), (1, 0), (0, 1), (0, 0))


def split3(x):
    """``x`` (float32) as three bfloat16 parts whose sum is ``x``: each
    the nearest bfloat16 to what the parts before it left.  The kernel's
    own (and the interpreter's); under XLA see :func:`split3_xla`."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def split3_xla(x):
    """:func:`split3` for a program XLA compiles.  XLA removes a
    conversion to bfloat16 and back (``xla_allow_excess_precision``, the
    default), and with it the ``mid`` and ``lo`` parts: read on the v5e
    as a gradient 2.1e-4 off float64's where the six partial products
    leave 3e-7 (PERF.md section 6, PR 46).  ``reduce_precision`` is the
    rounding it has to leave alone."""
    nearest = functools.partial(lax.reduce_precision, exponent_bits=8,
                                mantissa_bits=7)
    hi = nearest(x)
    mid = nearest(x - hi)
    lo = (x - hi) - mid     # what eight bits of mantissa hold: exact
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, lo))


@dataclasses.dataclass(frozen=True)
class SoftmaxPanelPlan:
    """How a ``float32[rows, dim]`` matrix is walked for ``classes``
    classes."""

    rows: int
    dim: int            # the real columns
    classes: int
    block_tiles: int    # 128-column tiles of a block
    blocks: int         # blocks of a panel
    vmem_limit: int

    @property
    def dim_padded(self) -> int:
        return self.blocks * self.block_cols

    @property
    def block_cols(self) -> int:
        return self.block_tiles * _LANES

    @property
    def class_rows(self) -> int:
        """``Kp``: the rows a part takes in a stack, whole bfloat16
        sublane groups."""
        return pl.cdiv(self.classes, _PART_ROWS) * _PART_ROWS

    @property
    def panels(self) -> int:
        return self.rows // PANEL_ROWS

    @property
    def slots(self) -> int:
        """Two banks of a panel's blocks."""
        return 2 * self.blocks

    @property
    def held_share(self) -> float:
        """A panel is held whole between its sweeps or there is no plan:
        the matrix crosses HBM once a gradient."""
        return 1.0

    @property
    def ahead_share(self) -> float:
        """The second bank: the whole next panel is on its way while the
        arithmetic between a panel's two sweeps runs."""
        return 1.0

    @property
    def vmem_bytes(self) -> int:
        """What the plan counts against ``vmem_limit``: the two banks,
        the stack of the weights' parts, the gradient, what Mosaic keeps
        of a block between its operations (the float32 block, its three
        parts, the six products and their sum), and ``pallas_lr``'s
        slack."""
        kp, dp = self.class_rows, self.dim_padded
        block = self.block_cols * (PANEL_ROWS * (4 + 3 * 2) + 7 * kp * 4)
        return (self.slots * PANEL_ROWS * self.block_cols * 4
                + 3 * kp * dp * 2 + kp * dp * 4 + block + _VMEM_SLACK)


def softmax_panel_plan(rows: int, dim: int, classes: int, *,
                       vmem_limit: int = VMEM_LIMIT_BYTES,
                       block_tiles: int | None = None
                       ) -> SoftmaxPanelPlan | None:
    """The plan for a ``float32[rows, dim]`` matrix and ``classes``
    classes under ``vmem_limit`` bytes of VMEM, or None where the kernel
    cannot run: rows that are not whole panels, a class axis whose three
    parts do not stand side by side in a tile (``3 K > 128``), or a limit
    that two panels, the weights' parts and the gradient do not fit.
    ``block_tiles`` is the tests' and the instrument's; left alone it is
    chosen from the shape (``_BLOCK_TILES``)."""
    if (rows <= 0 or dim <= 0 or rows % PANEL_ROWS
            or not 2 <= classes <= _LANES // 3):
        return None
    tiles = pl.cdiv(dim, _LANES)
    if block_tiles is None:
        block_tiles = min(_BLOCK_TILES, key=lambda t: (
            pl.cdiv(tiles, min(t, tiles)) * min(t, tiles), -t))
        block_tiles = min(block_tiles, tiles)
    blocks = pl.cdiv(tiles, block_tiles)
    plan = SoftmaxPanelPlan(rows, dim, classes, block_tiles, blocks,
                            vmem_limit)
    return plan if plan.vmem_bytes <= vmem_limit else None


def _six(parts, kp, terms):
    """The partial products named by ``terms`` summed in that order:
    ``parts[i]`` is the product of X's part ``i`` with the stack's prefix,
    so its rows ``[j kp, (j + 1) kp)`` are the term ``(i, j)``."""
    return functools.reduce(
        jnp.add, (parts[i][j * kp:(j + 1) * kp] for i, j in terms))


def _kernel(plan: SoftmaxPanelPlan, resident: bool, terms, first_ref, x_hbm,
            wt_ref, y_ref, mask_ref, g_ref, buf, sems):
    """``first_ref``: ``i32[1]`` in SMEM, the window's first row of
    ``x_hbm`` (a multiple of eight), or None where the matrix is read
    from row 0; ``x_hbm``: ``f32[R, dim_padded]`` in HBM, ``R`` no fewer
    than ``rows``; ``wt_ref``: ``bf16[3 Kp, dim_padded]``, the stack of
    the transposed weights' parts; ``y_ref`` (``i32``), ``mask_ref``
    (``f32``): ``[panels, 128]``, a panel's a row; ``g_ref``:
    ``f32[Kp, dim_padded]``, the transposed gradient; ``buf``:
    ``f32[2 blocks, 128, block_cols]``; ``sems``: a DMA semaphore a slot.
    ``resident``: panel 0 is fetched once and every panel's arithmetic
    runs over those bytes (the instrument's reading of the arithmetic
    alone)."""
    k, kp = plan.classes, plan.class_rows
    panels, blocks, cols = plan.panels, plan.blocks, plan.block_cols
    first = 0 if first_ref is None else first_ref[0]
    nt = (((1,), (1,)), ((), ()))   # A . B^T

    def loop(n, body, start=0):
        """``body(i)`` for each ``start <= i < n``, for what it does."""
        def step(i, carry):
            body(i)
            return carry

        lax.fori_loop(start, n, step, 0)

    def slot(panel, block):
        return block if resident else (panel % 2) * blocks + block

    def copy(panel, block):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(pl.multiple_of(first + panel * PANEL_ROWS, 8),
                           PANEL_ROWS),
                     pl.ds(pl.multiple_of(block * cols, _LANES), cols)],
            buf.at[slot(panel, block)], sems.at[slot(panel, block)])

    def at(block):
        return pl.ds(pl.multiple_of(block * cols, _LANES), cols)

    def forward(panel, block, acc):
        """Sweep 1 over a block: X's part ``i`` against the first
        ``3 - i`` parts of the weights' stack."""
        x = split3(buf[slot(panel, block)])
        return tuple(
            a + lax.dot_general(wt_ref[:(3 - i) * kp, at(block)], x[i], nt,
                                preferred_element_type=jnp.float32)
            for i, a in enumerate(acc))

    def backward(panel, block, rt, opening):
        """Sweep 2 over a block: the stack of the residual's parts
        against X's; the call's ``opening`` panel writes the gradient's
        block, so nothing is zeroed."""
        x = split3(buf[slot(panel, block)])
        g = _six([jnp.dot(rt[:(3 - i) * kp], x[i],
                          preferred_element_type=jnp.float32)
                  for i in range(3)], kp, terms)
        g_ref[:, at(block)] = g if opening else g_ref[:, at(block)] + g

    loop(blocks, lambda b: copy(0, b).start())
    if resident:
        loop(blocks, lambda b: copy(0, b).wait())
    elif panels > 1:
        loop(blocks, lambda b: copy(1, b).start())
    cls = lax.broadcasted_iota(jnp.int32, (kp, PANEL_ROWS), 0)

    def panel(p, opening=False):
        def block_forward(b, acc):
            if not resident:
                copy(p, b).wait()
            return forward(p, b, acc)

        zt = _six(lax.fori_loop(0, blocks, block_forward, tuple(
            jnp.zeros(((3 - i) * kp, PANEL_ROWS), jnp.float32)
            for i in range(3))), kp, terms)
        # classes in the sublanes, the panel's rows in the lanes
        zt = jnp.where(cls < k, zt, -jnp.inf)
        e = jnp.exp(zt - jnp.max(zt, axis=0, keepdims=True))
        prob = e / jnp.sum(e, axis=0, keepdims=True)
        hot = (cls == y_ref[pl.ds(p, 1), :]).astype(jnp.float32)
        rt = jnp.concatenate(
            split3((prob - hot) * mask_ref[pl.ds(p, 1), :]), axis=0)

        def block_backward(b):
            backward(p, b, rt, opening)
            if not resident:
                # the slot's last reader is done: the fetch two panels on
                pl.when(p + 2 < panels)(lambda: copy(p + 2, b).start())

        loop(blocks, block_backward)

    panel(0, opening=True)
    loop(panels, panel, start=1)


def softmax_grad_panels(W, Xp, y, mask, plan: SoftmaxPanelPlan, *,
                        first=None, interpret: bool = False,
                        resident: bool = False):
    """``X^T ((softmax(X W) - onehot(y)) * mask[:, None])``,
    ``float32[dim, classes]``, from ``Xp = pad_columns(X, plan)`` and
    ``W`` (``float32[dim, classes]``): the unnormalised softmax-regression
    gradient, both products by :data:`SIX`.

    ``first`` is ``lr_grad_panels``': the rows are the window ``[first,
    first + plan.rows)`` of a taller ``Xp``, ``y`` and ``mask`` the
    window's own.  ``resident`` is the instrument's
    (``benchmarks/exp_softmax_step.py``): the arithmetic of every panel
    over panel 0's bytes, fetched once; no gradient of anything."""
    whole = (plan.rows, plan.dim_padded)
    fits = (Xp.shape == whole if first is None
            else Xp.shape[1] == plan.dim_padded and Xp.shape[0] >= plan.rows
            and Xp.shape[0] % 8 == 0)
    if not fits or Xp.dtype != jnp.float32:
        raise ValueError(
            f"the kernel reads float32{list(whole)} (pad_columns), or a "
            "window of whole panels of such rows from a first row, not "
            f"{Xp.dtype}{list(Xp.shape)}")
    k, kp = plan.classes, plan.class_rows
    if W.shape != (plan.dim, k):
        raise ValueError(f"the plan is for float32{[plan.dim, k]} weights, "
                         f"not {list(W.shape)}")
    wt = jnp.concatenate(split3_xla(jnp.pad(
        W.astype(jnp.float32).T,
        ((0, kp - k), (0, plan.dim_padded - plan.dim)))), axis=0)
    row = lambda v, dtype: v.astype(dtype).reshape(  # noqa: E731
        plan.panels, PANEL_ROWS)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    operands = [Xp, wt, row(y, jnp.int32), row(mask, jnp.float32)]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY), vmem, vmem, vmem]
    if first is None:
        kernel = functools.partial(_kernel, plan, resident, SIX, None)
    else:
        kernel = functools.partial(_kernel, plan, resident, SIX)
        operands.insert(0, jnp.clip(jnp.asarray(first, jnp.int32),
                                    0, Xp.shape[0] - plan.rows).reshape(1))
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
    gt = pl.pallas_call(
        kernel,
        in_specs=in_specs,
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((kp, plan.dim_padded), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((plan.slots, PANEL_ROWS, plan.block_cols),
                       jnp.float32),
            pltpu.SemaphoreType.DMA((plan.slots,)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=plan.vmem_limit),
        name="softmax_grad_panels",
        interpret=interpret,
    )(*operands)
    return gt[:k, :plan.dim].T
