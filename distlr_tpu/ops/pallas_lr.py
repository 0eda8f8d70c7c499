"""Pallas TPU kernel: the logistic gradient from one HBM read of X.

XLA computes ``g = X^T ((sigmoid(X w) - y) * mask)`` as two fusions, the
forward ``X w`` and the backward ``X^T r``, and each streams the whole
feature matrix out of HBM; for a wide resident shard (a PS worker's
384 x 1,000,000 float32: 1.5 GB) that traffic is the step
(``step_hbm_roofline`` counts the matrix once and read 46%: PERF.md
section 5).  Nothing on the chip holds 1.5 GB, but the forward only has
to finish for *a row* before that row's backward can run, so the matrix
is walked in **row panels**: the eight rows of one sublane group, every
column, 32 MB at D = 1M, which the v5e's 128 MiB of VMEM does hold.

What that asks of the layout: the rows' *columns* in the lanes
(``float32[rows, Dp]`` with ``Dp`` a multiple of 128, the device's
default row-major tiling ``T(8,128)``), so that a panel is one
contiguous run of HBM.  ``float32[384, 1000000]`` is not held that way
by default (1,000,000 is no multiple of 128, so the device puts the rows
in the lanes): :func:`pad_columns` is the relayout, paid once by whoever
keeps the matrix on the device (``PSWorker._place_shard``).

The kernel, float32 throughout, both sweeps on the VPU (an M = 1 forward
or a K = 8 backward wastes the MXU, and float32 costs it several
passes):

* X stays in HBM; a panel is fetched as ``chunks`` column chunks of
  about a megabyte by ``make_async_copy`` into VMEM slots;
* sweep 1 accumulates ``x * w`` elementwise over a panel's column tiles
  as the chunks land (one lane reduction at the end gives ``z`` for the
  eight rows), then ``r = (sigmoid(z) - y) * mask``;
* sweep 2 accumulates ``x * r`` into eight sublane partials of the
  gradient from the same VMEM bytes (the call's first panel writes them,
  so nothing is zeroed), and as it leaves a chunk's slot it starts the
  fetch that slot is next for;
* **look-ahead slots**: with exactly a panel's ``chunks`` slots, the next
  panel's chunk ``k`` cannot start before sweep 2 has left this panel's
  chunk ``k``, so from the landing of a panel's last chunk through its
  forward, the lane reduction, the sigmoid and the first backward no copy
  is in flight: 44.1 us a panel at D = 1M where the stream needs 39.2
  (PERF.md section 6, PR 41).  A plan therefore has ``ahead`` slots more,
  out of the VMEM that is left: a whole second bank where it fits
  (``ahead == chunks``), as many as fit otherwise.  The held chunks turn
  through ``held + ahead`` slots in the order they are read, and a
  slot's next fetch starts when its last reader is done, so ``ahead``
  fetches are queued whenever the arithmetic between the sweeps runs;
* where VMEM holds only ``held`` of a panel's ``chunks`` (a share
  ``f = held / chunks``), the others go through a ring of two slots in
  both sweeps: the matrix is read ``2 - f`` times, not twice, and
  nothing is left to fetch ahead into (``ahead`` = 0).
  :func:`panel_plan` works ``f`` and ``ahead`` out from the shape and
  the VMEM limit;
* after the last panel **the eight partials are summed where they lie**,
  in VMEM, and the kernel's result is the gradient's ``Dp`` columns once
  (a tile a row, 4 MB): 32 MB of partials do not cross HBM twice for
  XLA to add eight numbers a column.

The gradient is cut to ``D`` and turned into the mean gradient with its
L2 term by plain ``jnp`` around the call (:meth:`BinaryLR.grad_panels`),
in the caller's jitted function; the weights' pad and that trim stay
XLA's (``D`` = 1,000,000 is no whole number of tiles: the last chunk of
``w`` would be ragged).

A **window**.  The matrix may be taller than the plan: a minibatch
worker keeps its whole shard resident and a round's batch is
``plan.rows`` rows of it from a first row that changes every round
(``PSWorker``).  That first row is a scalar operand in SMEM, added to a
panel's row where its fetch is addressed, so one executable serves every
window and the window is read where it lies: a ``dynamic_slice`` in
front of the call would write the window out and read it again, twice
the step's bytes.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8   # rows of a panel: one float32 sublane group
#: column tiles of a chunk, about: 256 tiles x 8 rows x 512 B = 1 MB, large
#: enough that a DMA runs at HBM's rate.  A chunk is also the grain of the
#: hand-over between fetch and arithmetic; what HBM loses while a panel's
#: arithmetic runs is the look-ahead slots' to cover, not the chunk
#: size's: with none, the ledger read 44.1 us a panel for a stream of 39.2
#: (4-12% by the size of the call, not "a few percent")
_CHUNK_TILES = 256
#: slots the chunks that do not stay go through, in turn
_RING = 2
#: VMEM left to Mosaic's own scratch and the labels' and masks' blocks
_VMEM_SLACK = 4 << 20
#: The scoped-VMEM limit handed to Mosaic.  A v5e core has 128 MiB
#: (``pltpu.get_tpu_info().vmem_capacity_bytes``).  Mosaic does not check
#: the limit itself, only what it allocates against the core's capacity:
#: under 126 MiB a plan that this module counts at 125.5 MiB compiled and
#: ran on the chip, and 130.4 MiB of allocations are refused (PERF.md
#: section 7).  What is left goes to the operands XLA keeps in VMEM.
VMEM_LIMIT_BYTES = 120 << 20


def _whole_groups(n: int) -> int:
    """``n`` rows as VMEM holds them: whole sublane groups."""
    return pl.cdiv(n, _SUBLANES) * _SUBLANES


@dataclasses.dataclass(frozen=True)
class PanelPlan:
    """How a ``float32[rows, dim]`` matrix is walked."""

    rows: int
    dim: int            # the real columns
    chunk_tiles: int    # 128-column tiles of a chunk
    chunks: int         # chunks of a panel
    held: int           # of them, those that stay in VMEM between the sweeps
    ahead: int          # slots beyond them, for the next panel's fetches
    vmem_limit: int

    @property
    def dim_padded(self) -> int:
        return self.chunks * self.chunk_tiles * _LANES

    @property
    def chunk_cols(self) -> int:
        return self.chunk_tiles * _LANES

    @property
    def weight_rows(self) -> int:
        """Rows a chunk's weights take, one tile a row, whole groups."""
        return _whole_groups(self.chunk_tiles)

    @property
    def held_share(self) -> float:
        """``f``: the matrix crosses HBM ``2 - f`` times a gradient."""
        return self.held / self.chunks

    @property
    def ahead_share(self) -> float:
        """``ahead / chunks``: how much of the next panel is on its way
        while the arithmetic between a panel's two sweeps runs."""
        return self.ahead / self.chunks

    @property
    def slots(self) -> int:
        return (self.held + self.ahead
                + (_RING if self.held < self.chunks else 0))

    @property
    def slot_bytes(self) -> int:
        return _SUBLANES * self.chunk_cols * 4

    @property
    def fixed_bytes(self) -> int:
        """VMEM beside the slots: the eight partials of g, g itself a
        tile a row, w, and the slack."""
        return (_SUBLANES * self.dim_padded * 4
                + _whole_groups(self.chunks * self.chunk_tiles) * _LANES * 4
                + self.chunks * self.weight_rows * _LANES * 4 + _VMEM_SLACK)

    @property
    def vmem_bytes(self) -> int:
        """What the plan counts against ``vmem_limit``."""
        return self.fixed_bytes + self.slots * self.slot_bytes


def panel_plan(rows: int, dim: int, *, vmem_limit: int = VMEM_LIMIT_BYTES,
               chunk_tiles: int = _CHUNK_TILES) -> PanelPlan | None:
    """The plan for a ``float32[rows, dim]`` matrix under ``vmem_limit``
    bytes of VMEM, or None where the kernel cannot run: rows that are not
    whole sublane groups, or a limit that leaves no chunk of a panel in
    place once the gradient, its partials and the weights are counted.
    Where a whole panel stays, what VMEM is left is the look-ahead's
    (``ahead``), up to a second bank; nothing a caller sets."""
    if rows <= 0 or dim <= 0 or rows % _SUBLANES:
        return None
    tiles = pl.cdiv(dim, _LANES)
    chunks = pl.cdiv(tiles, chunk_tiles)
    plan = PanelPlan(rows, dim, pl.cdiv(tiles, chunks), chunks, chunks, 0,
                     vmem_limit)
    slots = (vmem_limit - plan.fixed_bytes) // plan.slot_bytes
    if slots >= chunks:
        return dataclasses.replace(plan, ahead=min(slots - chunks, chunks))
    if slots - _RING < 1:
        return None
    return dataclasses.replace(plan, held=slots - _RING)


def pad_columns(X, plan: PanelPlan, rows: int | None = None):
    """``X`` as the kernel reads it: ``float32[rows, dim_padded]``, the
    pad columns zero.  On a TPU this is the relayout to row-major
    (module docstring); call it once for a matrix that stays.  ``rows``
    (no fewer than ``X`` has) adds zero rows below: a shard whose last
    window would otherwise run past its end."""
    below = 0 if rows is None else rows - X.shape[0]
    return jnp.pad(X.astype(jnp.float32),
                   ((0, below), (0, plan.dim_padded - plan.dim)))


def lr_logits_rows(w, Xp, plan: PanelPlan):
    """``X w``, ``float32[rows]``, from ``Xp = pad_columns(X, plan)``: the
    kernel's forward sweep alone, for whoever wants the logits and no
    gradient (a PS worker's eval over its resident test rows).  Nothing
    has to stay in VMEM between two sweeps here, so this is plain
    ``jnp``: the elementwise product and the lane reduction are one XLA
    fusion that streams ``Xp`` out of HBM once, float32 throughout (a
    ``dot`` of float32 operands is the MXU's in bfloat16 passes, and an
    N = 1 product wastes it)."""
    if Xp.shape != (plan.rows, plan.dim_padded) or Xp.dtype != jnp.float32:
        raise ValueError(
            f"the forward reads float32{[plan.rows, plan.dim_padded]} "
            f"(pad_columns), not {Xp.dtype}{list(Xp.shape)}")
    wp = jnp.pad(w.astype(jnp.float32), (0, plan.dim_padded - plan.dim))
    return jnp.sum(Xp * wp[None, :], axis=1)


def _kernel(plan: PanelPlan, first_ref, x_hbm, w_ref, y_ref, mask_ref, g_ref,
            part, buf, sems):
    """``first_ref``: ``i32[1]`` in SMEM, the window's first row of
    ``x_hbm`` (a multiple of eight), or None where the matrix is read
    from row 0; ``x_hbm``: ``f32[R, dim_padded]`` in HBM, ``R`` no fewer
    than ``rows``; ``w_ref``: ``f32[chunks, weight_rows, 128]``, chunk
    ``k``'s tiles one a row; ``y_ref``, ``mask_ref``: ``f32[rows, 1]``,
    the window's; ``g_ref``: ``f32[dim_padded / 128, 128]``, the
    gradient a tile a row; ``part``: ``f32[8, dim_padded]``, its sublane
    partials; ``buf``: ``f32[slots, 8, chunk_cols]``; ``sems``: a DMA
    semaphore a slot."""
    panels = plan.rows // _SUBLANES
    first = 0 if first_ref is None else first_ref[0]
    tiles, cols = plan.chunk_tiles, plan.chunk_cols
    held, streamed = plan.held, plan.chunks - plan.held
    bank = held + plan.ahead    # slots the held chunks turn through
    kept = held * panels        # held chunks of the call, in the order read
    ring_fetches = 2 * streamed * panels

    def loop(n, body, start=0):
        """``body(k)`` for each ``start <= k < n``, for what it does."""
        def step(k, carry):
            body(k)
            return carry

        lax.fori_loop(start, n, step, 0)

    def copy(panel, chunk, slot):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(pl.multiple_of(first + panel * _SUBLANES,
                                          _SUBLANES), _SUBLANES),
                     pl.ds(pl.multiple_of(chunk * cols, _LANES), cols)],
            buf.at[slot], sems.at[slot])

    def held_copy(q):
        """The ``q``-th held chunk's fetch: chunk ``q % held`` of panel
        ``q // held``, into the slot the ``q - bank``-th has left."""
        return copy(q // held, q % held, q % bank)

    def ring_copy(q):
        """The ``q``-th fetch through the ring: the streamed chunks of
        panel 0 for sweep 1, again for sweep 2, then panel 1's."""
        return copy(q // (2 * streamed), held + q % streamed,
                    bank + q % _RING)

    def start_ring(q):
        pl.when(q < ring_fetches)(lambda: ring_copy(q).start())

    def forward(slot, chunk, acc):
        """Sweep 1 over the chunk in ``slot``: ``acc[u] += x * w``, a
        tile each, eight independent chains."""
        def group(i, acc, count=_SUBLANES):
            """``count`` tiles from tile ``8 i`` on; their weights are one
            aligned (8, 128) load, a tile's a row of it."""
            first = pl.multiple_of(i * _SUBLANES, _SUBLANES)
            w8 = w_ref[chunk, pl.ds(first, _SUBLANES), :]
            acc = list(acc)
            for u in range(count):
                col = pl.multiple_of((first + u) * _LANES, _LANES)
                acc[u] += buf[slot, :, pl.ds(col, _LANES)] * w8[u:u + 1, :]
            return tuple(acc)

        groups = tiles // _SUBLANES
        acc = lax.fori_loop(0, groups, group, acc)
        if tiles % _SUBLANES:
            acc = group(groups, acc, tiles % _SUBLANES)
        return acc

    def backward(slot, chunk, r, opening):
        """Sweep 2 over the chunk in ``slot``: ``part[:, cols] += x * r``;
        the call's ``opening`` panel writes, so nothing is zeroed."""
        base = chunk * cols

        def add(col, width):
            at = pl.ds(pl.multiple_of(base + col, _LANES), width)
            xr = buf[slot, :, pl.ds(col, width)] * r[:, :width]
            part[:, at] = xr if opening else part[:, at] + xr

        wide = _SUBLANES * _LANES
        if tiles >= _SUBLANES:
            loop(tiles // _SUBLANES,
                 lambda i: add(pl.multiple_of(i * wide, wide), wide))
        full = tiles // _SUBLANES * wide
        if cols > full:
            add(full, cols - full)

    loop(min(bank, kept), lambda q: held_copy(q).start())
    if streamed:
        for q in range(_RING):
            start_ring(q)

    def panel(p, opening=False):
        acc = (jnp.zeros((_SUBLANES, _LANES), jnp.float32),) * _SUBLANES

        def held_forward(k, acc):
            q = p * held + k
            held_copy(q).wait()
            return forward(q % bank, k, acc)

        acc = lax.fori_loop(0, held, held_forward, acc)

        def ring_pass(first, body, carry):
            """The streamed chunks once, each from its ring slot, the
            slot's next fetch started as soon as it is read."""
            def step(j, carry):
                q = first + j
                ring_copy(q).wait()
                carry = body(bank + q % _RING, held + j, carry)
                start_ring(q + _RING)
                return carry

            return lax.fori_loop(0, streamed, step, carry)

        if streamed:
            acc = ring_pass(2 * streamed * p, forward, acc)
        z = jnp.sum(functools.reduce(jnp.add, acc), axis=1, keepdims=True)
        at = pl.ds(pl.multiple_of(p * _SUBLANES, _SUBLANES), _SUBLANES)
        r = (jax.nn.sigmoid(z) - y_ref[at, :]) * mask_ref[at, :]
        r = jnp.broadcast_to(r, (_SUBLANES, _SUBLANES * _LANES))

        def held_backward(k):
            """A slot's last reader is done: the fetch ``bank`` chunks on
            starts, so ``ahead`` of them are queued whenever the
            arithmetic between the two sweeps runs."""
            q = p * held + k
            backward(q % bank, k, r, opening)
            pl.when(q + bank < kept)(lambda: held_copy(q + bank).start())

        def ring_backward(slot, chunk, carry):
            backward(slot, chunk, r, opening)
            return carry

        loop(held, held_backward)
        if streamed:
            ring_pass(2 * streamed * p + streamed, ring_backward, 0)

    panel(0, opening=True)
    loop(panels, panel, start=1)

    # the eight partials summed where they lie, a tile a row of ``g_ref``:
    # no fetch is left for this to delay
    def total(tile):
        at = pl.ds(pl.multiple_of(tile * _LANES, _LANES), _LANES)
        return jnp.sum(part[:, at], axis=0, keepdims=True)

    def sum_group(i, count=_SUBLANES):
        first = pl.multiple_of(i * _SUBLANES, _SUBLANES)
        for u in range(count):
            g_ref[pl.ds(first + u, 1), :] = total(first + u)

    groups, rest = divmod(plan.chunks * tiles, _SUBLANES)
    loop(groups, sum_group)
    if rest:
        sum_group(groups, rest)


def lr_grad_panels(w, Xp, y, mask, plan: PanelPlan, *, first=None,
                   interpret: bool = False):
    """``X^T ((sigmoid(X w) - y) * mask)``, ``float32[dim]``, from
    ``Xp = pad_columns(X, plan)``: the unnormalised logistic gradient.

    With ``first`` (an int32 scalar, traced or not; a multiple of eight)
    the rows are the window ``[first, first + plan.rows)`` of a taller
    ``Xp``, read where it lies; ``y`` and ``mask`` are the window's own
    ``plan.rows`` values.  A window that would run past the last row
    starts where it still fits, as ``lax.dynamic_slice`` has it."""
    whole = (plan.rows, plan.dim_padded)
    fits = (Xp.shape == whole if first is None
            else Xp.shape[1] == plan.dim_padded and Xp.shape[0] >= plan.rows
            and Xp.shape[0] % _SUBLANES == 0)
    if not fits or Xp.dtype != jnp.float32:
        raise ValueError(
            f"the kernel reads float32{list(whole)} (pad_columns), or a "
            "window of whole groups of such rows from a first row, not "
            f"{Xp.dtype}{list(Xp.shape)}")
    # chunk k's tiles one a row, each chunk's rows padded to whole groups:
    # a group of eight tiles' weights is one aligned (8, 128) load
    w3 = jnp.pad(
        jnp.pad(w.astype(jnp.float32), (0, plan.dim_padded - plan.dim))
        .reshape(plan.chunks, plan.chunk_tiles, _LANES),
        ((0, 0), (0, plan.weight_rows - plan.chunk_tiles), (0, 0)))
    column = lambda v: v.astype(jnp.float32).reshape(plan.rows, 1)  # noqa: E731
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    operands = [Xp, w3, column(y), column(mask)]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY), vmem, vmem, vmem]
    if first is None:
        kernel = functools.partial(_kernel, plan, None)
    else:
        kernel = functools.partial(_kernel, plan)
        operands.insert(0, jnp.clip(jnp.asarray(first, jnp.int32),
                                    0, Xp.shape[0] - plan.rows).reshape(1))
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
    g = pl.pallas_call(
        kernel,
        in_specs=in_specs,
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((plan.dim_padded // _LANES, _LANES),
                                       jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((_SUBLANES, plan.dim_padded), jnp.float32),
            pltpu.VMEM((plan.slots, _SUBLANES, plan.chunk_cols), jnp.float32),
            pltpu.SemaphoreType.DMA((plan.slots,)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=plan.vmem_limit),
        name="lr_grad_panels",
        interpret=interpret,
    )(*operands)
    return g.reshape(plan.dim_padded)[:plan.dim]
