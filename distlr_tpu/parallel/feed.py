"""Host -> device placement of one leaf of a sync batch.

What the runtime does with ``device_put(x, sharding)`` of a host array,
read from a profiler trace on a TPU v5e (PERF.md section 5): one
``pjrt-tpu-tasks`` thread relays the whole array into the device's
layout (``XlaLinearize``, farmed out to the runtime's worker threads),
and only when the whole array is relaid is its DMA issued
(``TransferToDevice``), 14 GB/s, one DMA at a time.  The device's
default layout for a ``(rows, D)`` array puts whichever dimension is a
multiple of 128 in the lanes; for ``bf16[768, 1000000]`` that is the
*row* index, so the relayout of the host's row-major rows is a
transpose of 1.5 GB at 5-7 GB/s, in series with the 108 ms DMA.

:func:`place` hands such a leaf over as the bytes the host already
holds: the array's buffer viewed as ``(n, 128)`` 32-bit words, whose
device layout is the host's own order, so the runtime's relayout is a
straight copy (39 ms for 1.5 GB where the transpose took 190), in
pieces, so that a piece's DMA runs while the next is copied and the
link is never idle (the trace still names the copy's chunks
``Transpose::ExecuteChunk``: the runtime's one relayout routine).  ``(rows, D)``, the dtype and the layout the step's
kernels were compiled for are restored on the device, at HBM speed, by
a small program of the feed's own (``jit_feed_restore``), dispatched by
the thread that did the put.  What the caller gets back is equal in
values, shape, dtype and sharding to a plain ``device_put``, and so in
the device's default layout (for ``[rows, 1000000]`` the rows in the
lanes): a caller that wants the columns there, as a PS worker's resident
shard does for its one-pass step, relays it once on the device itself
(``PSWorker._place_shard``).

It engages per leaf, on what it can observe: a C-contiguous numpy
matrix of one of the dense feature dtypes, large enough that the
relayout outweighs a dispatch, whose default layout on the mesh's
devices is *not* the host's row-major (on the CPU backend it always
is), with rows and row bytes that the word view divides.  Everything
else (labels, masks, sparse leaves, device arrays, the CPU) takes the
plain ``device_put``.  ``distlr_h2d_bytes_total{layout}`` says which
way each byte went.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distlr_tpu.obs.registry import get_registry
from distlr_tpu.parallel.mesh import DATA_AXIS

_H2D_BYTES = get_registry().counter(
    "distlr_h2d_bytes_total",
    "bytes of sync batches handed to the runtime for the copy to the "
    "device, by how: as_held = the host's own row-major bytes, relaid on "
    "the device; default = a plain device_put, relaid by the runtime's "
    "host threads when the device's layout differs",
    labelnames=("layout",),
)

#: A leaf under this size keeps the plain ``device_put``.  The restore
#: program costs a dispatch, a launch (1.2 ms on the v5e) and 8 us a
#: megabyte on the device; the runtime's transpose costs 120 us a
#: megabyte on the host's threads.  Under a few tens of megabytes the
#: launch is most of what there is to win, and a device-bound step (the
#: sparse families: 10 MB leaves, the chip 91% busy) would pay for it.
AS_HELD_MIN_BYTES = 32 << 20
#: Size of the pieces a leaf is handed over in.  A piece's DMA is issued
#: when the whole piece is relaid, so the first DMA starts after one
#: piece's copy (about 10 ms at this size) and not after the whole
#: leaf's (39 ms for 1.5 GB); the restore kernel is unrolled over the
#: pieces, so more of them buy little and compile slower.
_PIECE_BYTES = 192 << 20
_LANES = 128
#: the dense feature dtypes (``Config.feature_dtype``)
_DTYPES = (np.dtype(np.float32), np.dtype(jnp.bfloat16), np.dtype(np.int8))
#: rows of a shard the restore kernel holds in one block (VMEM)
_MAX_SHARD_ROWS = 4096


@dataclasses.dataclass(frozen=True)
class _Plan:
    """How one ``(rows, dim)`` leaf of ``dtype`` crosses to ``mesh``."""

    mesh: Mesh
    rows: int        # of one device's shard
    dim: int
    dtype: np.dtype
    pieces: int      # copies a shard is handed over in

    @property
    def row_words(self) -> int:
        return self.dim * self.dtype.itemsize // 4

    @property
    def group(self) -> int:
        """Fewest consecutive rows that fill whole 128-word lines."""
        return _LANES // math.gcd(self.row_words, _LANES)


@functools.lru_cache(maxsize=256)
def _default_is_row_major(dtype: np.dtype, shard_shape: tuple, device) -> bool:
    """Whether ``device``'s default layout for a shard keeps the host's
    order of dimensions.  A backend that cannot say (UNIMPLEMENTED) is
    taken to have nothing to relay."""
    try:
        layout = Layout.from_pjrt_layout(
            device.client.get_default_layout(dtype, shard_shape, device))
    except jax.errors.JaxRuntimeError:
        return True
    return tuple(layout.major_to_minor) == tuple(range(len(shard_shape)))


def _plan(x, mesh: Mesh) -> _Plan | None:
    """The plan for ``x``, or None where the plain put is the way."""
    if not (isinstance(x, np.ndarray) and x.ndim == 2
            and x.dtype in _DTYPES and x.flags.c_contiguous
            and x.nbytes >= AS_HELD_MIN_BYTES):
        return None
    n_dev = mesh.shape[DATA_AXIS]
    if mesh.size != n_dev or x.shape[0] % n_dev:
        return None
    rows, dim = x.shape[0] // n_dev, x.shape[1]
    if _default_is_row_major(x.dtype, (rows, dim), mesh.devices.flat[0]):
        return None
    if dim * x.dtype.itemsize % 4 or rows % _LANES or rows > _MAX_SHARD_ROWS:
        return None
    plan = _Plan(mesh, rows, dim, x.dtype, 1)
    if plan.group > 8:
        return None
    # a piece holds whole groups of rows, so it is whole 128-word lines,
    # and a multiple of 8 of them, the sublanes of a tile
    tiles = rows // plan.group // 8
    want = max(1, rows * dim * x.dtype.itemsize // _PIECE_BYTES)
    pieces = max(k for k in range(1, min(want, tiles) + 1) if tiles % k == 0)
    return dataclasses.replace(plan, pieces=pieces)


def _pallas():
    """Pallas, imported when a leaf first goes the ``as_held`` way: the
    import costs a process most of a second, and one that never engages
    (the CPU, the sparse families) does not pay it."""
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    return pl, pltpu


def _unflatten_kernel(plan: _Plan, cols: int, *refs):
    """One grid step: ``cols`` 32-bit columns of every row of the shard.

    ``refs``: the pieces in HBM, each ``u32[groups, group * row_words]``
    (a row of it is ``group`` consecutive rows of the matrix end to end,
    which is whole 128-word lines); the output block ``dtype[pack * cols,
    rows]``; a landing buffer, the assembled block and DMA semaphores.

    Row ``group * g + j`` of the matrix is the ``j``-th stretch of
    ``row_words`` words in line ``g``: it starts ``(j * row_words) % 128``
    lanes into a line, so each stretch is fetched as an aligned window
    and read from the buffer at that lane offset; the rows of the
    stretches are interleaved by strided stores; the transpose puts the
    rows in the lanes, where the device's layout has them, and the
    bitcast splits each word into the columns that share it."""
    pl, pltpu = _pallas()
    *pieces, out_ref, landing, block, sems = refs
    rows, words, group = plan.rows, plan.row_words, plan.group
    per_piece = rows // group // len(pieces)
    pack = 4 // plan.dtype.itemsize
    i, last = pl.program_id(0), pl.num_programs(0) - 1
    tail = words - (pl.cdiv(words, cols) - 1) * cols

    lanes = [j * words % _LANES for j in range(group)]

    def fetch(widths):
        copies = [
            pltpu.make_async_copy(
                piece.at[:, pl.ds(
                    pl.multiple_of(j * words // _LANES * _LANES + i * cols,
                                   _LANES), widths[j])],
                landing.at[j, pl.ds(k * per_piece, per_piece),
                           pl.ds(0, widths[j])],
                sems.at[j, k])
            for j in range(group) for k, piece in enumerate(pieces)]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    # a window runs one line past the stretch's columns; the last one
    # stops where the stretch does, which for the last stretch of a line
    # is the end of the piece
    pl.when(i < last)(lambda: fetch([cols + _LANES] * group))
    pl.when(i == last)(lambda: fetch(
        [-(-(lane + tail) // _LANES) * _LANES for lane in lanes]))

    for c in range(cols // _LANES):
        for j, lane in enumerate(lanes):
            block[c, pl.ds(j, rows // group, stride=group), :] = (
                landing[j, :, lane + c * _LANES:lane + (c + 1) * _LANES])
        out_ref[c * pack * _LANES:(c + 1) * pack * _LANES, :] = (
            pltpu.bitcast(block[c].T, out_ref.dtype))


def _unflatten(plan: _Plan, pieces, interpret: bool):
    """The pieces of one shard -> ``dtype[rows, dim]``, the same bits."""
    pl, pltpu = _pallas()
    rows, words, group = plan.rows, plan.row_words, plan.group
    pack = 4 // plan.dtype.itemsize
    cols = _LANES * min(4, _MAX_SHARD_ROWS // rows)
    out = pl.pallas_call(
        functools.partial(_unflatten_kernel, plan, cols),
        grid=(pl.cdiv(words, cols),),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(pieces),
        out_specs=pl.BlockSpec((pack * cols, rows), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pack * words, rows), plan.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, rows // group, cols + _LANES), jnp.uint32),
            pltpu.VMEM((cols // _LANES, rows, _LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA((group, len(pieces))),
        ],
        interpret=interpret,
    )(*pieces)
    # (dim, rows) row-major is (rows, dim) with the rows in the lanes:
    # the transpose is the device's default layout, not a copy
    return out.T


@functools.lru_cache(maxsize=64)
def _restore_program(plan: _Plan):
    """The jitted program that turns a shard's pieces back into
    ``dtype[rows, dim]`` on each device.  Its name carries no ``step``:
    the benchmark finds the train step's runs in a trace by that."""
    line = plan.group * plan.row_words
    interpret = plan.mesh.devices.flat[0].platform != "tpu"

    def local(*pieces):
        # whole lines of a piece side by side: one relayout on the device
        return _unflatten(
            plan, [p.reshape(-1, line) for p in pieces], interpret)

    def feed_restore(*pieces):
        return jax.shard_map(
            local, mesh=plan.mesh,
            in_specs=(P(DATA_AXIS, None, None),) * len(pieces),
            out_specs=P(DATA_AXIS), check_vma=False,
        )(*pieces)

    return jax.jit(
        feed_restore, out_shardings=NamedSharding(plan.mesh, P(DATA_AXIS)))


def place(x, mesh: Mesh):
    """Put one leaf of a host batch on ``mesh``, sharded over ``data``
    along its leading axis."""
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    plan = _plan(x, mesh)
    if plan is None:
        _H2D_BYTES.labels(layout="default").inc(getattr(x, "nbytes", 0))
        return jax.device_put(x, sharding)
    # (device, piece, line, 128): a device's piece is one contiguous run
    # of the host's buffer, so every copy below is a view, never a copy
    words = x.reshape(-1).view(np.uint32).reshape(
        mesh.shape[DATA_AXIS], plan.pieces, -1, _LANES)
    by_device = NamedSharding(mesh, P(DATA_AXIS, None, None))
    pieces = [jax.device_put(words[:, k], by_device)
              for k in range(plan.pieces)]
    _H2D_BYTES.labels(layout="as_held").inc(x.nbytes)
    return _restore_program(plan)(*pieces)
