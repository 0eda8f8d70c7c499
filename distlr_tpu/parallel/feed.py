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
pieces, so that a piece's DMA runs while the next is copied (the trace
still names the copy's chunks ``Transpose::ExecuteChunk``: the
runtime's one relayout routine).  ``(rows, D)``, the dtype and the
layout the step's kernels were compiled for are restored on the device,
at HBM speed, by a small program of the feed's own
(``jit_feed_restore``), dispatched by the thread that did the put.
What the caller gets back is equal in
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

*When* a piece is handed over is the caller's: the runtime starts DMAs
in the order they were put, one at a time, and a program's launch goes
through that same queue, twice (its argument table is a small copy of
its own, behind every copy queued when the program is dispatched with
its inputs ready; the execution is then sequenced behind the copy in
flight: PERF.md section 5).  ``place(x, mesh)`` puts a leaf's pieces one
after the other without a pause, which is right for a leaf placed once
(a PS worker's shard, a test split).  A caller that streams batch after
batch, the sync trainer's producer thread, passes the :class:`Pacer` it
owns: a piece is then put only when the piece ``AHEAD`` before it, of
this leaf or of the batch before, has landed, so the queue a launch
waits behind is ``AHEAD`` pieces long and never a batch, and never
empty either.  The pacer engages where the plan does and does nothing
for a plain ``device_put``, whose copies are megabytes.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distlr_tpu.obs.registry import get_registry
from distlr_tpu.obs.tracing import loop_span
from distlr_tpu.parallel.mesh import DATA_AXIS

_H2D_BYTES = get_registry().counter(
    "distlr_h2d_bytes_total",
    "bytes of sync batches handed to the runtime for the copy to the "
    "device, by how: as_held = the host's own row-major bytes, relaid on "
    "the device; default = a plain device_put, relaid by the runtime's "
    "host threads when the device's layout differs",
    labelnames=("layout",),
)
_PACED_PIECES = get_registry().counter(
    "distlr_h2d_paced_pieces_total",
    "pieces of sync batches put through the producer's pacer "
    "(parallel/feed.py), by whether the producer had to wait for an "
    "earlier piece to land first: a share of no near 1 says the host is "
    "behind the link (AHEAD too small, or the relayout too slow), near 0 "
    "that the link sets the pace",
    labelnames=("waited",),
)

#: A leaf under this size keeps the plain ``device_put``.  The restore
#: program costs a dispatch, a launch (1.2 ms on the v5e) and 8 us a
#: megabyte on the device; the runtime's transpose costs 120 us a
#: megabyte on the host's threads.  Under a few tens of megabytes the
#: launch is most of what there is to win, and a device-bound step (the
#: sparse families: 10 MB leaves, the chip 91% busy) would pay for it.
AS_HELD_MIN_BYTES = 32 << 20
#: Size of the pieces a leaf is handed over in (the most; the plan takes
#: the count that divides the shard's tiles: six of 256 MB for
#: ``bf16[768, 1000000]``).  A piece's DMA is issued when the whole piece
#: is relaid, so the first DMA starts after one piece's copy (7 ms at
#: this size) and not after the whole leaf's (39 ms for 1.5 GB); under a
#: :class:`Pacer` a launch waits behind ``AHEAD + 1`` pieces, 18 ms each;
#: the restore kernel is unrolled over the pieces, so more of them
#: compile slower.  Twelve of 128 MB (the v5e, PERF.md section 6) carry
#: the same bytes a second, halve a launch's wait (a step's ``compute``
#: span 27 ms for 46) and double the producer's wake-ups; the link is the
#: wall either way, so the size stayed.
_PIECE_BYTES = 192 << 20
#: Pieces a :class:`Pacer` lets stand between the producer and the link:
#: piece *k* is put when piece *k - AHEAD* has landed.  The smallest
#: value at which the link never waits for the host: a piece's relayout
#: (7 ms) has to run under the DMA of the piece before it (18 ms), so
#: one piece must still be queued while the next is relaid.  Every piece
#: more is 36 ms that a step's launch waits and a sixth of a batch more
#: on the device.  Read on the v5e, 768 x 1M bfloat16 a batch, six
#: pieces (PERF.md section 6; samples/s, a step's ``compute`` span,
#: ``memory_peak_bytes``): 1: 4,833, 19 ms, 5.15 GB (the link idle for
#: every relayout: 25.4 ms a piece); **2: 7,050, 46 ms, 5.91 GB** (the
#: link's own 7,080); 3: 7,082, 82 ms, 6.94 GB.  The bare stream, no
#: consumer (``benchmarks/exp_h2d_layout.py``): 9.99, 13.80, 13.80 GB/s.
AHEAD = 2
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


class Stopped(Exception):
    """Out of a paced :func:`place`: the pacer's owner has given the fit
    up, and the rest of the batch is not put."""


class Pacer:
    """A streaming producer's hold on the host link, for as long as it
    streams (the sync trainer makes one a ``fit``, on its producer
    thread; see the module's docstring for why).

    It keeps the last ``AHEAD`` pieces it has put, oldest first, across
    leaves and batches; before the next is put the oldest of them has
    landed (``block_until_ready`` on the caller's thread, under an
    ``h2d_pace`` span that carries ``step``) and is let go: the restore
    program holds the pieces it needs.  ``stop`` set by then ends the
    batch with :class:`Stopped`.  ``device_put`` is the runtime's; a
    test hands in pieces whose landing it controls."""

    def __init__(self, stop: threading.Event, device_put=jax.device_put):
        self._stop = stop
        self._device_put = device_put
        self._sent: collections.deque = collections.deque()
        #: the batch the next pieces belong to, set by the producer
        self.step: int | None = None

    def put(self, piece, sharding):
        """``device_put(piece, sharding)``, in its turn."""
        waited = "no"
        if len(self._sent) >= AHEAD:
            oldest = self._sent.popleft()
            if not oldest.is_ready():
                waited = "yes"
                with loop_span("h2d_pace", self.step):
                    oldest.block_until_ready()
            del oldest  # let go before the next piece is allocated
        if self._stop.is_set():
            raise Stopped
        _PACED_PIECES.labels(waited=waited).inc()
        out = self._device_put(piece, sharding)
        self._sent.append(out)
        return out


def place(x, mesh: Mesh, pacer: Pacer | None = None):
    """Put one leaf of a host batch on ``mesh``, sharded over ``data``
    along its leading axis.  With a ``pacer`` the pieces of a large
    dense leaf are put in its turn; without one, at once."""
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
    put = jax.device_put if pacer is None else pacer.put
    pieces = [put(words[:, k], by_device) for k in range(plan.pieces)]
    _H2D_BYTES.labels(layout="as_held").inc(x.nbytes)
    return _restore_program(plan)(*pieces)
