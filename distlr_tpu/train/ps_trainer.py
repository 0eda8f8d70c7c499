"""Parameter-server training mode (sync-BSP or async/Hogwild over the
native KV server group).

This is the reference-faithful alternative to the SPMD fast path: the
control flow is a line-for-line behavioral mirror of the reference worker
(``RunWorker``, ``src/main.cc:124-170`` + ``LR::Train``, ``src/lr.cc:28-45``)
— pull weights, compute the minibatch gradient, push, repeat — except the
gradient math is a jitted JAX step on the accelerator instead of the
O(B*D^2) scalar loop.  Use this mode to reproduce the reference's
*asynchronous* convergence behavior (stale gradients are real here: each
worker pulls whatever the servers have now) and for PS-style deployments
where workers and servers are separate hosts over DCN.

Worker lifecycle parity:
  * every worker computes the identical init (Q2 — reference ``srand(0)``),
    rank 0 pushes it as the first push (server init branch), others wait
    at the group barrier (``src/main.cc:141-150``)
  * sync mode: the blocking push IS the BSP barrier (deferred replies)
  * rank 0 evaluates every ``test_interval`` epochs and prints the
    reference-format line
  * each worker text-exports its final *pulled* weights to
    ``models/part-00{rank+1}`` (Q8: per-worker files, ``src/main.cc:168-169``)
"""

from __future__ import annotations

import collections
import contextlib
import functools
import importlib
import json
import os
import sys
import threading
import time
import types

import jax
import numpy as np

from distlr_tpu.compress import GradientAccumulator
from distlr_tpu.config import Config
from distlr_tpu.data import DataIter
from distlr_tpu.data.iterator import SparseDataIter, Window
from distlr_tpu.data.sharding import part_name
from distlr_tpu.models import get_model, host_math
from distlr_tpu.models.linear import BinaryLR, SoftmaxRegression
from distlr_tpu.obs import dtrace, jaxrt
from distlr_tpu.obs.registry import COUNT_BUCKETS, get_registry
from distlr_tpu.obs.tracing import get_tracer, loop_span, trace_phase
from distlr_tpu.parallel import feed
from distlr_tpu.parallel.mesh import make_mesh
from distlr_tpu.ps import KVWorker, RetryPolicy, ServerGroup
from distlr_tpu.train.export import save_model_text
from distlr_tpu.train.metrics import MetricsLogger, StepTimer
from distlr_tpu.utils.logging import get_logger, log_eval_line

log = get_logger(__name__)

#: Gradient staleness, measured as WEIGHT AGE: seconds between pulling
#: the weights a gradient was computed from and that gradient landing on
#: the servers.  In sync BSP this is just the round latency; in async
#: (Hogwild) it is the real staleness bound the convergence analyses
#: (arXiv:1508.05711) reason about — peers' pushes during this window are
#: what the gradient is stale against.
_STALENESS = get_registry().gauge(
    "distlr_train_staleness_seconds",
    "age of the weights behind the most recent gradient push",
    labelnames=("rank",),
)
#: The SAME staleness, but in the unit the Hogwild convergence analyses
#: actually bound (arXiv:1508.05711 states tau in *updates*, not
#: seconds): the server group's global push clock
#: (:meth:`KVWorker.global_pushes`) sampled after the pull and again
#: just before the push — the delta is how many peer updates landed on
#: the weights this gradient was computed from.  Sampling is throttled
#: (one probed pair per _PUSHES_SAMPLE_INTERVAL_S per worker) so the
#: extra stats round trips never show up in the step rate.
_STALENESS_PUSHES = get_registry().histogram(
    "distlr_train_staleness_pushes",
    "Hogwild gradient staleness in pushes-behind: peer updates applied "
    "between this worker's pull and its push",
    labelnames=("rank",),
    buckets=COUNT_BUCKETS,
)
#: Min seconds between probed pull/push clock pairs per worker.  A stats
#: probe costs one round trip per server rank; at 20 samples/s the
#: overhead is noise even for the ~1 ms localhost dense steps, while a
#: multi-epoch run still banks thousands of histogram observations.
_PUSHES_SAMPLE_INTERVAL_S = 0.05
#: Cooldown before rebuilding a failed push-clock probe connection.  A
#: probe failure used to disable the staleness histogram for the
#: worker's lifetime — defensible when the only failures were dying
#: groups, wrong once a chaos plan makes transient probe faults routine
#: (the reset can land on the probe's frame instead of a training op's).
#: One reconnect attempt per cooldown keeps observability self-healing
#: without reconnect spam against a genuinely gone group.
_PROBE_RETRY_COOLDOWN_S = 5.0
_RESTARTS = get_registry().counter(
    "distlr_ps_worker_restarts_total",
    "PS workers rebuilt in place after a failure (max_restarts path)",
)
#: Current AdaBatch span of each PS worker (batches per push) — moves
#: on the growth schedule, so a dashboard shows the push-traffic divisor
#: next to the push-byte compression ratio it multiplies.
_ACCUM_K = get_registry().gauge(
    "distlr_train_accum_batches",
    "current AdaBatch accumulation span of the PS worker loop "
    "(batches per push)",
    labelnames=("rank",),
)


#: What a worker keeps on its step's device, placed once by
#: :meth:`PSWorker.load_data`: a dense worker's shard (features, labels,
#: mask: the batch of a whole-shard worker, the rows a minibatch worker's
#: windows lie in), a keyed ``sparse_lr`` worker's localised shard
#: (each window's entries sorted by place, lane-dense: place and row in
#: one int32, values, a base row a chunk of lines; labels, mask); a
#: streaming worker's series stays absent.
_RESIDENT_BYTES = get_registry().gauge(
    "distlr_ps_resident_bytes",
    "bytes of a PS worker's shard held on its step's device (a dense "
    "shard's rows; a keyed sparse shard's entries sorted by place within "
    "a window, place and row in one int32 beside the values, a base row "
    "for every eight lines of them, labels and mask)",
    labelnames=("rank",),
)
#: Rounds whose batch was a window of a resident shard, and the real rows
#: those rounds read (a shard's last, short window counts its real rows).
_WINDOW_ROUNDS = get_registry().counter(
    "distlr_ps_window_rounds_total",
    "rounds of a PS worker's dense step whose batch was a window of its "
    "resident shard",
    labelnames=("rank",),
)
_WINDOW_ROWS = get_registry().counter(
    "distlr_ps_window_rows_total",
    "real rows the windowed rounds of a PS worker's dense step read",
    labelnames=("rank",),
)


#: Rank 0's test split where its eval keeps it on the eval's device
#: (:meth:`PSWorker.evaluate`): placed at the first eval, read in place
#: by every later one; 0 where each eval streams the split from the host
#: (it does not fit beside what the device holds, or the eval is numpy's).
_TEST_RESIDENT_BYTES = get_registry().gauge(
    "distlr_ps_test_resident_bytes",
    "bytes of the test split a PS worker's eval keeps on its device "
    "(0 = every eval streams the split from the host)",
    labelnames=("rank",),
)
_EVALS = get_registry().counter(
    "distlr_ps_evals_total",
    "evals of the whole test split a PS worker ran (rank 0's, every "
    "test_interval epochs, and any direct evaluate() call)",
    labelnames=("rank",),
)
_EVAL_ROWS = get_registry().counter(
    "distlr_ps_eval_rows_total",
    "test rows a PS worker's evals covered: the whole split an eval",
    labelnames=("rank",),
)
#: A test split, or a shard read in windows, is kept on the device where
#: the device says it has this many times its bytes free: at the peak of
#: placement the bytes as handed over, their restored form and the
#: row-major relayout stand together (``feed.place``,
#: ``_row_major_program``).
_PLACE_HEADROOM = 3


#: Which device of its process a dense worker's step is pinned to (the
#: ``id`` JAX gives it): worker *i* of a process takes device
#: ``i % len(devices)`` of the job's (``worker_devices``); absent where
#: the step computes in numpy on the host.
_STEP_DEVICE = get_registry().gauge(
    "distlr_ps_step_device",
    "id of the jax device a PS worker's dense step, resident shard, "
    "weights and gradient live on",
    labelnames=("rank",),
)


#: Which program a worker's step ran, a round.  A dense step on a jax
#: device: ``one_pass`` is a row-panel kernel over a resident row-major
#: shard (``ops/pallas_lr.py`` for a binary model,
#: ``ops/pallas_softmax.py`` for a float32 softmax), ``two_pass`` is
#: ``model.grad`` under XLA, whose forward and backward each stream the
#: features.  A keyed step: ``keyed_device`` is the compiled program over
#: a window of the resident localised shard (``_compiled_keyed_fns``),
#: ``keyed_host`` numpy's over a batch from the host (``host_math``).
_GRAD_ROUNDS = get_registry().counter(
    "distlr_ps_grad_rounds_total",
    "rounds of a PS worker's step, by the program that ran it: a dense "
    "step on a jax device by how often it reads the features out of HBM "
    "(one_pass = a row-panel kernel, the binary model's or the float32 "
    "softmax's; two_pass = XLA's forward and backward products), a keyed "
    "step by where it ran (keyed_device = the compiled step over a window "
    "of the resident localised shard, whose compute span says which "
    "program: kernel = lookups in tables held in VMEM, on a TPU; xla = "
    "XLA's gather and segment sum; keyed_host = numpy's over a batch from "
    "the host)",
    labelnames=("rank", "path"),
)
#: A keyed round's size: the unique table rows it pulled and pushed (its
#: keys on the wire where a row is one key, ``RowKeys``) and its real rows.
_KEYED_KEYS = get_registry().counter(
    "distlr_ps_keyed_keys_total",
    "unique table rows (the keys of a binary sparse model) the keyed "
    "rounds of a PS worker pulled and pushed",
    labelnames=("rank",),
)
_KEYED_ROWS = get_registry().counter(
    "distlr_ps_keyed_rows_total",
    "real rows the keyed rounds of a PS worker computed on",
    labelnames=("rank",),
)
_GRAD_DISPATCHES = get_registry().counter(
    "distlr_ps_grad_dispatches_total",
    "rounds of a PS worker's dense step on a jax device, by whether the "
    "program was dispatched with the copy of its weights to the device "
    "still in flight (the round's chain stood enqueued whole) or landed",
    labelnames=("rank", "weights"),
)
_DELAYED_ROUNDS = get_registry().counter(
    "distlr_ps_delayed_rounds_total",
    "rounds of a BSP worker under bounded delay (ps_max_delay=1), by how "
    "many rounds' updates the weights under the round's gradient lacked: "
    "round 0 of a fit runs on what the worker holds with nothing in flight "
    "and counts under behind=\"0\", every other round, round 1 included, "
    "runs on the weights from before the push still at the servers and "
    "counts under \"1\", so a fit of E rounds adds 1 and E - 1",
    labelnames=("rank", "behind"),
)
_KEYED_LINEAGE = get_registry().counter(
    "distlr_ps_keyed_pull_lineage_total",
    "keyed pulls of a sparse_lr worker under bounded delay "
    "(ps_max_delay=1), by how many of this worker's own pushes the reply "
    "is behind: the rounds of this fit before the pull's own, less the "
    "pushes the connection had had acknowledged at the instant the pull "
    "was issued (KVWorker.acknowledged: the connection's op sequence, not "
    "the exchange's book); a fit's first pull counts under behind=\"0\" "
    "and every other under \"1\", so a fit of E rounds adds 1 and E - 1, "
    "and any other label is a broken lineage",
    labelnames=("rank", "behind"),
)
_PANEL_HELD = get_registry().gauge(
    "distlr_ps_grad_panel_held",
    "share f of a row panel the one-pass step keeps in VMEM between its "
    "forward and backward sweeps: X crosses HBM 2 - f times a round "
    "(0 = the two-pass program; the softmax kernel holds a panel whole or "
    "has no plan)",
    labelnames=("rank",),
)
_PANEL_AHEAD = get_registry().gauge(
    "distlr_ps_grad_panel_ahead",
    "share ahead / chunks of the next row panel whose fetches the one-pass "
    "step has queued while the arithmetic between a panel's two sweeps "
    "runs: slots of VMEM beyond a held panel's (0 = a part-held panel, or "
    "the two-pass program; the softmax kernel's second bank is 1)",
    labelnames=("rank",),
)
#: The class axis of a worker's step: the columns of the weights it pulls,
#: of the logits it forms and of the gradient it pushes a feature.
_STEP_CLASSES = get_registry().gauge(
    "distlr_ps_step_classes",
    "class axis of a PS worker's step: values a feature's row of the "
    "weights and of the pushed gradient holds (1 = a binary model)",
    labelnames=("rank",),
)
#: How a resident shard's features are held on the step's device: 1 on the
#: series of the layout they are in, 0 on the other (``_place_rows``).
_RESIDENT_LAYOUTS = ("default", "row_major")
_RESIDENT_LAYOUT = get_registry().gauge(
    "distlr_ps_resident_layout",
    "how a PS worker's resident shard is held: row_major = relaid once on "
    "the device, columns in the lanes and zero-padded, for the one-pass "
    "step (a binary model's or a float32 softmax's row-panel kernel); "
    "default = as the device lays the shape out by itself, which XLA's two "
    "products read with no copy",
    labelnames=("rank", "layout"),
)
#: Where a dense step's parameters take the model's shape.  The wire, the
#: servers and ``grad_step`` carry them as one flat vector; a model whose
#: ``param_shape`` has a rank above 1 restores it inside the jitted
#: program (``device``: what crosses the host link, in and out, is the
#: rank-1 array, a straight copy) or, for the numpy step, as a view on the
#: host (``host``); rank-1 parameters are their own shape (``none``).
_PARAMS_SHAPED_AT = ("none", "host", "device")
_PARAMS_SHAPED = get_registry().gauge(
    "distlr_ps_step_params_shaped",
    "where a PS worker's dense step gives the flat parameter vector the "
    "model's shape: none = rank-1 parameters, host = a numpy view, device "
    "= inside the jitted program (1 on the series in force)",
    labelnames=("rank", "where"),
)


class _StepTrace:
    """StepTimer proxy that puts each ``start()``/``stop()`` bracket —
    one training batch, whatever the exchange — under its own
    distributed-trace root (:mod:`distlr_tpu.obs.dtrace`).  Sampled
    steps get a ``train.step`` span whose KV pulls/pushes carry the
    trace trailer, so the server-side apply is causally linked to the
    pull that staled it on the ``trace-agg`` timeline.  With tracing
    unconfigured, ``new_trace()`` is None and each step pays one
    function call."""

    def __init__(self, timer: StepTimer, rank: int):
        self._timer = timer
        self._rank = rank
        self._scope: contextlib.ExitStack | None = None

    def start(self) -> None:
        if self._scope is not None:  # an exception ended the last step
            self._scope.close()
        self._timer.start()
        ctx = dtrace.new_trace()
        if ctx is not None:
            scope = contextlib.ExitStack()
            scope.enter_context(dtrace.use(ctx))
            scope.enter_context(
                dtrace.span("train.step", tags={"rank": self._rank}))
            self._scope = scope

    def stop(self, n: int):
        if self._scope is not None:
            self._scope.close()
            self._scope = None
        return self._timer.stop(n)

    def __getattr__(self, name):
        return getattr(self._timer, name)


# Below this many per-batch elements (param_dim * batch), the gradient
# step is cheaper on the host CPU backend than the accelerator's dispatch
# latency (~0.1 ms of math vs 1-80 ms of round trip for reference-scale
# D=123 steps: the 4-worker async run at the reference's width went
# dispatch-bound without this).  2^25 elements ≈ 5-10 ms of
# CPU math — the crossover against typical remote-dispatch cost.
_PS_AUTO_CPU_THRESHOLD = 1 << 25
# Below this, even the jitted host-CPU step is dominated by jax dispatch
# overhead (measured 213 us dispatch vs 44 us of numpy math at D=123,
# B=256 — and dispatch is GIL-bound, so threaded workers serialize on
# it): the step drops to plain numpy/BLAS.  f32 numpy is also CLOSER to
# the f32 reference trajectory than the bf16-matmul jax step.
_PS_AUTO_NUMPY_THRESHOLD = 1 << 20
# A keyed step's work is its entries, rows x non-zeros, each a gather, a
# product and a scattered add through an index: numpy's step costs 7 ns
# an entry (4.5 ms at 16,384 x 39; PERF.md section 6, PR 51) where the
# dense thresholds above count elements BLAS streams at 0.15-0.3 ns, so
# an entry stands for this many of them.  16,384 x 39 entries are then
# over the accelerator's threshold, 256 x 39 under numpy's.
_PS_KEYED_ENTRY_WORK = 64
#: The compiled keyed step takes the pulled vector padded to one key
#: count for the whole shard: the largest window's, up to a whole
#: multiple of this, so that no window compiles and workers whose
#: largest windows differ by a few hundred keys share one executable.
_KEYED_KEY_QUANTUM = 8192
#: A keyed shard stays on the device where the device says it has this
#: many times its bytes free: the entries as they are put (sorted where
#: they lie) and the sort's own temporaries, as many bytes again,
#: stand there together once, at load (``_keyed_sort_program``: 2.0
#: times by the compiler's count for a v5e); what stays is the entries.
_KEYED_PLACE_HEADROOM = 2.5
#: A window's entries lie in whole multiples of this many lines of 128:
#: whole grid steps of the step's kernel, whatever it takes at once
#: (``ops.pallas_keyed.keyed_plan`` answers None for lines that are not,
#: and the step is XLA's).  Kept here, where no Pallas is imported.
_KEYED_LINE_QUANTUM = 128
#: threads of one worker's localisation (numpy's indexing releases the
#: interpreter)
_LOCALISE_THREADS = 8


def ps_retry_policy(cfg: Config) -> RetryPolicy | None:
    """The worker-side retry policy a config asks for, or None.

    Retry sits BEFORE the restart/resume ladder: a transient transport
    fault (reset, delay spike, short partition) costs an in-place
    reconnect + re-issue inside :class:`KVWorker`; only when the policy
    exhausts does the failure surface to ``run_ps_workers``'s
    ``max_restarts`` / job-level checkpoint-resume machinery.  Async
    only — a sync (BSP) round's failed push is the named straggler
    signal and must stay fail-fast (the barrier cannot be retried
    without mixing gradients across rounds).
    """
    if cfg.sync_mode:
        return None
    return RetryPolicy.from_config(cfg)


def server_optimizer(cfg: Config) -> str:
    """The update rule the server group actually runs: ``signsgd``
    compression replaces the rule wholesale (1-bit votes through any
    other optimizer would be sign-mean, not majority vote), otherwise
    the configured ``ps_optimizer`` — shared by local spawns and
    ``launch ps-server`` so the two deployment shapes cannot diverge."""
    return "signsgd" if cfg.ps_compress == "signsgd" else cfg.ps_optimizer


def ps_compute_device(cfg: Config, rows: int | None = None, device=None,
                      *, nnz: int | None = None):
    """Where PS workers run their step: the string ``"numpy"``
    (host numpy/BLAS, no jax dispatch), a jax device, or None (default
    backend).  ``device`` is the device of the default backend the job
    gave this worker (:func:`worker_devices`); it stands wherever the
    choice is the default backend, and decides nothing else.

    The reference's workers are host-CPU programs (``src/lr.cc:35-41``);
    our PS mode jits the same math, but for tiny models the accelerator
    round trip per minibatch dwarfs the math, so small steps stay on the
    host — below ``_PS_AUTO_NUMPY_THRESHOLD`` as plain
    numpy (jit dispatch itself dominates there), below
    ``_PS_AUTO_CPU_THRESHOLD`` on the jitted CPU backend — and sends big
    ones to the accelerator.

    ``rows`` is the actual per-step row count (minibatch size, full train
    shard, or full test set — the train and eval steps each pass their
    own).  When it is unknown (``None`` with ``batch_size=-1``), the step
    is assumed big enough to amortize accelerator dispatch.

    ``nnz``: the step is a keyed one over ``rows`` rows of ``nnz`` slots,
    and its work is its entries (``_PS_KEYED_ENTRY_WORK`` elements each),
    not ``param_dim x rows``; the same thresholds then decide.
    """
    if jax.default_backend() == "cpu" and rows is None:
        return device
    if rows is None:
        rows = cfg.batch_size
    if rows <= 0:
        return device
    work = (ps_param_dim(cfg) * rows if nnz is None
            else rows * nnz * _PS_KEYED_ENTRY_WORK)
    if work < _PS_AUTO_NUMPY_THRESHOLD:
        return "numpy"
    if jax.default_backend() == "cpu" or work >= _PS_AUTO_CPU_THRESHOLD:
        return device
    # raises when JAX_PLATFORMS names no cpu backend: the operator
    # excluded the host, and this says so rather than pick for them
    return jax.devices("cpu")[0]


def _jax_device(choice):
    """The jax device a :func:`ps_compute_device` choice that is not
    ``"numpy"`` computes on: None is the default backend's first."""
    return jax.devices()[0] if choice is None else choice


def _describe_compute_device(device) -> str:
    """Log form of a :func:`ps_compute_device` choice."""
    if device == "numpy":
        return "numpy (host, no jax)"
    d = _jax_device(device)
    return f"{d.platform}:{d.device_kind} (id {d.id})"


def _device_free_bytes(device) -> int | None:
    """Bytes ``device`` says it can still hand out, or None where the
    backend keeps no such count (the CPU: host RAM, where the rows
    already are)."""
    stats = device.memory_stats() or {}
    if "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


def worker_devices(n: int) -> list:
    """The step device of each of a process's ``n`` PS workers: worker
    *i* takes local device ``i % len(devices)`` of the default backend,
    so four workers on a four-chip host compute on a chip each, more
    workers than devices wrap, and one device serves every worker."""
    devices = jax.local_devices()
    return [devices[i % len(devices)] for i in range(n)]


@functools.lru_cache(maxsize=None)
def _compiled_fns(model, l2_c: float, l2_scale_by_batch: bool):
    """Jitted gradient step shared across PSWorker instances and runs.

    ``jax.jit`` keys its compile cache on function identity, so
    per-instance lambdas would recompile on every run (models are frozen
    dataclasses — hashable cache keys).  The gradient math reads exactly
    ``l2_c`` and ``l2_scale_by_batch`` from the config (models/linear.py
    ``_l2_grad``), which is why those two are the only cfg-derived keys;
    a model that grows a new cfg dependency fails loudly here with
    AttributeError."""
    gcfg = types.SimpleNamespace(l2_c=l2_c, l2_scale_by_batch=l2_scale_by_batch)

    # a name of its own: a trace shows the program as ``jit_ps_grad_step``,
    # which no other jitted function of the process shares.  ``panels``
    # (static) is the plan of a resident row-major ``X``: the one-pass
    # program of ``_one_pass_plan``, in this same jitted function.
    # ``first`` (traced: one executable for every value) makes the batch
    # a window of the resident arrays from that row on: ``panels.rows``
    # rows read in place by the kernel or, with no plan, ``window``
    # (static) rows sliced out for ``model.grad``.
    #
    # ``w`` and the result are the flat vector the wire carries: the
    # model's shape exists in here alone (the two reshapes; for rank-1
    # parameters they trace to nothing), so the host link moves a rank-1
    # array each way and the relayout is the device's (``_PARAMS_SHAPED``).
    def ps_grad_step(w, X, y, mask, first=None, panels=None, interpret=False,
                     window=None):
        w = w.reshape(model.param_shape)
        if panels is not None:
            g = model.grad_panels(w, (X, y, mask), gcfg, panels,
                                  first=first, interpret=interpret)
        else:
            if first is not None:
                X, y, mask = (
                    jax.lax.dynamic_slice_in_dim(a, first, window)
                    for a in (X, y, mask))
            g = model.grad(w, (X, y, mask), gcfg)
        return g.reshape(-1)

    return jax.jit(ps_grad_step,
                   static_argnames=("panels", "interpret", "window"))


@functools.lru_cache(maxsize=None)
def _compiled_keyed_fns(l2_c: float, l2_scale_by_batch: bool):
    """The jitted keyed step of ``sparse_lr``, shared across workers and
    runs as :func:`_compiled_fns` is: ``host_math.sparse_batch_grad`` on
    the worker's device, over window ``j`` of the resident localised
    shard (:meth:`PSWorker._place_keyed_shard`).

    ``w_u`` is the pulled vector padded to the shard's one key count;
    ``packed`` and ``vals`` are the shard's entries, lane-dense
    (``[windows * lines, 128]``) and within a window **sorted by place**:
    ``packed`` is ``place << row_bits | row`` (the row within the
    window), pad entries (place 0, value 0) among the first; ``y`` and
    ``mask`` the float32 labels and real-row flags, ``windows * rows``
    long; ``bases`` the base row of every chunk of eight lines
    (``[windows, lines / 8]``).  ``j`` is traced, so every window runs the one
    executable and reads its entries where they lie; what crosses the
    host link a round is ``w_u`` in and the gradient out.

    ``plan`` (static; ``ops.pallas_keyed.keyed_plan``) makes the two
    irregular passes the kernel's lookups in tables that stay in VMEM
    (on a TPU: ``_ONE_PASS_PLATFORMS``, as the dense kernels); with None
    they are XLA's gather and ``segment_sum`` over the same sorted
    leaves.  float32 either way; the sums' order is the device's, not
    numpy's."""
    jnp = jax.numpy

    # a name of its own: a trace shows the program as
    # ``jit_ps_keyed_grad_step``
    def ps_keyed_grad_step(w_u, packed, vals, y, mask, bases, j, rows,
                           row_bits, plan=None, interpret=False):
        yw, m = (jax.lax.dynamic_slice_in_dim(a, j * rows, rows)
                 for a in (y, mask))
        n = jnp.maximum(jnp.sum(m), 1.0)
        if plan is not None:
            from distlr_tpu.ops.pallas_keyed import keyed_sums  # noqa: PLC0415

            sums, entries = keyed_sums(w_u, packed, vals, bases, yw, m, j,
                                       plan, l2=bool(l2_c),
                                       interpret=interpret)
        else:
            lines = packed.shape[0] // bases.shape[0]
            pk, v = (jax.lax.dynamic_slice_in_dim(a, j * lines, lines)
                     .reshape(-1) for a in (packed, vals))
            p, r = pk >> row_bits, pk & ((1 << row_bits) - 1)
            keyed = dict(num_segments=w_u.shape[0], indices_are_sorted=True,
                         mode="promise_in_bounds")
            z = jax.ops.segment_sum(
                w_u.at[p].get(mode="promise_in_bounds",
                              indices_are_sorted=True) * v,
                r, num_segments=rows, mode="promise_in_bounds")
            resid = (jax.nn.sigmoid(z) - yw) * m
            sums = jax.ops.segment_sum(
                resid.at[r].get(mode="promise_in_bounds") * v, p, **keyed)
            entries = jax.ops.segment_sum(
                (v != 0).astype(jnp.float32), p, **keyed) if l2_c else None
        g = sums / n
        if l2_c:
            # lazily, on the keys some real entry touches
            # (``sparse_batch_grad``): a pad entry's key decays with the
            # entries that name it, not every round
            term = jnp.float32(l2_c) * w_u * (entries > 0)
            g = g + (term / n if l2_scale_by_batch else term)
        return g

    return jax.jit(ps_keyed_grad_step,
                   static_argnames=("rows", "row_bits", "plan", "interpret"))


@functools.lru_cache(maxsize=None)
def _keyed_sort_program(windows: int, rows: int, slots: int, row_bits: int):
    """The jitted ordering of a placed keyed shard: every one of its
    ``windows`` windows' entries (``[windows * lines, 128]`` as they were
    put, row-major, ``place << row_bits`` and the values; given up to the
    call, and as they stay) gets its row within the window beside its
    place (entry ``i`` of a window is slot ``i % slots`` of row ``i //
    slots``; what lies behind the ``rows x slots`` is row 0's) and the
    window is sorted by ``place << row_bits | row`` with the values;
    and the base row of every chunk from its first entry's place.  Once a
    worker, at load; its name carries no ``step``: the benchmark finds
    the step's runs by that."""
    from distlr_tpu.ops.pallas_keyed import (  # noqa: PLC0415
        CHUNK_LINES,
        chunk_base,
    )

    def ps_keyed_shard_sort(packed, vals):
        packed, vals = (a.reshape(windows, -1) for a in (packed, vals))
        at = jax.lax.broadcasted_iota(jax.numpy.int32, packed.shape, 1)
        row = jax.numpy.where(at < rows * slots, at // slots, 0)
        packed, vals = jax.lax.sort((packed | row, vals), dimension=1,
                                    num_keys=1, is_stable=False)
        first = packed[:, ::CHUNK_LINES * 128] >> row_bits
        return (packed.reshape(-1, 128), vals.reshape(-1, 128),
                chunk_base(first))

    return jax.jit(ps_keyed_shard_sort, donate_argnums=(0, 1))


#: platforms on which a resident shard's step is the one-pass program
#: (elsewhere the kernel runs only interpreted, in tests)
_ONE_PASS_PLATFORMS = ("tpu",)


def _one_pass_plan(model, rows: int, dim: int, device, *, forward=False):
    """The row-panel plan for a resident ``float32[rows, dim]`` shard
    whose step runs on ``device``, or None where the step stays
    ``model.grad`` under XLA: a device that is no TPU, a model with
    ``int8_dot``, rows that are not whole sublane groups (a
    ``BinaryLR``: ``ops.pallas_lr.panel_plan``) or whole 128-row panels
    (a ``SoftmaxRegression``: ``ops.pallas_softmax.softmax_panel_plan``),
    a panel VMEM does not hold (the binary kernel: none of it; the
    softmax kernel: two whole ones beside the weights' parts and the
    gradient).

    The softmax model gets a plan where its arithmetic is the kernel's:
    ``compute_dtype`` float32 (both products the six bfloat16 partial
    products ``Precision.HIGHEST`` is; ``bfloat16`` keeps XLA's one-pass
    products), a class axis whose three parts stand side by side in a
    tile (``3 K <= 128``), and a gradient step: ``forward`` (the rows are
    an eval's) answers None for it, since its forward alone is one XLA
    fusion and one read already.  Without a plan its resident shard
    keeps the device's default layout (``float32[3968, 62061]``: the
    rows in the lanes), which XLA's two products each stream at
    700-740 GB/s with no copy before them (2.89 ms a step on the v5e;
    with the plan the shard crosses HBM once:
    ``benchmarks/exp_softmax_step.py``; PERF.md section 6, PRs 44-46)."""
    if (not isinstance(model, (BinaryLR, SoftmaxRegression)) or model.int8_dot
            or device.platform not in _ONE_PASS_PLATFORMS):
        return None
    if isinstance(model, BinaryLR):
        from distlr_tpu.ops.pallas_lr import panel_plan  # noqa: PLC0415

        return panel_plan(rows, dim)
    if forward or jax.numpy.dtype(model.compute_dtype) != jax.numpy.float32:
        return None
    from distlr_tpu.ops.pallas_softmax import (  # noqa: PLC0415
        softmax_panel_plan,
    )

    return softmax_panel_plan(rows, dim, model.num_classes)


def _import_kernels_beside_the_load(keyed: bool = False) -> None:
    """Start importing a worker's kernels on a thread of their own.
    Pallas costs a process 1.5 s to import (read on the v5e's host,
    PERF.md section 6, PR 46), and ``_one_pass_plan`` wants it only once
    a worker's shard is parsed and densified (a keyed worker's sort and
    plan once its shard is localised and put): on a platform with a
    one-pass program the import runs beside that instead of behind it.
    Whoever needs the modules first waits on the import lock as for any
    import; a failure surfaces there."""
    # each imports ``pallas_lr`` too, the keyed one ``pallas_softmax``
    name = "distlr_tpu.ops." + ("pallas_keyed" if keyed else "pallas_softmax")
    if (name not in sys.modules
            and jax.default_backend() in _ONE_PASS_PLATFORMS):
        threading.Thread(target=importlib.import_module, args=(name,),
                         name="distlr-import-kernels", daemon=True).start()


@functools.lru_cache(maxsize=None)
def _row_major_program(plan, rows=None):
    """The jitted relayout of a placed shard's features to what the
    one-pass step reads (``ops.pallas_lr.pad_columns``), with zero rows
    below up to ``rows`` where the shard's last window is short.  With no
    plan (a device that is no TPU, a model the kernel does not serve) the
    features keep the device's default layout, which is what
    ``model.grad``'s products read without a copy (``_one_pass_plan``),
    and the program adds those rows alone.  Its name carries no ``step``:
    the benchmark finds the step's runs by that."""
    from distlr_tpu.ops.pallas_lr import pad_columns  # noqa: PLC0415

    def ps_shard_row_major(X):
        if plan is None:
            return jax.numpy.pad(X, ((0, rows - X.shape[0]), (0, 0)))
        return pad_columns(X, plan, rows)

    return jax.jit(ps_shard_row_major)


@functools.lru_cache(maxsize=None)
def _compiled_acc(model):
    """Eval takes no cfg, so its cache is keyed on the model alone
    (an L2 sweep must not recompile the full-test-set eval program).
    Returns ``(accuracy, test_logloss)`` — logloss is the driver's
    parity metric (BASELINE.json epochs-to-logloss) — from ONE forward
    pass: the logits are formed once and both numbers read off them.
    ``panels`` (static) is the plan of a resident row-major ``X``
    (``PSWorker._place_rows``): the float32 forward over it, in this same
    jitted function."""
    # a name of its own and no ``step`` in it: a trace shows the program
    # as ``jit_ps_eval``, and the benchmark finds the gradient step's
    # runs by theirs
    def ps_eval(w, X, y, mask, panels=None):
        w = w.reshape(model.param_shape)  # flat in, as ``ps_grad_step``'s
        z = (model.logits(w, X) if panels is None
             else model.logits_panels(w, X, panels))
        return model.eval_from_logits(z, y, mask)

    return jax.jit(ps_eval, static_argnames=("panels",))


def _ps_resume_state(cfg: Config, rank: int):
    """``(start_epoch, weights | None, attempt | None)`` from
    ``cfg.checkpoint_dir`` (``attempt`` is None when no sidecar exists).

    Every rank reads the epoch from a JSON sidecar (``ps_latest.json``,
    written atomically by rank 0 at each checkpoint) so sync-mode workers
    agree on how many epochs remain without concurrently opening the
    orbax manager; rank 0 additionally restores the weights, which reach
    the servers through its init push.  Multi-host deployments need
    ``checkpoint_dir`` on a shared filesystem — the same rule orbax has.
    """
    sidecar = os.path.join(cfg.checkpoint_dir, "ps_latest.json")
    if not os.path.exists(sidecar):
        return 0, None, None
    with open(sidecar) as f:
        data = json.load(f)
    epoch = int(data["epoch"])
    attempt = int(data.get("attempt", 0))
    if rank != 0 or epoch == 0:
        # epoch 0 = a resume-attempt sidecar written before the first
        # checkpoint existed (bump_resume_attempt on a crashed-early run):
        # there is no orbax step to restore, only a barrier generation to
        # advance.
        return epoch, None, attempt
    from distlr_tpu.train.checkpoint import Checkpointer  # noqa: PLC0415

    with Checkpointer(cfg.checkpoint_dir) as ckpt:
        # Restore exactly the sidecar's step, NOT latest: a crash between
        # orbax save N and the sidecar rename leaves latest=N with the
        # sidecar still naming N-interval — resuming N-interval epochs on
        # top of step-N weights would double-train the gap.
        state = ckpt.restore(epoch) if epoch in ckpt.all_steps() else None
    if state is None:  # sidecar without its orbax step: corrupt dir
        raise FileNotFoundError(
            f"{sidecar} names epoch {epoch} but {cfg.checkpoint_dir} holds "
            f"no orbax checkpoint for that step"
        )
    return epoch, np.asarray(state["weights"]).reshape(-1), attempt


def bump_resume_attempt(cfg: Config) -> None:
    """Advance the sidecar's resume-attempt counter (launcher-side).

    Called ONCE per resumed job, on the rank-0 host, BEFORE any worker
    starts (multi-host: start the rank-0 host first).  Each resume then
    rendezvouses on barrier generations the server group has never
    released: a surviving group already released the previous run's
    startup generation, and a barrier vote on a released generation
    returns immediately — which would let peers pull stale crash-time
    weights before rank 0's forced init overwrites them.
    """
    if not cfg.checkpoint_dir:
        return
    sidecar = os.path.join(cfg.checkpoint_dir, "ps_latest.json")
    if os.path.exists(sidecar):
        with open(sidecar) as f:
            data = json.load(f)
    else:
        # Workers can crash BEFORE the first checkpoint writes a sidecar;
        # a resume must still advance the barrier generation, or peers
        # ride barrier(0) — which a surviving server group already
        # released — straight past rank 0's re-init (the race this
        # counter exists to close).  Create the sidecar at epoch 0.
        os.makedirs(cfg.checkpoint_dir, exist_ok=True)
        data = {"epoch": 0, "attempt": 0}
    data["attempt"] = int(data.get("attempt", 0)) + 1
    tmp = sidecar + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f)
    os.replace(tmp, sidecar)


class RowKeys:
    """How a connection addresses table rows ``width`` values wide: THE
    one place that decides it.  Blocked tables gather R-lane rows, sparse
    softmax K-class rows; where the group's range boundaries align to the
    width a row crosses the wire as ONE key (``vals_per_key=width``,
    ps-lite lens-style: ~2.7x fewer keyed bytes at R=32 than R expanded
    keys), elsewhere as ``width`` expanded per-lane keys, with
    bit-identical semantics either way (the server walks rows and flat
    keys with the same loops, slot for slot).  ``vpk`` goes with every
    keyed op of the connection and with the accumulator's ``add_rows`` /
    ``flush_keyed``; :meth:`keys` names unique row ids on the wire."""

    def __init__(self, kv, width: int):
        self.width = width
        self.vpk = width if kv.supports_vals_per_key(width) else 1

    def keys(self, rows: np.ndarray) -> np.ndarray:
        if self.vpk == self.width:
            return rows.astype(np.uint64)
        return host_math.expand_block_keys(rows, self.width)


def keyed_model(cfg: Config):
    """``(row width, gradient)`` of a keyed model, None for a dense one:
    how many values a row of its table holds (one key each, ``RowKeys``)
    and its gradient wrt a batch's unique rows, host-side."""
    return {
        "sparse_lr": (1, host_math.sparse_batch_grad),
        "sparse_softmax": (cfg.num_classes,
                           host_math.sparse_softmax_batch_grad),
        "blocked_lr": (cfg.block_size, host_math.blocked_batch_grad),
    }.get(cfg.model)


def _key_count(keys) -> dict:
    """A keyed operation's span says how many keys it moved."""
    return {} if keys is None else {"keys": len(keys)}


class _PulledVector:
    """A host vector a keyed device step hands its device, of the
    shard's padded key count: a round's pulled weights at its head, then
    zeros (the kernel multiplies the whole table by one-hots, so what
    lies behind the window's keys is an operand too).  The exchange
    pulls into it (``KVWorker.pull(out=)``) and the step puts it as it
    stands; a window of fewer keys than the one before zeroes the
    stretch between, not the vector.  A worker keeps ONE
    (``PSWorker._keyed_ring``, of one) and under bounded delay a **ring
    of two**: round *k*'s weights live in vector *k* mod 2.

    The fence (``_bind_dense_step``'s, kept for a buffer that is
    reused): nothing writes ``buf`` between a round's ``device_put`` of
    it and the return of that round's step, which waits for the gradient
    and so for the program that read the copy.  Serialized, the next
    write is the next round's :meth:`room`, on the same thread, after
    that.  Under bounded delay (:class:`_KeyedDelayed`) the writer is
    the comm thread, and the ring is what keeps it off the vector being
    read: while the loop puts and steps vector *k* mod 2 the one pull
    the comm thread may run is ``L_{k+1}``, into vector (*k* + 1) mod 2;
    ``L_{k+2}``, the next writer of vector *k* mod 2, is handed to the
    comm thread only by round *k*'s ``send``, after its step returned."""

    def __init__(self, padded: int):
        self.buf = np.zeros(padded, np.float32)
        #: values at the head that may be other than zero
        self.filled = 0

    def room(self, count: int) -> np.ndarray:
        """The vector, for a reply of ``count`` values: zeros from
        ``count`` on."""
        if count < self.filled:
            self.buf[count:self.filled] = 0.0
        self.filled = count
        return self.buf

    def holds(self, w_u) -> bool:
        """Whether ``w_u`` is the reply where :meth:`room` had it land:
        the head's view, as ``pull(out=)`` returned it."""
        return (getattr(w_u, "base", None) is self.buf
                and len(w_u) == self.filled)


class _Exchange:
    """An exchange is how a round's weights reach :meth:`PSWorker.fit`'s
    one loop and its gradient the servers: ``weights(keys)`` and
    ``send(g, keys)`` a round.  When nothing of the worker's is in
    flight is the exchange's to say, stated once, here.  ``drain()``:
    nothing is when it returns; ``fit`` calls it before whatever reads
    the servers' weights (rank 0's eval, a checkpoint), and it costs
    nothing where nothing is out.  ``finish()``: ``fit`` is about to
    return; a drain, after which the worker holds the last reply.
    ``epoch_end()``: what an epoch's end means to the protocol; a drain,
    but under bounded delay (:class:`_Delayed`), where an epoch is no
    boundary and the loop's epochs decide no round's staleness.  What
    outlives a ``fit`` stays on the worker: what the benchmark reads
    (``_w_cache``, ``_comm``, ``_in_flight``, ``_keyed_flight``) and the
    staleness stamp
    (``_w_time``, ``_w_pushes``); its ``kv.pull`` / ``kv.push_pull`` /
    ``_comm_pool()`` are looked up at
    every call: taps replace them on the instance.  Each variant stamps
    where its weights arrive (async only counts: in BSP the weight age is
    the round); all feed ``_STALENESS`` and the pushes-behind histogram."""

    def __init__(self, worker, accum: GradientAccumulator | None = None):
        self.w, self.accum = worker, accum
        self.vpk = worker._rows.vpk if worker._rows is not None else 1
        self.sync = worker.cfg.sync_mode
        self._age = None if self.sync else _STALENESS.labels(rank=worker.rank)

    def stamp(self, since: float | None = None) -> None:
        """The weights under the coming gradients are in: pulled at
        ``since`` (now: on arrival), the group's push clock as it is."""
        w = self.w
        w._w_time = time.perf_counter() if since is None else since
        w._w_pushes = None if self.sync else w._sample_push_clock()

    def aged(self) -> None:
        """A gradient is about to leave: the age of the weights under it."""
        if not self.sync:
            w = self.w
            self._age.set(time.perf_counter() - w._w_time)
            w._record_pushes_behind(w._w_pushes)

    def drain(self):
        pass

    def epoch_end(self):
        self.drain()

    def finish(self):
        self.drain()


class _Serialized(_Exchange):
    """The reference's protocol (``src/lr.cc:116-132``): pull,
    then push and wait, two blocking round trips a round.  Dense with
    ``ps_pipeline=False``, and every keyed model in BOTH modes, its step
    on the host or on the device, unless the configuration states a
    bounded delay: the asynchronous ``sparse_lr`` job over a resident,
    windowed shard then runs :class:`_KeyedDelayed` (``ps_max_delay=1``),
    which is another result and not a faster form of this one.  Sync: a
    pull issued before the round's push would read pre-round weights and
    change the BSP trajectory.  Async, where no delay is stated: a pull
    issued before the worker's own push is acknowledged returns other
    weights, so nothing here overlaps; and no fused op exists to REMOVE
    a round trip (pull and push key sets differ per batch).  What a comm
    thread is worth to a keyed round depends on who holds the
    interpreter: with numpy's step on the host (4 workers, D=200k,
    B=512) a comm-thread pipeline read ~10% SLOWER (560-570k serialized
    vs ~490-520k pipelined: the step held the GIL and the per-op executor
    hand-off cost more than the ~50us round trip it hid); with the step
    on the chip (the loop waits in ``block_until_ready``, the comm thread
    in a ctypes call) the delayed exchange hides four fifths of the wire
    and still gains only a few percent at four workers a process, because
    eight threads then share one interpreter and every hand-over waits
    for it (PERF.md section 6, PR 57).

    What a round hands the client was made at load where it could be: a
    resident keyed shard's window names its keys by the frame the
    connection holds (``KVWorker.hold``, checked once when the window
    was localised, :meth:`PSWorker._place_keyed_shard`), so neither op
    passes over them again, and the reply to its pull lands in the
    padded vector the device step takes (:class:`_PulledVector`).  Every
    other caller's keys (a streamed batch's ``np.unique``, a span's
    union) are new arrays a round and are checked by the op, as ever."""

    def weights(self, keys):
        since = time.perf_counter()  # the stamp: from before the pull
        got = self.pull(keys)
        self.stamp(since)
        return got

    def send(self, g, keys):
        self.aged()
        self.push(g, keys)

    def pull(self, keys):
        w = self.w
        with w._span("pull", **_key_count(keys)):
            vector = w._keyed_vector
            if vector is None or keys is None:
                return w.kv.pull(keys=keys, vals_per_key=self.vpk)
            return w.kv.pull(keys=keys, vals_per_key=self.vpk,
                             out=vector.room(len(keys) * self.vpk))

    def push(self, g, keys):
        w = self.w
        with w._span("push", **_key_count(keys)):
            w.kv.wait(w.kv.push(g, keys=keys, vals_per_key=self.vpk))


class _DenseSpan(_Serialized):
    """AdaBatch local accumulation (``--accum-start/--accum-max``) round
    the serialized exchange: push a span's MEAN every k rounds, k growing
    on the schedule, which divides push traffic by k on top of the wire
    codec's ratio; in sync mode the BSP round IS the span, workers in
    lockstep on the shared schedule.  A dense span pulls once, at its
    start, and its age runs from that reply's arrival to the flush.
    Spans flush at an epoch's end too (``drain``: partial), so epochs
    stay self-contained for eval.  The fused and pipelined protocols are
    bypassed: the span already removes k-1 of every k round trips,
    without overlapping state."""

    def weights(self, keys):
        w = self.w
        if self.accum.batches == 0:
            w._w_cache = self.pull(None)
            self.stamp()
        return w._w_cache

    def send(self, g, keys):
        self.accum.add(g)
        if self.accum.ready:
            self.drain()

    def drain(self):
        g = self.accum.flush_dense()
        if g is not None:
            super().send(g, None)


class _KeyedSpan(_Serialized):
    """The accumulated exchange of a keyed model: a round pulls its own
    rows, as serialized, and the flush unions the span's touched rows
    into ONE keyed frame (deduped keys: fewer keyed bytes on top of the
    k-fold frequency cut)."""

    def send(self, g, keys):
        self.aged()
        self.accum.add_rows(keys, g, self.vpk)
        if self.accum.ready:
            self.drain()

    def drain(self):
        # None: an empty span (no batches), symmetric across workers.  A
        # span whose gradients cancelled to exact zeros still pushes an
        # EMPTY keyed frame in sync mode: the BSP "present" vote peers'
        # deferred replies are waiting on.
        flushed = self.accum.flush_keyed(self.vpk)
        if flushed is not None and (flushed[0].size or self.sync):
            self.push(flushed[1], flushed[0])


class _Fused(_Exchange):
    """BSP: ONE deferred round trip a round, a ``push_pull`` blocking on
    the loop's own thread, whose reply the worker holds (``_w_cache``) as
    the next round's weights: the post-round state, what the next pull
    would return (rounds totally ordered, so the trajectory is the
    serialized one bit for bit: the oracle parity tests pin it).  A
    worker that holds no weights yet pulls once, before its first round."""

    def __init__(self, worker):
        super().__init__(worker)
        if worker._w_cache is None:
            with worker._span("pull"):
                reply = worker.kv.pull()
            self.arrived(reply)

    def weights(self, keys):
        return self.w._w_cache

    def arrived(self, reply) -> None:
        self.w._w_cache = reply

    def send(self, g, keys):
        w = self.w
        with w._span("push"):
            w._w_cache = w.kv.push_pull(g)


class _Pipelined(_Fused):
    """The fused round trip double-buffered against compute on the comm
    thread: batch k+1's gradient is computed while batch k's
    ``push_pull`` is in flight (``PSWorker._in_flight``), so the weights
    used are stale by exactly the one in-flight push.  KV ops stay
    serialized on the comm thread (one connection, never two ops
    concurrently).  As it stands it is the async (Hogwild) protocol: none
    is in flight across an epoch's end (``epoch_end`` drains), so a
    whole-shard epoch hides nothing.  :class:`_Delayed` is the same
    pipeline against BSP servers."""

    def arrived(self, reply) -> None:
        self.w._w_cache = reply
        self.stamp()

    def send(self, g, keys):
        w = self.w
        # g rides weights that arrived at _w_time; its round trip starts
        # now, so the age at landing is ~this (+ one in-flight RTT, bounded
        # by the next wait).  The pushes-behind twin: the clock now minus
        # the clock when _w_cache arrived = peer updates plus our own (<=1)
        # in-flight fused push.
        self.aged()
        self._wait()
        # the step's dtrace context and its round count ride along
        # explicitly: the comm thread is a different thread, and the fused
        # op belongs to the step that SUBMITTED it; so does the instant of
        # the hand-over, which ``wire_handoff`` runs from
        w._in_flight = w._comm_pool().submit(
            w._traced_push_pull, g, dtrace.current(), w.rounds,
            time.perf_counter())

    def drain(self):
        # no round's compute is left to hide this push
        self._wait(drain=1)

    def _wait(self, **more):
        reply = self._reply(**more)
        if reply is not None:
            self.arrived(reply)

    def _reply(self, **more):
        """The reply to the push in flight, waited for under a ``push``
        span; None where none is out.  ``reply_wake``, inside it: how
        long a reply that was there waited for this loop to run, from
        the later of the span's start and the ``wire`` span's end to
        ``result()`` returned; ``push`` less it is what the loop waited
        on the wire."""
        w = self.w
        fut, w._in_flight = w._in_flight, None
        if fut is None:
            return None
        with w._span("push", **more):
            reply = fut.result()
            tracer = get_tracer()
            there = max(tracer.opened_at(), w._wire_done)
            tracer.completed("reply_wake", there,
                             time.perf_counter() - there)
            return reply


class _Delayed(_Pipelined):
    """BSP under bounded delay, tau = 1 (``ps_max_delay``; Li et al.,
    OSDI 2014): the pipeline against ``sync=1`` servers.  Round *k*'s
    gradient is computed while the push of round *k* - 1 stands withheld
    at the servers' barrier, on the weights after round *k* - 2.  With
    rounds numbered from 0 inside one ``fit``, ``w_0`` what the worker
    holds when it begins (the pull, or the last reply of the ``fit``
    before) and ``v_k`` the weights under round *k*'s gradient:

        v_0 = v_1 = w_0;   v_k = w_{k-1}  (k >= 2: the reply to the
                                           worker's own push of round k-2)
        w_{k+1} = w_k - lr * (sum over the W workers of g_r(v_k)) / W

    The servers do what they do in lock step (merge W pushes, one update,
    then every reply), and a worker sends push *k* only after the reply
    to push *k* - 1, so no server holds two open rounds.  Every worker
    runs round *k* on the same ``v_k``, bit for bit: the run has a
    trajectory, and it is fixed by the rule and not by timing.  A reply
    is taken where the rule has it (the ``send`` after the next
    gradient) and never because it happened to be in; a ``drain`` that
    waits for one early (rank 0's eval, a checkpoint) sets it aside, and
    the next round still runs one round behind, so where the observers
    fall changes nothing any worker computes.  An epoch is no boundary
    (``epoch_end`` does nothing).  ``finish`` leaves the worker holding
    ``w_E`` with nothing out.  Counted in
    ``distlr_ps_delayed_rounds_total{rank, behind}``."""

    def __init__(self, worker):
        super().__init__(worker)
        #: a reply a drain waited for, ahead of the round it is for
        self.early = None
        #: rounds this fit has begun: its first lacks nothing, every
        #: later one lacks one round's update
        self.begun = 0
        rank = str(worker.rank)
        self._behind = tuple(_DELAYED_ROUNDS.labels(rank=rank, behind=b)
                             for b in ("0", "1"))

    def weights(self, keys):
        self._behind[self.begun > 0].inc()
        self.begun += 1
        return self.w._w_cache

    def send(self, g, keys):
        self._settle()
        super().send(g, keys)

    def epoch_end(self):
        pass

    def drain(self):
        reply = self._reply(drain=1)
        if reply is not None:
            self.early = reply

    def finish(self):
        self.drain()
        self._settle()

    def _settle(self):
        early, self.early = self.early, None
        if early is not None:
            self.arrived(early)


class _KeyedDelayed(_Exchange):
    """The asynchronous keyed ``sparse_lr`` job under bounded delay, tau =
    1 (``ps_max_delay``; Li et al., OSDI 2014, 3.4): the exchange on the
    comm thread, under the step.  Rounds *k* = 0 ... *R* - 1 are numbered
    inside one ``fit``; round *k* reads window *j*(*k*) = *k* mod
    ``len(_window_keys)`` of the resident shard (an epoch is no
    boundary), ``K_k`` is that window's keys, ``L_k`` the keyed pull of
    ``K_k`` and ``P_k`` the keyed push of ``(K_k, g_k)``.  The worker's
    one connection carries, each op blocking and whole, one at a time:

        L_0, L_1, P_0, L_2, P_1, L_3, ..., P_{R-3}, L_{R-1}, P_{R-2}, P_{R-1}

    ``L_{k+1}`` is issued after ``P_{k-1}`` is acknowledged and before
    ``P_k`` is issued, so ``v_k``, the reply to ``L_k`` and the weights
    under ``g_k``, reflects this worker's own pushes 0 ... *k* - 2 whole
    and none later: **exactly one own push behind** for every *k* >= 1,
    none for *k* = 0; peers' pushes as their arrival has them (the
    servers apply on arrival, as ever).  ``g_k`` is the window's gradient
    at ``v_k``.  No pull is issued for a round that will not run.

    How: round *k*'s ``send`` hands the comm thread ONE task, ``P_k``
    then ``L_{k+2}`` (``wire``, with ``push`` and ``pull`` inside, each
    under the step of the round it is for), and then takes the reply to
    ``L_{k+1}``, which the task before ran under round *k*'s ``w_put``,
    ``compute`` and ``grad_d2h``: both inside one ``exchange_wait`` span,
    all of the exchange the loop still sees.  The first ``weights`` of a
    ``fit`` hands over ``L_0`` and ``L_1``, a task each, and waits for
    the first.  ``L_k`` lands in vector *k* mod 2 of the worker's ring
    (:class:`_PulledVector`: the fence).  The next windows' keys are the
    exchange's own to look up (``_window_keys``, by its count of rounds
    begun; the loop's keys are held to them, by identity).

    ``drain`` (rank 0's eval, a checkpoint) waits until nothing of this
    worker's is at the servers; a reply that is in by then, one or two
    rounds early, is still the reply of its round, so where the
    observers fall changes no op's place in the order.  ``epoch_end``
    does nothing.  ``finish`` is a drain that leaves no reply behind.
    The stamp is taken where the pull is issued and the age where the
    push is (on the comm thread, carried with the round).  Counted in
    ``distlr_ps_keyed_pull_lineage_total{rank, behind}`` from the
    connection's own count of acknowledged pushes at each pull's issue.
    A task that raises breaks the exchange: the tasks behind it do
    nothing, and the loop's next wait raises what it raised."""

    def __init__(self, worker, rounds: int = 0):
        super().__init__(worker)
        #: rounds this fit runs, begun so far, and the step of round 0
        self.rounds, self.begun, self.step0 = rounds, 0, 0
        self.windows = worker._window_keys
        #: round -> the future of the task that pulls its weights
        self.pulls: dict = {}
        #: the reply taken for the round about to begin: (weights,
        #: when its pull was issued, the group's push clock after it)
        self.taken = None
        self.broken = False
        self.acked0 = worker.kv.acknowledged("push")

    # -- the loop's side --------------------------------------------------
    def weights(self, keys):
        w, k = self.w, self.begun
        if k == 0:
            self.step0 = w.rounds
            with w._span("exchange_wait", keys=len(keys)):
                for j in range(min(2, self.rounds)):
                    self._submit(pull=j)
                self.taken = self._take(0)
        if keys is not self.windows[k % len(self.windows)]:
            raise RuntimeError(
                f"rank {w.rank}: round {k} of this fit reads another "
                "window than the delayed exchange pulled for")
        self.begun = k + 1
        (reply, w._w_time, w._w_pushes), self.taken = self.taken, None
        return reply

    def send(self, g, keys):
        w, k = self.w, self.begun - 1
        ahead = k + 2 if k + 2 < self.rounds else None
        with w._span("exchange_wait", keys=len(keys)):
            self._submit(push=(k, g, keys, w._w_time, w._w_pushes),
                         pull=ahead)
            if k + 1 < self.rounds:
                self.taken = self._take(k + 1)

    def epoch_end(self):
        pass

    def drain(self):
        flight = self.w._keyed_flight
        if all(f.done() for f in flight):
            self._settle()
            return
        # no round's step is left to hide what is still out
        with self.w._span("exchange_wait", drain=1):
            self._settle()

    def finish(self):
        self.drain()
        if self.pulls:
            raise RuntimeError(
                f"rank {self.w.rank}: replies for rounds "
                f"{sorted(self.pulls)} that did not run are left")

    def _submit(self, push=None, pull=None) -> None:
        w = self.w
        fut = w._comm_pool().submit(
            self._wire, dtrace.current(), w.rounds, time.perf_counter(),
            push, pull)
        w._keyed_flight.append(fut)
        if pull is not None:
            self.pulls[pull] = fut

    def _take(self, k: int):
        """The reply to ``L_k``, waited for inside the caller's
        ``exchange_wait``; ``reply_wake`` as :meth:`_Pipelined._reply`
        records it, from the task's own end."""
        fut = self.pulls.pop(k)
        reply, done = fut.result()
        tracer = get_tracer()
        there = max(tracer.opened_at(), done)
        tracer.completed("reply_wake", there, time.perf_counter() - there)
        if fut in self.w._keyed_flight:
            self._settle(fut)
        return reply

    def _settle(self, upto=None) -> None:
        """Wait for the tasks out, oldest first (through ``upto``), and
        raise what one of them raised."""
        flight = self.w._keyed_flight
        while flight:
            head = flight.popleft()
            head.result()
            if head is upto:
                break

    # -- the comm thread's side -------------------------------------------
    def _wire(self, ctx, step, submitted, push, pull):
        """One task: ``P_k`` then ``L_{k+2}`` (either may be absent),
        under the submitting round's trace context and step.  Returns
        the pull's ``(weights, issued at, push clock)`` or None, and
        when the task ended."""
        if self.broken:
            raise RuntimeError("an earlier exchange of this fit failed")
        w, got = self.w, None
        try:
            with dtrace.use(ctx), loop_span("wire", step, rank=w.rank):
                tracer = get_tracer()
                tracer.completed("wire_handoff", submitted,
                                 tracer.opened_at() - submitted,
                                 inside=False)
                if push is not None:
                    self._push(*push)
                if pull is not None:
                    got = self._pull(pull)
        except BaseException:
            self.broken = True
            raise
        return got, time.perf_counter()

    def _push(self, k, g, keys, since, clock) -> None:
        w = self.w
        self._age.set(time.perf_counter() - since)
        w._record_pushes_behind(clock)
        with loop_span("push", self.step0 + k, rank=w.rank, keys=len(keys)):
            w.kv.wait(w.kv.push(g, keys=keys, vals_per_key=self.vpk))

    def _pull(self, k):
        w = self.w
        keys = self.windows[k % len(self.windows)]
        vector = w._keyed_ring[k % 2]
        behind = k - (w.kv.acknowledged("push") - self.acked0)
        _KEYED_LINEAGE.labels(rank=str(w.rank), behind=str(behind)).inc()
        since = time.perf_counter()  # the stamp: from before the pull
        with loop_span("pull", self.step0 + k, rank=w.rank, keys=len(keys)):
            reply = w.kv.pull(keys=keys, vals_per_key=self.vpk,
                              out=vector.room(len(keys) * self.vpk))
        return reply, since, w._sample_push_clock()


class PSWorker:
    """One worker's training loop against a KV server group.

    Dense models (``binary_lr``, ``softmax``) pull/push the full weight
    vector per batch like the reference worker.  ``sparse_lr`` uses
    *keyed* Push/Pull (the ps-lite capability the reference app never
    exercises — its key set is always dense 0..D-1, ``src/lr.cc:117-121``):
    each batch pulls and pushes only its unique touched columns, so a
    D=1M-bucket CTR model ships KBs per step instead of 12 MB.  Where its
    step is worth the trip its shard is **resident and localised** on the
    step's device and the step is one compiled program there
    (:meth:`_bind_keyed_step`, :meth:`_place_keyed_shard`: each window's
    sorted unique keys and each entry's place among them worked out once,
    at load, and the window's entries sorted by place on the device; on a
    TPU the step's gather and segment sum are then lookups in tables that
    stay in VMEM, ``ops/pallas_keyed.py``;
    ``distlr_ps_grad_rounds_total{path="keyed_device"}``,
    ``distlr_ps_keyed_keys_total``, ``distlr_ps_keyed_rows_total``); the
    other keyed models (``blocked_lr``, ``sparse_softmax``) and every
    small or streamed keyed batch keep numpy's step on the host
    (``path="keyed_host"``).

    Where the data lives.  A dense worker whose step runs on a jax device
    keeps its shard **resident** wherever its iterator serves the shard's
    rows in the order it holds them (``DataIter.held_rows``: no shuffle,
    no Q5 wrap), whatever ``batch_size`` is: :meth:`load_data` places
    ``X``, ``y`` and ``mask`` on the step's device once (``shard_put``,
    through ``parallel.feed.place``) and every round computes on those
    arrays; a round then moves only the weights in and the gradient out.
    With the reference's ``BATCH_SIZE=-1`` the batch is all of them, the
    same rows every iteration.  With ``batch_size`` B a round's batch is
    the **window** ``[k B, k B + B)`` of them (``iterator.Window``), its
    first row an operand of the one compiled step; a shard that B does
    not divide gets masked zero rows below it on the device, so that the
    last, short batch is a window like the others (padded and masked, as
    ``DataIter`` has it; not upstream's Q5 wrap).  It is chosen from what
    the worker sees (the iterator; a step device other than ``"numpy"``;
    for a minibatch worker, that the device says it has room:
    ``_PLACE_HEADROOM`` times the shard's bytes free, no option).
    What still streams numpy batches from host RAM, one ``device_put`` a
    step: a shuffled or ``wrap_compat`` iterator and a shard the device
    has no room for; a keyed model under either, and ``blocked_lr`` and
    ``sparse_softmax`` always, compute in numpy on the host.
    ``distlr_ps_resident_bytes{rank}`` is the shard as held;
    ``distlr_ps_window_rounds_total`` and ``distlr_ps_window_rows_total``
    count the windowed rounds and the real rows they read.

    Which device.  ``ps_compute_device`` decides host or accelerator
    from the step's size (no option); which accelerator device is the job's to say
    (``device``: ``run_ps_workers`` hands worker *i* of a process local
    device ``i % len(devices)``, ``worker_devices``), and the shard, the
    round's weights, the step, the gradient's readback and the eval all
    follow it.  ``distlr_ps_step_device{rank}`` is its id, and the
    ``pinned`` log line names it.  Without one the worker takes the
    default backend's first device.

    The test split (rank 0).  An eval on a jax device keeps the split
    there too: the first :meth:`evaluate` places it once, as the shard is
    placed (``feed.place``, then row-major where a plan reads it), where
    the device says it has room (``_test_on_device``: the split's bytes
    against the device's free memory, no option), and every later eval
    is the weights in, one forward pass over the resident rows
    (``jit_ps_eval``: the logits once, accuracy and logloss off them) and
    two scalars out.  Where it does not fit, or the eval is numpy's, each
    eval reads the host's rows as before.
    ``distlr_ps_test_resident_bytes{rank}`` says which,
    ``distlr_ps_evals_total`` and ``distlr_ps_eval_rows_total`` count.

    How a resident shard is held, and what reads it.  Where the model is
    a ``BinaryLR`` without ``int8_dot``, the device a TPU, the rows a
    step reads (the shard's, or a window's B) whole groups of eight and
    VMEM holds at least a part of a row panel (``_one_pass_plan``: no
    option), ``X`` is relaid once, inside ``shard_put``, to
    ``float32[rows, Dp]`` with the columns in the lanes and zero pad
    columns, and ``jit_ps_grad_step`` is the row-panel kernel of
    ``ops/pallas_lr.py``: the gradient from ONE read of ``X`` out of HBM
    (XLA's forward and backward fusions each stream it).  A window is
    read where it lies: its first row goes to the kernel as a scalar,
    which adds it to a panel's row (no ``dynamic_slice`` of ``X``, which
    would write the window out and read it again).  A ``softmax`` model
    whose ``compute_dtype`` is float32 gets the same where its rows are
    whole 128-row panels, three parts of its class axis fit a tile
    (``3 K <= 128``) and VMEM holds two panels beside the weights' parts
    and the gradient: the kernel of ``ops/pallas_softmax.py``, both
    products float32 by the six bfloat16 partial products ``HIGHEST``
    is.  Everything else (streamed batches, which would pay the relayout
    every round; a bfloat16 or ``int8_dot`` softmax, whose products stay
    XLA's one pass; a B that is no multiple of eight, or of 128 for the
    softmax; the CPU) keeps the device's default layout and
    ``model.grad`` under XLA, over a ``dynamic_slice`` of the resident
    rows where the batch is a window, and its rounds count
    ``path="two_pass"``.  Rank 0's test split keeps the default layout
    under a softmax model whatever the step does: its forward alone is
    one XLA fusion and one read.
    ``distlr_ps_resident_layout{rank, layout}`` says which way a resident
    shard is held (``row_major`` / ``default``),
    ``distlr_ps_step_classes{rank}`` the class axis of the step (20
    columns a feature pulled, computed and pushed; 1 for a binary model).
    ``distlr_ps_grad_rounds_total{rank, path}``
    counts the rounds of each, ``distlr_ps_grad_panel_held{rank}`` is the
    share of a panel VMEM holds, ``distlr_ps_grad_panel_ahead{rank}`` the
    share of the next one it has slots to fetch ahead into.  A round's
    device chain (weights in, the program, the gradient out) is enqueued
    whole and waited for once
    (``_bind_dense_step``); ``distlr_ps_grad_dispatches_total{rank,
    weights}`` counts the rounds whose program was dispatched with the
    weights' copy still ``in_flight``, and those where it had ``landed``.

    ``run()`` is ``load_data()`` (iterators, the device choice, the
    placement, the gradient step; once), ``start()`` (seed push, start
    barrier), ``fit()`` (the epochs) and ``finish()`` (final pull, export,
    exit barrier, retiring the group).  ``fit(epochs=E)`` can be called
    again on the same worker: it loads, places and compiles nothing.

    The loop.  An epoch of ``fit()`` is ONE loop, whatever the model and
    the protocol: a round is its batch (``_rounds``; a keyed one then
    names its unique rows, ``_keyed_round``), ``exchange.weights``,
    :attr:`grad_step` (every model's, bound once by ``load_data()``),
    ``exchange.send``; ``exchange.epoch_end()`` ends the epoch, which to
    every protocol but the bounded delay's means nothing stays in flight
    across it.  The loop asks for nothing in flight (``exchange.drain()``)
    before rank 0's eval and before a checkpoint, and ``exchange.finish()``
    before it returns; when else a push may be out is the exchange's to
    say (:class:`_Exchange`).  The exchange
    is chosen once a ``fit`` from the config and the model (``_exchange``;
    ``_Exchange`` and its variants say what each does: serialized, a
    span's mean, fused in lock step, pipelined in the asynchronous job,
    and with ``ps_max_delay=1`` the pipeline against BSP servers,
    :class:`_Delayed`, or the asynchronous ``sparse_lr`` job's exchange
    under the step, :class:`_KeyedDelayed`); how a keyed
    model's rows cross the wire is ``RowKeys``' to say.

    Spans (``obs.tracing.loop_span``: ``PhaseTracer`` and, while a
    profiler trace is taken, a ``TraceAnnotation``) carry ``step`` = the
    worker's round count (:attr:`rounds`) and ``rank``: ``load_data`` and
    ``shard_put`` once (a resident keyed shard: ``localise`` before it,
    under ``load_data``: every window's unique keys and places; its
    ``shard_put`` holds the device's sort of every window by place); a
    round: ``data_load`` (fetching the batch: the
    numpy slice, nothing for a resident shard or a window of one; a
    keyed batch's unique rows, inside the step: an ``np.unique`` of a
    streamed batch's ids, a lookup for a window localised at load),
    then ``round`` (the
    round's body, ``timer.start()`` to ``timer.stop()``: the parent of
    the spans below, on the tracer alone; its self seconds are the
    loop's own Python between them),
    ``h2d`` (a streamed batch's put, where the step's device is named:
    none opens in a resident or windowed round), ``w_put`` (the
    weights handed to the runtime for the device, the flat vector the
    wire carries whatever the model's shape: staging and enqueue, not
    the copy), ``compute`` (dispatch to the worker's own program
    finished, the rest of the weights' copy before it included; the
    readback is enqueued inside, behind the program; under bounded delay
    it carries ``in_flight``: 0|1, a push of this worker's stood at the
    servers while it ran; on the keyed job ``w_put`` and ``grad_d2h``
    carry it too and it counts the tasks at the comm thread, 0, 1 or 2),
    ``grad_d2h`` (the
    rest of that readback, of a flat gradient: a class axis is restored
    and flattened inside the program, ``distlr_ps_step_params_shaped``),
    ``push`` (the loop blocked on its exchange; a keyed round's
    ``pull``, ``push`` and device chain carry ``keys``, the round's key
    count; in a pipelined
    exchange the waits no round's compute is left to hide carry
    ``drain=1`` beside ``step`` and ``rank``: an epoch's last in the
    asynchronous job; under bounded delay a ``fit``'s last, and rank 0's
    before an eval or a checkpoint; ``reply_wake`` inside it: a reply
    that was there, waiting for the loop to run), ``pull``; ``wire`` on
    the comm thread (a pipelined push-pull, send to reply, with the step
    that submitted it; ``wire_handoff`` under it: the loop's ``submit``
    to the span's start); on the keyed job under bounded delay
    ``exchange_wait`` in ``push``'s stead (the hand-over of round *k*'s
    task and the wait for the reply to round *k* + 1's pull, ``reply_wake``
    inside; ``drain=1`` where an observer or ``fit``'s end waits), and a
    ``wire`` a task on the comm thread with the round's ``push`` and the
    ``pull`` of the round after next inside, each with ``keys`` and the
    ``step`` of the round it is for; ``staleness_probe`` (an asynchronous worker's
    kStats round trips on its probe connection, where one is made);
    after an epoch's last round ``epoch_end`` (to the next epoch's first
    ``data_load``, on the tracer alone: the exchange's end of the epoch,
    so the asynchronous drain's ``push``, the runtime's probes, ``eval``
    and ``checkpoint`` inside it; its self seconds are an epoch's
    bookkeeping); ``barrier_wait``; under ``eval`` (rank 0,
    a dense model): ``eval_pull`` (the weights after the round, pulled as
    the reference's ``Test`` pulls them), ``test_put`` (the first eval
    alone: the test split placed on the eval's device, to ready),
    ``eval_w_put``, ``eval_compute`` (dispatch to accuracy and logloss
    ready; a plain annotation, ``compute`` and the step marker stay the
    gradient step's), ``eval_d2h``.  Under whichever of them
    is open when a keyed operation returns, ``KVWorker`` records that
    operation's six phases, one site for every exchange here
    (``KVWorker._record_op``): ``xchg_enter`` (the op's first
    instruction in Python to the native call's start: its scopes; the
    keys' checks only where they are no frame the connection holds,
    ``KVWorker.hold``), then from the
    native client's own instants ``xchg_send`` (to the last request byte
    handed to the kernel), ``xchg_await`` (to the first reply header
    read: the servers' read, merge, wait for the round and release) and
    ``xchg_recv`` (to the last value read), ``xchg_wake`` (to Python
    running again: the wait for the interpreter) and ``xchg_account``
    (to the op's return: its counters and these spans).  They are
    ``PhaseTracer`` spans with their parent's ``step`` and ``rank`` and
    no annotations: an annotation cannot be entered after the fact.
    """

    def __init__(self, cfg: Config, rank: int, hosts: str, *, train_iter=None,
                 test_iter=None, device=None):
        self.cfg = cfg
        self.rank = rank
        #: the device of the default backend the job gave this worker
        #: (``worker_devices``); None is the backend's first
        self._device = device
        self.model = get_model(cfg)
        if cfg.feature_dtype != "float32":
            # PS workers stream float32 numpy batches from host RAM per
            # step, and a resident shard is that same float32 array
            # placed once: no quantized path exists
            # on this plane. Reject rather than silently ignore the
            # documented +11%/2x expectation.
            raise ValueError(
                "feature_dtype quantization applies to the sync SPMD "
                "trainer's device-resident features; PS mode streams "
                "host batches (set feature_dtype='float32')"
            )
        if cfg.model in ("sparse_lr", "blocked_lr") and cfg.sync_last_gradient:
            # Q1 is a dense-reference parity quirk; with keyed pushes
            # "the last worker's gradient" touches an arbitrary key
            # subset per server — no reference behavior exists to mirror.
            raise ValueError(
                "sync_last_gradient (Q1 compat) is a dense-model parity "
                f"quirk; {cfg.model} PS training requires the correct-mean "
                "update (compat_mode='correct')"
            )
        self.kv = KVWorker(
            hosts, self._param_dim(), client_id=rank,
            timeout_ms=cfg.ps_timeout_ms, sync_group=cfg.sync_mode,
            retry=ps_retry_policy(cfg),
            # negotiated gradient wire codec (dense f32 when the group
            # doesn't advertise it — KVWorker logs the fallback)
            compress=cfg.ps_compress,
        )
        self._hosts = hosts
        # Push-clock probe for the pushes-behind staleness histogram
        # (async only): a DEDICATED connection, because the main one may
        # have a fused op in flight on the comm thread, and KV ops must
        # never overlap on one stream.  Lazy: first sample connects.
        self._push_probe: KVWorker | None = None
        self._push_probe_dead = cfg.sync_mode  # sync BSP: staleness is 0
        self._probe_retry_at = 0.0  # monotonic; rebuild cooldown gate
        self._last_pushes_sample = float("-inf")
        self._staleness_pushes = _STALENESS_PUSHES.labels(rank=str(rank))
        self._train_iter = train_iter
        self._test_iter = test_iter
        # Keyed models never use the jitted dense-batch fns (building
        # them would plant a lambda whose (X, y, mask) signature crashes
        # on padded-COO / blocked batches): their step is numpy host math
        # (models/host_math.py) or, for a resident ``sparse_lr`` shard,
        # the keyed program ``_bind_keyed_step`` binds.
        self._keyed = keyed_model(cfg)
        if self._keyed is not None:
            self._grad_fn = self._acc_fn = None
        else:
            self._grad_fn = _compiled_fns(self.model, cfg.l2_c, bool(cfg.l2_scale_by_batch))
            self._acc_fn = _compiled_acc(self.model)
        # runtime introspection (obs.jaxrt): compile-cache probes for the
        # jitted dense step/eval fns (the keyed device step adds its own
        # when it is bound; numpy host math has nothing to probe); ticked
        # at each epoch end
        self._jit_probes = [
            jaxrt.JitCacheProbe(fn, site)
            for fn, site in ((self._grad_fn, "train.ps.grad"),
                             (self._acc_fn, "train.ps.eval"))
            if fn is not None
        ]
        self.metrics = MetricsLogger()
        # Registry-backed step accounting; "ps" counters are cumulative
        # across the process's worker threads (Hogwild runs several),
        # while each worker's throughput gauge is its own instance.
        # _StepTrace additionally puts each start()/stop() bracket under
        # its own distributed-trace root (sampled per cfg.trace_sample),
        # so the step's pull/push KV ops — and their server-side apply
        # spans — land on the merged trace-agg timeline.
        self.timer = _StepTrace(StepTimer(loop="ps", instance=str(rank)),
                                rank)
        self.final_weights: np.ndarray | None = None
        self._barrier_base = 0
        self._sidecar_attempt = 0
        self._starts = 0
        #: batches this worker has taken: the ``step`` of its spans
        self.rounds = 0
        #: epochs finished; ``fit`` goes on from here
        self.epochs_done = 0
        # bound once by load_data()
        self._train = self._test = None
        self._eval_dev = None
        self._resident = None  # (X, y, mask) on the step's device
        self._resident_rows = 0
        #: a round's batch is a window of the resident rows, not all of them
        self._windowed = False
        #: rank 0's test split on the eval's device, the plan its X is held
        #: for (or None) and its count of rows: bound by the first eval
        #: that keeps it
        self._test_resident = None
        #: the one-pass step's plan where the resident X is held for it
        self._panels = None
        #: ``(flat weights, batch) -> flat float32 gradient``, bound by
        #: load_data(): a dense model's on the device it picked, a keyed
        #: model's over the batch's unique rows, in numpy or, a window of
        #: a resident ``sparse_lr`` shard, on the device
        self.grad_step = None
        #: keyed models: how the connection addresses their rows
        self._rows: RowKeys | None = None
        #: a resident keyed shard: window j's sorted unique keys as the
        #: wire names them, worked out once by load_data()
        self._window_keys: list[np.ndarray] | None = None
        #: and with it: the step's device, the slots a row has, and the
        #: one key count the compiled step takes the pulled vector padded to
        self._keyed_dev = self._keyed_bases = self._keyed_program = None
        self._keyed_row_bits = self._keyed_key_count = 0
        #: and the host vector of that count a round's pull lands in
        #: (``_Serialized.pull``) and its step puts; under bounded delay
        #: the first of a ring of two (round k's in vector k mod 2)
        self._keyed_vector: _PulledVector | None = None
        self._keyed_ring: tuple = ()
        # what the loop's exchange keeps here (``_Exchange``): the flat
        # weights the loop holds now (a span's pull or a fused reply), the
        # staleness stamp of the weights under the next gradient (when
        # they were pulled, the group's push clock then), and a single
        # comm thread (KV ops must never overlap on one connection)
        self._w_cache: np.ndarray | None = None
        self._w_time = 0.0
        self._w_pushes: float | None = None
        self._comm = None
        #: the comm thread's future of a fused push-pull now at the
        #: servers (a pipelined exchange's; :attr:`in_flight`), and when
        #: the comm thread's last ``wire`` span had ended
        self._in_flight = None
        self._wire_done = 0.0
        #: the keyed delayed exchange's tasks at the comm thread, oldest
        #: first, until the loop has waited for them (at most two: the
        #: round's own push and the pull after it, and the one before)
        self._keyed_flight: collections.deque = collections.deque()
        if cfg.model in ("sparse_lr", "blocked_lr") and cfg.l2_c > 0:
            # Keyed PS applies L2 lazily (only a batch's touched keys/rows
            # decay, scaled by touch frequency) while the sync trainer
            # decays every weight every step — same l2_c, different
            # effective regularization (PARITY.md).
            log.warning(
                "%s PS mode applies L2 lazily (touched keys only); "
                "effective regularization differs from the sync trainer "
                "at the same l2_c — see PARITY.md", cfg.model
            )

    def _param_dim(self) -> int:
        return ps_param_dim(self.cfg)

    # -- pushes-behind staleness probing (async/Hogwild only) -------------
    def _sample_push_clock(self) -> float | None:
        """The group's global push clock now, or None when throttled or
        the probe is unavailable.  A non-None return arms one
        :meth:`_record_pushes_behind` call at push time."""
        now = time.perf_counter()
        if now - self._last_pushes_sample < _PUSHES_SAMPLE_INTERVAL_S:
            return None
        if self._push_probe is None:
            if self._push_probe_dead or time.monotonic() < self._probe_retry_at:
                return None
            try:
                self._push_probe = KVWorker(
                    self._hosts, self._param_dim(),
                    client_id=0xFD00 + self.rank, timeout_ms=2000,
                    sync_group=False,
                )
            except Exception:
                # No probe, no histogram for now — observability must
                # never take the training loop down or spin on
                # reconnects; try again after the cooldown (a transient
                # fault must not silence the staleness series forever)
                self._probe_retry_at = (time.monotonic()
                                        + _PROBE_RETRY_COOLDOWN_S)
                return None
        clock = self._probe_push_clock()
        if clock is not None:
            self._last_pushes_sample = now
        return clock

    def _record_pushes_behind(self, pulled_clock: float | None) -> None:
        """Observe the staleness of the gradient about to be pushed:
        push-time clock minus ``pulled_clock`` (the pull-time sample) =
        peer updates the weights aged by while this worker computed."""
        if pulled_clock is None or self._push_probe is None:
            return
        clock = self._probe_push_clock()
        if clock is not None:
            self._staleness_pushes.observe(max(0.0, clock - pulled_clock))

    def _probe_push_clock(self) -> float | None:
        """The probe's round trips, one kStats read a server, under a
        ``staleness_probe`` span: a probe that is made, never a
        throttled return.  None, and the probe dropped, where it
        failed."""
        with self._loop_span("staleness_probe"):
            try:
                return self._push_probe.global_pushes()
            except Exception:
                self._drop_push_probe()
                return None

    def _drop_push_probe(self) -> None:
        # A failed probe may mean the group is dying — or, under a
        # chaos plan, a routine transient fault that happened to land
        # on the probe's connection.  Close it and rebuild after the
        # cooldown rather than going dark for the worker's lifetime; a
        # genuinely gone group just fails the rebuild once per cooldown
        # while the worker's own ops surface the real outage.
        probe, self._push_probe = self._push_probe, None
        self._probe_retry_at = time.monotonic() + _PROBE_RETRY_COOLDOWN_S
        if probe is not None:
            try:
                probe.close()
            except Exception:
                pass

    def _blocked_iter(self, path: str, batch_size: int, *, wrap=False):
        from distlr_tpu.data.hashing import resolve_ctr_fields  # noqa: PLC0415
        from distlr_tpu.data.iterator import BlockedDataIter  # noqa: PLC0415

        cfg = self.cfg
        return BlockedDataIter.from_file(
            path, resolve_ctr_fields(cfg.data_dir, cfg.ctr_fields),
            cfg.num_feature_dim // cfg.block_size, cfg.block_size,
            batch_size, seed=cfg.hash_seed, num_groups=cfg.block_groups,
            wrap_compat=wrap,
        )

    def _load_train_iter(self) -> DataIter:
        # Reference re-reads its shard every epoch (src/main.cc:158-159);
        # we parse once and reset (same samples, no quirk).
        path = os.path.join(self.cfg.data_dir, "train", part_name(self.rank))
        wrap = bool(self.cfg.wrap_final_batch)  # Q5
        if self.cfg.model in ("sparse_lr", "sparse_softmax"):
            return SparseDataIter.from_file(
                path, self.cfg.num_feature_dim, self.cfg.batch_size,
                nnz_max=self.cfg.nnz_max,
                multiclass=self.cfg.model == "sparse_softmax",
                wrap_compat=wrap)
        if self.cfg.model == "blocked_lr":
            return self._blocked_iter(path, self.cfg.batch_size, wrap=wrap)
        return DataIter.from_file(path, self.cfg.num_feature_dim, self.cfg.batch_size,
                                  multiclass=self.cfg.model == "softmax",
                                  wrap_compat=wrap)

    def _load_test_iter(self) -> DataIter:
        path = os.path.join(self.cfg.data_dir, "test", part_name(0))
        if self.cfg.model in ("sparse_lr", "sparse_softmax"):
            return SparseDataIter.from_file(
                path, self.cfg.num_feature_dim, -1,
                nnz_max=self.cfg.nnz_max,
                multiclass=self.cfg.model == "sparse_softmax")
        if self.cfg.model == "blocked_lr":
            return self._blocked_iter(path, -1)
        return DataIter.from_file(path, self.cfg.num_feature_dim, -1,
                                  multiclass=self.cfg.model == "softmax")

    def _span(self, name: str, *, marks_step: bool = False, **more):
        """A span of this worker's loop: its round count and its rank."""
        return loop_span(name, self.rounds, rank=self.rank,
                         marks_step=marks_step, **more)

    def _loop_span(self, name: str):
        """A span on the tracer alone (no annotation: ``compute`` stays
        the step marker and a profiler trace holds what it held):
        ``round`` and ``epoch_end``, whose self seconds are the loop's
        Python between their children, and the ``staleness_probe``."""
        return trace_phase(name, self.rounds, self.rank)

    @property
    def in_flight(self) -> int:
        """What of this worker's is at the servers or on its way there:
        1 while a fused push-pull is (submitted by a pipelined exchange,
        its reply not yet taken), and the keyed delayed exchange's tasks
        the comm thread has not finished (0, 1 or 2)."""
        return (int(self._in_flight is not None)
                + sum(not f.done() for f in tuple(self._keyed_flight)))

    def _compute_span(self):
        """A dense round's ``compute`` span and step marker.  Under
        bounded delay (``ps_max_delay``) it also says what stood in
        flight while it ran: ``in_flight=1`` where a push of this
        worker's was at the servers, 0 where none was."""
        if self.cfg.ps_max_delay:
            return self._span("compute", marks_step=True,
                              in_flight=self.in_flight)
        return self._span("compute", marks_step=True)

    def load_data(self) -> None:
        """Once a worker: bind the iterators (parsing the shards where
        none were handed in), pick the device of the dense step and of
        the eval, and place the shard there (class docstring).
        A second call does nothing."""
        if self._train is not None:
            return
        with self._span("load_data"):
            _import_kernels_beside_the_load(keyed=self._grad_fn is None)
            train = (self._train_iter if self._train_iter is not None
                     else self._load_train_iter())
            test = self._test_iter if self._test_iter is not None else (
                self._load_test_iter() if self.rank == 0 else None)
            if self._grad_fn is None:
                self._bind_keyed_step(train)
            else:
                self._bind_dense_step(train, test)
        self._train, self._test = train, test

    def _bind_keyed_step(self, train) -> None:
        """Keyed Push/Pull: only a batch's unique touched columns (sparse)
        or table rows (blocked, sparse softmax) travel: ps-lite's
        sliced-key capability, SURVEY.md §2.2 E1.d/g, which the reference
        app itself never exercises.

        Where the step runs.  A ``sparse_lr`` worker whose iterator
        serves its rows in the order it holds them
        (``SparseDataIter.held_rows``), whose step is worth the trip
        (``ps_compute_device``: rows x non-zeros, no option) and whose
        device has room keeps its shard **resident and localised**
        (:meth:`_place_keyed_shard`) and runs ``jit_ps_keyed_grad_step``
        there, a window a round.  Everything else keeps numpy's step over
        a batch from the host (``host_math``): ``blocked_lr`` and
        ``sparse_softmax`` (no device step is written for them), a
        shuffled or ``wrap_final_batch`` iterator, a small step, a shard
        the device has no room for.  The log line says which, and why.
        The eval is numpy's either way."""
        cfg = self.cfg
        width, grad = self._keyed
        self._rows = rows = RowKeys(self.kv, width)
        rank = str(self.rank)
        self._keyed_keys = _KEYED_KEYS.labels(rank=rank)
        self._keyed_rows = _KEYED_ROWS.labels(rank=rank)
        why = self._place_keyed_shard(train)
        if why is None:
            self.grad_step = self._keyed_device_step(train)
            log.info("rank %d %s steps pinned: train -> %s, a window of the "
                     "resident localised shard a round (%d windows of %d "
                     "rows, %d keys a step, program=%s); eval in numpy on "
                     "the host", self.rank, cfg.model,
                     _describe_compute_device(self._keyed_dev),
                     len(self._window_keys), train.batch_size,
                     self._keyed_key_count, self._keyed_program)
            return
        if cfg.ps_max_delay:
            raise ValueError(
                f"ps_max_delay=1: rank {self.rank}'s shard is not resident "
                f"and windowed on its step's device ({why}); the delayed "
                "keyed exchange pulls the NEXT windows' keys under the "
                "step, which only a shard localised at load names ahead of "
                "its rounds, and nothing falls back to the serialized "
                "exchange: unset ps_max_delay, or serve the shard in the "
                "order it is held and at a size the device takes")
        log.info("rank %d %s steps and eval run in numpy on the host (%s)",
                 self.rank, cfg.model, why)
        if width > 1:
            # visible (and test-assertable) record of which wire encoding
            # the keyed rounds use
            log.info("rank %d keyed wire encoding: %s", self.rank,
                     f"vals_per_key={rows.vpk}" if rows.vpk > 1
                     else "expanded per-lane keys")
        l2 = (cfg.l2_c, bool(cfg.l2_scale_by_batch))
        host_rounds = _GRAD_ROUNDS.labels(rank=rank, path="keyed_host")

        def grad_step(w_u, batch):
            with self._span("compute", marks_step=True):
                if width > 1:
                    w_u = w_u.reshape(-1, width)
                g = grad(w_u, *batch, *l2).reshape(-1)
            host_rounds.inc()
            return g
        self.grad_step = grad_step

    def _place_keyed_shard(self, train) -> str | None:
        """Localise a ``sparse_lr`` worker's shard and place it on its
        step's device, once; None where that was done, else why the
        worker keeps the host path (the log line's words).

        Localisation: window *j* is rows ``[j B, j B + B)`` of the shard
        in held order, every epoch from row 0, the last one short where
        ``B`` does not divide the shard.  For each, once, under a
        ``localise`` span: its sorted unique columns (``_window_keys``,
        as ``RowKeys.keys`` names them on the wire) and, in the ids'
        stead, each entry's place among them (``host_math.localise``, on
        a few threads), ``row_bits`` to the left: room for the entry's
        row within the window.  The connection takes each window's keys
        into its keeping there (``KVWorker.hold``): the check the native
        range slicer needs (ascending, in range) is made here, once a
        window, on those threads, and the read-only frame it returns is
        what ``_window_keys`` holds.  What a round's ``data_load`` then
        does is look ``_window_keys[j]`` up, and the round's pull and
        push, handed that frame, make no pass over its keys.

        Placement, under ``shard_put``: the packed indices (int32) and
        the values (float32) as ``[windows * lines, 128]``, a window's ``B
        x slots`` entries in ``lines`` lines of 128 (the TPU would pad a
        ``[rows, 39]`` array's lanes to 128, 3.3 times the bytes; whole
        grid steps of the step's kernel), with zero entries (place 0,
        value 0: they add nothing and name no key of their own) behind a
        window's last; labels and real-row flags as float32, ``windows
        * B`` long.  Then ONE device program (``_keyed_sort_program``)
        puts each entry's row beside its place (``place << row_bits |
        row``), **sorts every window by place** (a sorted window's
        consecutive entries name consecutive places, which is what lets
        the step look its weights up in a few rows of their table) and
        gives each chunk of lines its base row.  The span ends with the
        leaves put and that program handed to the device, not with its
        end (0.37 s for 240 windows on the v5e, PERF.md section 6, PR
        52): it runs beside whatever the host does next (in a job of
        several workers a process, the next worker's localisation), and
        the first step queues behind it.  The host's row arrays are let
        go afterwards
        (``SparseDataIter.drop_rows``).  ``distlr_ps_resident_bytes`` is
        those four leaves and the bases as they stay."""
        cfg = self.cfg
        if cfg.model != "sparse_lr":
            return f"no device step is written for {cfg.model}"
        held = train.held_rows() if isinstance(train, SparseDataIter) else None
        if held is None:
            return ("the iterator does not serve the rows in the order it "
                    "holds them: shuffled, or a wrapped short last batch")
        cols, vals, y, mask = held
        B, slots = train.batch_size, cols.shape[1]
        step_dev = ps_compute_device(cfg, B, self._device, nnz=slots)
        if step_dev == "numpy":
            return (f"{B} rows x {slots} slots a step are under the size "
                    "worth a jax dispatch")
        row_bits = max(B - 1, 1).bit_length()
        device = _jax_device(step_dev)
        windows = train.num_batches
        lanes = 128
        lines = (-(-B * slots // (lanes * _KEYED_LINE_QUANTUM))
                 * _KEYED_LINE_QUANTUM)
        nbytes = windows * (lines * lanes * 8 + B * 8)
        free = _device_free_bytes(device)
        if free is not None and free < _KEYED_PLACE_HEADROOM * nbytes:
            return (f"{nbytes} bytes localised, {free} free on "
                    f"{_describe_compute_device(step_dev)}")
        n = train.num_samples
        exact = windows * B == n and lines * lanes == B * slots
        packed = np.zeros((windows, lines * lanes), np.int32)
        # whole windows of whole lines are the host's own bytes, seen anew
        values = (np.ascontiguousarray(vals, np.float32).reshape(windows, -1)
                  if exact else np.zeros((windows, lines * lanes), np.float32))
        dim = self._param_dim()

        def one(j):
            at = slice(j * B, min(j * B + B, n))
            keys, place = host_math.localise(cols[at], dim, row_bits)
            packed[j, :place.size] = place.reshape(-1)
            if not exact:
                values[j, :place.size] = vals[at].reshape(-1)
            return self.kv.hold(self._rows.keys(keys), self._rows.vpk)

        with self._span("localise"):
            from concurrent.futures import ThreadPoolExecutor  # noqa: PLC0415

            with ThreadPoolExecutor(_LOCALISE_THREADS) as pool:
                self._window_keys = list(pool.map(one, range(windows)))
        most = max(len(k) for k in self._window_keys)
        if most - 1 >> 31 - row_bits:
            self._window_keys = None
            return (f"a place among {most} keys and a row among {B} do not "
                    "share an int32")
        self._keyed_key_count = (-(-most // _KEYED_KEY_QUANTUM)
                                 * _KEYED_KEY_QUANTUM)
        below = windows * B - n
        mesh = make_mesh(devices=[device])
        with self._span("shard_put"):
            packed, values, *rows = jax.block_until_ready(
                [feed.place(a, mesh) for a in (
                    packed.reshape(-1, lanes), values.reshape(-1, lanes),
                    np.pad(np.asarray(y, np.float32), (0, below)),
                    np.pad(np.asarray(mask, np.float32), (0, below)))])
            packed, values, self._keyed_bases = _keyed_sort_program(
                windows, B, slots, row_bits)(packed, values)
            self._resident = (packed, values, *rows)
        self._keyed_dev, self._keyed_row_bits = step_dev, row_bits
        self._windowed = True
        _RESIDENT_BYTES.labels(rank=str(self.rank)).set(
            sum(a.nbytes for a in (*self._resident, self._keyed_bases)))
        _STEP_DEVICE.labels(rank=str(self.rank)).set(device.id)
        train.drop_rows()
        return None

    def _keyed_plan(self, train):
        """The kernel's plan for this worker's placed shard, or None
        where its step stays XLA's: a device that is no TPU
        (``_ONE_PASS_PLATFORMS``, the dense kernels' rule), or a shape the
        kernel does not serve (``ops.pallas_keyed.keyed_plan``)."""
        if _jax_device(self._keyed_dev).platform not in _ONE_PASS_PLATFORMS:
            return None
        from distlr_tpu.ops.pallas_keyed import keyed_plan  # noqa: PLC0415

        lines = self._resident[0].shape[0] // self._keyed_bases.shape[0]
        return keyed_plan(train.batch_size, lines, self._keyed_key_count,
                          self._keyed_row_bits)

    def _keyed_device_step(self, train):
        """The keyed round's device chain, enqueued whole and waited for
        once as the dense step's is (``_bind_dense_step``): the pulled
        vector, padded to the shard's key count where the reply landed
        (:class:`_PulledVector`; weights that are not its head, a test's
        or a probe's own array, are staged into it first), handed over
        (``w_put``), ``jit_ps_keyed_grad_step`` over the round's window
        (``compute``), the readback of the gradient (``grad_d2h``), of
        which the window's own keys' part goes to the push.  The three
        spans carry the round's key count (``keys``), ``compute`` also
        which program ran: ``program="kernel"`` (the lookups in VMEM,
        ``_keyed_plan``) or ``"xla"``."""
        cfg = self.cfg
        fn = _compiled_keyed_fns(cfg.l2_c, bool(cfg.l2_scale_by_batch))
        self._jit_probes.append(jaxrt.JitCacheProbe(fn, "train.ps.keyed_grad"))
        step_dev = self._keyed_dev
        ring = self._keyed_ring = tuple(
            _PulledVector(self._keyed_key_count)
            for _ in range(2 if cfg.ps_max_delay else 1))
        self._keyed_vector = ring[0]
        # under bounded delay the chain's spans say what of this worker's
        # stood at the comm thread when each began
        flight = ((lambda: {"in_flight": self.in_flight})
                  if cfg.ps_max_delay else dict)
        shape = dict(rows=train.batch_size, row_bits=self._keyed_row_bits,
                     plan=self._keyed_plan(train))
        self._keyed_program = program = (
            "xla" if shape["plan"] is None else "kernel")
        rank = str(self.rank)
        device_rounds = _GRAD_ROUNDS.labels(rank=rank, path="keyed_device")
        dispatches = {landed: _GRAD_DISPATCHES.labels(
            rank=rank, weights="landed" if landed else "in_flight")
            for landed in (False, True)}

        def grad_step(w_u, window):
            keys = len(w_u)
            with self._span("w_put", keys=keys, **flight()):
                # the hand-over (enqueue), not the copy
                vector = next((v for v in ring if v.holds(w_u)), None)
                if vector is None:
                    vector = ring[0]
                    vector.room(keys)[:keys] = w_u
                w = jax.device_put(vector.buf, step_dev)
            with self._span("compute", marks_step=True, keys=keys,
                            program=program, **flight()):
                landed = w.is_ready()
                g = fn(w, *self._resident, self._keyed_bases,
                       np.int32(window.first // shape["rows"]), **shape)
                g.copy_to_host_async()
                # the span ends with this worker's own program and
                # encloses no other's, as the dense step's
                jax.block_until_ready(g)
            device_rounds.inc()
            dispatches[landed].inc()
            with self._span("grad_d2h", keys=keys, **flight()):
                # the rest of the copy already under way; the client
                # sends the window's own keys' part of this buffer
                return np.asarray(g)[:keys]
        return grad_step

    def _bind_dense_step(self, train, test) -> None:
        cfg = self.cfg
        # Committed inputs pin each jitted step to its device; jax.jit
        # keys its executable cache on input placement, so both
        # backends can coexist in one process.  Train and eval steps
        # size their choice independently (a tiny minibatch must not
        # drag a huge full-test-set eval onto the host CPU).
        train_rows = cfg.batch_size if cfg.batch_size > 0 else train.num_samples
        step_dev = ps_compute_device(cfg, train_rows, self._device)
        self._eval_dev = (
            ps_compute_device(cfg, test.num_samples, self._device)
            if test is not None else None)
        log.info(
            "rank %d dense steps pinned: train -> %s%s",
            self.rank, _describe_compute_device(step_dev),
            "" if test is None
            else f", eval -> {_describe_compute_device(self._eval_dev)}")
        K = cfg.num_classes if cfg.model == "softmax" else None
        _STEP_CLASSES.labels(rank=str(self.rank)).set(K or 1)
        shaped_at = ("none" if len(self.model.param_shape) == 1
                     else "host" if step_dev == "numpy" else "device")
        for where in _PARAMS_SHAPED_AT:
            _PARAMS_SHAPED.labels(rank=str(self.rank), where=where).set(
                where == shaped_at)
        if step_dev == "numpy":
            def grad_step(wf, batch):
                W = wf.reshape(cfg.num_feature_dim, K) if K else wf
                with self._compute_span():
                    return host_math.dense_grad(
                        W, *batch, cfg.l2_c, bool(cfg.l2_scale_by_batch), K
                    ).reshape(-1)
        else:
            self._resident = self._place_shard(train, step_dev)
            rank = str(self.rank)
            _STEP_DEVICE.labels(rank=rank).set(_jax_device(step_dev).id)
            plan = self._panels
            if self._resident is not None:
                held = "default" if plan is None else "row_major"
                for layout in _RESIDENT_LAYOUTS:
                    _RESIDENT_LAYOUT.labels(rank=rank, layout=layout).set(
                        layout == held)
            _PANEL_HELD.labels(rank=rank).set(plan.held_share if plan else 0.0)
            _PANEL_AHEAD.labels(rank=rank).set(
                plan.ahead_share if plan else 0.0)
            # the kernel is interpreted off the TPU (tests)
            one_pass = {} if plan is None else dict(
                panels=plan,
                interpret=_jax_device(step_dev).platform != "tpu")
            # a window of the resident rows: the plan's rows where the
            # kernel reads it in place, else sliced out for ``model.grad``
            windowed = one_pass or dict(window=train.batch_size)
            window_rounds = _WINDOW_ROUNDS.labels(rank=rank)
            window_rows = _WINDOW_ROWS.labels(rank=rank)

            def grad_step(wf, batch):
                # The round's device chain is enqueued whole and waited
                # for once: the weights' copy, the program behind it, the
                # readback behind the program.  The runtime orders the
                # three on the device; the host stands between no two.
                # The fence on ``wf``: the readback cannot end before the
                # program has run, nor the program start before the copy
                # has left the host, so ``wf`` is free again when this
                # returns and nobody may write it before.  The exchanges
                # hand in what ``pull`` / ``push_pull`` returned, a fresh
                # array every reply and never written in place; a client
                # that keeps its reply buffer has to keep this fence.
                #
                # the plan goes with the resident rows alone: their X is
                # held for it (``_place_shard``)
                if isinstance(batch, Window):
                    # the first row is an operand, not a shape: every
                    # window runs the one executable
                    how = dict(windowed, first=np.int32(batch.first))
                    window_rounds.inc()
                    window_rows.inc(batch.rows)
                    batch = self._resident
                elif batch is self._resident:
                    how = one_pass
                else:
                    how = {}
                    with self._span("h2d"):
                        batch = self._place(step_dev, *batch)
                with self._span("w_put"):
                    # the hand-over (staging, enqueue), not the copy; the
                    # flat vector as it is: the program shapes it
                    w = jax.device_put(wf, step_dev)
                with self._compute_span():
                    landed = w.is_ready()
                    g = self._grad_fn(w, *batch, **how)
                    g.copy_to_host_async()
                    # the span ends with this worker's own program and
                    # encloses no other's: the readers of ``compute`` and
                    # of its step marker rest on that
                    jax.block_until_ready(g)
                _GRAD_ROUNDS.labels(
                    rank=rank,
                    path="one_pass" if "panels" in how else "two_pass").inc()
                _GRAD_DISPATCHES.labels(
                    rank=rank,
                    weights="landed" if landed else "in_flight").inc()
                with self._span("grad_d2h"):
                    # the rest of the copy already under way; the program's
                    # result is flat and the client sends from this buffer
                    return np.asarray(g)
        self.grad_step = grad_step

    def _place_shard(self, train, step_dev):
        """``(X, y, mask)`` of the shard on the step's device, or None
        where the worker streams.  A whole-shard batch: every epoch
        yields these same rows, so they cross to the device once.  A
        minibatch iterator that serves the rows in the order it holds
        them (``DataIter.held_rows``): every batch is a window of them,
        so they cross once too, where the device says it has room
        (``_PLACE_HEADROOM`` times their bytes free; no option), with
        masked zero rows below where the last batch is short, so that
        every window has ``batch_size`` rows to read.  Each leaf goes
        through ``feed.place``, which picks the layout it is handed over
        in and counts it in ``distlr_h2d_bytes_total``; where the
        one-pass step will read them (``_one_pass_plan``), the features
        are then relaid on the device, row-major and padded."""
        device = _jax_device(step_dev)
        if train.num_batches == 1 and train.batch_size == train.num_samples:
            # the arrays the iterator holds where the batch is just those
            # (no 1.5 GB gather of every row in turn, as ``next_batch``
            # makes)
            batch = train.whole_shard()
            if batch is None:
                train.reset()
                batch = train.next_batch()
            window = None
        else:
            batch = train.held_rows()
            if batch is None:
                return None
            free = _device_free_bytes(device)
            nbytes = sum(a.nbytes for a in batch)
            if free is not None and free < _PLACE_HEADROOM * nbytes:
                log.info("rank %d streams its shard: %d bytes, %d free on %s",
                         self.rank, nbytes, free,
                         _describe_compute_device(step_dev))
                return None
            window = train.batch_size
        placed, self._panels = self._place_rows(
            "shard_put", batch, device, window=window,
            below=0 if window is None else max(
                0, train.num_batches * window - train.num_samples))
        self._windowed = window is not None
        self._resident_rows = int(batch[-1].sum())
        _RESIDENT_BYTES.labels(rank=str(self.rank)).set(
            sum(a.nbytes for a in batch))
        return placed

    def _place_rows(self, span: str, batch, device, *, window=None, below=0,
                    forward=False):
        """``(X, y, mask)`` on ``device`` to stay, under a span called
        ``span``, and the row-panel plan ``X`` is held for (or None):
        each leaf through ``feed.place``; where a plan reads the rows
        (``_one_pass_plan``) the features are then relaid on the device,
        row-major and padded.  ``window``: the rows a step reads of them
        (all, where None), which the plan is for; ``below``: masked zero
        rows to stand under them, the features' made on the device;
        ``forward``: the rows are an eval's, read by no gradient step."""
        X, y, mask = batch
        if below:
            y = np.concatenate([y, np.zeros(below, y.dtype)])
            mask = np.concatenate([mask, np.zeros(below, mask.dtype)])
        mesh = make_mesh(devices=[device])
        plan = _one_pass_plan(self.model, window or X.shape[0], X.shape[1],
                              device, forward=forward)
        with self._span(span):
            X, y, mask = (feed.place(a, mesh) for a in (X, y, mask))
            if plan is not None or below:
                # once, on the device: the columns into the lanes
                X = _row_major_program(plan, len(y) if below else None)(X)
            placed = jax.block_until_ready((X, y, mask))
        return placed, plan

    def _rounds(self, train):
        """An epoch's rounds: each batch beside its count of real rows.
        ``data_load`` is what fetching a dense one cost the loop: the
        numpy slice of a streamed batch; nothing for a resident shard, of
        which a minibatch is a :class:`~distlr_tpu.data.iterator.Window`.
        A keyed batch comes as the iterator has it (a resident keyed
        shard's as a ``Window``): its ``data_load`` is
        :meth:`_keyed_round`'s, inside the step."""
        keyed, resident, windowed = (
            self._rows is not None, self._resident, self._windowed)
        for _ in range(train.num_batches):
            self.rounds += 1
            if keyed:
                batch = train.next_window() if windowed else train.next_batch()
            else:
                with self._span("data_load"):
                    batch = (train.next_batch() if resident is None
                             else train.next_window() if windowed
                             else resident)
            yield batch, (int(batch[-1].sum()) if resident is None
                          else batch.rows if windowed
                          else self._resident_rows)

    def _keyed_round(self, batch, n_real: int):
        """A keyed round's ``data_load``: the batch's unique rows as wire
        keys, and each entry's place among them in the ids' stead.  A
        window of a resident shard was localised at load: a lookup."""
        with self._span("data_load"):
            if isinstance(batch, Window):
                keys = self._window_keys[batch.first // self._train.batch_size]
                unique = len(keys)
            else:
                ids = batch[0]
                ub, pos = np.unique(ids, return_inverse=True)
                batch, keys = ((pos.reshape(ids.shape), *batch[1:]),
                               self._rows.keys(ub))
                unique = len(ub)
        self._keyed_keys.inc(unique)
        self._keyed_rows.inc(n_real)
        return batch, keys

    def start(self, *, resume=False, rejoin=False) -> None:
        """Seed the group (rank 0) and meet the peers at the start
        barrier; on ``resume``, from the checkpoint's epoch."""
        cfg = self.cfg
        start_epoch = 0
        restored = None
        attempt = None
        if resume and cfg.checkpoint_dir:
            start_epoch, restored, attempt = _ps_resume_state(cfg, self.rank)
        self.epochs_done = max(self.epochs_done, start_epoch)

        # Identical deterministic init on every worker (Q2); only rank 0
        # pushes — via the IDEMPOTENT init op, so a restarted rank 0
        # re-sending it cannot corrupt live weights (a plain re-push
        # would land in the async path as a bogus gradient).  On resume,
        # the restored weights take the init push's place.
        #
        # Barrier generations: fresh runs use (0, 1) for (startup, exit).
        # Resumed runs derive a FRESH pair from the sidecar's attempt
        # counter (bumped once per resume by the launcher,
        # bump_resume_attempt): a surviving server group already released
        # the previous run's generations, and votes on a released
        # generation return immediately — reusing one would let peers
        # pull stale crash-time weights before rank 0's forced init
        # lands.  All ranks read the same sidecar, so they agree; late
        # re-votes of a released generation (worker rejoin) still return
        # immediately, so a restarted worker neither hangs nor pairs
        # with peers' exit votes.  A worker started again (a second
        # ``start`` on this object) takes the next pair by the same rule.
        if self.rank == 0:
            w0 = (restored if restored is not None
                  else np.asarray(self.model.init(cfg)).reshape(-1))
            # force on resume: against a SURVIVING (already-initialized)
            # server group the restored checkpoint — or, when the crash
            # predated the first checkpoint, the fresh epoch-0 init —
            # must overwrite the stale crash-time weights; a plain
            # idempotent init would no-op and silently resume from the
            # wrong state.  A restarted worker (rejoin) must NOT force:
            # it would roll peers back mid-run.
            force = resume and not rejoin
            with self._span("push"):
                self.kv.wait(self.kv.push_init(w0, force=force))
        self._barrier_base = ((0 if attempt is None else 2 * (attempt + 1))
                              + 2 * self._starts)
        self._starts += 1
        self._sidecar_attempt = 0 if attempt is None else attempt
        with self._span("barrier_wait"):
            self.kv.barrier(self._barrier_base)

    def run(self, *, eval_fn=None, save=True, resume=False,
            rejoin=False) -> np.ndarray:
        cfg = self.cfg
        self.load_data()
        self.start(resume=resume, rejoin=rejoin)

        ckpt = None
        if self.rank == 0 and cfg.checkpoint_dir:
            from distlr_tpu.train.checkpoint import Checkpointer  # noqa: PLC0415

            ckpt = Checkpointer(cfg.checkpoint_dir)

        with contextlib.ExitStack() as stack:
            # §5.1 tracing hook, PS flavor: rank 0's worker loop (jit
            # steps + KV round trips) lands in a jax.profiler trace.
            if self.rank == 0 and cfg.profile_dir:
                stack.enter_context(jax.profiler.trace(cfg.profile_dir))
            if ckpt is not None:
                stack.callback(ckpt.close)
            self.fit(eval_fn=eval_fn, ckpt=ckpt)
            return self.finish(save=save)

    def _checkpoint(self, ckpt, epoch: int) -> None:
        """Rank 0: snapshot the servers' weights + the epoch sidecar
        (atomic rename) every ``checkpoint_interval`` epochs."""
        ckpt.save(epoch, self.kv.pull(), extra={"epoch": epoch})
        sidecar = os.path.join(self.cfg.checkpoint_dir, "ps_latest.json")
        tmp = sidecar + ".tmp"
        with open(tmp, "w") as f:
            # attempt is preserved, not reset: a rejoining worker re-reads
            # the sidecar mid-run and must derive the same barrier base.
            json.dump({"epoch": epoch, "attempt": self._sidecar_attempt}, f)
        os.replace(tmp, sidecar)

    def _exchange(self, rounds: int = 0) -> _Exchange:
        """The exchange of this worker's rounds, chosen once a
        :meth:`fit` from the config and the model; ``rounds``: how many
        the ``fit`` runs (the keyed delayed exchange pulls ahead, and
        not for a round that will not run)."""
        cfg = self.cfg
        keyed = self._rows is not None
        if cfg.ps_accum_max > 1:
            return (_KeyedSpan if keyed else _DenseSpan)(
                self, GradientAccumulator(
                    self._param_dim(), start=cfg.ps_accum_start,
                    growth=cfg.ps_accum_growth,
                    growth_every=cfg.ps_accum_growth_every,
                    max_k=cfg.ps_accum_max,
                    gauge=_ACCUM_K.labels(rank=str(self.rank))))
        if keyed and cfg.ps_max_delay:
            return _KeyedDelayed(self, rounds)
        if keyed or not cfg.ps_pipeline:
            return _Serialized(self)
        if not cfg.sync_mode:
            return _Pipelined(self)
        return _Delayed(self) if cfg.ps_max_delay else _Fused(self)

    def fit(self, epochs: int | None = None, *, eval_fn=None,
            ckpt=None) -> None:
        """Run ``epochs`` more epochs (default: what is left of
        ``cfg.num_iteration``) from :attr:`epochs_done`, against a group
        :meth:`start` has seeded (class docstring: the loop).  Nothing
        is in flight where it returns (``exchange.finish()``), nor while
        rank 0 evaluates or a checkpoint is taken (``exchange.drain()``);
        a ``fit`` that raises leaves what it had out to :meth:`close`."""
        cfg = self.cfg
        self.load_data()
        train = self._train

        first = self.epochs_done
        last = cfg.num_iteration if epochs is None else first + epochs
        exchange = self._exchange(max(last - first, 0) * train.num_batches)
        grad_step = self.grad_step
        keyed, keys = self._rows is not None, None
        for epoch in range(first, last):
            train.reset()
            for batch, n_real in self._rounds(train):
                with self._loop_span("round"):
                    self.timer.start()
                    if keyed:
                        batch, keys = self._keyed_round(batch, n_real)
                    w = exchange.weights(keys)
                    g = grad_step(w, batch)
                    exchange.send(g, keys)
                    self.timer.stop(n_real)
            with self._loop_span("epoch_end"):
                self._epoch_end(exchange, epoch + 1, eval_fn, ckpt)

        exchange.finish()
        if ckpt is not None and last > first and ckpt.latest_step() != last:
            with self._span("checkpoint"):
                self._checkpoint(ckpt, last)

    def _epoch_end(self, exchange: _Exchange, done: int, eval_fn,
                   ckpt) -> None:
        """What the loop does between an epoch's last round and the
        next one's first, ``done`` epochs finished: the exchange's own
        end of the epoch, the runtime's probes, rank 0's eval and the
        checkpoint where one is due."""
        cfg, test = self.cfg, self._test
        exchange.epoch_end()
        # runtime introspection (obs.jaxrt): fold this epoch's jit
        # cache growth into distlr_jax_compiles_total and refresh
        # the live device-buffer gauges (walk throttled process-wide)
        for probe in self._jit_probes:
            probe.tick()
        jaxrt.maybe_sample_device_bytes()
        if (
            self.rank == 0
            and test is not None
            and cfg.test_interval > 0
            and done % cfg.test_interval == 0
        ):
            exchange.drain()
            with self._span("eval"):
                acc, test_ll = self.evaluate()
            self.metrics.log(epoch=done, accuracy=acc,
                             test_logloss=test_ll,
                             samples_per_sec=self.timer.samples_per_sec)
            if eval_fn is not None:
                eval_fn(done, acc)
            else:
                log_eval_line(done, acc)
        if (
            ckpt is not None
            and cfg.checkpoint_interval > 0
            and done % cfg.checkpoint_interval == 0
        ):
            exchange.drain()
            with self._span("checkpoint"):
                self._checkpoint(ckpt, done)
        self.epochs_done = done

    def evaluate(self, w: np.ndarray | None = None) -> tuple[float, float]:
        """``(accuracy, logloss)`` on the test split: of what the servers
        hold now or, for a dense model, of the flat weights ``w``.  The
        eval the epoch loop runs on rank 0."""
        test = self._test
        if self._rows is None:
            return self._dense_eval(w, test)
        got = self._keyed_eval(test)
        self._count_eval(test.num_samples)
        return got

    def _count_eval(self, rows: int) -> None:
        rank = str(self.rank)
        _EVALS.labels(rank=rank).inc()
        _EVAL_ROWS.labels(rank=rank).inc(rows)

    def _dense_eval(self, w, test) -> tuple[float, float]:
        """One forward pass over the whole split at ``w`` (pulled here,
        as the reference's ``Test`` pulls, where none is given).  On a
        jax device the split is **resident** where it fits: the first
        eval places it (:meth:`_test_on_device`) and every later one
        reads it in place, so an eval moves the weights in and two
        scalars out.  Spans: ``eval_pull``, ``test_put`` (once),
        ``eval_w_put``, ``eval_compute`` (dispatch to both scalars
        ready; no step marker: ``compute`` is the gradient step's),
        ``eval_d2h``; a split that is streamed crosses under ``h2d``."""
        cfg = self.cfg
        if w is None:
            with self._span("eval_pull"):
                w = self.kv.pull()
        if self._eval_dev == "numpy":
            K = cfg.num_classes if cfg.model == "softmax" else None
            Xt, yt, mt = self._test_batch(test)
            self._count_eval(int(mt.sum()))
            return host_math.dense_eval(
                w.reshape(cfg.num_feature_dim, K) if K else w,
                Xt, yt, mt.astype(np.float32), K)
        batch, how, rows = self._test_on_device(test)
        self._count_eval(rows)
        with self._span("eval_w_put"):
            wd = jax.block_until_ready(jax.device_put(
                w, _jax_device(self._eval_dev)))
        with self._span("eval_compute"):
            got = jax.block_until_ready(self._acc_fn(wd, *batch, **how))
        with self._span("eval_d2h"):
            # both scalars in one readback, not one after the other
            a, ll = (float(v) for v in jax.device_get(got))
        return a, ll

    @staticmethod
    def _test_batch(test):
        """The whole split as one batch: the arrays the iterator holds
        where the batch is just those (no gather of every row a call)."""
        batch = test.whole_shard()
        if batch is None:
            test.reset()
            batch = test.next_batch()
        return batch

    def _test_on_device(self, test):
        """``((X, y, mask), how, rows)`` for the eval program on the
        eval's device: the batch, the plan its ``X`` is held for as the
        program's keyword, and the rows its mask counts.  The split is
        placed **once**, by the first eval, where the device has room for
        it beside what the worker already keeps there
        (``_PLACE_HEADROOM`` times its bytes free, by the device's
        own count; a backend that keeps none is the host's memory, where
        the rows are): as the train shard is placed, under ``test_put``,
        counted in ``distlr_h2d_bytes_total`` and in
        ``distlr_ps_test_resident_bytes``.  Where it does not fit, every
        eval streams it under ``h2d`` as before and the gauge reads 0."""
        if self._test_resident is None:
            batch = self._test_batch(test)
            device = _jax_device(self._eval_dev)
            nbytes, rows = sum(a.nbytes for a in batch), int(batch[-1].sum())
            free = _device_free_bytes(device)
            gauge = _TEST_RESIDENT_BYTES.labels(rank=str(self.rank))
            if free is not None and free < _PLACE_HEADROOM * nbytes:
                gauge.set(0)
                with self._span("h2d"):
                    return self._place(device, *batch), {}, rows
            self._test_resident = (
                *self._place_rows("test_put", batch, device, forward=True),
                rows)
            gauge.set(nbytes)
            log.info("rank %d test split resident on %s: %d rows, %d bytes",
                     self.rank, _describe_compute_device(self._eval_dev),
                     test.num_samples, nbytes)
        placed, plan, rows = self._test_resident
        return placed, ({} if plan is None else {"panels": plan}), rows

    def finish(self, *, save=True) -> np.ndarray:
        """Pull the final weights, export them, meet the peers at the
        exit barrier and (rank 0) retire the group."""
        cfg = self.cfg
        with self._span("pull"):
            self.final_weights = self.kv.pull()
        if save:
            path = os.path.join(cfg.data_dir, "models", part_name(self.rank))
            os.makedirs(os.path.dirname(path), exist_ok=True)
            save_model_text(path, self.final_weights)
        # ps::Finalize(do_barrier=true) parity (reference src/main.cc:179):
        # a global exit barrier (startup generation + 1) so no server
        # retires while a peer still trains, then rank 0 retires the
        # group — this is what lets foreground `launch ps-server` hosts
        # exit when training is done (local mode: ServerGroup.stop()
        # finds the procs exited).
        with self._span("barrier_wait"):
            self.kv.barrier(self._barrier_base + 1)
        if self.rank == 0:
            self.kv.shutdown_servers()
        return self.final_weights

    def _keyed_eval(self, test) -> tuple[float, float]:
        """Full-test-set ``(accuracy, logloss)`` of a keyed model: a
        keyed pull of the rows the split touches, scattered into a full
        table, then ONE forward pass for both numbers (numpy, host-side:
        the keyed evals are exactly the small-step regime where a second
        full-test-set forward would double the eval cost)."""
        rows, model = self._rows, self.cfg.model
        test.reset()
        ids, vals, y, mask = test.next_batch()
        ub = np.unique(ids)
        table = np.zeros((self._param_dim() // rows.width, rows.width),
                         np.float32)
        table[ub] = self.kv.pull(keys=rows.keys(ub), vals_per_key=rows.vpk
                                 ).reshape(len(ub), rows.width)
        if model == "sparse_softmax":
            return host_math.softmax_eval_from_logits(
                (table[ids] * vals[..., None]).sum(axis=1), y, mask)
        z = ((table[ids] * vals).sum(axis=(-1, -2)) if model == "blocked_lr"
             else (table[ids, 0] * vals).sum(axis=-1))
        return host_math.binary_eval_from_logits(z, y, mask)

    @staticmethod
    def _place(device, *arrays):
        if device is None:
            return arrays
        return tuple(jax.device_put(a, device) for a in arrays)

    def _comm_pool(self):
        if self._comm is None:
            from concurrent.futures import ThreadPoolExecutor  # noqa: PLC0415

            self._comm = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix=f"ps-comm-{self.rank}"
            )
        return self._comm

    def _traced_push_pull(self, g, ctx, step, submitted):
        """Comm-thread half of the pipelined fused op: re-install the
        submitting step's distributed-trace context (thread-local, so it
        doesn't cross the executor by itself) before issuing.  ``wire``
        is the exchange itself, send to reply, under the submitter's
        step; ``wire_handoff``, recorded under it, is what led to it:
        the loop's ``submit`` (at ``submitted``) to the span's start:
        the executor's queue and this thread's wake-up."""
        with dtrace.use(ctx), loop_span("wire", step, rank=self.rank):
            tracer = get_tracer()
            tracer.completed("wire_handoff", submitted,
                             tracer.opened_at() - submitted, inside=False)
            reply = self.kv.push_pull(g)
        self._wire_done = time.perf_counter()
        return reply

    def close(self, *, wait: bool = True):
        self._drop_push_probe()
        comm, self._comm = self._comm, None
        if comm is None:
            self.kv.close()
            return
        if wait:
            comm.shutdown(wait=True)
            self.kv.close()
            return
        # wait=False: the failure/restart path must not block behind an
        # in-flight push_pull to a dead server (its ps_timeout_ms is
        # minutes — far past the 5 s server-respawn reconnect window);
        # the rebuilt PSWorker creates a fresh executor.  But the native
        # handle must NOT be freed under a live ctypes call (the GIL is
        # released inside it — kv_close then is a use-after-free), so
        # the handle close rides a reaper thread that first drains the
        # executor.
        comm.shutdown(wait=False, cancel_futures=True)

        def _reap():
            comm.shutdown(wait=True)
            self.kv.close()

        threading.Thread(target=_reap, daemon=True,
                         name=f"ps-close-{self.rank}").start()


def run_ps_workers(cfg: Config, hosts: str, ranks, *, eval_fn=None, save=False,
                   on_error=None, resume=False, max_restarts=0):
    """Run the given worker ranks (threads) against an EXISTING server
    group at ``hosts`` — the multi-host entry point: each host runs its
    subset of ranks against remote servers (started via
    ``python -m distlr_tpu.launch ps-server`` or :class:`ServerGroup`).

    Worker threads share one JAX backend/jit cache; worker *i* of the
    call computes on local device ``i % len(devices)``
    (:func:`worker_devices`: a chip each where the host has as many
    chips as workers, all on the one device where it has one); each
    blocks independently in the native client (the GIL is released during
    ctypes calls), so async staleness is real.  ``on_error`` runs once
    if any worker raises (local mode uses it to tear the servers down so
    peers blocked on the sync barrier fail fast instead of hanging).
    Returns ``{rank: final_weights}``.

    ``max_restarts`` (async mode only): a failed worker is rebuilt on a
    fresh connection and rejoins up to N times — Hogwild tolerates
    arbitrary rejoin, and the server's disconnect rollback already
    undid any half-round state.  Sync (BSP) runs keep fail-fast
    semantics: rounds are counted per worker, so the recovery path for
    sync is job-level ``checkpoint_dir`` + ``resume``, not in-place
    restart.  The reference has neither path (SURVEY.md §5.3: its only
    outcome is an eternal deadlock).
    """
    ranks = list(ranks)
    if resume and 0 in ranks:
        # Once per resumed job, before any worker reads the sidecar:
        # advance the barrier-generation epoch so the rendezvous below
        # cannot ride generations a surviving server group already
        # released (multi-host: the rank-0 host must launch first).
        bump_resume_attempt(cfg)
    results: dict[int, np.ndarray | None] = {r: None for r in ranks}
    errors: list[Exception] = []
    devices = worker_devices(len(ranks))
    workers = [PSWorker(cfg, r, hosts, device=d)
               for r, d in zip(ranks, devices)]

    def run_one(i, r):
        attempts = 0
        while True:
            try:
                results[r] = workers[i].run(eval_fn=eval_fn if r == 0 else None,
                                            save=save, resume=resume,
                                            rejoin=attempts > 0)
                return
            except Exception as e:  # surface worker failures to the caller
                workers[i].close(wait=False)
                attempts += 1
                if cfg.sync_mode or attempts > max_restarts:
                    errors.append(e)
                    if on_error is not None:
                        # A dead worker would deadlock every peer blocked
                        # on the sync barrier (the reference's named
                        # straggler failure, SURVEY.md §5.3).
                        on_error()
                    return
                _RESTARTS.inc()
                log.warning("worker %d failed (%s); restart %d/%d",
                            r, e, attempts, max_restarts)
                # Rebuild with a short reconnect window: when the failure
                # was a SERVER death, a supervisor needs a beat to respawn
                # the rank before this worker's fresh connect can succeed
                # (ServerSupervisor poll+respawn is ~100 ms; 5 s covers a
                # slow spawn without masking genuinely-gone servers).
                deadline = time.monotonic() + 5.0
                while True:
                    try:
                        workers[i] = PSWorker(cfg, r, hosts,
                                              device=devices[i])
                        break
                    except Exception as e2:
                        if time.monotonic() >= deadline:
                            errors.append(e2)  # servers gone: give up
                            if on_error is not None:
                                on_error()
                            return
                        time.sleep(0.2)

    threads = [
        threading.Thread(target=run_one, args=(i, r), daemon=True)
        for i, r in enumerate(ranks)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for wk in workers:
        wk.close()
    if errors:
        raise errors[0]
    return results


def ps_param_dim(cfg: Config) -> int:
    """Flat KV key-space size for a config (must match between servers
    and workers — softmax flattens its (D, K) weight matrix)."""
    return cfg.num_feature_dim * (
        cfg.num_classes if cfg.model in ("softmax", "sparse_softmax") else 1)


def server_group(cfg: Config) -> ServerGroup:
    """The local native server group a config asks for, not yet started
    (``with server_group(cfg) as group``): what :func:`run_ps_local`
    spawns, for callers that drive their own :class:`PSWorker` threads
    against it."""
    via_chaos = None
    if cfg.chaos_plan:
        from distlr_tpu.chaos import load_plan  # noqa: PLC0415

        # parsed HERE, before any server spawns: a malformed plan must
        # fail the launch, not leak a fault-free run that looks chaotic
        via_chaos = load_plan(cfg.chaos_plan, seed=cfg.chaos_seed)
    return ServerGroup(
        cfg.num_servers,
        cfg.num_workers,
        ps_param_dim(cfg),
        learning_rate=cfg.learning_rate,
        sync=cfg.sync_mode,
        last_gradient=bool(cfg.sync_last_gradient),
        via_chaos=via_chaos,
        optimizer=server_optimizer(cfg),
        ftrl_alpha=cfg.ftrl_alpha,
        ftrl_beta=cfg.ftrl_beta,
        ftrl_l1=cfg.ftrl_l1,
        ftrl_l2=cfg.ftrl_l2,
        # distributed tracing (ISSUE 8): locally spawned server ranks
        # journal their handler spans into the run dir's spans/ next to
        # the Python ranks' journals, so `launch trace-agg` sees both
        trace_journal_dir=(
            os.path.join(cfg.obs_run_dir.split(os.pathsep)[0], "spans")
            if cfg.obs_run_dir and cfg.trace_sample > 0 else None),
        # continuous profiling (ISSUE 9): locally spawned ranks journal
        # per-handler thread-CPU windows into the run dir's profiles/
        # next to the Python samplers', so `launch prof-agg` sees both
        prof_journal_dir=(
            os.path.join(cfg.obs_run_dir.split(os.pathsep)[0], "profiles")
            if cfg.obs_run_dir and cfg.prof_hz > 0 else None),
        prof_window_s=cfg.prof_window_s,
        # durable store (ISSUE 20): ranks persist + self-recover their
        # slices under <ps_store_dir>/rank-<r>/; with supervise_servers
        # the supervisor prefers the disk state over its RAM snapshot
        store_dir=cfg.ps_store_dir,
        store_interval_s=cfg.ps_store_interval_s,
        store_wal=cfg.ps_store_wal,
        store_wal_fsync_s=cfg.ps_store_wal_fsync_s,
    )


def run_ps_local(cfg: Config, *, eval_fn=None, save=False, resume=False,
                 max_restarts=0, supervise_servers=False):
    """Single-host PS run: native server subprocesses + threaded workers.

    The local-mode successor of ``examples/local.sh`` for the PS path
    (the scheduler role is gone — rendezvous is just TCP connect).
    Multi-host deployments start servers with ``launch ps-server`` and
    per-host workers with :func:`run_ps_workers` instead.

    ``supervise_servers`` (async mode only) attaches a
    :class:`distlr_tpu.ps.ServerSupervisor`: dead server ranks are
    respawned and re-seeded from a rolling snapshot, completing the
    two-sided §5.3 recovery story (pair it with ``max_restarts > 0`` so
    workers whose stream broke rejoin).
    """
    group = server_group(cfg)
    with contextlib.ExitStack() as stack:
        stack.enter_context(group)
        if supervise_servers:
            from distlr_tpu.ps import ServerSupervisor  # noqa: PLC0415

            stack.enter_context(ServerSupervisor(group))
        results = run_ps_workers(
            cfg, group.hosts, range(cfg.num_workers),
            eval_fn=eval_fn, save=save, on_error=group.stop, resume=resume,
            max_restarts=max_restarts,
        )
    return [results[r] for r in range(cfg.num_workers)]
