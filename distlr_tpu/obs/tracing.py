"""Phase spans — per-step timing breakdown + Chrome trace-event dumps.

Every trainer step in this repo is a pipeline of phases (data load,
host->device, compute, pull, push, barrier wait, weight swap, eval) and
every perf question — "why is the async run slower?", "did the prefetch
actually overlap?" — is a question about where the time went *between*
them.  ``trace_phase("pull")`` wraps a block; each span is

* accumulated into a per-phase (total seconds, self seconds, count)
  breakdown that survives any event-buffer cap — this is what
  the on-chip benchmark's per-layer readers
  (``chipbench/layer_metrics``) report; and
* recorded into the registry histogram ``distlr_phase_seconds{phase=}``
  so the /metrics scrape carries the same story; and
* appended (bounded) as a Chrome trace event, dumpable as JSON that
  loads directly in Perfetto / ``chrome://tracing``.

Spans may run concurrently on many threads (prefetch producer, PS comm
thread, microbatch flusher, N Hogwild workers); each event carries its
thread id so the trace shows real overlap, not an interleaved fiction.

A span knows what caused it: the span open on the same thread when it
started is its ``parent`` (``with trace_phase("data_load"): with
trace_phase("h2d_wait"): ...``), and a span's *self* time is its
duration less what its children cover, so a sum of self seconds counts
no interval twice.  ``trace_phase(name, step=n)`` tags the span with the
unit of work it belongs to: the producer thread's spans for batch *n*
and the consumer's for step *n* share the id, which is how one step is
followed across threads in the dumped trace.  ``rank=r`` says whose span
it is where several workers are threads of one process (the PS plane's
Hogwild workers and their comm threads): a label on the event, so the
breakdown and ``distlr_phase_seconds{phase}`` keep one row a phase.

:func:`loop_span` is the form the training loops use: the same span
also written into a ``jax.profiler`` trace while one is taken.
"""

from __future__ import annotations

import array
import contextlib
import itertools
import json
import os
import threading
import time

from distlr_tpu.obs.registry import MetricsRegistry, get_registry

#: Bounded event buffer: a long training run must not grow without limit.
#: An event is one entry in each of eight typed columns
#: (:class:`_Events`), 54 B and with the arrays' headroom under 58: the
#: cap holds a buffer under 47 MB (as a tuple of nine boxed values an
#: event took 200-260 B, so 200,000 of them 40-52 MB), and a 40 s window
#: of the benchmark's busiest PS cell, some 420,000 events, fits whole.
#: The per-phase breakdown keeps aggregating past the cap (only the
#: *timeline* truncates, and the dump records how many events were
#: dropped).
MAX_TRACE_EVENTS = 800_000

#: what a column holds where an event has no parent, step or rank
_NO_ID, _NO_STEP, _NO_RANK = 0, -(1 << 63), -(1 << 31)


class _Events:
    """The event buffer as columns: one typed ``array`` a field, a
    phase's name interned to its index, and the further stats of the rare
    span that has any in a dictionary by event index.  Appended to and
    copied under the tracer's lock; read as rows outside it."""

    CODES = "HQddqqqi"  # name, tid, start, dur, span id, parent, step, rank

    def __init__(self, columns=None, names=(), stats=()):
        self.columns = columns or [array.array(c) for c in self.CODES]
        self.names: dict[str, int] = dict(names)
        self.stats: dict[int, dict] = dict(stats)
        #: the columns' ``append``, bound once: a span is a few
        #: microseconds of the loop it times
        self.appends = tuple(c.append for c in self.columns)

    def __len__(self) -> int:
        return len(self.columns[0])

    def copy(self) -> "_Events":
        return _Events([c[:] for c in self.columns], self.names, self.stats)

    def rows(self) -> list[tuple]:
        """``(name, tid, start, duration, span id, parent id, step, rank,
        further stats or None)`` an event, None where it has none."""
        names = list(self.names)
        stats = self.stats.get
        return [
            (names[n], tid, t0, dur, span_id,
             None if parent == _NO_ID else parent,
             None if step == _NO_STEP else step,
             None if rank == _NO_RANK else rank, stats(i))
            for i, (n, tid, t0, dur, span_id, parent, step, rank)
            in enumerate(zip(*self.columns))]


class _ThreadCells(dict):
    """One thread's shares of the phase histogram, by phase name
    (``registry`` cells: a span's seconds are observed with no lock, by
    the one thread that times it).  It lives in the tracer's
    ``threading.local`` and goes with its thread, leaving each share to
    the series it is of."""

    def __del__(self):
        for cell in self.values():
            cell.retired = True


class _Span:
    """One ``with`` block's span (:meth:`PhaseTracer.phase`).  A class
    and not a generator's context manager: entering and leaving is most
    of what a span costs the loop it times."""

    __slots__ = ("tracer", "name", "step", "rank", "stats", "stack",
                 "frame", "parent")

    def __init__(self, tracer, name, step, rank, stats):
        self.tracer, self.name, self.step, self.rank, self.stats = (
            tracer, name, step, rank, stats)

    def __enter__(self) -> None:
        tracer = self.tracer
        try:
            stack = tracer._open.stack
        except AttributeError:
            stack = tracer._open.stack = []
        self.stack = stack
        self.parent = stack[-1][0] if stack else None
        self.frame = frame = [next(tracer._ids), 0.0, self.step, self.rank,
                              0.0]
        stack.append(frame)
        frame[4] = time.perf_counter()

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        frame, stack = self.frame, self.stack
        dur = t1 - frame[4]
        stack.pop()
        if stack:
            stack[-1][1] += dur
        self.tracer._keep(self.name, frame[4], dur,
                          max(dur - frame[1], 0.0), frame[0], self.parent,
                          self.step, self.rank, self.stats)


class PhaseTracer:
    """Thread-safe span recorder with Chrome trace export."""

    def __init__(self, registry: MetricsRegistry | None = None,
                 max_events: int = MAX_TRACE_EVENTS):
        self._registry = registry or get_registry()
        self._max_events = max_events
        self._lock = threading.Lock()
        self._events = _Events()
        self._dropped = 0
        self._totals: dict[str, list] = {}  # phase -> [seconds, count, self]
        self._epoch = time.perf_counter()
        self._ids = itertools.count(1)
        # per thread: the open spans, innermost last, each
        # [span id, seconds its finished children took, step, rank, start]
        # (``stack``), and the thread's shares of the histogram (``cells``)
        self._open = threading.local()
        self._hist = self._registry.histogram(
            "distlr_phase_seconds",
            "wall seconds spent per pipeline phase (a PS worker's: "
            "data_load, round and inside it w_put, compute, grad_d2h, push, "
            "pull; epoch_end and inside it eval, checkpoint; "
            "staleness_probe; wire on the comm thread, wire_handoff and "
            "reply_wake to and from it; under the keyed bounded delay "
            "exchange_wait in push's stead, the loop's hand-over to and "
            "wait for its comm thread, whose wire then holds a push and a "
            "pull; a keyed op's xchg_enter, xchg_send, "
            "xchg_await, xchg_recv, xchg_wake, xchg_account)",
            labelnames=("phase",),
        )

    def phase(self, name: str, step: int | None = None,
              rank: int | None = None, **stats) -> "_Span":
        """``with tracer.phase("pull", step=n): ...``: a span round the
        block, a child of the span open on this thread."""
        return _Span(self, name, step, rank, stats or None)

    def completed(self, name: str, start: float, duration: float, *,
                  inside: bool = True) -> None:
        """Record a span that has already ended: ``start`` and
        ``duration`` in seconds on ``time.perf_counter``'s clock, read by
        whoever did the work (the native KV client notes the instants of
        an exchange; ``CLOCK_MONOTONIC`` is that clock).  The span open
        on the calling thread is its parent: it gives ``step`` and
        ``rank`` and counts the duration among its children's, so its
        ``self_seconds`` leaves it out.  ``inside=False``: a span that
        led to its parent and ended where that began (the hand-over to
        the thread that opened it) takes nothing from the parent's own
        seconds.  With none open the span stands
        alone.  It is in the breakdown, the histogram and the event
        buffer as any span; it is no ``TraceAnnotation`` (one cannot be
        entered after the fact), so a profiler trace's readers get it
        from :meth:`chrome_trace`."""
        stack = getattr(self._open, "stack", None)
        parent = step = rank = None
        if stack:
            top = stack[-1]
            if inside:
                top[1] += duration
            parent, step, rank = top[0], top[2], top[3]
        self._keep(name, start, duration, duration, next(self._ids), parent,
                   step, rank)

    def completed_run(self, names, starts, end: float | None = None) -> None:
        """Record consecutive spans that have already ended, as
        :meth:`completed` once each in order would (the same parent, ids
        one after another, each duration counted among the parent's
        children's), taking the tracer's lock once for all of them:
        ``names[i]`` from ``starts[i]`` to ``starts[i + 1]``, the last to
        ``end``.  ``end=None`` is now, read when the others are recorded,
        so a last span that holds its recorder's own bookkeeping
        (``xchg_account``) holds the wait for the lock and the others'
        recording too, and leaves out only its own."""
        stack = getattr(self._open, "stack", None)
        top = stack[-1] if stack else None
        parent, step, rank = (top[0], top[2], top[3]) if top else (
            None, None, None)
        cells = [self._cell(name) for name in names]
        ids = [next(self._ids) for _ in names]
        tid = threading.get_ident()
        record, last = self._record_locked, len(names) - 1
        with self._lock:
            for i, (name, cell, span_id) in enumerate(zip(names, cells, ids)):
                t0 = starts[i]
                if i < last:
                    t1 = starts[i + 1]
                else:
                    t1 = time.perf_counter() if end is None else end
                dur = t1 - t0
                cell.observe(dur)
                if top:
                    top[1] += dur
                record(name, tid, t0, dur, dur, span_id, parent, step, rank,
                       None)

    def opened_at(self) -> float | None:
        """When the innermost span open on the calling thread began, on
        ``time.perf_counter``'s clock; None with none open."""
        stack = getattr(self._open, "stack", None)
        return stack[-1][4] if stack else None

    def _cell(self, name):
        """The calling thread's share of ``distlr_phase_seconds{phase}``
        for ``name``, bound at the thread's first span of that name."""
        local = self._open
        try:
            cells = local.cells
        except AttributeError:
            cells = local.cells = _ThreadCells()
        cell = cells.get(name)
        if cell is None:
            cell = cells[name] = self._hist.labels(phase=name).cell()
        return cell

    def _keep(self, name, t0, dur, own, span_id, parent, step, rank,
              stats=None) -> None:
        self._cell(name).observe(dur)
        tid = threading.get_ident()
        with self._lock:
            self._record_locked(name, tid, t0, dur, own, span_id, parent,
                                step, rank, stats)

    def _record_locked(self, name, tid, t0, dur, own, span_id, parent, step,
                       rank, stats) -> None:
        """One span into the breakdown and the event buffer, under the
        tracer's lock."""
        tot = self._totals.get(name)
        if tot is None:
            self._totals[name] = [dur, 1, own]
        else:
            tot[0] += dur
            tot[1] += 1
            tot[2] += own
        events = self._events
        kept = len(events)
        if kept < self._max_events:
            index = events.names.get(name)
            if index is None:
                index = events.names[name] = len(events.names)
            if stats:
                events.stats[kept] = stats
            a_name, a_tid, a_t0, a_dur, a_id, a_parent, a_step, a_rank = (
                events.appends)
            a_name(index)
            a_tid(tid)
            a_t0(t0 - self._epoch)
            a_dur(dur)
            a_id(span_id)
            a_parent(_NO_ID if parent is None else parent)
            a_step(_NO_STEP if step is None else step)
            a_rank(_NO_RANK if rank is None else rank)
        else:
            self._dropped += 1

    def breakdown(self) -> dict[str, dict]:
        """``{phase: {"seconds", "count", "self_seconds"}}`` accumulated
        since reset.  ``self_seconds`` leaves out what the phase's child
        spans cover: sum that, not ``seconds``, across nested phases."""
        with self._lock:
            return {
                name: {"seconds": round(sec, 6), "count": count,
                       "self_seconds": round(own, 6)}
                for name, (sec, count, own) in sorted(self._totals.items())
            }

    def phase_names(self) -> set[str]:
        with self._lock:
            return set(self._totals)

    def reset(self) -> None:
        with self._lock:
            self._events = _Events()
            self._totals.clear()
            self._dropped = 0
            self._epoch = time.perf_counter()

    # -- Chrome trace-event export ---------------------------------------
    def chrome_trace(self) -> dict:
        """Trace-event JSON object (``ph: "X"`` complete events, us
        timestamps) — loadable in Perfetto / chrome://tracing.  Each
        event's ``args`` hold its span ``id`` and, where it has them, its
        ``parent`` span's id, its ``step``, its ``rank`` and whatever
        further stats it was opened with (a ``push`` span that is an
        epoch's drain: ``drain``)."""
        pid = os.getpid()
        with self._lock:
            recorded = self._events.copy()
            dropped = self._dropped
        events = []
        for (name, tid, t0, dur, span_id, parent, step, rank,
             stats) in recorded.rows():
            args = {"id": span_id, **(stats or {})}
            if parent is not None:
                args["parent"] = parent
            if step is not None:
                args["step"] = step
            if rank is not None:
                args["rank"] = rank
            events.append({
                "name": name,
                "cat": "phase",
                "ph": "X",
                "pid": pid,
                "tid": tid,
                "ts": round(t0 * 1e6, 3),
                "dur": round(dur * 1e6, 3),
                "args": args,
            })
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"producer": "distlr_tpu.obs", "pid": pid},
        }
        if dropped:
            doc["otherData"]["dropped_events"] = dropped
        return doc

    def dump_chrome_trace(self, path: str) -> str:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.chrome_trace(), f)
        os.replace(tmp, path)
        return path


_TRACER = PhaseTracer()


def get_tracer() -> PhaseTracer:
    """The process-wide tracer every instrumented loop records into."""
    return _TRACER


def trace_phase(name: str, step: int | None = None,
                rank: int | None = None, **stats):
    """``with trace_phase("compute", step=n): ...`` on the default tracer."""
    return _TRACER.phase(name, step, rank, **stats)


@contextlib.contextmanager
def loop_span(name: str, step: int | None = None, *,
              rank: int | None = None, marks_step: bool = False, **more):
    """One span of a training loop, on both records: the process's
    ``PhaseTracer`` and, while a ``jax.profiler`` trace is being taken
    (``cfg.profile_dir``, or a caller's own ``jax.profiler.trace``), the
    host lines of the same ``.xplane.pb`` as the device operations, so
    that the two share a clock.  An annotation records nothing while no
    trace is open.  ``marks_step`` makes it the step marker the
    profiler's tools group device work by.  ``more`` are further stats
    of the span, on both records beside ``step`` and ``rank``
    (``drain=1``: a ``push`` that is an epoch's last, with no round's
    compute left to hide it).  JAX is imported here, by the loops that
    have it already, and not with this module."""
    import jax  # noqa: PLC0415

    stats = {k: v for k, v in (("step", step), ("rank", rank))
             if v is not None} | more
    if marks_step:
        stats.pop("step", None)
        annotation = jax.profiler.StepTraceAnnotation(
            name, step_num=step, **stats)
    else:
        annotation = jax.profiler.TraceAnnotation(name, **stats)
    with trace_phase(name, step, rank, **more), annotation:
        yield
