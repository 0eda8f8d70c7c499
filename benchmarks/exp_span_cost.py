"""Experiment: what a PS round's tracing costs the interpreter, on
whatever host this runs on (through ``chiprun --chips 1``: the chip's).

One thread, no profiler trace open, the process's own ``PhaseTracer`` and
registry: the microseconds of one ``loop_span`` (tracer span and a
``TraceAnnotation`` that records nothing), one ``trace_phase``, one
``PhaseTracer.completed``, one pass through ``KVWorker``'s
``_observe_op`` and one ``_account_push_bytes``; then a dense pipelined
round's worth of them as the tree stands that the script runs in:

``before``  what a round recorded until PR 49: six ``loop_span``
            (``data_load``, ``w_put``, ``compute``, ``grad_d2h``,
            ``push``, ``wire``), three ``completed`` (``xchg_send``,
            ``xchg_await``, ``xchg_recv``), the op's counters.
``after``   with PR 49's spans: ``round`` and (a whole-shard round ends
            an epoch) ``epoch_end`` as ``trace_phase``, five more
            ``completed`` (``xchg_enter``, ``xchg_wake``,
            ``xchg_account``, ``wire_handoff``, ``reply_wake``).

Each figure is the median over ``--repeats`` timings of ``--calls``
calls; the tracer is reset between timings, so no event is dropped and
every call pays for its event.  ``--tree build/parent`` times another
checkout (the same primitives, where it has them; the sums are printed
from its own costs).  Four workers take turns at one interpreter: a
round's figure times four is what the job's rounds pay.

Then ``op_return`` (PR 50): what a keyed op's return costs where W
workers are answered at the same instant.  W threads, a handle each,
run ``KVWorker.push_pull`` of 4,096 values under a ``push`` span
``--rounds`` times against one native lock-step server, whose release
answers the W at once (no stand-in that sleeps to a shared instant: a
timer on a shared host wakes 150 us late and 300 at worst, several
times what is being read; the barrier's release is the real thing and
the native client notes the reply's last instant itself).  Printed for
one thread and for four: the mean ``xchg_wake`` and ``xchg_account`` a
thread, and ``return``, the reply's last value to the caller's clock
after the ``push`` span (the two, the retry and trace scopes' exits and
the span's); each the median over ``--repeats`` readings, a thread
sleeping 0.3 ms between its ops as a round's compute would keep it, so
that the W stand at the barrier when it opens.
Then the same with one item of the return path at a time taken out (a
stand-in that keeps what later items need and does none of the work),
for the items the tree has: ``observe_op`` (the histogram and counters
of ``_observe_op``'s exit), ``wire_sent`` (``kv_last_wire_sent``
answered without releasing the interpreter), ``record_exchange`` (two
getters and the payload counters), ``account_push_bytes`` (two counters,
two family walks, the gauge), ``record_op`` (the six spans: ``return``
alone says what it cost).  A row less the whole is what the item costs
where it stands, the hand-overs it causes included.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import statistics
import sys
import threading
import time


def _knock_outs(client):
    """``{item: a context manager that takes it out}`` for the items of
    the return path this tree has."""
    worker = client.KVWorker

    @contextlib.contextmanager
    def swapped(owner, name, stand_in):
        kept = getattr(owner, name)
        setattr(owner, name, stand_in)
        try:
            yield
        finally:
            setattr(owner, name, kept)

    @contextlib.contextmanager
    def no_observe(op, **_kw):
        yield

    def no_exchange(self, op, back):
        self._lib.kv_last_exchange(self._h, self._xchg)
        self._answered = (*self._xchg, back)

    def no_spans(self, entered):
        self._answered = None

    lib = client._load()
    outs = {}
    if hasattr(client, "_account_push_bytes"):
        outs["observe_op"] = lambda: swapped(client, "_observe_op", no_observe)
    if not lib.kv_last_wire_sent._flags_ & 0x4:  # FUNCFLAG_PYTHONAPI
        outs["wire_sent"] = lambda: swapped(
            lib, "kv_last_wire_sent", lambda h: 16_416)
    if hasattr(worker, "_record_exchange"):
        outs["record_exchange"] = lambda: swapped(
            worker, "_record_exchange", no_exchange)
    if hasattr(client, "_account_push_bytes"):
        outs["account_push_bytes"] = lambda: swapped(
            client, "_account_push_bytes", lambda raw, wire: None)
    outs["record_op"] = lambda: swapped(worker, "_record_op", no_spans)
    return outs


def op_return(client, tracer, rounds: int, repeats: int) -> None:
    import ctypes

    import numpy as np

    from distlr_tpu.obs.tracing import trace_phase
    from distlr_tpu.ps import KVWorker, ServerGroup

    dim = 4096
    grad = np.full(dim, 1e-3, np.float32)

    def run(threads: int) -> dict:
        returns = [0.0] * threads

        def work(r, kv):
            last, late = (ctypes.c_double * 4)(), 0.0
            for k in range(rounds):
                with trace_phase("push", k, r):
                    kv.push_pull(grad)
                back = time.perf_counter()
                kv._lib.kv_last_exchange(kv._h, last)
                late += back - last[3]
                time.sleep(3e-4)  # a round's compute: all W at the barrier
            returns[r] = late / rounds

        with ServerGroup(1, threads, dim, sync=True) as group:
            kvs = [KVWorker(group.hosts, dim, client_id=r)
                   for r in range(threads)]
            kvs[0].push_init(np.zeros(dim, np.float32))
            tracer.reset()
            pool = [threading.Thread(target=work, args=(r, kvs[r]))
                    for r in range(threads)]
            for t in pool:
                t.start()
            for t in pool:
                t.join()
            for kv in kvs:
                kv.close()
        spans = tracer.breakdown()
        got = {"return": statistics.mean(returns) * 1e6}
        for name in ("xchg_wake", "xchg_account"):
            if name in spans:
                assert spans[name]["count"] == threads * rounds
                got[name] = spans[name]["seconds"] / spans[name]["count"] * 1e6
        return got

    # a repeat reads every row in turn, so that a host that drifts
    # drifts under all of them
    rows = {"whole": contextlib.nullcontext} | {
        f"less {item}": out for item, out in _knock_outs(client).items()}
    readings = {label: ([], []) for label in rows}
    for _ in range(repeats):
        for label, out in rows.items():
            with out():
                for got, threads in zip(readings[label], (1, 4)):
                    got.append(run(threads))
    for label, (one, four) in readings.items():
        print(f"op_return, us an op a thread, {label}: " + " ".join(
            "{}={:.1f}/{:.1f}".format(name, *(
                statistics.median(got[name] for got in runs)
                for runs in (one, four)))
            for name in ("xchg_wake", "xchg_account", "return")
            if name in one[0]) + " (1 thread/4 threads)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--calls", type=int, default=20000)
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--rounds", type=int, default=1000,
                    help="op_return: lock-step rounds a reading")
    ap.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="the checkout whose distlr_tpu is timed (default: this one)")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.tree))

    from distlr_tpu.obs.tracing import get_tracer, loop_span, trace_phase
    from distlr_tpu.ps import client

    tracer = get_tracer()
    n = args.calls

    def timed(body) -> float:
        """Median microseconds of one call of ``body(i)``."""
        got = []
        for _ in range(args.repeats):
            tracer.reset()
            t = time.perf_counter()
            with trace_phase("parent", 1, 0):
                for i in range(n):
                    body(i)
            got.append((time.perf_counter() - t) / n * 1e6)
        return statistics.median(got)

    def a_loop_span(i):
        with loop_span("w_put", i, rank=0):
            pass

    def a_trace_phase(i):
        with trace_phase("round", i, 0):
            pass

    now = time.perf_counter()

    def a_completed(i):
        tracer.completed("xchg_send", now, 1e-3)

    def an_observed_op(i):
        with client._observe_op("push_pull", sent=lambda: 4_000_024,
                                received=4_000_000, dense="rows"):
            pass

    def an_account(i):
        client._account_push_bytes(4_000_016, 4_000_024)

    starts = (now,) * 6

    def a_completed_run(i):
        tracer.completed_run(client._XCHG, starts)

    def nothing(i):
        pass

    bodies = [("loop_span", a_loop_span), ("trace_phase", a_trace_phase),
              ("completed", a_completed)]
    if hasattr(client, "_account_push_bytes"):  # until PR 50
        bodies += [("observe_op", an_observed_op),
                   ("account_push_bytes", an_account)]
    if hasattr(tracer, "completed_run"):  # PR 50: an op's six in one call
        bodies += [("completed_run_of_6", a_completed_run)]
    base = timed(nothing)
    cost = {name: timed(body) - base for name, body in bodies}
    print("span cost, us a call: " + " ".join(
        f"{name}={us:.2f}" for name, us in cost.items())
        + f" (empty loop {base:.3f})")
    if "observe_op" in cost:
        before = (6 * cost["loop_span"] + 3 * cost["completed"]
                  + cost["observe_op"] + cost["account_push_bytes"])
        after = before + 2 * cost["trace_phase"] + 5 * cost["completed"]
        print(f"span cost, us a round a worker: before={before:.1f} "
              f"after={after:.1f} added={after - before:.1f}")
    op_return(client, tracer, args.rounds, args.repeats)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
