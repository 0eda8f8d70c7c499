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
every call pays for its event.  ``--tree build/parent`` times PR 49's
parent (the same primitives; the sums are printed from its own costs).
Four workers take turns at one interpreter: a round's figure times four
is what the job's rounds pay.
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--calls", type=int, default=20000)
    ap.add_argument("--repeats", type=int, default=7)
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

    def nothing(i):
        pass

    base = timed(nothing)
    cost = {name: timed(body) - base for name, body in (
        ("loop_span", a_loop_span), ("trace_phase", a_trace_phase),
        ("completed", a_completed), ("observe_op", an_observed_op),
        ("account_push_bytes", an_account))}
    before = (6 * cost["loop_span"] + 3 * cost["completed"]
              + cost["observe_op"] + cost["account_push_bytes"])
    after = before + 2 * cost["trace_phase"] + 5 * cost["completed"]
    print("span cost, us a call: " + " ".join(
        f"{name}={us:.2f}" for name, us in cost.items())
        + f" (empty loop {base:.3f})")
    print(f"span cost, us a round a worker: before={before:.1f} "
          f"after={after:.1f} added={after - before:.1f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
