"""Input layer, seen from the device: from the start of a step's
``compute`` span to the start of its program on the device, averaged
over the steps of the traced ``fit``, in milliseconds.  The step is
dispatched with its own batch resident, so this is the dispatch (about
a millisecond) and whatever the launch queues behind on the device's
side: on the TPU runtime, the copies put before it.  It is the part of
``step_ms`` that belongs to the input layer.

Both ends are in the profiler's trace, on one clock: the program's
loop spans are ``TraceAnnotation``s on the host's lines while a trace
is taken, and the program's runs are on the device's ``XLA Modules``
line.  Nothing where the trace holds no ``compute`` annotation."""

from chipbench import trace_reduce

#: the host's and the device's lines agree to a fraction of a
#: millisecond; a run is taken for a span's if it ends inside the span,
#: give or take this (under the shortest step's run, so that the next
#: step's run is never taken for this one's)
CLOCK_SLACK_S = 2e-3


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    xtrace = tr["xtrace"]
    marks = [(s, s + d) for plane, lines in xtrace.items()
             if not trace_reduce.DEVICE_PLANE.match(plane)
             for events in lines.values()
             for name, s, d in events if name == "compute"]
    runs = trace_reduce.module_runs(xtrace, tr["step_program"], tr["window"])
    waits = []
    for lo, hi in marks:
        # the span ends when the step's weights are ready: its run is the
        # first that ends after the span began, and ends before the span
        ended = [(e, s) for s, e in runs if e >= lo]
        if ended and min(ended)[0] <= hi + CLOCK_SLACK_S:
            waits.append(max(min(ended)[1] - lo, 0.0))
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
