"""PS worker's step, seen from the device: from the start of a worker's
``compute`` span to the start of its gradient program on the device,
averaged over the rounds of the traced ``fit``, in milliseconds: the
dispatch, and the wait behind the other workers' programs on the one
chip.  It is the part of ``step_ms`` that is not the program's own run.

Both ends are in the profiler's trace, on one clock, as for
``launch_wait_ms``.  Several workers' spans overlap here and all launch
the same program, so a span's run is found from its end: the span ends
when its own gradient is ready, and the runs are one after another on
the device, so the last run to end inside the span is the span's own.
Nothing where the trace holds no ``compute`` annotation."""

from chipbench import trace_reduce
from chipbench.layer_metrics.launch_wait_ms import CLOCK_SLACK_S


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
        own = [(e, s) for s, e in runs if lo <= e <= hi + CLOCK_SLACK_S]
        if own:
            waits.append(max(max(own)[1] - lo, 0.0))
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
