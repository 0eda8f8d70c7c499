"""Device step, a worker to a chip: how unevenly the workers' chips were
busy in the traced window: the largest less the smallest of their
planes' busy seconds (``trace_reduce.busy``'s ``per_device_s``) over
their mean, in percent.  Lock step gives every chip the same rounds, so
a spread says one chip's program, link or host thread is slower.
Nothing without a trace or where the run names no planes."""

from chipbench import trace_reduce


def read(run):
    tr = run.get("trace")
    if not tr or not tr.get("plane_of_rank"):
        return None
    if not trace_reduce.device_planes(tr["xtrace"]):
        return None
    per = trace_reduce.busy(tr["xtrace"], tr["window"])["per_device_s"]
    busy = [per[p] for p in tr["plane_of_rank"].values() if p in per]
    mean = sum(busy) / len(busy) if busy else 0.0
    if mean <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / mean
