"""From a profiler trace to numbers: busy and idle, steps, top operations.

The reduction works on a plain structure so that it can be checked on a
hand-built trace: a trace is ``{plane_name: {line_name: [(name, start_s,
dur_s), ...]}}``.  :func:`load_xplane` reads that out of the
``.xplane.pb`` JAX's profiler writes, with nothing but JAX.

On a TPU the device planes are named ``/device:TPU:<n>``; the line
``XLA Ops`` holds one event per executed operation and ``XLA Modules``
one per executed program.  Host threads live in ``/host:CPU``.
"""

from __future__ import annotations

import glob
import json
import os
import re

_HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the harness wraps the traced calls in a TraceAnnotation of this name
#: and notes the host clock at its start, which ties the two clocks
ANCHOR = "chipbench_traced_window"


def peaks_for(device_kind: str) -> dict:
    """The table's row for this device; an unknown device is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in chipbench/peaks.json "
            f"(it has {sorted(table)}); add its published peaks with "
            "their source before reporting a share of them")
    return table[device_kind]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    trace: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = trace.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((ev.name, ev.start_ns * 1e-9,
                               ev.duration_ns * 1e-9))
    return trace


def device_planes(trace: dict) -> list[str]:
    return sorted(p for p in trace if DEVICE_PLANE.match(p))


def union(intervals) -> list[tuple[float, float]]:
    """Sorted disjoint ``(start, end)`` covering the same instants."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _ops(trace, plane):
    return trace[plane].get(OPS_LINE, [])


def window_of(trace: dict) -> tuple[float, float]:
    """The anchored window if the anchor was recorded, else the span of
    every device operation."""
    for lines in trace.values():
        for events in lines.values():
            for name, s, d in events:
                if name == ANCHOR:
                    return s, s + d
    spans = [(s, s + d) for p in device_planes(trace)
             for _, s, d in _ops(trace, p)]
    if not spans:
        raise ValueError("no device operation in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def busy(trace: dict, window=None) -> dict:
    """Seconds in which an operation ran, per device and averaged."""
    lo, hi = window or window_of(trace)
    per = {}
    for p in device_planes(trace):
        iv = _clip(union((s, s + d) for _, s, d in _ops(trace, p)), lo, hi)
        per[p] = sum(e - s for s, e in iv)
    if not per:
        raise ValueError("no device plane in the trace")
    return {"window_s": hi - lo, "per_device_s": per,
            "busy_s": sum(per.values()) / len(per)}


def top_ops(trace: dict, window=None, n: int = 8) -> list[list]:
    """The operations with most device time, summed by name over the
    first device (every device of a data-parallel mesh runs the same)."""
    lo, hi = window or window_of(trace)
    planes = device_planes(trace)
    totals: dict[str, float] = {}
    for name, s, d in _ops(trace, planes[0]) if planes else []:
        if s + d > lo and s < hi:
            totals[name] = totals.get(name, 0.0) + d
    return [[k, v] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def module_runs(trace: dict, match: str, window=None) -> list[tuple]:
    """``(start, end)`` of each run, inside the window, of the programs
    whose name contains ``match``, on the first device."""
    lo, hi = window or window_of(trace)
    planes = device_planes(trace)
    if not planes:
        return []
    return [(s, s + d) for name, s, d
            in trace[planes[0]].get(MODULES_LINE, [])
            if match in name and s >= lo and s + d <= hi]


def busy_per_step(trace: dict, match: str, window=None) -> float | None:
    """Seconds the first device was busy inside one run of the step
    program, averaged over its runs in the window."""
    runs = module_runs(trace, match, window)
    if not runs:
        return None
    ops = union((s, s + d) for _, s, d in _ops(trace, device_planes(trace)[0]))
    total = sum(e - s for rs, re_ in runs for s, e in _clip(ops, rs, re_))
    return total / len(runs)


def idle_gaps(trace: dict, host_spans, clock_offset: float, window=None,
              order=("h2d", "data_load", "eval", "compute")) -> list[list]:
    """Idle seconds of the first device, summed by what the host was in.

    ``host_spans`` are ``(name, thread, start_s, dur_s)`` on the host's
    clock; ``clock_offset`` is trace time minus host time.  A gap goes to
    the first name of ``order`` that has a span over it, piece by piece,
    and what no span covers goes to ``other``."""
    lo, hi = window or window_of(trace)
    planes = device_planes(trace)
    if not planes:
        return []
    busy_iv = _clip(union((s, s + d) for _, s, d in _ops(trace, planes[0])),
                    lo, hi)
    gaps, t = [], lo
    for s, e in busy_iv:
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    by_name = {}
    for name, _tid, s, d in host_spans:
        by_name.setdefault(name, []).append(
            (s + clock_offset, s + d + clock_offset))
    totals = {name: 0.0 for name in (*order, "other")}
    for gs, ge in gaps:
        left = [(gs, ge)]
        for name in order:
            cover = union(by_name.get(name, []))
            nxt = []
            for s, e in left:
                cut = _clip(cover, s, e)
                totals[name] += sum(b - a for a, b in cut)
                t = s
                for a, b in cut:
                    if a > t:
                        nxt.append((t, a))
                    t = b
                if e > t:
                    nxt.append((t, e))
            left = nxt
        totals["other"] += sum(e - s for s, e in left)
    return [[k, v] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1]) if v > 0]


# -- the least a step has to move ---------------------------------------
def step_bytes_floor(family: str, **step) -> float:
    """Bytes one SGD step of the family cannot avoid moving through HBM
    on one device, from the step's shapes (``rows``, ``dim``, ``nnz``):
    the function of that name in ``chipbench/families/<family>.py``."""
    from chipbench import reference

    return reference.family(family).step_bytes_floor(**step)
