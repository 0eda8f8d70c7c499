"""PS worker round, the clocks of a traced run: milliseconds by which
the device planes' clock leads the host's in the profiler's trace (a
chip's events stand that much EARLIER on the trace's axis than they
happened on the host's: PERF.md's sense of "leads" since PR 32), the
mean over the chips that run a worker's step: what
``ps_idle_exchange_share``, ``ps_idle_link_share`` and
``ps_idle_unnamed_share`` shift a chip's operations by before they lay
them beside the workers' spans.

Three clocks stand in a traced run: ``time.perf_counter`` (the program's
spans), the profiler's host lines, the device planes.  The driver's
anchor ties the first two (``clock_offset``).  The third is tied by what
the trace holds already: a worker's ``compute`` span encloses its own run
of the step program in true time (the dispatch before it, the wake-up
after it), so with the lead ``d`` of a chip's clock every pair gives
``span_start - run_start <= d <= span_end - run_end``.  No pairing is
guessed: on a chip the *i*-th span to start belongs to some run that
starts after it and the *i*-th to end to some run that ended before it,
so the bounds hold between the sorted starts and between the sorted
ends, whoever's run is whose (several workers share the one chip of a
one-chip cell).  Over a traced run's hundreds of rounds the largest
lower and the smallest upper bound close on ``d`` to within the quickest
dispatch plus the quickest wake-up; the reading is the bracket's middle,
and the line this reader prints has each chip's bracket.

Which chip a thread's spans belong to: where the run names each rank's
plane and its ``compute`` marks (a worker to a chip), the thread whose
``compute`` spans coincide with a rank's marks is that rank's worker;
elsewhere every worker computes on the first chip.  Nothing without a
trace, without a device plane (a rehearsal on the CPU), where a chip's
runs and its workers' ``compute`` spans differ in number, or where a
chip's bracket is empty."""

from chipbench import trace_reduce

#: a gap's piece goes to the first of these that any of the chip's own
#: workers is in (``trace_reduce.idle_gaps``' rule, with the PS loop's
#: names); what none covers is ``unnamed``
ORDER = (("compute", ("compute",)),
         ("link", ("w_put", "grad_d2h", "h2d")),
         ("exchange", ("push", "pull", "wire")))
#: a thread is the worker of the rank whose marks its ``compute`` spans
#: start nearest to, if within this on average (a span and its mark are
#: entered microseconds apart)
SAME_SPAN_S = 2e-4

_last: tuple | None = None  # (the trace it was computed for, the account)


def _threads_of_planes(tr, spans_of) -> dict[str, list]:
    """``{plane: [thread, ...]}`` for the chips that run a worker's step."""
    planes = trace_reduce.device_planes(tr["xtrace"])
    by_rank, marks = tr.get("plane_of_rank"), tr.get("marks")
    if not by_rank or not marks:
        return {planes[0]: list(spans_of)}
    out: dict[str, list] = {}
    for tid, spans in spans_of.items():
        starts = sorted(s for s, _e in spans.get("compute", []))
        apart = [(sum(abs(a - b) for a, b in zip(starts, theirs)), plane)
                 for plane, theirs in (
                     (plane, sorted(s for s, _e in marks.get(rank, [])))
                     for rank, plane in by_rank.items())
                 if starts and len(theirs) == len(starts)]
        if apart and min(apart)[0] <= SAME_SPAN_S * len(starts):
            out.setdefault(min(apart)[1], []).append(tid)
    return out


def bracket(marks, runs):
    """``(lower, upper)`` on how much earlier ``runs`` stand on their
    clock than on the clock ``marks`` are on, each mark enclosing one of
    the runs in true time; nothing where they differ in number or there
    are none."""
    if not marks or len(marks) != len(runs):
        return None
    lower = max(m - r for m, r in zip(sorted(s for s, _e in marks),
                                      sorted(s for s, _e in runs)))
    upper = min(m - r for m, r in zip(sorted(e for _s, e in marks),
                                      sorted(e for _s, e in runs)))
    return lower, upper


def _split(gaps, covers):
    """Seconds of ``gaps`` under each cover of ``ORDER`` in turn, and
    under none."""
    totals, left = {}, gaps
    for name, _spans in ORDER:
        cover, nxt = trace_reduce.union(covers[name]), []
        totals[name] = 0.0
        for s, e in left:
            t = s
            for a, b in trace_reduce._clip(cover, s, e):
                totals[name] += b - a
                if a > t:
                    nxt.append((t, a))
                t = b
            if e > t:
                nxt.append((t, e))
        left = nxt
    totals["unnamed"] = sum(e - s for s, e in left)
    return totals


def account(tr):
    """``{"lead_s": {plane: d}, "bracket_s": {plane: (lower, upper)},
    "idle_s": {"compute", "link", "exchange", "unnamed"}}`` over the
    chips that run a worker's step, or nothing (the module's docstring
    says where)."""
    global _last
    if _last is not None and _last[0] is tr:
        return _last[1]
    _last = (tr, _account(tr))
    return _last[1]


def _account(tr):
    xtrace = tr["xtrace"]
    if not trace_reduce.device_planes(xtrace):
        return None
    lo, hi = tr["window"]
    offset = tr["clock_offset"]
    spans_of: dict = {}
    for name, tid, s, d in tr["host_spans"]:
        spans_of.setdefault(tid, {}).setdefault(name, []).append(
            (s + offset, s + d + offset))
    out = {"lead_s": {}, "bracket_s": {},
           "idle_s": dict.fromkeys((*(n for n, _ in ORDER), "unnamed"), 0.0)}
    for plane, tids in sorted(_threads_of_planes(tr, spans_of).items()):
        marks = [iv for tid in tids for iv in spans_of[tid].get("compute", [])]
        runs = [(s, s + d) for name, s, d
                in xtrace[plane].get(trace_reduce.MODULES_LINE, [])
                if tr["step_program"] in name]
        b = bracket(marks, runs)
        if b is None or b[0] > b[1]:
            return None
        lead = (b[0] + b[1]) / 2
        out["lead_s"][plane], out["bracket_s"][plane] = lead, b
        busy = trace_reduce._clip(trace_reduce.union(
            (s + lead, s + d + lead)
            for _n, s, d in xtrace[plane].get(trace_reduce.OPS_LINE, [])),
            lo, hi)
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = e
        if hi > t:
            gaps.append((t, hi))
        covers = {name: [iv for tid in tids for span in spans
                         for iv in spans_of[tid].get(span, [])]
                  for name, spans in ORDER}
        for name, seconds in _split(gaps, covers).items():
            out["idle_s"][name] += seconds
    return out if out["lead_s"] else None


def _of(run):
    tr = run.get("trace")
    return account(tr) if tr and run.get("ps") else None


def idle_share(run, name):
    """Percent of the chips' idle seconds that lie under ``name``."""
    got = _of(run)
    total = sum(got["idle_s"].values()) if got else 0.0
    if total <= 0:
        return None
    return 100.0 * got["idle_s"][name] / total


def read(run):
    got = _of(run)
    if not got:
        return None
    print("chipbench clock lead of the device planes, ms, lower/upper: "
          + " ".join(f"{plane}={1e3 * lo:.4f}/{1e3 * hi:.4f}"
                     for plane, (lo, hi) in sorted(got["bracket_s"].items())),
          flush=True)
    leads = list(got["lead_s"].values())
    return 1e3 * sum(leads) / len(leads)
