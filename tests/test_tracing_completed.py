"""``PhaseTracer.completed``: a span recorded after it ended, from
instants somebody else read on ``time.perf_counter``'s clock.  It is the
child of the span open on the calling thread (``parent``, ``step``,
``rank`` inherited, the parent's ``self_seconds`` less by it), stands
alone with none open, and is in the breakdown, the histogram and the
event buffer as any span."""

import threading
import time

import pytest

from distlr_tpu.obs.registry import MetricsRegistry
from distlr_tpu.obs.tracing import PhaseTracer


@pytest.fixture()
def tracer():
    return PhaseTracer(registry=MetricsRegistry())


def _events(tracer):
    return {e["name"]: e for e in tracer.chrome_trace()["traceEvents"]}


def test_it_takes_parent_step_and_rank_from_the_span_that_is_open(tracer):
    with tracer.phase("push", step=7, rank=2):
        t0 = time.perf_counter()
        time.sleep(0.01)
        tracer.completed("xchg_send", t0, 0.004)
        tracer.completed("xchg_await", t0 + 0.004, 0.005)
    ev = _events(tracer)
    for name in ("xchg_send", "xchg_await"):
        assert ev[name]["args"]["parent"] == ev["push"]["args"]["id"]
        assert ev[name]["args"]["step"] == 7 and ev[name]["args"]["rank"] == 2
        assert ev[name]["tid"] == ev["push"]["tid"] == threading.get_ident()
    assert ev["xchg_send"]["args"]["id"] != ev["xchg_await"]["args"]["id"]
    assert ev["xchg_send"]["dur"] == pytest.approx(4000.0)
    # where it lies on the tracer's own time axis: inside its parent
    assert ev["push"]["ts"] <= ev["xchg_send"]["ts"]
    assert (ev["xchg_await"]["ts"] + ev["xchg_await"]["dur"]
            <= ev["push"]["ts"] + ev["push"]["dur"] + 1)


def test_the_parents_self_seconds_are_less_by_the_child(tracer):
    with tracer.phase("push"):
        t0 = time.perf_counter()
        time.sleep(0.02)
        tracer.completed("xchg_recv", t0, 0.015)
    b = tracer.breakdown()
    assert b["xchg_recv"] == {"seconds": 0.015, "count": 1,
                              "self_seconds": 0.015}
    assert b["push"]["seconds"] >= 0.02
    assert b["push"]["self_seconds"] == pytest.approx(
        b["push"]["seconds"] - 0.015, abs=1e-5)


def test_children_of_both_kinds_add_up_under_one_parent(tracer):
    with tracer.phase("wire", step=1, rank=0):
        with tracer.phase("inner"):
            time.sleep(0.005)
        t0 = time.perf_counter()
        time.sleep(0.003)
        tracer.completed("xchg_send", t0, 0.002)
    b = tracer.breakdown()
    assert b["wire"]["self_seconds"] == pytest.approx(
        b["wire"]["seconds"] - b["inner"]["seconds"] - 0.002, abs=1e-5)
    ev = _events(tracer)
    assert (ev["inner"]["args"]["parent"] == ev["xchg_send"]["args"]["parent"]
            == ev["wire"]["args"]["id"])
    # a span opened with no step of its own hands none on
    assert "step" not in ev["inner"]["args"]


def test_the_innermost_open_span_is_the_parent(tracer):
    with tracer.phase("eval", step=3, rank=1):
        with tracer.phase("pull", step=4, rank=1):
            tracer.completed("xchg_await", time.perf_counter(), 0.001)
    ev = _events(tracer)
    assert ev["xchg_await"]["args"]["parent"] == ev["pull"]["args"]["id"]
    assert ev["xchg_await"]["args"]["step"] == 4
    b = tracer.breakdown()
    assert b["eval"]["self_seconds"] == pytest.approx(
        b["eval"]["seconds"] - b["pull"]["seconds"], abs=1e-5)


def test_with_none_open_the_span_stands_alone(tracer):
    tracer.completed("xchg_send", time.perf_counter() - 0.003, 0.003)
    ev = _events(tracer)["xchg_send"]
    assert set(ev["args"]) == {"id"}
    assert tracer.breakdown()["xchg_send"] == {
        "seconds": 0.003, "count": 1, "self_seconds": 0.003}
    # a span that closed earlier on this thread is nobody's parent
    with tracer.phase("push", step=1, rank=0):
        pass
    tracer.completed("xchg_recv", time.perf_counter(), 0.001)
    assert set(_events(tracer)["xchg_recv"]["args"]) == {"id"}


def test_another_threads_open_span_is_not_the_parent(tracer):
    entered, done = threading.Event(), threading.Event()

    def hold():
        with tracer.phase("push", step=9, rank=3):
            entered.set()
            done.wait(5)

    t = threading.Thread(target=hold)
    t.start()
    entered.wait(5)
    tracer.completed("xchg_send", time.perf_counter(), 0.001)
    done.set()
    t.join()
    assert set(_events(tracer)["xchg_send"]["args"]) == {"id"}
    b = tracer.breakdown()
    assert b["push"]["self_seconds"] == pytest.approx(b["push"]["seconds"])


def test_it_is_in_the_histogram_and_outlives_the_event_cap():
    reg = MetricsRegistry()
    tracer = PhaseTracer(registry=reg, max_events=2)
    for _ in range(5):
        tracer.completed("xchg_recv", time.perf_counter(), 0.002)
    series = dict(reg.get("distlr_phase_seconds").children())[("xchg_recv",)]
    assert series.count == 5 and series.sum == pytest.approx(0.010)
    doc = tracer.chrome_trace()
    assert len(doc["traceEvents"]) == 2
    assert doc["otherData"]["dropped_events"] == 3
    assert tracer.breakdown()["xchg_recv"]["count"] == 5
    tracer.reset()
    assert tracer.breakdown() == {} and "dropped_events" not in (
        tracer.chrome_trace()["otherData"])


# -- a span that led to its parent, and where the open span began ------------
def test_a_span_that_led_to_its_parent_takes_nothing_from_its_own_seconds(
        tracer):
    submitted = time.perf_counter()
    time.sleep(0.003)
    with tracer.phase("wire", step=5, rank=1):
        began = tracer.opened_at()
        tracer.completed("wire_handoff", submitted, began - submitted,
                         inside=False)
        time.sleep(0.002)
    ev = _events(tracer)
    assert ev["wire_handoff"]["args"] == {
        "id": ev["wire_handoff"]["args"]["id"], "step": 5, "rank": 1,
        "parent": ev["wire"]["args"]["id"]}
    # it ends where its parent starts, to the microsecond's thousandth
    assert (ev["wire_handoff"]["ts"] + ev["wire_handoff"]["dur"]
            == pytest.approx(ev["wire"]["ts"], abs=0.002))
    assert ev["wire_handoff"]["dur"] >= 3000
    b = tracer.breakdown()
    assert b["wire"]["self_seconds"] == b["wire"]["seconds"]


def test_opened_at_is_the_innermost_open_spans_start(tracer):
    assert tracer.opened_at() is None
    before = time.perf_counter()
    with tracer.phase("push"):
        outer = tracer.opened_at()
        with tracer.phase("inner"):
            inner = tracer.opened_at()
        assert tracer.opened_at() == outer
    assert before <= outer <= inner <= time.perf_counter()
    assert tracer.opened_at() is None
    ev = _events(tracer)
    assert (inner - outer) * 1e6 == pytest.approx(
        ev["inner"]["ts"] - ev["push"]["ts"], abs=0.002)


# -- the event buffer: columns, not a tuple of boxed values an event ---------
def test_the_buffer_keeps_a_whole_window_in_under_64_mb():
    """800,000 events, each with parent, step and rank (the most an
    event holds but for the rare span's further stats), kept in at most
    64 MB; the next one is counted as dropped and changes nothing."""
    import tracemalloc

    from distlr_tpu.obs.tracing import MAX_TRACE_EVENTS

    assert MAX_TRACE_EVENTS >= 800_000
    tracemalloc.start()
    try:
        tracer = PhaseTracer(registry=MetricsRegistry())
        before = tracemalloc.get_traced_memory()[0]
        t0 = time.perf_counter()
        with tracer.phase("push", step=12345678, rank=3):
            for i in range(MAX_TRACE_EVENTS):
                tracer.completed("xchg_send", t0 + i * 1e-5, 1e-5 + i * 1e-9)
            held = tracemalloc.get_traced_memory()[0] - before
            tracer.completed("xchg_recv", t0, 1e-5)      # one too many
        after = tracemalloc.get_traced_memory()[0] - before
    finally:
        tracemalloc.stop()
    assert held <= 64e6, held
    assert after <= held + 4096
    doc = tracer.chrome_trace()
    assert len(doc["traceEvents"]) == MAX_TRACE_EVENTS
    # the one over and the parent, which ended after it
    assert doc["otherData"]["dropped_events"] == 2
    last = doc["traceEvents"][-1]
    assert last["name"] == "xchg_send" and last["args"]["step"] == 12345678
    b = tracer.breakdown()
    assert (b["xchg_send"]["count"], b["xchg_recv"]["count"],
            b["push"]["count"]) == (MAX_TRACE_EVENTS, 1, 1)
    tracer.reset()
    assert tracer.chrome_trace()["traceEvents"] == []


def test_the_dump_is_what_the_tuple_form_gave(monkeypatch):
    """A recorded fixture: twelve spans (parents, steps and ranks there
    and missing, further stats, the largest thread id) and the
    ``chrome_trace()`` and ``breakdown()`` the tracer gave for them while
    its buffer was a list of tuples (PR 48's ``obs/tracing.py``)."""
    import json
    import os

    from distlr_tpu.obs import tracing

    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "phase_trace_tuple_form.json")
    with open(path) as f:
        fixture = json.load(f)
    tracer = PhaseTracer(registry=MetricsRegistry())
    tracer._epoch = 0.0
    monkeypatch.setattr(os, "getpid", lambda: fixture["pid"])
    for name, tid, t0, dur, own, sid, parent, step, rank, stats in (
            fixture["spans"]):
        monkeypatch.setattr(tracing.threading, "get_ident", lambda tid=tid: tid)
        tracer._keep(name, t0, dur, own, sid, parent, step, rank, stats)
    monkeypatch.undo()
    monkeypatch.setattr(os, "getpid", lambda: fixture["pid"])
    assert tracer.chrome_trace() == fixture["chrome_trace"]
    assert tracer.breakdown() == fixture["breakdown"]
    # and through JSON, as a dump is read
    assert json.loads(json.dumps(tracer.chrome_trace())) == (
        fixture["chrome_trace"])


def test_four_threads_appending_at_once_lose_none(tracer):
    per, start = 20_000, threading.Barrier(4)

    def work(rank):
        start.wait(5)
        with tracer.phase("round", step=rank, rank=rank, worker=rank):
            for i in range(per):
                tracer.completed(f"span{rank}", float(i), 1e-6)

    threads = [threading.Thread(target=work, args=(r,)) for r in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events = tracer.chrome_trace()["traceEvents"]
    assert len(events) == 4 * (per + 1)
    assert "dropped_events" not in tracer.chrome_trace()["otherData"]
    rounds = {e["args"]["rank"]: e for e in events if e["name"] == "round"}
    assert {r: e["args"]["worker"] for r, e in rounds.items()} == {
        0: 0, 1: 1, 2: 2, 3: 3}
    by_rank = {r: [e for e in events if e["name"] == f"span{r}"]
               for r in range(4)}
    for rank, mine in by_rank.items():
        # every column of an event is its own event's: none torn
        assert len(mine) == per
        assert {e["tid"] for e in mine} == {rounds[rank]["tid"]}
        assert {e["args"]["parent"] for e in mine} == {
            rounds[rank]["args"]["id"]}
        assert all(e["args"]["step"] == e["args"]["rank"] == rank
                   for e in mine)
        starts = [e["ts"] for e in mine]
        assert starts == sorted(starts)
    assert len({e["args"]["id"] for e in events}) == len(events)


RUN = ("xchg_enter", "xchg_send", "xchg_await", "xchg_recv", "xchg_wake",
       "xchg_account")


def _recorded(tracer):
    """What a tracer kept, less the ids, on its own time axis."""
    events = tracer.chrome_trace()["traceEvents"]
    ids = {e["args"]["id"]: e["name"] for e in events}
    rows = [(e["name"], e["dur"], ids.get(e["args"].get("parent")),
             e["args"].get("step"), e["args"].get("rank")) for e in events]
    order = [e["name"] for e in sorted(events, key=lambda e: e["args"]["id"])]
    return rows, order, tracer.breakdown()


@pytest.mark.parametrize("under", ["a span", "nothing"])
def test_a_run_is_what_completed_once_each_in_order_would_record(under):
    """``completed_run``: the same names, durations, parent, step and
    rank, ids in the same order, the same seconds off the parent's own."""
    starts = [100.0, 100.001, 100.003, 100.0045, 100.007, 100.0071]
    end = 100.0074
    one, run = (PhaseTracer(registry=MetricsRegistry()) for _ in range(2))

    def each(tracer):
        for name, t0, t1 in zip(RUN, starts, [*starts[1:], end]):
            tracer.completed(name, t0, t1 - t0)

    for tracer, record in ((one, each), (run, lambda t: t.completed_run(
            RUN, starts, end))):
        tracer._epoch = 99.0
        if under == "a span":
            with tracer.phase("push", step=3, rank=1):
                record(tracer)
        else:
            record(tracer)
    rows_one, order_one, spans_one = _recorded(one)
    rows_run, order_run, spans_run = _recorded(run)
    xchg = [r for r in rows_run if r[0] in RUN]
    assert xchg == [r for r in rows_one if r[0] in RUN] and len(xchg) == 6
    assert order_run == order_one
    for name in RUN:
        assert spans_run[name] == spans_one[name]
    if under == "a span":
        assert all(r[2:] == ("push", 3, 1) for r in xchg)
        for spans in (spans_one, spans_run):
            assert spans["push"]["self_seconds"] == pytest.approx(
                max(spans["push"]["seconds"] - 0.0074, 0.0), abs=1e-6)
    # the histogram has them too, from the thread's own shares
    hist = run._registry.get("distlr_phase_seconds")
    assert [hist.labels(phase=n).count for n in RUN] == [1] * 6
    assert hist.labels(phase="xchg_await").sum == pytest.approx(0.0015)


def test_a_run_with_no_end_given_ends_now(tracer):
    t0 = time.perf_counter()
    with tracer.phase("push"):
        tracer.completed_run(("xchg_wake", "xchg_account"),
                             (t0 - 0.002, t0 - 0.001))
        after = time.perf_counter()
    spans = tracer.breakdown()
    assert spans["xchg_wake"]["seconds"] == pytest.approx(0.001, abs=1e-6)
    assert 0.001 <= spans["xchg_account"]["seconds"] <= after - t0 + 0.001


def test_a_threads_spans_stay_in_the_histogram_when_it_is_gone(tracer):
    """A thread observes into its own shares of
    ``distlr_phase_seconds{phase}``; they are counted while it lives and
    the series' own once it has ended."""
    hist = tracer._registry.get("distlr_phase_seconds")
    ready, go = threading.Event(), threading.Event()

    def work():
        for _ in range(50):
            with tracer.phase("round"):
                pass
        ready.set()
        go.wait(10)

    t = threading.Thread(target=work)
    t.start()
    assert ready.wait(10)
    series = hist.labels(phase="round")
    assert series.count == 50 and len(series._cells) == 1
    go.set()
    t.join(10)
    assert series.count == 50 and series._cells == []
    assert series.count == 50
    assert tracer.breakdown()["round"]["count"] == 50
