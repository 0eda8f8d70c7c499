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
