"""A keyed operation's six phases on the client: ``xchg_enter`` (the
op's first instruction in Python to the native call's start),
``xchg_send``, ``xchg_await``, ``xchg_recv`` (from four instants the
native client notes on ``time.perf_counter``'s clock), ``xchg_wake`` (the
last value read to Python running again) and ``xchg_account`` (to the
op's return), recorded by ``KVWorker`` as the op returns.  One site
serves every loop variant of ``PSWorker.fit``: the six lie under
whichever of ``push``, ``pull`` and the comm thread's ``wire`` is open,
one after another, and cover it but for that span's own entry and exit."""

import collections
import ctypes
import sys
import threading
import time

import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.data.synthetic import write_synthetic_shards
from distlr_tpu.obs.tracing import get_tracer, trace_phase
from distlr_tpu.ps import KVWorker, ServerGroup
from distlr_tpu.train.ps_trainer import run_ps_local

DIM, ITERATIONS = 24, 12
NATIVE = ("xchg_send", "xchg_await", "xchg_recv")
XCHG = ("xchg_enter", *NATIVE, "xchg_wake", "xchg_account")
PARENTS = {"push", "pull", "wire", "eval", "checkpoint"}


def _instants(kv):
    out = (ctypes.c_double * 4)()
    kv._lib.kv_last_exchange(kv._h, out)
    return list(out)


def test_the_four_instants_are_ordered_on_perf_counters_clock():
    dim = 1 << 16
    with ServerGroup(2, 1, dim, sync=True) as g, \
            KVWorker(g.hosts, dim, client_id=0) as kv:
        ops = {
            "push_init": lambda: kv.wait(kv.push_init(np.ones(dim, np.float32))),
            "push": lambda: kv.wait(kv.push(np.ones(dim, np.float32))),
            "pull": kv.pull,
            "push_pull": lambda: kv.push_pull(np.ones(dim, np.float32)),
            "keyed pull": lambda: kv.pull(keys=np.arange(3, dtype=np.uint64)),
        }
        for name, op in ops.items():
            before = time.perf_counter()
            op()
            after = time.perf_counter()
            t = _instants(kv)
            assert before <= t[0] <= t[1] <= t[2] <= t[3] <= after, (name, t)
        # a barrier is no keyed op: it goes the same way and records no span
        tracer = get_tracer()
        tracer.reset()
        kv.barrier(1)
        assert not set(XCHG) & tracer.phase_names()


def test_a_pull_of_no_keys_reads_no_reply_and_records_nothing():
    with ServerGroup(1, 1, 64, sync=False) as g, \
            KVWorker(g.hosts, 64, client_id=0) as kv:
        kv.wait(kv.push_init(np.ones(64, np.float32)))
        tracer = get_tracer()
        tracer.reset()
        assert kv.pull(keys=np.zeros(0, np.uint64)).size == 0
        t = _instants(kv)
        assert t[2] == 0.0 and t[0] > 0
        assert not set(XCHG) & tracer.phase_names()


def _one_after_another(k, parent):
    """The six of one op, in order and abutting within 10 us, inside
    ``parent``; what they leave of it, in microseconds."""
    assert sorted(k) == sorted(XCHG)
    assert parent["ts"] - 1 <= k["xchg_enter"]["ts"]
    for a, b in zip(XCHG, XCHG[1:]):
        assert k[a]["dur"] >= 0 and k[b]["dur"] >= 0, (a, b)
        assert k[a]["ts"] + k[a]["dur"] == pytest.approx(k[b]["ts"], abs=10), (
            a, b)
    assert (k["xchg_account"]["ts"] + k["xchg_account"]["dur"]
            <= parent["ts"] + parent["dur"] + 1)
    return parent["dur"] - sum(e["dur"] for e in k.values())


def test_the_six_partition_the_span_they_lie_in():
    dim = 1 << 20
    with ServerGroup(2, 1, dim, sync=True) as g, \
            KVWorker(g.hosts, dim, client_id=0) as kv:
        kv.wait(kv.push_init(np.zeros(dim, np.float32)))
        grad = np.full(dim, 1e-3, np.float32)
        tracer = get_tracer()
        tracer.reset()
        for step in range(1, 6):
            with trace_phase("push", step=step, rank=0):
                kv.push_pull(grad)
        events = tracer.chrome_trace()["traceEvents"]
        spans = tracer.breakdown()
    pushes = {e["args"]["id"]: e for e in events if e["name"] == "push"}
    kids = collections.defaultdict(dict)
    for e in events:
        if e["name"] in XCHG:
            assert (e["args"]["step"], e["args"]["rank"]) == (
                pushes[e["args"]["parent"]]["args"]["step"], 0)
            kids[e["args"]["parent"]][e["name"]] = e
    assert len(kids) == 5
    for pid, k in kids.items():
        p = pushes[pid]
        # one after another, inside the parent
        assert p["ts"] <= k["xchg_send"]["ts"]
        assert (k["xchg_send"]["ts"] + k["xchg_send"]["dur"]
                == pytest.approx(k["xchg_await"]["ts"], abs=0.01))
        assert (k["xchg_await"]["ts"] + k["xchg_await"]["dur"]
                == pytest.approx(k["xchg_recv"]["ts"], abs=0.01))
        assert (k["xchg_recv"]["ts"] + k["xchg_recv"]["dur"]
                <= p["ts"] + p["dur"] + 0.01)
        # 2 MB a server each way is no microsecond
        assert k["xchg_send"]["dur"] > 50 and k["xchg_recv"]["dur"] > 50
        # the six leave the span its own entry and exit
        assert _one_after_another(k, p) <= max(0.03 * p["dur"], 30.0)
    # the parent's own seconds are what the six leave
    assert spans["push"]["self_seconds"] == pytest.approx(
        spans["push"]["seconds"] - sum(spans[n]["seconds"] for n in XCHG),
        abs=1e-5)


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("ps-xchg"))
    write_synthetic_shards(d, 100, DIM, num_parts=1, seed=9, sparsity=0.0)
    return d


@pytest.mark.parametrize("mode,kw,under,whole", [
    ("pipelined", {}, {"wire", "pull", "push"}, {"wire"}),
    ("serialized", dict(ps_pipeline=False), {"pull", "push"},
     {"pull", "push"}),
    ("fused-bsp", dict(sync_mode=True), {"push", "pull"}, {"push"}),
    ("fused-bsp-resident", dict(sync_mode=True, sync_last_gradient=False),
     {"push", "pull"}, {"push"}),
    ("minibatch", dict(batch_size=32), {"wire", "pull"}, {"wire"}),
    ("numpy", dict(), {"wire", "pull"}, {"wire"}),
    ("accumulated", dict(ps_accum_max=2, batch_size=32), {"pull", "push"},
     {"pull", "push"}),
])
def test_every_loop_variant_records_them_under_its_exchange(
        data_dir, mode, kw, under, whole, ps_steps_on):
    """``under``: the spans of the variant that hold an exchange;
    ``whole``: those of them a round repeats, whose every exchange the
    six cover but for the span's own entry and exit.  One worker, so
    that no other worker's loop holds the interpreter between a call's
    return and its span's end."""
    base = dict(data_dir=data_dir, num_feature_dim=DIM, model="binary_lr",
                num_workers=1, num_servers=2, sync_mode=False,
                batch_size=-1, num_iteration=ITERATIONS, learning_rate=0.2,
                l2_c=0.0, test_interval=0)
    tracer = get_tracer()
    tracer.reset()
    with ps_steps_on("numpy" if mode == "numpy" else "device"):
        run_ps_local(Config(**{**base, **kw}), save=False)
    events = tracer.chrome_trace()["traceEvents"]
    ids = {e["args"]["id"]: e for e in events}
    kids = collections.defaultdict(list)
    for e in events:
        if e["name"] in XCHG:
            parent = ids[e["args"]["parent"]]      # never without one
            assert parent["name"] in PARENTS, (mode, parent["name"])
            assert parent["tid"] == e["tid"]
            assert e["args"]["rank"] == parent["args"]["rank"] == 0
            assert e["args"]["step"] == parent["args"]["step"]
            kids[parent["args"]["id"]].append(e)
    assert under <= {ids[p]["name"] for p in kids}, mode
    gaps = collections.defaultdict(list)
    for pid, six in kids.items():
        parent = ids[pid]
        # one after another inside the span they lie in (microseconds):
        # the native client's clock is the tracer's
        gap = _one_after_another({e["name"]: e for e in six}, parent)
        gaps[parent["name"]].append((gap, parent["dur"]))
    # ... and leave it its own entry and exit (the loop's annotation, a
    # serialized push's ``wait``, the comm thread's ``wire_handoff``):
    # within 3% of the span or, at this test's 24 weights, where a whole
    # exchange is 100-300 us, what those cost: 15 to 30 us on an idle
    # host, held to 100 because the suite's other workers take the cores
    # (500 while the Python round the native call was in it: the 50 to
    # 75 us of ISSUE 34 are ``xchg_enter`` and ``xchg_account`` now), on
    # the round disturbed least
    for name in whole:
        assert len(gaps[name]) >= ITERATIONS, (mode, name)
        gap, span = min(gaps[name])
        assert gap <= max(0.03 * span, 100.0), (mode, name, gap, span)


def test_a_thread_that_holds_the_interpreter_shows_in_the_wake_alone():
    """A second thread in a pure-Python loop gives the interpreter up a
    switch interval after it is asked to, and no sooner.  Where it took
    the interpreter while a native call ran, the op runs again that much
    after its reply was there: the interval shows whole in ``xchg_wake``,
    and in none of the native three, which need no interpreter."""
    dim, ops, interval = 1 << 18, 40, 0.02
    longest = {}
    was = sys.getswitchinterval()
    with ServerGroup(1, 1, dim, sync=False) as g, \
            KVWorker(g.hosts, dim, client_id=0) as kv:
        kv.wait(kv.push_init(np.zeros(dim, np.float32)))
        grad = np.full(dim, 1e-3, np.float32)
        tracer = get_tracer()
        for held in (False, True):
            stop = threading.Event()

            def spin():
                n = 0
                while not stop.is_set():
                    n += 1

            spinner = threading.Thread(target=spin, daemon=True)
            if held:
                sys.setswitchinterval(interval)
                spinner.start()
            try:
                tracer.reset()
                for step in range(1, ops + 1):
                    with trace_phase("push", step=step, rank=0):
                        kv.push_pull(grad)
                events = tracer.chrome_trace()["traceEvents"]
            finally:
                stop.set()
                sys.setswitchinterval(was)
                if held:
                    spinner.join()
            longest[held] = {n: max(e["dur"] for e in events
                                    if e["name"] == n) * 1e-6 for n in XCHG}
            assert sum(e["name"] == "xchg_wake" for e in events) == ops
    alone, shared = longest[False], longest[True]
    assert alone["xchg_wake"] < 0.25 * interval
    assert shared["xchg_wake"] >= 0.75 * interval
    for name in NATIVE:
        assert shared[name] < alone[name] + 0.75 * interval, (
            name, alone, shared)
