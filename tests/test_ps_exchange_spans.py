"""An exchange's three phases on the client: ``xchg_send``,
``xchg_await``, ``xchg_recv``, recorded by ``KVWorker`` after each keyed
operation returns, from four instants the native client notes on
``time.perf_counter``'s clock.  One site serves every loop variant of
``PSWorker.fit``: the three lie under whichever of ``push``, ``pull`` and
the comm thread's ``wire`` is open, and cover it but for the call's entry
and exit."""

import collections
import ctypes
import time

import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.data.synthetic import write_synthetic_shards
from distlr_tpu.obs.tracing import get_tracer, trace_phase
from distlr_tpu.ps import KVWorker, ServerGroup
from distlr_tpu.train.ps_trainer import run_ps_local

DIM, ITERATIONS = 24, 12
XCHG = ("xchg_send", "xchg_await", "xchg_recv")
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


def test_the_three_partition_the_span_they_lie_in():
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
    # the parent's own seconds are what the three leave
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
    three cover but for the call's entry and exit.  One worker, so that
    no other thread holds the interpreter between a call's return and
    its span's end."""
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
    for pid, three in kids.items():
        parent = ids[pid]
        assert sorted(e["name"] for e in three) == sorted(XCHG)
        # one after another inside the span they lie in (microseconds):
        # the native client's clock is the tracer's
        k = {e["name"]: e for e in three}
        assert parent["ts"] - 1 <= k["xchg_send"]["ts"]
        assert (k["xchg_send"]["ts"] + k["xchg_send"]["dur"]
                <= k["xchg_await"]["ts"] + 1)
        assert (k["xchg_await"]["ts"] + k["xchg_await"]["dur"]
                <= k["xchg_recv"]["ts"] + 1)
        assert (k["xchg_recv"]["ts"] + k["xchg_recv"]["dur"]
                <= parent["ts"] + parent["dur"] + 1), (mode, parent["name"])
        covered = sum(e["dur"] for e in three)
        gaps[parent["name"]].append((parent["dur"] - covered, parent["dur"]))
    # ... and leave it the call's entry and exit: within 2% of the span
    # or, at this test's 24 weights, where a whole exchange is 100 us,
    # what the Python round the call costs (the loop's annotation, the
    # retry and trace scopes, the op's counters: 50 to 75 us on an idle
    # host, ISSUE 34's 50 in the client alone; held to 500 because the
    # suite's other workers take the cores), on the round disturbed least
    for name in whole:
        assert len(gaps[name]) >= ITERATIONS, (mode, name)
        gap, span = min(gaps[name])
        assert gap <= max(0.02 * span, 500.0), (mode, name, gap, span)
