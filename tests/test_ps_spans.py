"""The PS worker loop's spans: each with the worker's round count, its
rank and the span it lies in (``round`` and ``epoch_end`` round the
named ones, ``data_load`` between them); ``wire``, on the comm thread,
under the step that submitted it, the hand-overs to and from that thread
under ``wire`` and ``push``; four workers' spans kept apart."""

import collections

import jax
import pytest

from distlr_tpu.config import Config
from distlr_tpu.data.synthetic import write_synthetic_shards
from distlr_tpu.obs.tracing import get_tracer
from distlr_tpu.train.ps_trainer import run_ps_local

DIM, WORKERS, ITERATIONS = 24, 4, 3
ROUND = ("data_load", "w_put", "compute", "grad_d2h", "push")
#: a keyed op's six phases, recorded under whichever span is open when
#: it returns (tests/test_ps_exchange_spans.py holds them)
XCHG = ("xchg_enter", "xchg_send", "xchg_await", "xchg_recv", "xchg_wake",
        "xchg_account")
#: the tree the loop opens: a span's name -> the names its parent may
#: have (None: it may stand at the top of its thread).  ``push`` at the
#: top is rank 0's seed push and a bounded-delay ``fit``'s last drain,
#: ``staleness_probe`` there the stamp of the weights a ``fit`` opens with
TREE = {
    "load_data": {None}, "shard_put": {"load_data"}, "barrier_wait": {None},
    "data_load": {None}, "round": {None}, "epoch_end": {None},
    "w_put": {"round"}, "compute": {"round"}, "grad_d2h": {"round"},
    "h2d": {"round"}, "pull": {None, "round"},
    "push": {None, "round", "epoch_end"},
    "staleness_probe": {None, "round", "epoch_end"},
    "reply_wake": {"push"}, "wire": {None}, "wire_handoff": {"wire"},
    "eval": {"epoch_end"}, "checkpoint": {"epoch_end"},
    **{name: {"push", "pull", "wire", "eval_pull", "checkpoint"}
       for name in XCHG},
}


def _holds_the_tree(events):
    """Every event's parent is one :data:`TREE` allows it, of its own
    rank; which names stood under which."""
    ids = {e["args"]["id"]: e for e in events}
    seen = collections.defaultdict(set)
    for e in events:
        parent = ids.get(e["args"].get("parent"))
        under = None if parent is None else parent["name"]
        assert under in TREE[e["name"]], (e["name"], under)
        if parent is not None:
            assert parent["args"]["rank"] == e["args"]["rank"]
            assert parent["tid"] == e["tid"]
        seen[under].add(e["name"])
    return seen


pytestmark = pytest.mark.usefixtures("ps_steps_on_device")


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("ps-spans"))
    write_synthetic_shards(d, 100 * WORKERS, DIM, num_parts=WORKERS, seed=9,
                           sparsity=0.0)
    return d


def _cfg(data_dir, **kw):
    base = dict(data_dir=data_dir, num_feature_dim=DIM, model="binary_lr",
                num_workers=WORKERS, num_servers=2, sync_mode=False,
                batch_size=-1, num_iteration=ITERATIONS, learning_rate=0.2,
                l2_c=0.0, test_interval=0)
    return Config(**{**base, **kw})


def _events(cfg):
    tracer = get_tracer()
    tracer.reset()
    run_ps_local(cfg, save=False)
    return tracer.chrome_trace()["traceEvents"]


def _by(events, key):
    out = collections.defaultdict(list)
    for e in events:
        out[key(e)].append(e)
    return out


def test_every_span_has_its_step_its_rank_and_its_parent(data_dir):
    events = _events(_cfg(data_dir))
    names = _by(events, lambda e: e["name"])
    assert {"load_data", "shard_put", "pull", "wire", "barrier_wait",
            *ROUND} <= set(names)
    for e in events:
        assert e["args"]["rank"] in range(WORKERS), e
        assert 0 <= e["args"]["step"] <= ITERATIONS, e
    per_rank = _by(events, lambda e: (e["name"], e["args"]["rank"]))
    for rank in range(WORKERS):
        assert len(per_rank["load_data", rank]) == 1
        assert len(per_rank["shard_put", rank]) == 1
        assert len(per_rank["barrier_wait", rank]) == 2  # start and exit
        for name in (*ROUND, "wire"):
            # step 0 is before the first round: rank 0's seed push
            got = sorted(e["args"]["step"] for e in per_rank[name, rank]
                         if e["args"]["step"])
            assert got == list(range(1, ITERATIONS + 1)), (name, rank, got)
    assert [e["args"]["rank"] for e in names["push"]
            if e["args"]["step"] == 0] == [0]
    # the tree the loop opens: the placement inside the load; a round's
    # device chain inside ``round``; an epoch's drain (a whole-shard
    # epoch's only wait) inside ``epoch_end``, ``data_load`` between the
    # two; the hand-overs where they were waited for
    seen = _holds_the_tree(events)
    assert seen["load_data"] == {"shard_put"}
    assert {"w_put", "compute", "grad_d2h"} <= seen["round"] <= {
        "w_put", "compute", "grad_d2h", "staleness_probe"}
    assert {"push"} <= seen["epoch_end"] <= {"push", "staleness_probe"}
    assert seen["wire"] == {"wire_handoff", *XCHG}
    assert seen["push"] == {"reply_wake", *XCHG}      # XCHG: the seed push
    assert {"round", "epoch_end", "data_load", "wire", "pull"} <= seen[None]
    # ``round`` and ``epoch_end``: one a round (an epoch is a round
    # here), with the round's count and the worker's rank
    for rank in range(WORKERS):
        for name in ("round", "epoch_end", "wire_handoff", "reply_wake"):
            got = sorted(e["args"]["step"] for e in per_rank[name, rank])
            assert got == list(range(1, ITERATIONS + 1)), (name, rank, got)


def test_a_round_and_an_epochs_end_cover_the_loop_between_them(data_dir):
    """On a worker's loop thread ``data_load``, ``round`` and
    ``epoch_end`` follow one another, a minibatch epoch being three
    rounds: between the first round's start and the last epoch's end
    they leave the loop's own ``for`` and the spans' entries and exits."""
    events = _events(_cfg(data_dir, num_workers=1, batch_size=32))
    tops = sorted((e for e in events
                   if e["name"] in ("data_load", "round", "epoch_end")),
                  key=lambda e: e["ts"])
    assert [e["name"] for e in tops] == (
        ["data_load", "round"] * 3 + ["epoch_end"]) * ITERATIONS
    assert [e["args"]["step"] for e in tops if e["name"] == "epoch_end"] == [
        3, 6, 9]
    assert len({e["tid"] for e in tops}) == 1
    for a, b in zip(tops, tops[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1
    covered = sum(e["dur"] for e in tops)
    wall = tops[-1]["ts"] + tops[-1]["dur"] - tops[0]["ts"]
    # 21 spans in a run of some tens of milliseconds: what they leave is
    # their own entries and exits (held loosely: the suite's other
    # workers take the cores)
    assert wall - covered <= max(0.05 * wall, 21 * 60.0), (wall, covered)


def test_the_hand_overs_lie_where_they_were_waited_for(data_dir):
    """``wire_handoff`` on the comm thread under ``wire``, ending where
    it starts (it led to it); ``reply_wake`` on the loop's thread at the
    end of its ``push``, not before the ``wire`` it waited for ended."""
    events = _events(_cfg(data_dir, batch_size=32))
    ids = {e["args"]["id"]: e for e in events}
    per_rank = _by(events, lambda e: (e["name"], e["args"]["rank"]))
    for rank in range(WORKERS):
        (loop_tid,) = {e["tid"] for e in per_rank["round", rank]}
        wires = {e["args"]["step"]: e for e in per_rank["wire", rank]}
        assert len(per_rank["wire_handoff", rank]) == len(wires) == 9
        for e in per_rank["wire_handoff", rank]:
            wire = ids[e["args"]["parent"]]
            assert wire is wires[e["args"]["step"]]
            assert e["tid"] == wire["tid"] != loop_tid
            assert e["ts"] + e["dur"] == pytest.approx(wire["ts"], abs=0.01)
            # submitted inside the round that computed the gradient
            (rnd,) = [r for r in per_rank["round", rank]
                      if r["args"]["step"] == e["args"]["step"]]
            assert rnd["ts"] <= e["ts"] <= rnd["ts"] + rnd["dur"]
        assert len(per_rank["reply_wake", rank]) == len(
            per_rank["push", rank]) - (rank == 0)         # the seed push
        for e in per_rank["reply_wake", rank]:
            push = ids[e["args"]["parent"]]
            assert push["name"] == "push" and e["tid"] == push["tid"] == loop_tid
            wire = wires[push["args"]["step"] - ("drain" not in push["args"])]
            assert e["ts"] >= max(push["ts"], wire["ts"] + wire["dur"]) - 0.01
            assert e["ts"] + e["dur"] <= push["ts"] + push["dur"] + 0.01


@pytest.mark.parametrize("kw,probed", [
    (dict(), True),
    (dict(batch_size=32), True),
    (dict(sync_mode=True), False),
    (dict(sync_mode=True, ps_max_delay=1, sync_last_gradient=False), False),
], ids=["async", "async-minibatch", "bsp", "bsp-delay1"])
def test_the_staleness_probe_is_a_span_of_asynchronous_runs_alone(
        data_dir, kw, probed, monkeypatch):
    from distlr_tpu.train import ps_trainer

    # every stamp a probe: the 50 ms throttle would leave a short run one
    monkeypatch.setattr(ps_trainer, "_PUSHES_SAMPLE_INTERVAL_S", 0.0)
    events = _events(_cfg(data_dir, **kw))
    probes = [e for e in events if e["name"] == "staleness_probe"]
    assert bool(probes) == probed
    ids = {e["args"]["id"]: e for e in events}
    loops = {e["args"]["rank"]: e["tid"] for e in events
             if e["name"] == "round"}
    for e in probes:
        if e["args"]["step"]:     # 0: the weights a fit opens with
            assert ids[e["args"]["parent"]]["name"] in ("round", "epoch_end")
        assert e["tid"] == loops[e["args"]["rank"]]
        assert 0 <= e["args"]["step"] <= ITERATIONS * 3
    if probed:
        assert {e["args"]["rank"] for e in probes} == set(range(WORKERS))


def test_wire_runs_on_the_comm_thread_under_its_submitters_step(data_dir):
    events = _events(_cfg(data_dir))
    per_rank = _by(events, lambda e: (e["name"], e["args"]["rank"]))
    for rank in range(WORKERS):
        loop_tids = {e["tid"] for name in ROUND for e in per_rank[name, rank]}
        wire_tids = {e["tid"] for e in per_rank["wire", rank]}
        assert len(loop_tids) == 1 and len(wire_tids) == 1
        assert loop_tids != wire_tids
        computed = {e["args"]["step"]: e for e in per_rank["compute", rank]}
        pushed = {e["args"]["step"]: e for e in per_rank["push", rank]}
        for e in per_rank["wire", rank]:
            step = e["args"]["step"]
            # submitted when the step's gradient was ready, answered
            # before the loop's wait for it ended
            assert e["ts"] >= computed[step]["ts"] + computed[step]["dur"] - 1
            assert (e["ts"] + e["dur"]
                    <= pushed[step]["ts"] + pushed[step]["dur"] + 1)


def test_spans_of_four_threads_do_not_nest_into_each_other(data_dir):
    """A span's parent is the span open on its own thread: with four
    workers and four comm threads at once none is taken for a child of
    another's, and no thread holds two ranks."""
    events = _events(_cfg(data_dir))
    ids = {e["args"]["id"]: e for e in events}
    for e in events:
        parent = ids.get(e["args"].get("parent"))
        if parent is not None:
            assert parent["tid"] == e["tid"]
            if e["name"] == "wire_handoff":   # it led to its parent
                assert e["ts"] + e["dur"] <= parent["ts"] + 0.01
                continue
            assert parent["ts"] <= e["ts"] + 0.01
            assert parent["ts"] + parent["dur"] >= e["ts"] + e["dur"] - 0.01
    ranks_of = _by(events, lambda e: e["tid"])
    assert len(ranks_of) == 2 * WORKERS
    for tid, evs in ranks_of.items():
        assert len({e["args"]["rank"] for e in evs}) == 1, tid


@pytest.mark.parametrize("mode,kw,has,lacks", [
    ("serialized", dict(ps_pipeline=False), {"pull", "push"}, {"wire"}),
    ("fused-bsp", dict(sync_mode=True), {"push"}, {"wire"}),
    # the benchmark's BSP cell: a resident shard, the async round's spans
    # on the loop's own thread and no comm thread
    ("fused-bsp-resident", dict(sync_mode=True, sync_last_gradient=False),
     {"push", "pull", "shard_put", "w_put", "grad_d2h"}, {"wire", "h2d"}),
    # 80 rows a worker in windows of 32 of the resident shard; what a
    # window cannot serve (Q5's wrapped last batch) streams as before
    ("minibatch", dict(batch_size=32), {"shard_put", "wire"}, {"h2d"}),
    ("minibatch-wrapped", dict(batch_size=32, wrap_final_batch=True),
     {"h2d", "wire"}, {"shard_put"}),
    ("numpy", dict(), {"compute", "wire"},
     {"shard_put", "w_put", "grad_d2h", "h2d"}),
    ("accumulated", dict(ps_accum_max=2, batch_size=32),
     {"pull", "push", "shard_put"}, {"wire", "h2d"}),
])
def test_each_loop_variant_records_the_spans_it_has(data_dir, mode, kw, has,
                                                    lacks, ps_steps_on):
    with ps_steps_on("numpy" if mode == "numpy" else "device"):
        events = _events(_cfg(data_dir, **kw))
    names = {e["name"] for e in events}
    assert {"data_load", "compute", "load_data", "barrier_wait"} | has <= names
    assert not (lacks & names), mode
    assert all("rank" in e["args"] and "step" in e["args"] for e in events)
    steps = _by([e for e in events if e["name"] == "compute"],
                lambda e: e["args"]["rank"])
    for rank in range(WORKERS):
        got = sorted(e["args"]["step"] for e in steps[rank])
        assert got == list(range(1, len(got) + 1)) and got
    if mode != "fused-bsp-resident":
        return
    # every span of the round a step, on one thread a rank, and the
    # tree the loop opens: the placement inside the load, the round's
    # chain and its push inside ``round``, an epoch's end empty
    per_rank = _by(events, lambda e: (e["name"], e["args"]["rank"]))
    computed = _by([e for e in events if e["name"] == "compute"],
                   lambda e: e["args"]["step"])
    for rank in range(WORKERS):
        for name in ROUND:
            got = sorted(e["args"]["step"] for e in per_rank[name, rank]
                         if e["args"]["step"])
            assert got == list(range(1, ITERATIONS + 1)), (name, rank, got)
        assert len({e["tid"] for name in ROUND
                    for e in per_rank[name, rank]}) == 1
        # the fused push-pull ends after the slowest worker's gradient of
        # that round was ready: the barrier is inside the span
        for e in per_rank["push", rank]:
            if e["args"]["step"]:
                ready = max(c["ts"] + c["dur"]
                            for c in computed[e["args"]["step"]])
                assert e["ts"] + e["dur"] >= ready - 1
    seen = _holds_the_tree(events)
    assert seen["load_data"] == {"shard_put"}
    assert seen["round"] == {"w_put", "compute", "grad_d2h", "push"}
    assert "epoch_end" in seen[None] and "epoch_end" not in seen
    for rank in range(WORKERS):
        for name in ("round", "epoch_end"):
            got = sorted(e["args"]["step"] for e in per_rank[name, rank])
            assert got == list(range(1, ITERATIONS + 1)), (name, rank, got)


def test_a_profiler_trace_holds_the_workers_spans(data_dir, tmp_path):
    from jax.profiler import ProfileData

    from chipbench import trace_reduce

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        run_ps_local(_cfg(data_dir), save=False)
    found = collections.defaultdict(list)
    path = trace_reduce.find_xplane(str(tmp_path))
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                seed_push = ev.name == "push" and stats.get("step") == 0
                if ev.name in ("shard_put", "wire", *ROUND) and not seed_push:
                    found[ev.name].append(stats)
    for name in ("wire", *ROUND):
        assert len(found[name]) == WORKERS * ITERATIONS, (name, len(found[name]))
        assert {int(s["rank"]) for s in found[name]} == set(range(WORKERS))
    assert len(found["shard_put"]) == WORKERS
    # step_num on the step marker, step elsewhere
    assert {int(s["step_num"]) for s in found["compute"]} == {1, 2, 3}
    assert {int(s["step"]) for s in found["wire"]} == {1, 2, 3}


# -- the eval's spans (rank 0, a dense model on a jax device) ----------------
EVAL_PHASES = ("eval_pull", "eval_w_put", "eval_compute", "eval_d2h")


def test_an_evals_phases_lie_in_its_eval_span_with_its_rank_and_round(data_dir):
    """Lock step, an eval after every 2nd of 5 rounds: rank 0 alone opens
    ``eval``, its four phases and (the first time) ``test_put`` are its
    children, each with rank 0 and the round the eval follows; the pull's
    exchange phases hang off ``eval_pull``; ``compute`` stays the gradient
    step's, one a round."""
    events = _events(_cfg(data_dir, sync_mode=True, num_iteration=5,
                          test_interval=2))
    ids = {e["args"]["id"]: e for e in events}
    names = _by(events, lambda e: e["name"])
    assert [(e["args"]["rank"], e["args"]["step"]) for e in names["eval"]] == [
        (0, 2), (0, 4)]
    # an eval is an epoch's end's: its span lies in rank 0's ``epoch_end``
    for e in names["eval"]:
        end = ids[e["args"]["parent"]]
        assert (end["name"], end["args"]["rank"], end["args"]["step"]) == (
            "epoch_end", 0, e["args"]["step"])
    for name in EVAL_PHASES:
        got = names[name]
        assert [(e["args"]["rank"], e["args"]["step"]) for e in got] == [
            (0, 2), (0, 4)], name
        for e, parent in zip(got, names["eval"]):
            assert e["args"]["parent"] == parent["args"]["id"], name
            assert e["tid"] == parent["tid"]
    # in the order the reference's Test has them
    for parent in names["eval"]:
        inside = sorted((e["ts"], e["name"]) for e in events
                        if e["args"].get("parent") == parent["args"]["id"])
        assert [n for _ts, n in inside if n != "test_put"] == list(EVAL_PHASES)
    (put,) = names["test_put"]
    assert put["args"]["parent"] == names["eval"][0]["args"]["id"]
    assert (put["args"]["rank"], put["args"]["step"]) == (0, 2)
    pulls = {e["args"]["id"] for e in names["eval_pull"]}
    for name in XCHG:
        under = [e for e in names[name] if e["args"].get("parent") in pulls]
        assert [(e["args"]["rank"], e["args"]["step"]) for e in under] == [
            (0, 2), (0, 4)], name
    # an eval is no step: the marker's spans are the rounds', one a round
    computed = _by(names["compute"], lambda e: e["args"]["rank"])
    for rank in range(WORKERS):
        assert sorted(e["args"]["step"] for e in computed[rank]) == [
            1, 2, 3, 4, 5]
    assert all(ids[e["args"]["parent"]]["name"] != "eval_compute"
               for e in events if "parent" in e["args"])


def test_a_profiler_trace_holds_the_evals_phases_as_plain_annotations(
        data_dir, tmp_path):
    from jax.profiler import ProfileData

    from chipbench import trace_reduce

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        run_ps_local(_cfg(data_dir, sync_mode=True, num_iteration=4,
                          test_interval=2), save=False)
    found = collections.defaultdict(list)
    path = trace_reduce.find_xplane(str(tmp_path))
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("eval", "test_put", *EVAL_PHASES):
                    found[ev.name].append(dict(ev.stats))
    for name in ("eval", *EVAL_PHASES):
        assert [(int(s["rank"]), int(s["step"])) for s in found[name]] == [
            (0, 2), (0, 4)], name
        # a step marker would carry step_num: these group no device work
        assert not any("step_num" in s for s in found[name])
    assert len(found["test_put"]) == 1


def test_an_epochs_drain_is_told_apart_under_the_same_name(data_dir):
    """The pipelined async loop waits at every epoch's end for the push in
    flight: that ``push`` span carries ``drain`` beside ``step`` and
    ``rank``, the ones inside an epoch do not, and a whole-shard worker's
    every push is one."""
    events = _events(_cfg(data_dir, batch_size=32))
    per_rank = _by([e for e in events if e["name"] == "push"
                    and e["args"]["step"]], lambda e: e["args"]["rank"])
    for rank in range(WORKERS):
        drains = [e["args"]["step"] for e in per_rank[rank]
                  if e["args"].get("drain")]
        waits = [e["args"]["step"] for e in per_rank[rank]
                 if not e["args"].get("drain")]
        # three rounds an epoch: a wait at its second and third round,
        # the drain after the third
        assert drains == [3, 6, 9] and waits == [2, 3, 5, 6, 8, 9]
    assert not any(e["args"].get("drain") for e in events
                   if e["name"] != "push")
    whole = _events(_cfg(data_dir))
    pushes = [e for e in whole if e["name"] == "push" and e["args"]["step"]]
    assert len(pushes) == WORKERS * ITERATIONS
    assert all(e["args"].get("drain") == 1 for e in pushes)
