"""The thirteen readers PR 34 appended: an exchange's three phases from
the program's ``xchg_`` spans, a push's phases on the servers from the
registry's mirror of kStats, and where the chips' idle time goes, with the
lead of the device planes' clock, from a hand-built trace
(``trace_reduce``'s plain structure): two planes with a known lead and a
known idle split, one chip shared by two workers, an empty bracket; then
the three PS cells' rehearsals and the entries in ``BENCHMARK.json``."""

import hashlib
import importlib
import json

import pytest

from chipbench import manifest, run
from chipbench.layer_metrics import ps_clock_lead_ms

PS_CELLS = ["dense-ps-async-1chip", "dense-ps-bsp-1chip", "dense-ps-bsp-4chip"]
BSP_CELLS = PS_CELLS[1:]
SPANS = ["ps_xchg_send_ms", "ps_xchg_await_ms", "ps_xchg_recv_ms"]
COUNTERS = ["ps_server_recv_ms", "ps_server_merge_ms", "ps_reply_write_ms"]
BARRIER = ["ps_barrier_wait_ms", "ps_release_wall_ms", "ps_release_apply_ms"]
IDLE = ["ps_idle_exchange_share", "ps_idle_link_share",
        "ps_idle_unnamed_share"]
TRACED = [*IDLE, "ps_clock_lead_ms"]
NEW = [*SPANS, *COUNTERS, *BARRIER, *TRACED]
STEP = "jit_ps_grad_step"
#: sha256 of ``json.dumps(per_layer[:43], sort_keys=True)`` as PR 33 left it
ACCEPTED_43 = "d7c515b8addd183e3fc979c1ac66cdf9de129d5ef5918cafd1133d0287e7d192"


def _reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}").read


# -- the spans ----------------------------------------------------------------
def _run(**over):
    span = lambda ms, n=400: {"seconds": 1e-3 * ms * n, "count": n,  # noqa: E731
                              "self_seconds": 0.0}
    return {"window": {"wall_s": 6.4, "spans": {
        "push": span(6.0), "xchg_send": span(1.25), "xchg_await": span(3.5),
        "xchg_recv": span(1.2)}},
        "ps": {"workers": 4, "rounds_per_worker": 400, "server_pushes": 3200},
        "bsp": {"server_rounds": 800}, "trace": None, **over}


@pytest.mark.parametrize("name,want", zip(SPANS, (1.25, 3.5, 1.2)))
def test_an_exchange_phase_is_its_spans_mean(name, want):
    assert _reader(name)(_run()) == pytest.approx(want)
    parent = _run()
    for span in ("xchg_send", "xchg_await", "xchg_recv"):
        del parent["window"]["spans"][span]
    assert _reader(name)(parent) is None       # a program from before them


# -- the servers' counters ----------------------------------------------------
@pytest.fixture()
def mirror(monkeypatch):
    """A registry of its own, and the gauge the kStats reads mirror into."""
    from distlr_tpu.obs import registry

    fresh = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "REGISTRY", fresh)
    gauge = fresh.gauge("distlr_ps_server_stat", "kStats", ("rank", "stat"))

    def put(rank, **stats):
        for stat, value in stats.items():
            gauge.labels(rank=rank, stat=stat).set(value)
    return put


def _two_servers(put, rounds=800):
    for rank, slow in ((0, 1.0), (1, 1.1)):
        put(rank, total_pushes=4 * rounds + 1, sync_rounds=rounds,
            recv_seconds=slow * 1.0e-3 * 4 * rounds,
            merge_seconds=slow * 0.2e-3 * 4 * rounds,
            reply_write_seconds=slow * 0.9e-3 * 4 * rounds,
            sync_wait_seconds=slow * 0.8e-3 * 4 * rounds,
            release_wall_seconds=slow * 2.0e-3 * rounds,
            release_apply_seconds=slow * 0.7e-3 * rounds)


@pytest.mark.parametrize("name,want", [
    ("ps_server_recv_ms", 1.05), ("ps_server_merge_ms", 0.21),
    ("ps_reply_write_ms", 0.945), ("ps_barrier_wait_ms", 0.84),
    ("ps_release_wall_ms", 2.1), ("ps_release_apply_ms", 0.735)])
def test_a_push_phase_is_its_sum_over_the_servers_a_push_or_a_round(
        name, want, mirror):
    _two_servers(mirror)
    pushes = 2 * (4 * 800 + 1)
    a_push = want * 2 * 4 * 800 / pushes
    got = _reader(name)(_run())
    assert got == pytest.approx(want if "release" in name else a_push)


@pytest.mark.parametrize("name", [*COUNTERS, *BARRIER])
def test_a_counter_reader_says_nothing_where_nothing_is_counted(name, mirror):
    assert _reader(name)(_run()) is None            # no read has mirrored
    mirror(0, total_pushes=0, sync_rounds=0)
    assert _reader(name)(_run()) is None            # none pushed
    mirror(0, total_pushes=9, sync_rounds=2)        # a server from before
    assert _reader(name)(_run()) is None            # the five: no such stat
    _two_servers(mirror)
    assert _reader(name)({"window": {"spans": {}}}) is None   # no PS job
    if name in BARRIER:
        asynchronous = _run()
        del asynchronous["bsp"]
        assert _reader(name)(asynchronous) is None
        mirror(0, sync_rounds=0)
        mirror(1, sync_rounds=0)
        assert _reader(name)(_run()) is None        # no round released


# -- the clocks and the idle split --------------------------------------------
OFFSET = 100.0          # trace time less perf_counter, as the anchor gives it
ROUND, W_PUT, D2H, PUSH = 16e-3, 1.5e-3, 1.3e-3, 5.5e-3
RUN = 6.8e-3


def _a_worker_to_a_chip(leads=(1.5e-3, 1.7e-3), rounds=5,
                        dispatch=(0.5e-3, 0.3e-3, 0.4e-3, 0.3e-3, 0.6e-3),
                        wake=(0.3e-3, 0.5e-3, 0.4e-3, 0.6e-3, 0.3e-3)):
    """Two workers, a chip each, five lock-step rounds of 16 ms: ``w_put``
    1.5, ``compute`` (dispatch, the run of 6.8, the wake-up), ``grad_d2h``
    1.3, ``push`` 5.5, and the loop's 0.2 to the next round; chip *r*'s
    events stand ``leads[r]`` early.  The quickest dispatch and the
    quickest wake-up are both 0.3, so a bracket's middle is the lead."""
    xtrace, marks, host = {}, {}, []
    t_end = 0.0
    for r, lead in enumerate(leads):
        tid, runs, ops = 7000 + r, [], []
        t = 0.05 + 1e-4 * r
        for k in range(rounds):
            for name, dur in (("w_put", W_PUT),
                              ("compute", dispatch[k] + RUN + wake[k]),
                              ("grad_d2h", D2H), ("push", PUSH)):
                host.append((name, tid, t - OFFSET, dur))
                if name == "compute":
                    marks.setdefault(r, []).append((t + 5e-6, t + dur - 5e-6))
                    runs.append((f"{STEP}({k})", t + dispatch[k] - lead, RUN))
                    # two operations, back to back, inside the program
                    ops += [("tpu_custom_call", t + dispatch[k] - lead, 4e-3),
                            ("fusion", t + dispatch[k] + 4e-3 - lead,
                             RUN - 4e-3)]
                if name == "push":
                    # the exchange's children lie inside it and add nothing
                    host.append(("xchg_await", tid, t + 1e-3 - OFFSET, 3e-3))
                t += dur
            t += ROUND - (W_PUT + dispatch[k] + RUN + wake[k] + D2H + PUSH)
        t_end = max(t_end, t)
        xtrace[f"/device:TPU:{r}"] = {"XLA Modules": runs, "XLA Ops": ops}
    tr = {"xtrace": xtrace, "host_spans": host, "clock_offset": OFFSET,
          "window": (0.05, t_end), "step_program": STEP, "marks": marks,
          "plane_of_rank": {r: f"/device:TPU:{r}" for r in range(len(leads))}}
    return {**_run(), "trace": tr}


def test_two_planes_with_a_known_lead_and_a_known_idle_split(capsys):
    traced = _a_worker_to_a_chip()
    got = ps_clock_lead_ms.account(traced["trace"])
    for plane, lead in (("/device:TPU:0", 1.5e-3), ("/device:TPU:1", 1.7e-3)):
        lower, upper = got["bracket_s"][plane]
        assert lower == pytest.approx(lead - 0.3e-3)
        assert upper == pytest.approx(lead + 0.3e-3)
        assert got["lead_s"][plane] == pytest.approx(lead)
    assert _reader("ps_clock_lead_ms")(traced) == pytest.approx(1.6)
    said = capsys.readouterr().out
    assert "/device:TPU:0=1.2000/1.8000 /device:TPU:1=1.4000/2.0000" in said
    # a chip's round: busy 6.8 of 16; idle under its own worker's compute
    # (dispatch and wake-up, 0.8 or 0.9), the link 2.8, the exchange 5.5;
    # under no span the loop's 0.1 ms after three of the five rounds and
    # the 0.1 ms worker 1 starts, and so ends, behind worker 0
    rounds, chips = 5, 2
    launch = chips * sum((0.5 + 0.3, 0.3 + 0.5, 0.4 + 0.4, 0.3 + 0.6,
                          0.6 + 0.3)) * 1e-3
    link, exchange = chips * rounds * (W_PUT + D2H), chips * rounds * PUSH
    unnamed = chips * (3 * 0.1e-3 + 0.1e-3)
    idle = launch + link + exchange + unnamed
    assert idle == pytest.approx(
        chips * (traced["trace"]["window"][1] - 0.05 - rounds * RUN))
    assert got["idle_s"]["compute"] == pytest.approx(launch)
    assert got["idle_s"]["link"] == pytest.approx(link)
    assert got["idle_s"]["exchange"] == pytest.approx(exchange)
    assert got["idle_s"]["unnamed"] == pytest.approx(unnamed)
    assert _reader("ps_idle_link_share")(traced) == pytest.approx(
        100 * link / idle)
    assert _reader("ps_idle_exchange_share")(traced) == pytest.approx(
        100 * exchange / idle)
    assert _reader("ps_idle_unnamed_share")(traced) == pytest.approx(
        100 * unnamed / idle)
    assert sum(_reader(n)(traced) for n in IDLE) < 100


def test_the_split_is_the_same_whatever_the_lead():
    """What the shift is for: the same rounds with the chip's events on the
    host's own clock read a lead of 0 and the split the led trace reads
    after its shift; left 1.5 ms early, the program's first 1.5 ms would
    lie under ``w_put`` and its wake-up grow by as much."""
    traced = _a_worker_to_a_chip(leads=(1.5e-3,))
    shifted = ps_clock_lead_ms.account(traced["trace"])["idle_s"]
    early = _a_worker_to_a_chip(leads=(1.5e-3,))["trace"]
    for lines in early["xtrace"].values():      # as if no lead were found
        lines["XLA Ops"] = [(n, s + 1.5e-3, d) for n, s, d in lines["XLA Ops"]]
        lines["XLA Modules"] = [(n, s + 1.5e-3, d)
                                for n, s, d in lines["XLA Modules"]]
    assert ps_clock_lead_ms.account(early)["idle_s"] == pytest.approx(shifted)
    assert ps_clock_lead_ms.account(early)["lead_s"] == pytest.approx(
        {"/device:TPU:0": 0.0})


def test_a_workers_spans_count_for_its_own_chip_alone():
    """Worker 1 is 0.1 ms behind worker 0 all the way, so each stands in
    another span than the other for 0.1 ms at every boundary: were a
    chip's gaps laid beside both workers' spans, ``compute`` and the link
    would take those pieces from the exchange.  Two chips read twice
    what one reads alone."""
    traced = _a_worker_to_a_chip(leads=(1.5e-3, 1.5e-3))
    whole = ps_clock_lead_ms.account(traced["trace"])["idle_s"]
    one = _a_worker_to_a_chip(leads=(1.5e-3,))
    alone = ps_clock_lead_ms.account(one["trace"])["idle_s"]
    for name in ("compute", "link", "exchange"):
        assert whole[name] == pytest.approx(2 * alone[name])


def test_an_empty_bracket_reads_nothing(capsys):
    traced = _a_worker_to_a_chip()
    name, s, d = traced["trace"]["xtrace"]["/device:TPU:1"]["XLA Modules"][2]
    # one run of chip 1 stands 3 ms late: no lead puts it inside its span
    traced["trace"]["xtrace"]["/device:TPU:1"]["XLA Modules"][2] = (
        name, s + 3e-3, d)
    lower, upper = ps_clock_lead_ms.bracket(
        traced["trace"]["marks"][1],
        [(s, s + d) for _n, s, d
         in traced["trace"]["xtrace"]["/device:TPU:1"]["XLA Modules"]])
    assert lower > upper
    for reader in TRACED:
        assert _reader(reader)(traced) is None
    assert "clock lead" not in capsys.readouterr().out


@pytest.mark.parametrize("why", ["no trace", "no device plane",
                                 "a run short", "no PS job"])
def test_the_traced_readers_say_nothing_where_they_cannot_read(why):
    traced = _a_worker_to_a_chip()
    if why == "no trace":
        traced["trace"] = None
    elif why == "no device plane":      # a rehearsal on the CPU
        traced["trace"]["xtrace"] = {"/host:CPU": {"t": []}}
    elif why == "a run short":
        traced["trace"]["xtrace"]["/device:TPU:0"]["XLA Modules"].pop()
    else:
        del traced["ps"]
    for reader in TRACED:
        assert _reader(reader)(traced) is None


def test_one_chip_takes_every_workers_spans_and_pairs_none():
    """Two workers on the one chip, their programs one after the other:
    worker B's ``compute`` span holds both runs.  No pairing is guessed:
    the sorted starts and the sorted ends bound the lead, and the true
    one lies inside."""
    lead, host, runs, ops = 0.4e-3, [], [], []
    t = 0.02
    for k in range(6):
        a_run = (t + 0.3e-3 + 0.02e-3 * k, t + 2.6e-3)
        b_run = (a_run[1], a_run[1] + 2.3e-3)
        host += [("w_put", 1, t - 1.5e-3 - OFFSET, 1.5e-3),
                 ("w_put", 2, t - 1.4e-3 - OFFSET, 1.5e-3),
                 ("compute", 1, t - OFFSET, a_run[1] + 0.25e-3 - t),
                 ("compute", 2, t + 0.1e-3 - OFFSET,
                  b_run[1] + 0.2e-3 + 0.03e-3 * k - t - 0.1e-3),
                 ("wire", 11, a_run[1] + 0.4e-3 - OFFSET, 3e-3),
                 ("wire", 12, b_run[1] + 0.4e-3 - OFFSET, 3e-3)]
        for s, e in (a_run, b_run):
            runs.append((f"{STEP}({k})", s - lead, e - s))
            ops.append(("tpu_custom_call", s - lead, e - s))
        t += 9e-3
    tr = {"xtrace": {"/device:TPU:0": {"XLA Modules": runs, "XLA Ops": ops},
                     "/device:TPU:1": {"XLA Modules": [], "XLA Ops": []}},
          "host_spans": host, "clock_offset": OFFSET, "window": (0.018, t),
          "step_program": STEP}
    got = ps_clock_lead_ms.account(tr)
    assert list(got["lead_s"]) == ["/device:TPU:0"]
    lower, upper = got["bracket_s"]["/device:TPU:0"]
    # the quickest dispatch 0.3 (A's), the quickest wake-up 0.2 (B's)
    assert lower == pytest.approx(lead - 0.3e-3)
    assert upper == pytest.approx(lead + 0.2e-3)
    assert lower <= lead <= upper
    run = {**_run(), "trace": tr}
    del run["bsp"]
    assert _reader("ps_clock_lead_ms")(run) == pytest.approx(0.35)
    shares = {n: _reader(n)(run) for n in IDLE}
    # the comm threads' ``wire`` counts for the one chip too
    assert shares["ps_idle_exchange_share"] > 30
    assert shares["ps_idle_link_share"] > 10
    assert sum(shares.values()) <= 100 + 1e-9


# -- BENCHMARK.json and the rehearsals ----------------------------------------
def test_the_43_accepted_entries_stand_letter_for_letter_before_the_13():
    bench = manifest.load_benchmark()
    entries = bench["per_layer"]
    assert hashlib.sha256(json.dumps(entries[:43], sort_keys=True).encode()
                          ).hexdigest() == ACCEPTED_43
    assert [m["name"] for m in entries[43:56]] == NEW
    layers = {m["layer"] for m in entries[:43]}
    for m in entries[43:56]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] == "train_samples_per_s" and m["better"] == "lower"
        assert m["layer"] in layers                 # no new layer names
        assert m["workloads"] == (BSP_CELLS if m["name"] in BARRIER
                                  else PS_CELLS)    # one name, no twins
        assert m["unit"] == ("%" if m["name"] in IDLE else "ms")
        assert m["source"] == ("program_span" if m["name"] in SPANS else
                               "device_trace" if m["name"] in TRACED else
                               "program_counter")
        assert callable(_reader(m["name"]))
    sync = {m["name"] for m in manifest.Cell(bench, "dense-sync-1chip").per_layer}
    assert not sync & set(NEW)


@pytest.mark.parametrize("cell", PS_CELLS)
def test_a_rehearsal_lists_the_new_names_that_the_cpu_can_read(cell, capsys):
    rc = run.main(["--workload", cell, "--seed", "3400000013", "--seconds",
                   "0.2", "--trace", "1", "--rehearse"])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert rc == 0 and last.startswith("REHEARSAL ")
    doc = json.loads(last[len("REHEARSAL "):])
    assert doc["correct"] is True
    listed = set(doc["layer_metrics"])
    want = {*SPANS, *COUNTERS, *(BARRIER if cell in BSP_CELLS else ())}
    assert want <= listed
    # the CPU's trace has no device plane: the clocks cannot be tied
    assert not set(TRACED) & listed
    assert set(NEW) & listed == want
