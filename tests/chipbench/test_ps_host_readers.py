"""The seven readers PR 49 appended: what a PS round costs its host
beside the named parts of the exchange and of the device chain: a keyed
operation's way in, its way back to the interpreter and its own
bookkeeping (``ps_op_enter_ms``, ``ps_op_wake_ms``, ``ps_op_account_ms``),
the hand-overs to and from the comm thread (``ps_handoff_ms``), the
loop's own Python (``ps_loop_self_ms``), the staleness probe
(``ps_probe_ms``) and what no span covers (``ps_round_uncovered_ms``).
On hand-built runs, on the CPU rehearsal of a lock-step and of two
pipelined cells, and as entries of ``BENCHMARK.json``.

The entries list the three PS cells whose accepted tests let a later PR
add to what the cell reports (``dense-ps-async-1chip``,
``dense-ps-bsp-1chip``, ``dense-ps-bsp-4chip``, as PR 34's thirteen do).
The four younger cells' tests hold each cell to exactly its own readers
and the four list-less ones (``test_every_new_metric_is_read_in_its_own_
cell_only`` in ``test_dense_ps_bsp_eval.py``, ``_minibatch.py``,
``_softmax.py``, ``_bsp_delay.py``), and no file that is here is edited:
their programs record the same spans, the readers read them (below, on
two of their rehearsals), and a ``benchmark`` PR appends the cells."""

import importlib
import math
import os
import time

import pytest

from chipbench import manifest, run
from chipbench.compiles import Compiles

BENCH = manifest.load_benchmark()
PS_CELLS = ["dense-ps-async-1chip", "dense-ps-bsp-1chip", "dense-ps-bsp-4chip"]
PIPELINED = PS_CELLS[:1]
#: reader -> (its layer, the cells it lists)
NEW = {
    "ps_op_enter_ms": ("PS exchange", PS_CELLS),
    "ps_op_wake_ms": ("PS exchange", PS_CELLS),
    "ps_op_account_ms": ("PS exchange", PS_CELLS),
    "ps_handoff_ms": ("PS exchange", PIPELINED),
    "ps_loop_self_ms": ("PS worker round", PS_CELLS),
    "ps_probe_ms": ("PS worker round", PIPELINED),
    "ps_round_uncovered_ms": ("PS worker round", PS_CELLS),
}
#: what each rehearsed cell's program records a span for, listed or not:
#: a comm thread (``ps_handoff_ms``), asynchronous servers (``ps_probe_ms``)
RECORDS = {
    "dense-ps-bsp-1chip": set(NEW) - {"ps_handoff_ms", "ps_probe_ms"},
    "dense-ps-async-minibatch-1chip": set(NEW),
    "dense-ps-bsp-delay1-1chip": set(NEW) - {"ps_probe_ms"},
}
#: the spans each reads: without them it says nothing
READS = {
    "ps_op_enter_ms": ["xchg_enter"], "ps_op_wake_ms": ["xchg_wake"],
    "ps_op_account_ms": ["xchg_account"],
    "ps_handoff_ms": ["wire_handoff", "reply_wake"],
    "ps_loop_self_ms": ["round"], "ps_probe_ms": ["staleness_probe"],
    "ps_round_uncovered_ms": ["round"],
}


def _reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}").read


# -- hand-built runs ------------------------------------------------------------
def _run():
    """A worker's window of 400 rounds in 4 s, an epoch of two rounds:
    10 ms a round, of which the spans below."""
    def span(ms, n=400, own=None):
        return {"seconds": 1e-3 * ms * n, "count": n,
                "self_seconds": 1e-3 * (ms if own is None else own) * n}
    return {"window": {"wall_s": 4.0, "spans": {
        "data_load": span(0.01), "round": span(9.0, own=0.5),
        "epoch_end": span(1.9, n=200, own=0.3),
        "push": span(3.0), "wire": span(2.0),
        "wire_handoff": span(0.25), "reply_wake": span(0.07, n=600),
        "staleness_probe": span(0.6, n=80),
        "xchg_enter": span(0.08), "xchg_send": span(0.9),
        "xchg_await": span(0.3), "xchg_recv": span(0.7),
        "xchg_wake": span(0.4), "xchg_account": span(0.12)}},
        "trace": None}


@pytest.mark.parametrize("name,want", [
    ("ps_op_enter_ms", 0.08), ("ps_op_wake_ms", 0.4),
    ("ps_op_account_ms", 0.12),
    ("ps_handoff_ms", 0.25 + 0.07 * 600 / 400),
    ("ps_loop_self_ms", 0.5 + 0.3 * 200 / 400),
    ("ps_probe_ms", 0.6 * 80 / 400),
    ("ps_round_uncovered_ms", 10.0 - 9.0 - 0.01 - 1.9 * 200 / 400),
])
def test_a_reader_is_its_spans_seconds_over_the_rounds(name, want):
    assert _reader(name)(_run()) == pytest.approx(want)
    parent = _run()
    for span in READS[name]:
        del parent["window"]["spans"][span]
    assert _reader(name)(parent) is None        # a program from before them


def test_the_hand_overs_count_whichever_of_the_two_there_is():
    one = _run()
    del one["window"]["spans"]["reply_wake"]
    assert _reader("ps_handoff_ms")(one) == pytest.approx(0.25)
    none = _run()
    del none["window"]["spans"]["wire"]         # a lock-step job
    assert _reader("ps_handoff_ms")(none) is None


def test_the_loops_own_seconds_need_no_epochs_end():
    run_ = _run()
    del run_["window"]["spans"]["epoch_end"]
    assert _reader("ps_loop_self_ms")(run_) == pytest.approx(0.5)
    assert _reader("ps_round_uncovered_ms")(run_) == pytest.approx(0.99)


# -- the rehearsals ---------------------------------------------------------------
@pytest.fixture(scope="module", params=["dense-ps-bsp-1chip",
                                        "dense-ps-async-minibatch-1chip",
                                        "dense-ps-bsp-delay1-1chip"])
def rehearsed(request):
    """The cell's driver at the rehearsal's sizes on the CPU: the ``run``
    its readers get.  The lock-step job, the pipelined asynchronous one
    (a comm thread and a probe) and the pipeline against lock-step
    servers (a comm thread, no probe)."""
    cell = manifest.Cell(BENCH, request.param)
    # a window of a second: a fit's start and end (the threads' starts,
    # a delayed fit's last drain) are in the wall, once
    ctx = run.Context(cell=cell, seed=2147483659, seconds=1.0, trace=False,
                      rehearsal=True, devices=run.take_devices(1, True),
                      compiles=Compiles(), t_start=time.perf_counter())
    ctx.say = lambda text: None
    res = cell.driver.run(ctx)
    assert res["correct"] is True
    return cell, res["run"]


@pytest.mark.parametrize("name", NEW)
def test_a_rehearsal_reads_a_finite_number_where_the_program_has_the_span(
        rehearsed, name):
    cell, run_ = rehearsed
    got = _reader(name)(run_)
    if name not in RECORDS[cell.name]:
        assert got is None   # lock step: no comm thread; its servers: no probe
        return
    assert got is not None and math.isfinite(got) and got >= 0.0, (name, got)
    # in the result line where the cell lists the reader, and only there
    listed = cell.name in NEW[name][1]
    assert listed == (name in {m["name"] for m in cell.per_layer})
    assert listed == (name in run.layer_metrics(cell, run_))


def test_the_six_phases_of_an_op_are_its_parent_in_a_rehearsal(rehearsed):
    cell, run_ = rehearsed
    spans = run_["window"]["spans"]
    parent = "push" if cell.name == "dense-ps-bsp-1chip" else "wire"
    assert spans[parent]["count"] == spans["xchg_enter"]["count"]
    six = sum(spans[n]["seconds"] for n in (
        "xchg_enter", "xchg_send", "xchg_await", "xchg_recv", "xchg_wake",
        "xchg_account"))
    assert six <= spans[parent]["seconds"]
    assert six >= 0.97 * spans[parent]["seconds"]


def test_a_rehearsals_round_is_covered(rehearsed):
    """``ps_round_uncovered_ms`` under 5% of the round (the rehearsal's
    window is some eighty rounds: a ``fit``'s start and end are in it).
    Asynchronous workers finish apart and the window's wall waits for
    the last of them: a round or two of eighty here (in a 40 s window
    of thousands, nothing), so that cell is held to a quarter."""
    cell, run_ = rehearsed
    spans, wall = run_["window"]["spans"], run_["window"]["wall_s"]
    round_ms = 1e3 * wall / spans["round"]["count"]
    left = _reader("ps_round_uncovered_ms")(run_)
    lock_step = cell.name != "dense-ps-async-minibatch-1chip"
    assert 0.0 <= left <= (0.05 if lock_step else 0.25) * round_ms, (
        left, round_ms)
    # and what the loop's own seconds name is inside the round
    assert _reader("ps_loop_self_ms")(run_) <= round_ms


# -- BENCHMARK.json ---------------------------------------------------------------
def test_the_seven_entries_stand_at_the_end_with_a_file_each():
    tail = BENCH["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) == len(set(names)) == 86
    layers = {m["layer"] for m in BENCH["per_layer"][:-len(NEW)]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in tail:
        layer, listed = NEW[m["name"]]
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": layer,
                     "moves": "train_samples_per_s", "workloads": listed}
        assert layer in layers          # a layer the benchmark had
        assert set(listed) <= cells
        path = os.path.join(manifest.HERE, "layer_metrics",
                            f"{m['name']}.py")
        assert os.path.isfile(path)
        assert callable(_reader(m["name"]))
    # a comm thread and a probe are the asynchronous job's
    assert NEW["ps_probe_ms"][1] == NEW["ps_handoff_ms"][1] == [
        "dense-ps-async-1chip"]
