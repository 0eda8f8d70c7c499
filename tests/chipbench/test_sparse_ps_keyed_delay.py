"""The keyed FTRL cell under bounded delay, tau = 1: its plain reference
(where the delay puts each round: ``computed_on`` and ``solo`` against a
replay written out by hand), the whole runs that must not be ``correct``
(each by the row that names its fault), its configuration, its tap, its
per-layer readers and its entries in ``BENCHMARK.json``."""

import copy
import importlib
import json
import threading
import time

import numpy as np
import pytest

from chipbench import datagen, manifest, reference, run
from chipbench.drivers import ps_keyed_delay_epochs as driver
from chipbench.drivers import ps_keyed_epochs as keyed_driver
from chipbench.families import sparse_ps_keyed_delay as family
from chipbench.families import sparse_ps_keyed_ftrl

CELL = "sparse-ps-delay1-keyed-ftrl-1chip"
CONFIG = "criteo-ps-delay1-keyed-ftrl-1m"
SIBLING = "sparse-ps-async-keyed-ftrl-1chip"
READERS = ["kd_round_ms", "kd_exchange_wait_ms", "kd_wire_ms",
           "kd_overlap_share", "kd_pulls_behind", "kd_server_apply_ms",
           "kd_launch_wait_ms"]
#: the accepted metrics with no ``workloads`` list: read in every cell
LIST_LESS = ["compile_s", "input_wait_share", "step_ms", "step_hbm_roofline"]
ROWS = ["grad_norm_rel_gap", "grad_diff_rel", "replay_rel",
        "pulled_lineage_rel", "n_conservation_rel", "update_missing",
        "steps_miscount", "unacknowledged_recorded", "closed_form_rel",
        "zeros_mismatch", "untouched_moved", "no_opt_state",
        "test_logloss_rel_gap", "unacknowledged_window", "keys_mismatch",
        "window_rows_short", "dense_frames", "resident_short", "host_steps",
        "lineage_miscount_recorded", "lineage_miscount_window"]
WARM, RECORDED, PACE = 1, 1, 1  # the traffic file's epochs before the window
RULE = dict(alpha=0.1, beta=1.0, l1=3e-3, l2=0.25)
F32 = np.float32


def _rehearse(capsys, *extra):
    rc = run.main(["--workload", CELL, "--seed", "3100000057", "--seconds",
                   "0.2", "--trace", "0", "--rehearse", *extra])
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert rc == 0 and last.startswith("REHEARSAL ")
    return json.loads(last[len("REHEARSAL "):]), out


def _bad(doc):
    return {r["name"] for r in doc["compared"] if not r["ok"]}


def _bits(a, b):
    a, b = np.asarray(a, F32), np.asarray(b, F32)
    return a.shape == b.shape and np.array_equal(a.view(np.uint32),
                                                 b.view(np.uint32))


# -- the reference: where the delay puts a round -------------------------------
@pytest.fixture(scope="module")
def shard():
    cols, vals, y = datagen.make_rows(
        57, "train", 3 * 256, fields="criteo-kaggle", num_buckets=2048,
        label_scale=0.5, label_bias=-1.0)
    return cols, vals, y


def _frames(seed, ranks, rounds, dim=600, keys=200):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(ranks):
        mine = []
        for _k in range(rounds):
            u = np.sort(rng.choice(dim, size=keys, replace=False)).astype(
                np.uint64)
            g = (rng.standard_normal(keys) * 4e-3).astype(F32)
            g[rng.random(keys) < 0.1] = 0.0
            mine.append((u, g))
        out.append(mine)
    return out


def test_computed_on_is_every_earlier_ranks_prefix_and_own_pushes_but_the_last():
    pushes = _frames(3, ranks=3, rounds=4)
    rng = np.random.default_rng(1)
    w0 = (rng.standard_normal(600) * 0.02).astype(F32)
    z0 = (rng.standard_normal(600) * 0.01).astype(F32)
    n0 = (rng.random(600) * 1e-3).astype(F32)
    w0 = sparse_ps_keyed_ftrl.closed_form(z0, n0, **RULE)
    for rank in range(3):
        for k in range(4):
            # written out: the ranks before, whole; own pushes 0 .. k - 2
            order = [p for r in range(rank) for p in pushes[r]]
            order += pushes[rank][:max(k - 1, 0)]
            (w, _z, _n), _ = sparse_ps_keyed_ftrl.replay(
                order, w0, z0, n0, **RULE)
            at = pushes[rank][k][0].astype(np.int64)
            got = family.computed_on(k, pushes, (w0, z0, n0), rank=rank,
                                     **RULE)
            assert got.dtype == F32 and _bits(got, w[at])
            other = np.arange(0, 600, 7)
            assert _bits(family.computed_on(k, pushes, (w0, z0, n0),
                                            rank=rank, at=other, **RULE),
                         w[other])
    # rounds 0 and 1 of a worker run on the same state; round 2 holds
    # push 0 and not push 1; the serialized job's weights are another's
    at = pushes[1][2][0]
    same = [family.computed_on(k, pushes, (w0, z0, n0), rank=1, at=at, **RULE)
            for k in range(4)]
    assert _bits(same[0], same[1]) and not _bits(same[1], same[2])
    serialized = sparse_ps_keyed_ftrl.replay(
        pushes[0] + pushes[1][:2], w0, z0, n0, **RULE)[0][0][at.astype(int)]
    assert _bits(same[3], serialized) and not _bits(same[2], serialized)
    assert _bits(w0, sparse_ps_keyed_ftrl.closed_form(z0, n0, **RULE))


@pytest.mark.parametrize("rule", [dict(alpha=0.1, beta=1.0, l1=1e-3, l2=0.0),
                                  None], ids=["ftrl", "sgd"])
def test_solo_is_the_rule_followed_one_operation_at_a_time(shard, rule):
    """``solo`` against the connection's sequence written out: L_0, L_1,
    P_0, L_2, P_1, ..., each pull a copy of the table, each push a step."""
    cols, vals, y = shard
    R, B, dim = 7, 256, 2048
    w0 = (np.random.default_rng(2).standard_normal(dim) * 0.05).astype(F32)
    vs, gs, (w, z, n) = family.solo(w0, shard, R, batch=B, rule=rule, lr=0.2)
    table = [w0.copy(), np.zeros(dim, F32), np.zeros(dim, F32)]
    pulled, pushed = {}, {}

    def pull(k):
        at = family.window(k, len(y), B)
        u = family.keys(cols[at]).astype(np.int64)
        pulled[k] = (u, table[0][u].copy())

    def push(k):
        u, v = pulled[k]
        at = family.window(k, len(y), B)
        g = family.gradient(v, cols[at], vals[at], y[at], dim=dim)
        pushed[k] = g
        if rule is None:
            table[0][u] = family.sgd_step(table[0][u], g, lr=0.2)
        else:
            table[0][u], table[1][u], table[2][u] = family.ftrl_step(
                table[0][u], table[1][u], table[2][u], g, **rule)

    pull(0)
    pull(1)
    for k in range(R):
        push(k)
        if k + 2 < R:
            pull(k + 2)
    assert len(vs) == len(gs) == R
    for k in range(R):
        assert _bits(vs[k], pulled[k][1]) and _bits(gs[k], pushed[k])
    assert _bits(w, table[0])
    if rule is not None:
        assert _bits(z, table[1]) and _bits(n, table[2])
    else:
        assert not z.any() and not n.any()
    # window 0 comes again in round 3 (an epoch is no boundary), on other
    # weights; handed pushes stand where the reference's own stood
    assert np.array_equal(family.keys(cols[family.window(3, len(y), B)]),
                          family.keys(cols[family.window(0, len(y), B)]))
    assert not _bits(vs[3], vs[0])
    handed = [np.zeros_like(g) for g in gs]
    _v, _g, (w_still, _z, _n) = family.solo(w0, shard, R, batch=B, rule=rule,
                                            lr=0.2, pushes=handed)
    assert _bits(w_still, w0)


def test_the_family_is_the_siblings_and_states_what_it_departs_from():
    with open(family.__file__) as f:
        text = f.read()
    assert "distlr_tpu" not in text
    for said in ("OSDI 2014", "Algorithm 3", "3.4", "KKT filter",
                 "feature blocks", "tau = 1", "from\nmemory",
                 "L_0, L_1, P_0, L_2, P_1", "exactly one own push"):
        assert said in text, said
    for name in ("window", "keys", "gradient", "closed_form", "ftrl_step",
                 "replay", "step_bytes_floor", "logits", "evaluate"):
        assert getattr(family, name) is getattr(sparse_ps_keyed_ftrl, name)
    assert reference.family("sparse_ps_keyed_delay") is family
    assert family.DELAY == 1
    got = family.sgd_step(np.array([1.0, -2.0], F32),
                          np.array([0.5, 0.25], F32), lr=0.2)
    assert got.dtype == F32 and _bits(got, F32([1.0, -2.0]) - F32(0.2) * F32(
        [0.5, 0.25]))


# -- the configuration --------------------------------------------------------
def test_the_configuration_states_the_rule_and_the_guarantee_it_replaces():
    bench = manifest.load_benchmark()
    cell = manifest.Cell(bench, CELL)
    conf, traffic = cell.config, cell.traffic
    sibling = manifest.Cell(bench, SIBLING)
    theirs = sibling.config
    assert conf["reduced"] == theirs["reduced"] == [
        "train_rows", "test_rows", "num_iteration"]
    assert set(conf["reduced_why"]) == set(conf["reduced"])
    assert "No width is cut" in conf["reduced_why"]["train_rows"]
    assert "no boundary" in conf["reduced_why"]["train_rows"]
    assert conf["architecture"] is None
    assert conf["family"] == "sparse_ps_keyed_delay"
    # the sibling's program with the delay, its generator, its rehearsal
    assert conf["program"] == {**theirs["program"], "ps_max_delay": 1}
    assert conf["generator"] == theirs["generator"]
    assert conf["rehearsal"] == theirs["rehearsal"]
    assert conf["control"]["program"] == {"ps_max_delay": 0}
    assert conf["control"]["precision"] == "bfloat16"
    assert "serialized" in conf["control"]["why"]
    # nine guarantees, the fifth replaced
    assert len(conf["guarantees"]) == 9
    for k in range(9):
        assert (conf["guarantees"][k] == theirs["guarantees"][k]) == (k != 4)
    for said in ("through round k - 2 applied", "round k - 1 not",
                 "one operation at a time", "nothing of a worker's in flight",
                 "an eval, a checkpoint or fit's return"):
        assert said in conf["guarantees"][4]
    for said in ("L_0, L_1, P_0, L_2, P_1, L_3", "P_{R-3}, L_{R-1}, P_{R-2}, "
                 "P_{R-1}", "exactly one own push behind", "k mod 240",
                 "L_R does not exist", "second fit starts again from L_0, L_1",
                 "sigma = (sqrt(n + g^2) - sqrt(n)) / alpha"):
        assert said in conf["deployment"], said
    for said in ("tau", "algorithm_3", "batch_size", "sibling"):
        assert said in conf["assumed"]
    for said in ("KKT filter", "blocks of features", "FROM MEMORY", "NOT run"):
        assert said in conf["assumed"]["algorithm_3"], said
    assert "B = 16,384" in conf["assumed"]["batch_size"]
    for said in ("OSDI 2014", "3.4", "BOUNDED DELAY", "Algorithm 3",
                 "KDD 2013", "FROM MEMORY", "KKT filter"):
        assert said in conf["source_says"], said
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == conf["source"] and len(conf["source"]) <= 200
    for said in ("OSDI 2014", "3.4", "5.1", "Algorithm 3", "tau=1",
                 "KDD 2013", "criteo D=1M"):
        assert said in conf["source"], said
    assert entry["reduced"] == conf["reduced"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    # the limits: the sibling's, the stale row replaced by the lineage's
    assert set(conf["limits"]) == (set(theirs["limits"]) - {
        "pulled_stale_rel"}) | {"pulled_lineage_rel", "lineage_miscount"}
    for name, limit in theirs["limits"].items():
        if name != "pulled_stale_rel":
            assert conf["limits"][name] == limit
    assert conf["limits"]["pulled_lineage_rel"] == theirs["limits"][
        "pulled_stale_rel"] == 1e-6
    assert conf["limits"]["lineage_miscount"] == 0.5
    for name in conf["limits"]:
        assert name in conf["limits_from"], name
    # what stays on the chip: the sibling's, over a quarter of its memory
    prog, gen = conf["program"], conf["generator"]
    resident = prog["num_workers"] * keyed_driver.shard_bytes(
        gen["rows_per_worker"], prog["batch_size"], 39)
    assert round(resident / 1e9, 3) == 5.033
    assert resident >= 0.25 * 16 * 2**30
    assert "5.033 GB" in conf["device_memory"]
    assert "second 90,112-key" in conf["device_memory"]
    assert "TBD" not in json.dumps(conf)
    assert traffic["kind"] == "ps_keyed_delay_epochs"
    assert (traffic["warm_epochs"], traffic["recorded_epochs"],
            traffic["checked_rounds"], traffic["pace_epochs"]) == (
        WARM, RECORDED, 4, PACE)
    assert {k: v for k, v in traffic.items() if k not in (
        "kind", "what", "checked_rounds")} == {
        k: v for k, v in sibling.traffic.items() if k not in (
            "kind", "what", "checked_rounds")}


def test_the_program_takes_the_configuration_as_it_is_written():
    from distlr_tpu import Config
    from distlr_tpu.train import ps_trainer

    conf = manifest.Cell(manifest.load_benchmark(), CELL).config
    cfg = Config(data_dir="nowhere", test_interval=0, **conf["program"])
    assert cfg.model == "sparse_lr" and not cfg.sync_mode
    assert cfg.ps_max_delay == 1
    assert ps_trainer.server_optimizer(cfg) == "ftrl"
    control = Config(data_dir="nowhere", test_interval=0,
                     **{**conf["program"], **conf["control"]["program"]})
    assert control.ps_max_delay == 0


# -- whole runs ----------------------------------------------------------------
def test_the_rehearsal_is_correct_and_names_every_new_metric(capsys):
    doc, out = _rehearse(capsys)
    assert doc["correct"] is True, out
    # the trace's one (kd_launch_wait_ms) has nothing to read untraced
    assert set(READERS) - {"kd_launch_wait_ms"} <= set(doc["layer_metrics"])
    assert {"compile_s", "input_wait_share", "step_ms"} <= set(
        doc["layer_metrics"])
    assert not {m for m in doc["layer_metrics"]
                if m.startswith(("kx_", "kf_", "dl_"))}
    assert [r["name"] for r in doc["compared"]] == ROWS
    assert "optimizer=ftrl" in out and "prefix=3" in out
    assert "lineage_miscount=0" in out and "in_flight_at_return=0" in out
    assert "events_dropped=0" in out and "events_a_round=" in out
    assert out.count("a window of the resident localised shard") >= 4
    # a fit's first pull none behind, every other one: four fits
    counted = json.loads(out.split("pull_lineage=")[1].split(" in_flight")[0])
    assert counted["0"] == 4 and set(counted) == {"0", "1"}


def _with_program(monkeypatch, over):
    real = driver.effective_config

    def changed(cell, rehearsal):
        conf = copy.deepcopy(real(cell, rehearsal))
        conf["program"].update(over(conf))
        return conf

    monkeypatch.setattr(driver, "effective_config", changed)


def _exchange():
    from distlr_tpu.train import ps_trainer

    return ps_trainer._KeyedDelayed


def _the_serialized_exchange(monkeypatch):
    """The configuration's own control: pull, then push and wait."""
    _with_program(monkeypatch, lambda conf: conf["control"]["program"])


def _an_exchange_two_behind(monkeypatch):
    """A task pulls before it pushes: L_{k+1} goes out before P_{k-1}."""
    def wire(self, ctx, step, submitted, push, pull):
        got = None if pull is None else self._pull(pull)
        if push is not None:
            self._push(*push)
        return got, time.perf_counter()

    monkeypatch.setattr(_exchange(), "_wire", wire)


def _a_pull_carried_past_finish(monkeypatch):
    """Every round pulls two ahead, the last ones for rounds that do not
    run, and ``finish`` lets the replies lie."""
    def send(self, g, keys):
        w, k = self.w, self.begun - 1
        with w._span("exchange_wait", keys=len(keys)):
            self._submit(push=(k, g, keys, w._w_time, w._w_pushes),
                         pull=k + 2)
            if k + 1 < self.rounds:
                self.taken = self._take(k + 1)

    monkeypatch.setattr(_exchange(), "send", send)
    monkeypatch.setattr(_exchange(), "finish", _exchange().drain)


def _the_int8_wire(monkeypatch):
    """``Config`` refuses the coded wire under the delay: the workers'
    connections negotiate it behind its back."""
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.KVWorker
    monkeypatch.setattr(
        ps_trainer, "KVWorker",
        lambda *a, **kw: real(*a, **{**kw, "compress": "int8"}))


def _half_a_shard_masked(monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker._place_keyed_shard

    def half(self, train):
        why = real(self, train)
        if self.rank == 1 and why is None:
            p, v, y, mask = self._resident
            self._resident = (p, v, y,
                              mask * (np.arange(mask.shape[0]) % 2 == 0))
        return why

    monkeypatch.setattr(ps_trainer.PSWorker, "_place_keyed_shard", half)


def _a_window_one_round_short(monkeypatch):
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker.fit

    def one_short(self, epochs=None, **kw):
        # a worker's fourth fit is the window: warm-up, the recorded
        # epoch and the pacing epoch come before it
        if self.epochs_done == WARM + RECORDED + PACE:
            epochs -= 1
        return real(self, epochs, **kw)

    monkeypatch.setattr(ps_trainer.PSWorker, "fit", one_short)


def _the_numpy_step(monkeypatch):
    """numpy's step over a copy of the resident sorted leaves where the
    device step stood, counted as the host's."""
    from distlr_tpu.train import ps_trainer

    real = ps_trainer.PSWorker._keyed_device_step

    def on_the_host(self, train):
        real(self, train)
        B, bits = train.batch_size, self._keyed_row_bits
        packed, v, y, mask = (np.asarray(a) for a in self._resident)
        lines = len(packed) // len(self._window_keys)
        counted = ps_trainer._GRAD_ROUNDS.labels(rank=str(self.rank),
                                                 path="keyed_host")

        def grad_step(w_u, window):
            j = window.first // B
            at, rows = slice(j * lines, (j + 1) * lines), slice(j * B, j * B + B)
            p, vals = packed[at].reshape(-1), v[at].reshape(-1)
            place, row = p >> bits, p & ((1 << bits) - 1)
            with self._span("compute", marks_step=True):
                z = np.bincount(row, weights=w_u[place] * vals, minlength=B)
                resid = (1 / (1 + np.exp(-z)) - y[rows]) * mask[rows]
                g = np.bincount(place, weights=resid[row] * vals,
                                minlength=len(w_u)) / max(mask[rows].sum(), 1)
            counted.inc()
            return g.astype(np.float32)
        return grad_step

    monkeypatch.setattr(ps_trainer.PSWorker, "_keyed_device_step", on_the_host)


GRADIENTS = {"grad_norm_rel_gap", "grad_diff_rel"}
RULE_ROWS = {"closed_form_rel", "zeros_mismatch", "untouched_moved",
             "no_opt_state"}
SERVERS = {"replay_rel", "n_conservation_rel", "update_missing",
           "steps_miscount"}
COUNTS = {"keys_mismatch", "window_rows_short", "dense_frames",
          "resident_short", "host_steps"}
ACKED = {"unacknowledged_recorded", "unacknowledged_window"}
LINEAGE = {"pulled_lineage_rel", "lineage_miscount_recorded",
           "lineage_miscount_window"}


@pytest.mark.parametrize("fault,must_fail,must_hold", [
    # a sound job, and not this configuration's: the lineage alone
    (_the_serialized_exchange, LINEAGE,
     GRADIENTS | RULE_ROWS | SERVERS | COUNTS | ACKED
     | {"test_logloss_rel_gap"}),
    (_an_exchange_two_behind, LINEAGE,
     GRADIENTS | RULE_ROWS | SERVERS | COUNTS | ACKED),
    # the prefix's replies are the rule's; the count of pulls is not
    (_a_pull_carried_past_finish,
     {"lineage_miscount_recorded", "lineage_miscount_window"},
     GRADIENTS | RULE_ROWS | SERVERS | ACKED | {"pulled_lineage_rel"}
     | (COUNTS - {"dense_frames"})),
    # sound gradients of the right keys; the servers square and sum
    # something else
    (_the_int8_wire, {"n_conservation_rel"},
     GRADIENTS | RULE_ROWS | COUNTS | ACKED
     | {"lineage_miscount_recorded", "lineage_miscount_window"}),
    (_half_a_shard_masked, {"grad_diff_rel"},
     RULE_ROWS | SERVERS | ACKED | LINEAGE
     | (COUNTS - {"window_rows_short"})),
    (_a_window_one_round_short, {"window_rows_short",
                                 "lineage_miscount_window"},
     GRADIENTS | RULE_ROWS | SERVERS | {"pulled_lineage_rel",
                                        "lineage_miscount_recorded",
                                        "resident_short", "keys_mismatch"}),
    (_the_numpy_step, {"host_steps"},
     RULE_ROWS | SERVERS | ACKED | LINEAGE | (COUNTS - {"host_steps"})),
], ids=["serialized-exchange", "two-behind", "pull-past-finish", "int8-wire",
        "half-a-shard", "one-round-short", "numpy-step"])
def test_a_faulted_run_is_not_correct(capsys, monkeypatch, fault, must_fail,
                                      must_hold):
    fault(monkeypatch)
    doc, out = _rehearse(capsys)
    assert doc["correct"] is False
    assert must_fail <= _bad(doc), out
    assert not must_hold & _bad(doc), out


def test_a_program_without_the_lineage_series_leaves_at_once(monkeypatch):
    """What the parent of the PR that added the cell does: it keeps no
    count of a keyed pull's lineage, so the driver says so and makes no
    row."""
    monkeypatch.setattr(driver, "LINEAGE", "distlr_ps_no_such_series_total")
    monkeypatch.setattr(driver, "prepare", None)  # never reached
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.2",
                  "--trace", "0", "--rehearse"])
    assert e.value.code not in (0, None)
    assert "distlr_ps_no_such_series_total" in str(e.value.code)
    assert "ps_max_delay=1 is refused" in str(e.value.code)


def test_the_control_tool_reads_all_three_sides(capsys):
    rc = driver.main(["--workload", CELL, "--seeds", "21,22",
                      "--controls", "2", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out.strip().splitlines()[-1][len("CONTROL "):])
    got = doc["summary"]
    assert set(got) == set(ROWS) - {"test_logloss_rel_gap",
                                    "unacknowledged_window",
                                    "lineage_miscount_window"}
    # the serialized exchange: the lineage, and nothing else
    for name in ("pulled_lineage_rel", "lineage_miscount_recorded"):
        row = got[name]
        assert row["sound_max"] == 0 < row["limit"] < row["control_min"]
        assert row["bfloat16_min"] == 0
    for name in set(got) - {"pulled_lineage_rel",
                            "lineage_miscount_recorded"}:
        assert got[name]["control_min"] <= got[name]["limit"], name
    # the reference in bfloat16 stands in the gradients' place only
    for name in GRADIENTS:
        assert got[name]["sound_max"] < got[name]["limit"] < got[name][
            "bfloat16_min"], name
    for name in ("replay_rel", "n_conservation_rel", "closed_form_rel"):
        assert got[name]["bfloat16_min"] <= got[name]["sound_max"] < got[
            name]["limit"]


def test_the_tap_holds_each_workers_prefix_to_rank_order():
    """Three workers' connections, their operations issued as the rule
    has them (two pulls, then push and pull alternately): a worker's
    first pull waits for the last prefix push of the worker before it,
    and the state is read with every worker held after its own."""
    order, lock = [], threading.Lock()

    class KV:
        def __init__(self, rank):
            self.rank = rank

        def pull(self, keys=None, **kw):
            with lock:
                order.append(("pull", self.rank))
            return np.zeros(len(keys), np.float32)

        def push(self, vals, keys=None, **kw):
            with lock:
                order.append(("push", self.rank))
            return 7

        def wait(self, ts):
            assert ts == 7
            time.sleep(0.005)
            with lock:
                order.append(("acked", self.rank))

    held_at = []
    workers = []
    for r in range(3):
        w = type("W", (), {})()
        w.kv, w.rank = KV(r), r
        workers.append(w)
    turns = [threading.Event() for _ in workers]
    turns[0].set()
    held = threading.Barrier(3, action=lambda: held_at.append(len(order)))
    taps = [driver.PrefixTap(w, 2, 16, turns, held) for w in workers]
    rounds = 3

    def fit(w):
        keys = np.array([1, 4 + w.rank, 9], np.uint64)
        g = np.array([0.5, 0.0, -2.0], np.float32)
        w.kv.pull(keys=keys)
        w.kv.pull(keys=keys)
        for k in range(rounds):
            w.kv.wait(w.kv.push(g, keys=keys))
            if k + 2 < rounds:
                w.kv.pull(keys=keys)

    threads = [threading.Thread(target=fit, args=(w,))
               for w in reversed(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    for t in taps:
        t.remove()
    assert all(not vars(w.kv).keys() & {"pull", "push", "wait"}
               for w in workers)
    # the prefix: two rounds a worker, worker after worker
    one = ["pull", "pull", "push", "acked", "pull", "push", "acked"]
    assert order[:21] == [(what, r) for r in range(3) for what in one]
    assert held_at == [21]  # read with all three held, nothing in flight
    assert sorted(order[21:]) == sorted([("push", r) for r in range(3)]
                                        + [("acked", r) for r in range(3)])
    for t in taps:
        assert (t.rounds, t.acked, len(t.pulls), len(t.pushes)) == (3, 3, 2, 2)


def test_the_lineage_miscount_admits_the_rules_counts_only():
    assert driver.lineage_miscount({"0": 4, "1": 956}, 4, 960) == 0
    assert driver.lineage_miscount({}, 4, 960) == 960       # serialized
    assert driver.lineage_miscount({"0": 960}, 4, 960) == 956 * 2
    assert driver.lineage_miscount({"0": 4, "1": 952, "2": 4}, 4, 960) == 8
    assert driver.lineage_miscount({"0": 4, "1": 964}, 4, 960) == 8
    assert driver.lineage_miscount({"0": 4, "1": 956, "-1": 1}, 4, 960) == 1


# -- the per-layer readers -------------------------------------------------
def _run(**over):
    spans = {name: {"seconds": s, "count": 400, "self_seconds": s}
             for name, s in (("pull", 0.6), ("push", 0.8), ("compute", 1.6),
                             ("exchange_wait", 0.2))}
    base = {"window": {"wall_s": 2.0, "spans": spans},
            "kd": {"rounds_per_worker": 400, "server_pushes": 3200,
                   "server_merge_s": 1.6, "wire_s": 5.6,
                   "wire_under_chain_s": 4.9, "pulls_behind_sum": 1596,
                   "pulls_counted": 1600, "events_dropped": 0},
            "trace": None}
    return {**base, **over}


@pytest.mark.parametrize("name,want", [
    ("kd_round_ms", 5.0), ("kd_exchange_wait_ms", 0.5), ("kd_wire_ms", 3.5),
    ("kd_overlap_share", 87.5), ("kd_pulls_behind", 0.9975),
    ("kd_server_apply_ms", 0.5)])
def test_a_reader_on_a_recorded_run(name, want):
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    assert read(_run()) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_nothing_where_the_run_has_no_such_side(name):
    """Another cell's run (the sibling's carries ``kf``), or a program
    that recorded none of it: the reader says nothing and does not
    raise."""
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    other = _run(kf={"rounds_per_worker": 400, "server_pushes": 3200,
                     "server_merge_s": 1.6})
    del other["kd"]
    assert read(other) is None
    if name != "kd_launch_wait_ms":
        empty = _run(kd={}, window={"wall_s": 2.0, "spans": {}})
        assert read(empty) is None


@pytest.mark.parametrize("name", [
    "kf_round_ms", "kf_pull_ms", "kf_push_ms", "kf_server_apply_ms",
    "kf_server_lock_wait_ms", "kf_ftrl_ns_per_step", "kf_launch_wait_ms",
    "dl_overlap_share", "dl_rounds_behind", "dl_push_wait_ms"])
def test_the_siblings_readers_say_nothing_of_this_cells_run(name):
    read = importlib.import_module(f"chipbench.layer_metrics.{name}").read
    assert read(_run()) is None


def test_kd_side_reads_the_overlap_from_the_events():
    """Two workers: a comm thread's push and pull against its own
    worker's chain spans, never the other's; drains apart."""
    def ev(name, rank, lo, hi, **more):
        return {"name": name, "ts": lo * 1e6, "dur": (hi - lo) * 1e6,
                "args": {"rank": rank, **more}}

    events = [
        # rank 0's chain: 1.0-1.4, 1.4-3.0, 3.0-3.2, then 5.0-6.0
        ev("w_put", 0, 1.0, 1.4, in_flight=1),
        ev("compute", 0, 1.4, 3.0, in_flight=1),
        ev("grad_d2h", 0, 3.0, 3.2, in_flight=0),
        ev("compute", 0, 5.0, 6.0, in_flight=2),
        # its comm thread: a push wholly under, a pull half under
        ev("push", 0, 1.2, 2.2), ev("pull", 0, 3.0, 3.4),
        # and one under nothing of rank 0's (rank 1's chain is there)
        ev("pull", 0, 4.0, 4.5), ev("compute", 1, 3.9, 4.6, in_flight=0),
        ev("push", 1, 4.0, 4.2),
        ev("exchange_wait", 0, 3.2, 3.3), ev("exchange_wait", 0, 6.0, 6.4,
                                             drain=1),
        ev("xchg_await", 0, 1.3, 1.5), ev("wire", 0, 1.1, 3.5),
        {"name": "round", "ts": 0, "dur": 1, "args": {}},
    ]
    got = driver.kd_side(events, 3, {"0": 2, "1": 6}, rounds=4)
    assert got["wire_s"] == pytest.approx(1.0 + 0.4 + 0.5 + 0.2)
    assert got["wire_under_chain_s"] == pytest.approx(1.0 + 0.2 + 0.0 + 0.2)
    assert got["wires"] == 4
    assert got["exchange_wait"]["wait"] == {"seconds": pytest.approx(0.1),
                                            "count": 1}
    assert got["exchange_wait"]["drain"]["count"] == 1
    assert got["computes_in_flight"] == {"0": 1, "1": 1, "2": 1}
    assert (got["pulls_behind_sum"], got["pulls_counted"]) == (6, 8)
    assert got["events_dropped"] == 3
    assert got["events_a_round"] == pytest.approx((len(events) + 3) / 4)
    nothing = driver.kd_side([], 0, {}, rounds=0)
    assert nothing["wire_s"] == 0 and nothing["pulls_counted"] == 0


def test_kd_launch_wait_ms_reads_the_keyed_programs_runs():
    read = importlib.import_module(
        "chipbench.layer_metrics.kd_launch_wait_ms").read
    runs = [(0.100, 0.103), (0.103, 0.106), (0.106, 0.109), (0.109, 0.112)]
    marks = [(0.0995, e + 0.0001) for _s, e in runs]
    xtrace = {"/device:TPU:0": {"XLA Modules": [
        ("jit_ps_keyed_grad_step(1)", s, e - s) for s, e in runs]}}
    for k, (s, e) in enumerate(marks):
        xtrace[f"/host:CPU/{k}"] = {f"thread-{k}": [("compute", s, e - s)]}
    traced = _run(trace={"xtrace": xtrace,
                         "step_program": driver.STEP_PROGRAM,
                         "window": (0.0, 1.0)})
    assert read(traced) == pytest.approx((0.5 + 3.5 + 6.5 + 9.5) / 4)
    del traced["kd"]
    assert read(traced) is None


def test_the_roofline_share_asks_this_familys_floor():
    read = importlib.import_module(
        "chipbench.layer_metrics.step_hbm_roofline").read
    xtrace = {"/device:TPU:0": {
        "XLA Modules": [("jit_ps_keyed_grad_step(1)", 0.1, 0.002)],
        "XLA Ops": [("fusion", 0.1, 0.002)]}}
    step = {"rows": 16384, "nnz": 16384 * 39, "keys": 88000.5,
            "dim": 1000000}
    run_ = _run(family="sparse_ps_keyed_delay", device_kind="TPU v5 lite",
                step=step,
                trace={"xtrace": xtrace, "step_program": driver.STEP_PROGRAM,
                       "window": (0.0, 1.0)})
    floor = 16384 * 39 * 8 + 2 * 88000.5 * 4 + 16384 * 4
    assert read(run_) == pytest.approx(100.0 * floor / 819e9 / 0.002)
    assert read(run_) < 100.0


# -- BENCHMARK.json ------------------------------------------------------------
def test_every_new_metric_is_read_in_its_own_cell_only():
    bench = manifest.load_benchmark()
    mine = {m["name"] for m in manifest.Cell(bench, CELL).per_layer}
    assert mine == set(READERS) | set(LIST_LESS)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for other in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        theirs = {m["name"] for m in manifest.Cell(bench, other).per_layer}
        assert not set(READERS) & theirs
    e2e = {m["name"] for m in manifest.Cell(bench, CELL).end_to_end}
    assert e2e == {"train_samples_per_s", "setup_s"}
    for name in READERS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "train_samples_per_s"
        assert callable(manifest.Cell(bench, CELL).layer_reader(name))
        assert set(entries[name]) == {"name", "unit", "better", "source",
                                      "layer", "moves", "workloads"}
    # no new layer: each is named as the accepted benchmark names it
    assert {n: (entries[n]["layer"], entries[n]["source"], entries[n]["unit"],
                entries[n]["better"]) for n in READERS} == {
        "kd_round_ms": ("PS worker round", "host_clock", "ms", "lower"),
        "kd_exchange_wait_ms": ("PS exchange", "program_span", "ms", "lower"),
        "kd_wire_ms": ("PS exchange", "program_span", "ms", "lower"),
        "kd_overlap_share": ("PS exchange", "program_span", "%", "higher"),
        "kd_pulls_behind": ("PS exchange", "program_counter", "rounds",
                            "lower"),
        "kd_server_apply_ms": ("PS server apply", "program_counter", "ms",
                               "lower"),
        "kd_launch_wait_ms": ("PS worker round", "device_trace", "ms",
                              "lower")}
    were = {m["layer"] for m in bench["per_layer"]
            if m["name"] not in READERS}
    assert {entries[n]["layer"] for n in READERS} <= were
    for name in LIST_LESS:
        assert "workloads" not in entries[name]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "ps-keyed-delay-epochs", 1)
    assert len(cell["why"]) <= 200 and "delay 1" in cell["why"]
    for name in [CELL, CONFIG, cell["traffic"], *READERS]:
        assert manifest.NAME_RE.match(name)
    assert all(manifest.UNIT_RE.match(entries[n]["unit"]) for n in READERS)
    # the benchmark still fits its check, one cell in four at most on
    # four chips
    n = len(bench["workloads"])
    assert ((2 + 14 * n) * (bench["run_seconds"] + 60) + 180 * n
            + 1200) <= 43200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) == len(set(names))
