"""Protocol model checking (ISSUE 14 tentpole).

Four kinds of coverage, per the acceptance criteria:

* the INVARIANTS hold on the current spec: every standard scenario's
  state space closes under exhaustive BFS with zero violations;
* MUTANT rediscovery: reverting each named historical fix (the PR-5
  barrier fd-replace dedup, the PR-12 membership-layer push
  absorption) produces a counterexample schedule of <= 12 steps — a
  spec that cannot find known bugs is not verifying anything;
* CONFORMANCE: one real 2-server chaos run and one real live-resize
  run replay through the model with zero violations (every chaos/
  elastic e2e doubles as a witness), and a seeded out-of-order journal
  fails with a file:line step citation;
* the runner wiring: the protocol pass rides
  ``python -m distlr_tpu.analysis`` by default and ``make
  verify-protocol`` exists.
"""

from __future__ import annotations

import json
import os

import pytest

from distlr_tpu.analysis.protocol import (
    checker,
    conformance,
    mutants,
    spec as S,
)
from distlr_tpu.ps import wire


# ---------------------------------------------------------------------------
# the executable spec + checker
# ---------------------------------------------------------------------------


class TestSpecBasics:
    def test_wire_identities_come_from_the_mirror(self):
        # the spec's op table IS the wire module's — drift impossible
        assert S.OP_NAMES[wire.OP_EPOCH] == "epoch"
        assert S.FENCE_OP == wire.OP_EPOCH
        assert S.classify_reply(wire.OP_EPOCH,
                                wire.FLAG_RESPONSE | wire.FLAG_ERROR) \
            == "fence"
        assert S.classify_reply(wire.OP_PUSH,
                                wire.FLAG_RESPONSE | wire.FLAG_ERROR) \
            == "reject"
        assert S.classify_reply(wire.OP_PUSH, wire.FLAG_RESPONSE) == "ok"

    def test_frame_bytes_are_real_wire_framing(self):
        req = S.Req(wire.OP_PUSH, 0, 7, "p0.0", (1, 3), wire.CODEC_NONE)
        raw = S.frame_bytes(req)
        assert len(raw) == wire.HEADER_SIZE
        magic, op, _fl, _aux, cid, _ts, nk = wire.HEADER_STRUCT.unpack(raw)
        assert (magic, op, cid, nk) == (wire.MAGIC, wire.OP_PUSH, 7, 2)

    def test_split_ranges_cover_and_partition(self):
        for dim, n in ((4, 2), (7, 3), (5, 5)):
            rs = S.split_ranges(dim, n)
            assert rs[0][0] == 0 and rs[-1][1] == dim
            assert all(a[1] == b[0] for a, b in zip(rs, rs[1:]))


class TestInvariantsGreen:
    """Exhaustive closure of every standard scenario, zero violations
    — the acceptance's 'invariant checks green on the current spec'."""

    @pytest.mark.parametrize("factory", checker.STANDARD_SCENARIOS,
                             ids=lambda f: f.__name__)
    def test_scenario_closes_clean(self, factory):
        res = checker.explore(factory(), max_states=200_000)
        assert res.violation is None, res.render()
        assert res.complete, res.render()
        assert res.states > 1000  # a trivial space would prove nothing

    def test_interleaving_search_is_exhaustive_not_sampled(self):
        # determinism: same scenario, same exploration — a randomized
        # search could not promise rediscovery or closure
        a = checker.explore(checker.scenario_base(), max_states=50_000)
        b = checker.explore(checker.scenario_base(), max_states=50_000)
        assert (a.states, a.transitions, a.depth) \
            == (b.states, b.transitions, b.depth)

    @pytest.mark.slow
    def test_full_combined_space_closes_clean(self):
        from distlr_tpu.analysis.protocol.__main__ import scenario_full
        res = checker.explore(scenario_full(), max_states=2_000_000,
                              max_depth=80)
        assert res.violation is None, res.render()
        assert res.complete and res.states > 100_000, res.render()


class TestMutants:
    """Both reverted historical fixes must be rediscovered as
    counterexamples with <= 12-step schedules (acceptance criterion;
    `make verify-protocol` prints the same schedules)."""

    def test_all_mutants_rediscovered(self):
        assert mutants.check_all() == []

    @pytest.mark.parametrize("mutant", mutants.MUTANTS,
                             ids=lambda m: m.name)
    def test_counterexample_schedule_is_short_and_right(self, mutant):
        res = mutants.rediscover(mutant)
        assert res.violation is not None, \
            f"{mutant.name}: bug not rediscovered"
        msg, sched = res.violation
        assert mutant.expect in msg
        assert len(sched) <= mutants.MAX_SCHEDULE_STEPS, sched
        rendered = res.render()
        assert "counterexample" in rendered
        # the schedule names concrete protocol steps, not state dumps
        assert any("s0: process" in step for step in sched)

    def test_barrier_mutant_names_the_double_vote(self):
        res = mutants.rediscover(mutants.MUTANTS[0])
        msg, sched = res.violation
        # the schedule reproduces the production shape: vote, sever,
        # reconnect re-vote, early release
        text = " | ".join(sched)
        assert "re-vote" in text and "reset" in text
        assert "unvoted" in msg

    def test_straddle_mutant_names_the_double_apply(self):
        res = mutants.rediscover(mutants.MUTANTS[1])
        msg, sched = res.violation
        text = " | ".join(sched)
        assert "RE-ISSUE" in text and "fence" in text.lower() \
            or "retired" in text
        assert "double-apply" in msg

    def test_fixed_spec_closes_mutant_scenarios_clean(self):
        # the same scenarios under the FIXED spec: no violation in the
        # whole space — the fix, proven rather than spot-checked
        for m in mutants.MUTANTS:
            res = checker.explore(m.scenario, S.Spec(),
                                  max_states=200_000)
            assert res.violation is None, (m.name, res.render())
            assert res.complete


class TestFenceAmbiguityPin:
    """The protocol design pin the model adds on top of the two
    historical mutants: fence replies that echo the data op with
    kError are indistinguishable from config rejections."""

    def test_ambiguous_fence_shape_is_caught(self):
        res = checker.explore(
            mutants.MUTANTS[1].scenario,
            S.Spec(fence_uses_epoch_op=False),
            max_states=200_000)
        assert res.violation is not None
        assert "I3" in res.violation[0]


# ---------------------------------------------------------------------------
# trace conformance of real runs
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def chaos_run(tmp_path_factory):
    from distlr_tpu.analysis.protocol import witness
    return witness.chaos_witness(str(tmp_path_factory.mktemp("chaosrun")))


class TestConformanceRealRuns:
    def test_real_chaos_run_replays_clean(self, chaos_run):
        vs = conformance.check_run(chaos_run["journals"],
                                   chaos_run["chaos_events"],
                                   require_parents=True)
        assert vs == [], "\n".join(v.render() for v in vs)
        # the witness actually exercised the interesting paths: native
        # handler spans on both ranks, chaos delay + reset events
        names = set()
        for j in chaos_run["journals"]:
            recs, errs = conformance.load_span_journal(j)
            assert errs == []
            names |= {r.name for r in recs}
        assert {"ps.push", "kv.push", "kv.pull", "train.step"} <= names
        events, _ = conformance.load_chaos_events(
            chaos_run["chaos_events"])
        kinds = {kind for _l, kind, _d in events}
        assert {"delay", "reset"} <= kinds

    def test_real_live_resize_run_replays_clean(self, tmp_path):
        from distlr_tpu.analysis.protocol import witness
        arts = witness.resize_witness(str(tmp_path))
        vs = conformance.check_run(arts["journals"],
                                   require_parents=True)
        assert vs == [], "\n".join(v.render() for v in vs)
        # the run really crossed a membership flip
        recs, _ = conformance.load_span_journal(arts["journals"][0])
        names = {r.name for r in recs}
        assert "reshard.resize" in names

    def test_seeded_out_of_order_journal_fails_with_step_citation(
            self, chaos_run, tmp_path):
        src = chaos_run["journals"][0]
        lines = open(src).readlines()
        spans = [i for i, ln in enumerate(lines)
                 if '"type": "span"' in ln]
        assert len(spans) >= 2
        # swap the first and last span records: completion order now
        # contradicts the timestamps — no conforming writer does that
        lines[spans[0]], lines[spans[-1]] = \
            lines[spans[-1]], lines[spans[0]]
        bad = tmp_path / "out-of-order.jsonl"
        bad.write_text("".join(lines))
        vs = conformance.check_run([str(bad)])
        assert vs, "shuffled journal replayed clean"
        rendered = vs[0].render()
        # file:line-style step citation
        assert rendered.startswith(f"{bad}:")
        assert int(rendered.split(":")[1]) in \
            {i + 1 for i in (spans[0], spans[-1])} | \
            {i + 1 for i in range(len(lines))}
        assert "out of order" in rendered

    def test_seeded_wrong_parent_class_fails(self, chaos_run, tmp_path):
        # a kv.push span claiming a ps.pull parent cannot come from the
        # kv_client's one-stamp-per-op rule
        for src in chaos_run["journals"]:
            if "kvserver" not in os.path.basename(src):
                continue
            recs, _ = conformance.load_span_journal(src)
            if any(r.name == "kv.push" for r in recs):
                break
        client = [j for j in chaos_run["journals"]
                  if "worker" in os.path.basename(j)][0]
        crecs, _ = conformance.load_span_journal(client)
        pull_span = next(r.doc["span"] for r in crecs
                         if r.name == "ps.pull")
        lines = []
        for ln in open(src):
            if '"name":"kv.push"' in ln and '"parent":' in ln:
                doc = json.loads(ln)
                doc["parent"] = pull_span
                ln = json.dumps(doc) + "\n"
            lines.append(ln)
        bad = tmp_path / "wrong-parent.jsonl"
        bad.write_text("".join(lines))
        vs = conformance.check_run([str(bad), client],
                                   require_parents=True)
        assert any("parented under 'ps.pull'" in v.message for v in vs), \
            "\n".join(v.render() for v in vs)


class TestChaosLogSchema:
    """Satellite: the canonical event log is schema-pinned and the
    replayer (and `chaos.load_events_doc`) reject unknown schemas
    loudly instead of misparsing."""

    def test_event_schema_cross_pinned(self):
        from distlr_tpu.chaos import EVENT_SCHEMA
        assert EVENT_SCHEMA == conformance.CHAOS_SCHEMA

    def test_events_doc_shape(self):
        from distlr_tpu.chaos import ChaosFabric, EVENT_SCHEMA, parse_plan
        import socket
        lsock = socket.socket()
        lsock.bind(("127.0.0.1", 0))
        lsock.listen(1)
        try:
            fab = ChaosFabric([("127.0.0.1",
                                lsock.getsockname()[1])],
                              parse_plan({"seed": 3, "faults": []}))
            try:
                doc = fab.events_doc()
            finally:
                fab.stop()
        finally:
            lsock.close()
        assert doc["schema"] == EVENT_SCHEMA
        assert doc["seed"] == 3
        assert doc["truncated"] is False
        assert doc["events"] == []

    def test_headerless_log_rejected(self, tmp_path):
        from distlr_tpu.chaos import load_events_doc
        p = tmp_path / "old.json"
        p.write_text(json.dumps([[0, "delay", {"op": 1}]]))  # pre-pin
        with pytest.raises(ValueError, match="no schema header"):
            load_events_doc(str(p))
        _events, vs = conformance.load_chaos_events(str(p))
        assert vs and "no schema header" in vs[0].message

    def test_unknown_schema_rejected(self, tmp_path):
        from distlr_tpu.chaos import load_events_doc
        p = tmp_path / "future.json"
        p.write_text(json.dumps({"schema": 99, "events": []}))
        with pytest.raises(ValueError, match="schema 99"):
            load_events_doc(str(p))
        _events, vs = conformance.load_chaos_events(str(p))
        assert vs and "refusing to misparse" in vs[0].message

    def test_launch_chaos_writes_schema_doc(self, tmp_path):
        # the launch writer and the reader agree end to end
        from distlr_tpu.chaos import load_events_doc
        from distlr_tpu.chaos.proxy import EVENT_SCHEMA
        p = tmp_path / "events.json"
        p.write_text(json.dumps({"schema": EVENT_SCHEMA, "seed": 0,
                                 "truncated": False, "events": []}))
        doc = load_events_doc(str(p))
        assert doc["events"] == []

    def test_duplicate_reset_event_fails_conformance(self, tmp_path):
        p = tmp_path / "events.json"
        p.write_text(json.dumps({
            "schema": conformance.CHAOS_SCHEMA, "seed": 1,
            "truncated": False,
            "events": [[0, "reset", {"fault": 1, "op": 4}],
                       [0, "reset", {"fault": 1, "op": 9}]]}))
        vs = conformance.check_chaos_events(str(p))
        assert any("one-shot" in v.message for v in vs)

    def test_jittered_delay_log_conforms_out_of_op_order(self, tmp_path):
        # the canonical log is VALUE-sorted: a jittered plan's varying
        # `ms` legitimately reorders op offsets within one (link,
        # fault) — only a DUPLICATE offset is a violation (review fix)
        p = tmp_path / "events.json"
        p.write_text(json.dumps({
            "schema": conformance.CHAOS_SCHEMA, "seed": 1,
            "truncated": False,
            "events": [[0, "delay", {"fault": 0, "ms": 3.1, "op": 9}],
                       [0, "delay", {"fault": 0, "ms": 7.2, "op": 4}]]}))
        assert conformance.check_chaos_events(str(p)) == []
        p.write_text(json.dumps({
            "schema": conformance.CHAOS_SCHEMA, "seed": 1,
            "truncated": False,
            "events": [[0, "delay", {"fault": 0, "ms": 3.1, "op": 4}],
                       [0, "delay", {"fault": 0, "ms": 7.2, "op": 4}]]}))
        vs = conformance.check_chaos_events(str(p))
        assert any("appears twice" in v.message for v in vs)


class TestConformanceRobustness:
    """Artifacts are untrusted input: malformed fields must become
    file:line violations, never crash the lint runner (review fixes)."""

    def test_non_numeric_span_fields_are_violations(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text(
            '{"type": "span", "name": "x", "trace": "a", "span": "b", '
            '"ts": 0, "dur": "oops", "tid": 1}\n'
            '{"type": "instant", "name": "y", "ts": "nan?", "tid": 1}\n')
        vs = conformance.check_run([str(p)])
        assert len(vs) == 2
        assert all(v.file == str(p) for v in vs)
        assert any("not numeric" in v.message for v in vs)

    def test_malformed_reroute_epoch_is_a_violation(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text('{"type": "instant", "name": "ps.reroute", '
                     '"ts": 1.0, "tid": 1, "args": {"epoch": "abc"}}\n')
        vs = conformance.check_run([str(p)])
        assert any("aux range" in v.message for v in vs)

    def test_parentless_handler_span_fails_require_parents(
            self, tmp_path):
        p = tmp_path / "kv.jsonl"
        p.write_text('{"type": "span", "name": "kv.push", "trace": "a1", '
                     '"span": "b2", "ts": 1.0, "dur": 2.0, "tid": 1, '
                     '"args": {"op": "kv.push"}}\n')
        assert conformance.check_run([str(p)]) == []  # default: lenient
        vs = conformance.check_run([str(p)], require_parents=True)
        assert any("no parent at all" in v.message for v in vs)

    def test_run_dir_scan_includes_native_journals(self, tmp_path):
        for sub, name in (("spans", "worker-0.jsonl"),
                          ("native", "kvserver-0.jsonl")):
            d = tmp_path / sub
            d.mkdir()
            (d / name).write_text("")
        paths = conformance.run_dir_journals(str(tmp_path))
        names = {os.path.basename(p) for p in paths}
        assert names == {"worker-0.jsonl", "kvserver-0.jsonl"}


# ---------------------------------------------------------------------------
# runner + make wiring
# ---------------------------------------------------------------------------


class TestRunnerWiring:
    def test_protocol_pass_rides_the_default_runner(self):
        from distlr_tpu.analysis.__main__ import PASSES
        assert "protocol" in PASSES

    def test_protocol_pass_is_clean(self):
        from distlr_tpu.analysis.protocol import lint
        assert lint.check() == []

    def test_make_verify_protocol_target_exists(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "Makefile")) as f:
            text = f.read()
        assert "verify-protocol:" in text
        assert "distlr_tpu.analysis.protocol" in text

    def test_verify_protocol_cli_green(self, capsys):
        from distlr_tpu.analysis.protocol.__main__ import main
        assert main(["--mutants"]) == 0
        out = capsys.readouterr().out
        assert "counterexample" in out
        assert "barrier-double-vote" in out
        assert "reissue-straddling-push" in out


# ---------------------------------------------------------------------------
# carried debt: heterogeneous-dim namespace_layout rejection
# ---------------------------------------------------------------------------


class TestNamespaceLayoutHeterogeneousDims:
    def test_equal_width_still_works(self):
        from distlr_tpu.ps import namespace_layout
        assert namespace_layout("v1,v2", 16) == {"v1": (0, 16),
                                                 "v2": (16, 16)}
        # optimizer suffixes still strip
        assert namespace_layout("v1:ftrl,v2:sgd", 8) \
            == {"v1": (0, 8), "v2": (8, 8)}

    def test_equal_explicit_dims_accepted(self):
        from distlr_tpu.ps import namespace_layout
        assert namespace_layout("v1=16,v2=16", 16) == {"v1": (0, 16),
                                                       "v2": (16, 16)}

    def test_heterogeneous_dims_rejected_naming_followon(self):
        from distlr_tpu.ps import namespace_layout
        with pytest.raises(ValueError, match="packed namespace_layout"):
            namespace_layout("v1=8192,v2=1024", 8192)
        with pytest.raises(ValueError, match="ROADMAP"):
            namespace_layout({"v1": 8192, "v2": 1024}, 0)

    def test_explicit_dim_conflicting_with_uniform_rejected(self):
        from distlr_tpu.ps import namespace_layout
        with pytest.raises(ValueError, match="heterogeneous-dim"):
            namespace_layout("v1=32,v2=32", 16)

    def test_malformed_dim_named(self):
        from distlr_tpu.ps import namespace_layout
        with pytest.raises(ValueError, match="bad namespace dim"):
            namespace_layout("v1=abc", 16)
