"""Multi-tenant serving (ISSUE 10): keyed-PS namespaces, model-id
routing, per-tenant quotas, shadow scoring, and canary ramps with
automatic rollback.

Acceptance e2e (TestTwoVersionsOnePSGroup): two model versions served
from ONE native KV server group (namespaced key space) through ONE
router — a canary ramp from v1 to v2 completes under live client load
with zero failed accepted requests, and an injected bad candidate
(score-drift alert firing) auto-rolls-back with the primary's replies
unaffected.  Shadow scoring is proved off the hot path by byte-identical
primary replies with shadowing on and off.
"""

from __future__ import annotations

import json
import socket
import threading
import time

import numpy as np
import pytest

from distlr_tpu.config import Config
from distlr_tpu.obs.registry import get_registry
from distlr_tpu.serve.rollout import (
    RolloutController,
    RouterAdmin,
    parse_stages,
)
from distlr_tpu.serve.router import ScoringRouter
from distlr_tpu.serve.server import ScoringServer, score_lines_over_tcp
from distlr_tpu.serve.tenant import (
    TenantQuota,
    parse_model_spec,
    parse_quota_spec,
)

D = 8


def _wait_for(predicate, timeout_s: float = 30.0, what: str = "condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


def _engine(weights):
    from distlr_tpu.serve.engine import ScoringEngine

    cfg = Config(num_feature_dim=D, model="binary_lr", l2_c=0.0)
    eng = ScoringEngine(cfg, max_batch_size=64)
    eng.set_weights(np.asarray(weights, np.float32))
    return eng


W1 = np.linspace(-1, 1, D).astype(np.float32)
W2 = -W1


def _firing_alerts() -> list[str]:
    """Firing distlr_alert_* gauges of THIS process's registry — the
    in-process twin of fleet_alert_poller (same evidence, no obs-agg)."""
    snap = get_registry().snapshot()
    out = []
    for name, fam in snap.items():
        if not name.startswith("distlr_alert_"):
            continue
        for s in fam.get("series", []):
            if s.get("value"):
                out.append(name)
    return out


# ---------------------------------------------------------------------------
# specs and quotas (unit)
# ---------------------------------------------------------------------------

class TestModelSpec:
    def test_single_model_compat_form(self):
        assert parse_model_spec("h:1,h:2") == {"default": ["h:1", "h:2"]}
        assert parse_model_spec(["h:1"]) == {"default": ["h:1"]}

    def test_multi_model_form(self):
        got = parse_model_spec("v1=h:1+h:2,v2=h:3")
        assert got == {"v1": ["h:1", "h:2"], "v2": ["h:3"]}
        assert list(got) == ["v1", "v2"]  # order defines the default

    def test_rejections(self):
        with pytest.raises(ValueError, match="duplicate model id"):
            parse_model_spec("v1=h:1,v1=h:2")
        with pytest.raises(ValueError, match="no replica addresses"):
            parse_model_spec("v1=")
        with pytest.raises(ValueError, match="duplicate replica"):
            parse_model_spec("v1=h:1+h:1")
        with pytest.raises(ValueError, match="no replica addresses"):
            parse_model_spec("")

    def test_quota_spec(self):
        q = parse_quota_spec("v1=100:300,v2=50")
        assert q["v1"].rate == 100 and q["v1"].burst == 300
        assert q["v2"].burst == 100  # default 2*rate
        with pytest.raises(ValueError, match="bad quota entry"):
            parse_quota_spec("v1")
        with pytest.raises(ValueError, match="duplicate quota"):
            parse_quota_spec("v1=100,v1=5")
        assert parse_quota_spec(None) == {}


class TestTenantQuota:
    def test_burst_then_shed_then_refill(self):
        q = TenantQuota(10.0, burst=3)
        t0 = 1000.0
        q._at = t0  # pin the refill clock to the test's timeline
        assert all(q.try_admit(now=t0) for _ in range(3))
        assert not q.try_admit(now=t0)  # bucket empty
        assert q.shed == 1
        # 0.2s at 10/s refills 2 tokens
        assert q.try_admit(now=t0 + 0.2)
        assert q.try_admit(now=t0 + 0.2)
        assert not q.try_admit(now=t0 + 0.2)

    def test_validation(self):
        with pytest.raises(ValueError, match="rate"):
            TenantQuota(0)
        with pytest.raises(ValueError, match="burst"):
            TenantQuota(10, burst=0.5)


class TestStages:
    def test_parse(self):
        assert parse_stages("0.05:1,1.0:2") == [(0.05, 1.0), (1.0, 2.0)]
        assert parse_stages("1.0")[0][0] == 1.0  # default hold applied

    def test_rejections(self):
        with pytest.raises(ValueError, match="ascend"):
            parse_stages("0.5:1,0.25:1,1.0:1")
        with pytest.raises(ValueError, match="1.0"):
            parse_stages("0.25:1,0.5:1")
        with pytest.raises(ValueError, match="weight"):
            parse_stages("0:1,1.0:1")


# ---------------------------------------------------------------------------
# keyed-PS namespaces
# ---------------------------------------------------------------------------

class TestNamespaces:
    def test_layout(self):
        from distlr_tpu.ps import namespace_layout

        assert namespace_layout("v1,v2", 16) == {"v1": (0, 16),
                                                 "v2": (16, 16)}
        with pytest.raises(ValueError, match="duplicate"):
            namespace_layout("v1,v1", 16)
        with pytest.raises(ValueError, match="at least one"):
            namespace_layout("", 16)

    def test_namespace_isolation_on_one_group(self):
        """Two namespaces on ONE native server group: scoped pulls and
        pushes never touch the other namespace's slice."""
        from distlr_tpu.ps import KVWorker, ServerGroup

        with ServerGroup(1, 1, 2 * D, sync=False, learning_rate=1.0) as sg, \
                KVWorker(sg.hosts, 2 * D, sync_group=False) as kv:
            n1, n2 = kv.namespace(0, D), kv.namespace(D, D)
            # first namespace's idempotent seed initializes the group;
            # the second seeds its own slice with keyed force-init
            n1.push_init(np.full(D, 1.0, np.float32))
            n2.push_init(np.full(D, 2.0, np.float32), force=True)
            np.testing.assert_allclose(n1.pull(), np.full(D, 1.0))
            np.testing.assert_allclose(n2.pull(), np.full(D, 2.0))
            # a gradient push into n2 (lr=1) leaves n1 untouched
            n2.wait(n2.push(np.full(D, 1.0, np.float32)))
            np.testing.assert_allclose(n1.pull(), np.full(D, 1.0))
            np.testing.assert_allclose(n2.pull(), np.full(D, 1.0))
            # keyed / chunked / scatter forms stay namespace-local
            np.testing.assert_allclose(
                n2.pull(keys=np.array([3, 5], np.uint64)), [1.0, 1.0])
            assert n1.pull_chunked(chunk_rows=3).shape == (D,)
            tbl = np.zeros(D, np.float32)
            assert n2.pull_rows_into(tbl, np.array([2], np.uint64)) == 1
            assert tbl[2] == 1.0 and tbl.sum() == 1.0
            # vals_per_key rows inside an aligned namespace
            assert n2.supports_vals_per_key(4)
            np.testing.assert_allclose(
                n2.pull(keys=np.array([1], np.uint64), vals_per_key=4),
                np.full(4, 1.0))

    def test_namespace_validation(self):
        from distlr_tpu.ps.client import KVNamespace

        class _Fake:
            dim = 32

        with pytest.raises(ValueError, match="outside"):
            KVNamespace(_Fake(), 24, 16)
        with pytest.raises(ValueError, match="positive"):
            KVNamespace(_Fake(), 0, 0)


# ---------------------------------------------------------------------------
# multi-engine server
# ---------------------------------------------------------------------------

class TestMultiEngineServer:
    def test_model_scoping_and_addressing(self):
        srv = ScoringServer(engines={"v1": _engine(W1),
                                     "v2": _engine(W2)},
                            max_wait_ms=0.5).start()
        try:
            r = score_lines_over_tcp(srv.host, srv.port, [
                "1:1 3:1",            # default = first engine (v1)
                "@v2 1:1 3:1",        # per-request addressing
                "MODEL v2",           # connection scoping
                "1:1 3:1",
                "@v1 1:1 3:1",        # @ overrides the scope
                "MODEL nope",
                "@nope 1:1",
            ])
            assert r[1] == r[3] and r[0] != r[1]
            assert r[4] == r[0]
            assert r[5].startswith("ERR MODEL") and "hosted: v1,v2" in r[5]
            assert r[6].startswith("ERR MODEL")
            st = json.loads(
                score_lines_over_tcp(srv.host, srv.port, ["STATS"])[0])
            assert st["models"] == 2
            assert st["per_model"]["v1"]["requests"] == 2
            assert st["per_model"]["v2"]["requests"] == 2
        finally:
            srv.stop()

    def test_id_mode_and_json_compose_with_addressing(self, tmp_path):
        from distlr_tpu.feedback import FeedbackSink

        sink = FeedbackSink(str(tmp_path / "spool"), str(tmp_path / "shards"),
                            model="binary_lr", window_s=30.0,
                            shard_records=4)
        srv = ScoringServer(engines={"v1": _engine(W1), "v2": _engine(W2)},
                            max_wait_ms=0.5, feedback=sink).start()
        try:
            r = score_lines_over_tcp(srv.host, srv.port, [
                "@v2 ID r1 1:1 3:1",
                '@v2 {"rows": ["1:1"], "ids": ["r2"]}',
                "LABEL r1 1",
            ])
            assert not r[0].startswith("ERR")
            assert json.loads(r[1])["scores"]
            assert r[2] == "OK joined"
        finally:
            srv.stop()
        # the model id rode the spool into the joiner: the joined
        # example landed in v2's OWN shard stream
        assert (tmp_path / "shards" / "v2").is_dir()
        shards = list((tmp_path / "shards" / "v2").glob("shard-*.libsvm"))
        assert shards, "per-tenant shard not written"
        assert open(shards[0]).read().startswith("1 ")

    def test_single_engine_compat_keeps_flat_shards(self, tmp_path):
        from distlr_tpu.feedback import FeedbackSink

        sink = FeedbackSink(str(tmp_path / "spool"), str(tmp_path / "shards"),
                            model="binary_lr", window_s=30.0,
                            shard_records=1)
        srv = ScoringServer(_engine(W1), max_wait_ms=0.5,
                            feedback=sink).start()
        try:
            r = score_lines_over_tcp(srv.host, srv.port,
                                     ["ID q1 2:1", "LABEL q1 0"])
            assert r[1] == "OK joined"
        finally:
            srv.stop()
        flat = list((tmp_path / "shards").glob("shard-*.libsvm"))
        assert flat, "pre-tenant construction must keep flat shards"

    def test_spool_journal_carries_model_through_replay(self, tmp_path):
        from distlr_tpu.feedback.spool import FeedbackSpool, SpoolRecord

        sp = FeedbackSpool(str(tmp_path))
        sp.add(SpoolRecord(rid="a", ts=time.time(), line="1:1", score=0.5,
                           version=1, model="v2"))
        sp.close()
        sp2 = FeedbackSpool(str(tmp_path))
        assert sp2.replay(window_s=60.0) == 1
        assert sp2.pop("a").model == "v2"
        sp2.close()


# ---------------------------------------------------------------------------
# router: registry, quotas, shadow, split, promote
# ---------------------------------------------------------------------------

class TestRouterTenancy:
    def _two_version_tier(self, quotas=None, seed=7):
        s1 = ScoringServer(_engine(W1), max_wait_ms=0.5).start()
        s2 = ScoringServer(_engine(W2), max_wait_ms=0.5).start()
        router = ScoringRouter(
            {"v1": [f"{s1.host}:{s1.port}"], "v2": [f"{s2.host}:{s2.port}"]},
            quotas=quotas, seed=seed, health_interval_s=5.0,
        ).start()
        return s1, s2, router

    def test_model_routing_to_distinct_replicas(self):
        s1, s2, router = self._two_version_tier()
        try:
            d1 = score_lines_over_tcp(s1.host, s1.port, ["1:1 3:1"])[0]
            d2 = score_lines_over_tcp(s2.host, s2.port, ["1:1 3:1"])[0]
            r = score_lines_over_tcp(router.host, router.port,
                                     ["1:1 3:1", "@v2 1:1 3:1",
                                      "MODEL v2", "1:1 3:1"])
            assert r[0] == d1 and r[1] == d2 and r[3] == d2
        finally:
            router.stop(); s1.stop(); s2.stop()

    def test_quota_shed_distinct_from_capacity_shed(self):
        s1, s2, router = self._two_version_tier(quotas="v2=1000:2")
        try:
            # burst of 2, no refill to speak of: the third v2 request
            # sheds with the TENANT reply, v1 is untouched
            router.quotas["v2"].rate = 0.001
            replies = score_lines_over_tcp(
                router.host, router.port,
                ["@v2 1:1", "@v2 1:1", "@v2 1:1", "1:1"])
            assert not replies[0].startswith("ERR")
            assert not replies[1].startswith("ERR")
            assert replies[2].startswith("ERR SHED tenant"), replies[2]
            assert not replies[3].startswith("ERR")
            st = json.loads(score_lines_over_tcp(router.host, router.port,
                                                 ["STATS"])[0])
            # the tenant shed is per-model accounting, NOT the capacity
            # shed counter (they page different people)
            assert st["shed"] == 0
            assert st["per_model"]["v2"]["shed"] == 1
            assert st["per_model"]["v1"]["shed"] == 0
            assert st["per_model"]["v2"]["quota"]["shed"] == 1
        finally:
            router.stop(); s1.stop(); s2.stop()

    def test_unknown_quota_model_rejected(self):
        with pytest.raises(ValueError, match="unregistered"):
            ScoringRouter({"v1": ["h:1"]}, quotas="nope=10")

    def test_shadow_replies_byte_identical_and_psi_published(self):
        s1, s2, router = self._two_version_tier()
        try:
            lines = [f"{1 + (i % 7)}:1" for i in range(40)]
            before = score_lines_over_tcp(router.host, router.port, lines)
            router._shadow_block = 16  # close a PSI block within the test
            score_lines_over_tcp(router.host, router.port,
                                 ["SHADOW v1 v2 1.0"])
            after = score_lines_over_tcp(router.host, router.port, lines)
            # the mirror NEVER changes the primary's reply bytes
            assert before == after
            router._shadow_mirror.drain()
            st = json.loads(score_lines_over_tcp(router.host, router.port,
                                                 ["STATS"])[0])
            assert st["shadow"]["mirrored"] >= len(lines)
            assert st["shadow"]["dropped"] == 0
            # a full comparison block closed -> PSI published (W2 = -W1,
            # so the distributions genuinely differ)
            psi = router._shadow_mirror.psi("v1", "v2")
            assert psi is not None and psi > 0.0
            snap = get_registry().snapshot()
            fam = snap.get("distlr_tenant_shadow_psi")
            assert fam and any(
                s["labels"] == {"tenant": "v1", "candidate": "v2"}
                for s in fam["series"])
        finally:
            router.stop(); s1.stop(); s2.stop()

    def test_split_weights_and_promote(self):
        s1, s2, router = self._two_version_tier(seed=3)
        try:
            d1 = score_lines_over_tcp(s1.host, s1.port, ["2:1"])[0]
            d2 = score_lines_over_tcp(s2.host, s2.port, ["2:1"])[0]
            # weight 1.0: every request serves from the candidate
            score_lines_over_tcp(router.host, router.port,
                                 ["SPLIT v1 v2 1.0"])
            assert score_lines_over_tcp(router.host, router.port,
                                        ["2:1"])[0] == d2
            # weight 0 clears
            score_lines_over_tcp(router.host, router.port,
                                 ["SPLIT v1 v2 0"])
            assert score_lines_over_tcp(router.host, router.port,
                                        ["2:1"])[0] == d1
            # fractional weight: both versions answer over many draws
            score_lines_over_tcp(router.host, router.port,
                                 ["SPLIT v1 v2 0.5"])
            got = set(score_lines_over_tcp(router.host, router.port,
                                           ["2:1"] * 60))
            assert got == {d1, d2}
            # promote: tenant traffic serves the candidate from now on,
            # split cleared
            score_lines_over_tcp(router.host, router.port,
                                 ["PROMOTE v1 v2"])
            doc = json.loads(score_lines_over_tcp(router.host, router.port,
                                                  ["MODELS"])[0])
            assert doc["splits"] == {} and doc["serves_as"] == {"v1": "v2"}
            assert score_lines_over_tcp(router.host, router.port,
                                        ["2:1"])[0] == d2
        finally:
            router.stop(); s1.stop(); s2.stop()

    def test_shadow_still_mirrors_after_promote(self):
        """A PROMOTEd tenant's serve_as remap must not silently disable
        a later SHADOW (regression: the canary-vs-primary check used to
        compare the REMAPPED model id against the tenant)."""
        s1, s2, router = self._two_version_tier()
        try:
            score_lines_over_tcp(router.host, router.port,
                                 ["PROMOTE v1 v2", "SHADOW v1 v2 1.0"])
            score_lines_over_tcp(router.host, router.port, ["2:1"] * 5)
            router._shadow_mirror.drain()
            assert router._shadow_mirror.mirrored >= 5
        finally:
            router.stop(); s1.stop(); s2.stop()

    def test_addressed_label_broadcasts(self, tmp_path):
        """`@<id> LABEL ...` fans out to that model's replicas like a
        MODEL-scoped label (regression: it used to fall into the
        scoring path and reach exactly ONE replica)."""
        from distlr_tpu.feedback import FeedbackSink

        sink = FeedbackSink(str(tmp_path / "sp"), str(tmp_path / "sh"),
                            model="binary_lr", window_s=30.0)
        s1 = ScoringServer(_engine(W1), max_wait_ms=0.5,
                           feedback=sink).start()
        s3 = ScoringServer(_engine(W1), max_wait_ms=0.5).start()
        router = ScoringRouter(
            {"v1": [f"{s1.host}:{s1.port}", f"{s3.host}:{s3.port}"]},
            health_interval_s=5.0).start()
        try:
            # the impression lives ONLY on s1's sink: a single-replica
            # delivery has a 50% chance of missing it, a broadcast never
            score_lines_over_tcp(s1.host, s1.port, ["ID z1 1:1"])
            for _ in range(4):
                r = score_lines_over_tcp(router.host, router.port,
                                         ["@v1 LABEL z1 1"])
                assert r[0] in ("OK joined", "OK duplicate"), r
        finally:
            router.stop(); s1.stop(); s3.stop()

    def test_admin_validation(self):
        s1, s2, router = self._two_version_tier()
        try:
            r = score_lines_over_tcp(router.host, router.port, [
                "SPLIT v1 nope 0.5",
                "SPLIT v1 v2 1.5",
                "SHADOW v1 v1 0.5",
                "PROMOTE v1",
            ])
            assert all(x.startswith("ERR") for x in r), r
        finally:
            router.stop(); s1.stop(); s2.stop()

    def test_label_fanout_respects_model_scope(self, tmp_path):
        from distlr_tpu.feedback import FeedbackSink

        sink = FeedbackSink(str(tmp_path / "sp"), str(tmp_path / "sh"),
                            model="binary_lr", window_s=30.0)
        s1 = ScoringServer(_engine(W1), max_wait_ms=0.5,
                           feedback=sink).start()
        s2 = ScoringServer(_engine(W2), max_wait_ms=0.5).start()
        router = ScoringRouter(
            {"v1": [f"{s1.host}:{s1.port}"],
             "v2": [f"{s2.host}:{s2.port}"]},
            health_interval_s=5.0).start()
        try:
            r = score_lines_over_tcp(router.host, router.port, [
                "ID k1 1:1",          # scored on v1 (default) — spooled
                "LABEL k1 1",         # unscoped: broadcast finds v1
            ])
            assert r[1] == "OK joined"
            # a v2-scoped label can only reach v2's replicas (no sink
            # there): the router reports the failure loudly
            r2 = score_lines_over_tcp(router.host, router.port, [
                "ID k2 1:1", "MODEL v2", "LABEL k2 1"])
            assert r2[2].startswith("ERR LABEL")
        finally:
            router.stop(); s1.stop(); s2.stop()


# ---------------------------------------------------------------------------
# rollout controller
# ---------------------------------------------------------------------------

class TestRollout:
    def test_healthy_ramp_promotes_with_journal(self, tmp_path):
        s1, s2, router = TestRouterTenancy()._two_version_tier()
        try:
            ctrl = RolloutController(
                RouterAdmin(router.host, router.port), "v1", "v2",
                [(0.5, 0.2), (1.0, 0.2)], alert_poll=lambda: [],
                poll_interval_s=0.05, journal_dir=str(tmp_path))
            out = ctrl.run()
            assert out["outcome"] == "promoted"
            events = [json.loads(l)["event"]
                      for l in open(ctrl.journal_path)]
            assert events == ["start", "stage", "stage", "promote"]
        finally:
            router.stop(); s1.stop(); s2.stop()

    def test_alert_fires_mid_ramp_rolls_back(self, tmp_path):
        s1, s2, router = TestRouterTenancy()._two_version_tier()
        try:
            polls = {"n": 0}

            def poll():
                polls["n"] += 1
                return (["distlr_alert_score_drift"]
                        if polls["n"] >= 3 else [])

            ctrl = RolloutController(
                RouterAdmin(router.host, router.port), "v1", "v2",
                [(0.25, 10.0), (1.0, 10.0)], alert_poll=poll,
                poll_interval_s=0.05, journal_dir=str(tmp_path))
            out = ctrl.run()
            assert out["outcome"] == "rolled_back"
            assert out["alerts"] == ["distlr_alert_score_drift"]
            # the split cleared — no candidate traffic remains
            doc = json.loads(score_lines_over_tcp(
                router.host, router.port, ["MODELS"])[0])
            assert doc["splits"] == {}
            events = [json.loads(l)["event"]
                      for l in open(ctrl.journal_path)]
            assert events[-1] == "rollback"
        finally:
            router.stop(); s1.stop(); s2.stop()

    def test_pre_ramp_alert_aborts(self, tmp_path):
        s1, s2, router = TestRouterTenancy()._two_version_tier()
        try:
            ctrl = RolloutController(
                RouterAdmin(router.host, router.port), "v1", "v2",
                [(1.0, 0.1)], alert_poll=lambda: ["distlr_alert_x"],
                journal_dir=str(tmp_path))
            out = ctrl.run()
            assert out["outcome"] == "aborted"
            doc = json.loads(score_lines_over_tcp(
                router.host, router.port, ["MODELS"])[0])
            assert doc["splits"] == {}  # never started splitting
        finally:
            router.stop(); s1.stop(); s2.stop()

    def test_admin_failure_mid_ramp_rolls_back(self, tmp_path):
        """A failed SPLIT exchange mid-ramp must clear the previous
        stage's split instead of leaving it live and unwatched."""
        s1, s2, router = TestRouterTenancy()._two_version_tier()
        try:
            real = RouterAdmin(router.host, router.port)
            calls = {"splits": 0}

            class FlakyAdmin:
                def models(self):
                    return real.models()

                def send(self, line):
                    return real.send(line)

                def expect_ok(self, line):
                    if line.startswith("SPLIT") and not line.endswith(" 0"):
                        calls["splits"] += 1
                        if calls["splits"] == 2:
                            raise ConnectionError("admin link cut")
                    return real.expect_ok(line)

            ctrl = RolloutController(
                FlakyAdmin(), "v1", "v2", [(0.25, 0.1), (1.0, 5.0)],
                alert_poll=lambda: [], poll_interval_s=0.05,
                journal_dir=str(tmp_path))
            out = ctrl.run()
            assert out["outcome"] == "rolled_back"
            assert any("rollout_admin_failed" in a for a in out["alerts"])
            doc = json.loads(score_lines_over_tcp(
                router.host, router.port, ["MODELS"])[0])
            assert doc["splits"] == {}  # stage-1 split was cleared
        finally:
            router.stop(); s1.stop(); s2.stop()

    def test_unknown_candidate_aborts(self, tmp_path):
        s1, s2, router = TestRouterTenancy()._two_version_tier()
        try:
            ctrl = RolloutController(
                RouterAdmin(router.host, router.port), "v1", "v3",
                [(1.0, 0.1)], alert_poll=lambda: [])
            assert ctrl.run()["outcome"] == "aborted"
        finally:
            router.stop(); s1.stop(); s2.stop()


# ---------------------------------------------------------------------------
# acceptance e2e: two versions, one PS group, one router
# ---------------------------------------------------------------------------

class TestTwoVersionsOnePSGroup:
    """The ISSUE-10 acceptance shape: one native KV server group hosts
    TWO model namespaces; two live-PS-reloading engines behind one
    ScoringServer and one router serve them as v1/v2; a canary ramp
    completes under live load with zero failed accepted requests; an
    injected bad candidate auto-rolls-back on the drift alert with the
    primary's replies unaffected."""

    def _stack(self):
        from distlr_tpu.ps import KVWorker, ServerGroup
        from distlr_tpu.serve.engine import ScoringEngine
        from distlr_tpu.serve.reload import HotReloader, LivePSWatcher

        cfg = Config(num_feature_dim=D, model="binary_lr", l2_c=0.0)
        sg = ServerGroup(1, 1, 2 * D, sync=False, learning_rate=0.5)
        sg.start()
        seeder = KVWorker(sg.hosts, 2 * D, sync_group=False)
        seeder.namespace(0, D).push_init(W1)
        seeder.namespace(D, D).push_init(W2, force=True)
        engines, reloaders = {}, []
        for mid, base in (("v1", 0), ("v2", D)):
            eng = ScoringEngine(cfg, max_batch_size=64)
            src = LivePSWatcher(sg.hosts, D, ns_base=base,
                                ns_total_dim=2 * D,
                                client_id=4000 + base)
            rl = HotReloader(eng, src, interval_s=0.2).start()
            rl.wait_for_weights()
            engines[mid] = eng
            reloaders.append(rl)
        srv = ScoringServer(engines=engines, max_wait_ms=0.5,
                            extra_reloaders=reloaders[1:],
                            reloader=reloaders[0]).start()
        router = ScoringRouter(
            {"v1": [f"{srv.host}:{srv.port}"],
             "v2": [f"{srv.host}:{srv.port}"]},
            seed=11, health_interval_s=5.0).start()
        return sg, seeder, srv, router

    def test_two_versions_ramp_and_rollback(self, tmp_path):
        sg, seeder, srv, router = self._stack()
        try:
            # both namespaces serve THEIR weights through one group
            r = score_lines_over_tcp(router.host, router.port,
                                     ["1:1 3:1", "@v2 1:1 3:1"])
            assert r[0] != r[1]
            # libsvm indices are 1-based: "1:1 3:1" reads cols 0 and 2
            exp1 = 1.0 / (1.0 + np.exp(-(W1[0] + W1[2])))
            exp2 = 1.0 / (1.0 + np.exp(-(W2[0] + W2[2])))
            s1 = float(r[0].split()[1]); s2 = float(r[1].split()[1])
            # binary families serve P(y=1) as the score (loose bound:
            # the engine's matmul runs in the compute dtype)
            assert abs(s1 - exp1) < 5e-3
            assert abs(s2 - exp2) < 5e-3

            # live client load through the whole ramp
            stop = threading.Event()
            replies: list[str] = []
            errors: list[BaseException] = []

            def client():
                try:
                    with socket.create_connection(
                            (router.host, router.port), timeout=30) as s:
                        f = s.makefile("rwb")
                        while not stop.is_set():
                            f.write(b"1:1 3:1\n")
                            f.flush()
                            line = f.readline()
                            if not line:
                                raise ConnectionError("router closed")
                            replies.append(line.decode().strip())
                except BaseException as e:
                    errors.append(e)

            t = threading.Thread(target=client, daemon=True)
            t.start()
            _wait_for(lambda: len(replies) > 20, what="load ramp")
            ctrl = RolloutController(
                RouterAdmin(router.host, router.port), "v1", "v2",
                [(0.25, 0.3), (1.0, 0.3)], alert_poll=_firing_alerts,
                poll_interval_s=0.05, journal_dir=str(tmp_path))
            out = ctrl.run()
            stop.set()
            t.join(timeout=30)
            assert out["outcome"] == "promoted", out
            assert not errors, errors
            # ZERO failed accepted requests across the whole ramp
            failed = [x for x in replies if x.startswith("ERR")]
            assert failed == [], failed[:5]
            # post-promote: tenant v1 serves candidate scores
            assert score_lines_over_tcp(router.host, router.port,
                                        ["1:1 3:1"])[0] == r[1]

            # ---- injected BAD candidate: the drift alert fires mid-
            # ramp and the ramp auto-rolls-back; primary unaffected ----
            from distlr_tpu.feedback.drift import ScoreDriftDetector

            det = ScoreDriftDetector(block=32, threshold=0.25)
            rng = np.random.default_rng(0)
            det.observe(rng.uniform(0.0, 0.2, 32))   # reference block

            before = score_lines_over_tcp(router.host, router.port,
                                          ["1:1 3:1"])[0]
            polls = {"n": 0}

            def firing_with_injection():
                polls["n"] += 1
                if polls["n"] == 3:
                    # the candidate's served scores shift hard: the
                    # REAL block-wise PSI detector trips the REAL
                    # distlr_alert_score_drift gauge
                    det.observe(rng.uniform(0.8, 1.0, 32))
                return _firing_alerts()

            ctrl2 = RolloutController(
                RouterAdmin(router.host, router.port), "v2", "v1",
                [(0.25, 10.0), (1.0, 10.0)],
                alert_poll=firing_with_injection,
                poll_interval_s=0.05, journal_dir=str(tmp_path))
            out2 = ctrl2.run()
            assert out2["outcome"] == "rolled_back", out2
            assert any("score_drift" in a for a in out2["alerts"])
            # the primary's replies are unaffected by the aborted ramp
            after = score_lines_over_tcp(router.host, router.port,
                                         ["1:1 3:1"])[0]
            assert after == before
            doc = json.loads(score_lines_over_tcp(
                router.host, router.port, ["MODELS"])[0])
            assert doc["splits"] == {}
        finally:
            router.stop()
            srv.stop()
            seeder.close()
            sg.stop()


# ---------------------------------------------------------------------------
# rollout under chaos (serve-protocol fault injection)
# ---------------------------------------------------------------------------

class TestRolloutUnderChaos:
    def test_serve_protocol_faults_during_ramp(self, tmp_path):
        """The chaos proxy speaks the serve LINE protocol: delay + reset
        faults on the client->router serve connections while a canary
        ramp runs — the ramp still promotes, and no accepted request is
        answered ERR (transport cuts cost the client a reconnect, never
        a wrong reply)."""
        import json as _json

        from distlr_tpu.chaos import ChaosFabric, load_plan

        s1, s2, router = TestRouterTenancy()._two_version_tier()
        plan_path = tmp_path / "plan.json"
        plan_path.write_text(_json.dumps({"seed": 5, "faults": [
            {"kind": "delay", "links": [0], "delay_ms": 5,
             "jitter_ms": 2, "window": [0, 120]},
            {"kind": "reset", "links": [0], "after_ops": 25},
            {"kind": "reset", "links": [0], "after_ops": 60},
        ]}))
        fab = ChaosFabric(f"{router.host}:{router.port}",
                          load_plan(str(plan_path)), protocol="serve")
        host, port = fab.hosts.split(":")
        port = int(port)
        stop = threading.Event()
        replies: list[str] = []
        reconnects = {"n": 0}
        errors: list[BaseException] = []

        def client():
            # resilient serve client: a severed connection is re-dialed
            # (scores are idempotent), an ERR reply would be a failure
            try:
                while not stop.is_set():
                    try:
                        with socket.create_connection((host, port),
                                                      timeout=10) as s:
                            f = s.makefile("rwb")
                            while not stop.is_set():
                                f.write(b"1:1 3:1\n")
                                f.flush()
                                line = f.readline()
                                if not line:
                                    raise ConnectionError("severed")
                                replies.append(line.decode().strip())
                    except (ConnectionError, OSError):
                        reconnects["n"] += 1
                        time.sleep(0.02)
            except BaseException as e:
                errors.append(e)

        t = threading.Thread(target=client, daemon=True)
        try:
            t.start()
            _wait_for(lambda: len(replies) > 10, what="chaos load")
            ctrl = RolloutController(
                RouterAdmin(router.host, router.port), "v1", "v2",
                [(0.5, 0.4), (1.0, 0.4)], alert_poll=lambda: [],
                poll_interval_s=0.05, journal_dir=str(tmp_path))
            out = ctrl.run()
            _wait_for(lambda: reconnects["n"] >= 1,
                      what="an injected reset to land")
        finally:
            stop.set()
            t.join(timeout=30)
            fab.stop()
            router.stop(); s1.stop(); s2.stop()
        assert out["outcome"] == "promoted", out
        assert not errors, errors
        failed = [x for x in replies if x.startswith("ERR")]
        assert failed == [], failed[:5]
        kinds = {e[1] for e in fab.events()}
        assert {"delay", "reset"} <= kinds, kinds


# ---------------------------------------------------------------------------
# online trainer: sparse_softmax keyed rows per class
# ---------------------------------------------------------------------------

class TestOnlineSparseSoftmax:
    def test_learns_from_shards_keyed_per_class(self, tmp_path):
        from distlr_tpu.feedback.online import OnlineTrainer
        from distlr_tpu.ps import KVWorker, ServerGroup

        K, n = 3, 180
        rng = np.random.default_rng(1)
        # 3 linearly separable classes over disjoint feature groups
        y = rng.integers(0, K, n)
        shard_dir = tmp_path / "shards"
        shard_dir.mkdir()
        lines = [f"{int(y[i])} {int(y[i]) * 2 + 1}:1" for i in range(n)]
        (shard_dir / "shard-000000.libsvm").write_text("\n".join(lines))
        cfg = Config(model="sparse_softmax", num_feature_dim=D,
                     num_classes=K, batch_size=30, l2_c=0.0,
                     sync_mode=False, learning_rate=0.5)
        with ServerGroup(1, 1, D * K, sync=False, learning_rate=0.5) as sg:
            tr = OnlineTrainer(cfg, sg.hosts, str(shard_dir),
                               poll_interval_s=0.05)
            # keyed rows per class: one feature key owns its K lanes
            assert tr._rows.vpk == K
            stats = tr.run(max_shards=1)
            with KVWorker(sg.hosts, D * K) as kv:
                W = kv.pull().reshape(D, K)
            tr.close()
        assert stats["examples"] == n and stats["pushes"] >= 1
        # each class's marker feature weighs most toward that class
        # (libsvm indices are 1-based: marker 2k+1 lands on row 2k)
        for k in range(K):
            assert int(np.argmax(W[2 * k])) == k, W

    def test_namespace_scoped_online_training(self, tmp_path):
        """An online trainer pushes ONLY into its tenant's namespace of
        a shared group."""
        from distlr_tpu.feedback.online import OnlineTrainer
        from distlr_tpu.ps import KVWorker, ServerGroup

        shard_dir = tmp_path / "shards"
        shard_dir.mkdir()
        rng = np.random.default_rng(2)
        X = (rng.random((120, D)) < 0.5).astype(np.float32)
        w_true = np.linspace(-2, 2, D).astype(np.float32)
        yv = (X @ w_true > 0).astype(np.int32)
        (shard_dir / "shard-000000.libsvm").write_text("\n".join(
            f"{int(yv[i])} " + " ".join(
                f"{j}:1" for j in np.flatnonzero(X[i]))
            for i in range(len(yv))))
        cfg = Config(model="binary_lr", num_feature_dim=D, batch_size=30,
                     l2_c=0.0, sync_mode=False, learning_rate=0.5)
        with ServerGroup(1, 1, 2 * D, sync=False, learning_rate=0.5) as sg:
            tr = OnlineTrainer(cfg, sg.hosts, str(shard_dir),
                               poll_interval_s=0.05,
                               ns_base=D, ns_total_dim=2 * D)
            stats = tr.run(max_shards=1)
            with KVWorker(sg.hosts, 2 * D) as kv:
                table = kv.pull()
            tr.close()
        assert stats["pushes"] >= 1
        # the OTHER namespace's slice is untouched zeros
        assert float(np.abs(table[:D]).sum()) == 0.0
        assert float(np.abs(table[D:]).sum()) > 0.0
