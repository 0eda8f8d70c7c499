"""Durable parameter server: crash-consistent snapshots + push WAL
(ISSUE 20 tentpole).

Four layers of coverage, per the acceptance criteria:

* the on-disk format round-trips: ``ps/store.py`` reads back exactly
  what the NATIVE server wrote (meta fields, payload, generations);
* corrupt state is rejected LOUDLY, never restored silently — a torn
  write falls back one generation, a flipped byte fails the CRC, and
  both paths surface in the scan and the supervisor's audit trail;
* kill -9 under async load recovers within the RPO contract, audited
  via the push clock: WAL groups lose ZERO acked pushes, snapshot-only
  groups lose at most the final interval's acks;
* the chaos ``kill`` fault kind is validated at parse time like every
  other kind, fires exactly once at a deterministic offset, and drives
  the scaled-down disaster drill end to end (whole group SIGKILLed
  mid-push, supervisor cold-restarts from ``--store-dir``, the same
  client resumes pushing).
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from distlr_tpu.chaos import ChaosFabric, FaultPlanError, parse_plan
from distlr_tpu.config import Config
from distlr_tpu.ps import (
    KVWorker,
    RetryPolicy,
    ServerGroup,
    ServerSupervisor,
)
from distlr_tpu.ps import store as ps_store

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wait(pred, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


def _names(sup):
    """Supervisor audit-event names (events are (time, rank, name))."""
    return [e[2] for e in sup.events]


def _snap_now(group, rank=0):
    """SIGUSR1 = snapshot NOW (the native immediate-snapshot hook)."""
    os.kill(group.procs[rank].pid, signal.SIGUSR1)


def _scan(group, rank=0):
    return ps_store.scan_rank(group.store_rank_dir(rank))


# ---------------------------------------------------------------------------
# config / group validation
# ---------------------------------------------------------------------------

class TestValidation:
    def test_wal_needs_store_dir(self):
        with pytest.raises(ValueError, match="store_wal requires store_dir"):
            ServerGroup(1, 1, dim=4, sync=False, store_wal=True)

    def test_wal_needs_async_group(self, tmp_path):
        with pytest.raises(ValueError, match="async"):
            ServerGroup(1, 1, dim=4, sync=True,
                        store_dir=str(tmp_path), store_wal=True)

    def test_interval_must_be_positive(self, tmp_path):
        with pytest.raises(ValueError, match="store_interval_s"):
            ServerGroup(1, 1, dim=4, sync=False,
                        store_dir=str(tmp_path), store_interval_s=0.0)

    def test_wal_fsync_must_be_positive(self, tmp_path):
        with pytest.raises(ValueError, match="store_wal_fsync_s"):
            ServerGroup(1, 1, dim=4, sync=False, store_dir=str(tmp_path),
                        store_wal=True, store_wal_fsync_s=-1.0)

    def test_store_rank_dir_needs_store_dir(self):
        g = ServerGroup(1, 1, dim=4, sync=False)
        with pytest.raises(ValueError, match="no store_dir"):
            g.store_rank_dir(0)

    def test_config_wal_needs_dir(self):
        with pytest.raises(ValueError, match="ps_store_wal requires"):
            Config(ps_store_wal=True, sync_mode=False)

    def test_config_wal_needs_async(self):
        with pytest.raises(ValueError, match="async"):
            Config(ps_store_wal=True, ps_store_dir="/tmp/x", sync_mode=True)

    def test_config_interval_positive(self):
        with pytest.raises(ValueError, match="ps_store_interval_s"):
            Config(ps_store_dir="/tmp/x", ps_store_interval_s=0)


# ---------------------------------------------------------------------------
# chaos `kill` plan validation (satellite: malformed plans rejected
# loudly at parse time, same contract as the network fault kinds)
# ---------------------------------------------------------------------------

class TestKillPlanValidation:
    def test_after_ops_kill_parses(self):
        plan = parse_plan({"faults": [
            {"kind": "kill", "links": [0], "target": "rank:0",
             "after_ops": 4}]})
        (f,) = plan.faults
        assert f.kind == "kill"
        assert f.target == "rank:0"
        assert f.after_ops == 4
        assert f.at_s is None

    def test_at_s_kill_parses(self):
        plan = parse_plan({"faults": [
            {"kind": "kill", "target": "group", "at_s": 3.0}]})
        (f,) = plan.faults
        assert f.target == "group"
        assert f.at_s == 3.0
        assert f.after_ops is None

    def test_kill_rejects_window(self):
        with pytest.raises(FaultPlanError, match="one-shot point"):
            parse_plan({"faults": [
                {"kind": "kill", "links": [0], "target": "rank:0",
                 "after_ops": 2, "window": [0.0, 1.0]}]})

    def test_kill_needs_a_trigger(self):
        with pytest.raises(FaultPlanError,
                           match="exactly one of after_ops / at_s"):
            parse_plan({"faults": [{"kind": "kill", "target": "group"}]})

    def test_kill_rejects_both_triggers(self):
        with pytest.raises(FaultPlanError,
                           match="exactly one of after_ops / at_s"):
            parse_plan({"faults": [
                {"kind": "kill", "links": [0], "target": "group",
                 "after_ops": 2, "at_s": 1.0}]})

    def test_kill_target_required(self):
        with pytest.raises(FaultPlanError, match="target"):
            parse_plan({"faults": [{"kind": "kill", "at_s": 1.0}]})

    def test_kill_target_malformed(self):
        for bad in ("rank:x", "host:0", "rank:", "everything"):
            with pytest.raises(FaultPlanError, match="target"):
                parse_plan({"faults": [
                    {"kind": "kill", "target": bad, "at_s": 1.0}]})

    def test_after_ops_kill_needs_exactly_one_observing_link(self):
        with pytest.raises(FaultPlanError, match="ONE observing link"):
            parse_plan({"faults": [
                {"kind": "kill", "target": "rank:0", "after_ops": 2}]})
        with pytest.raises(FaultPlanError, match="ONE observing link"):
            parse_plan({"faults": [
                {"kind": "kill", "links": [0, 1], "target": "rank:0",
                 "after_ops": 2}]})

    def test_at_s_kill_rejects_links(self):
        with pytest.raises(FaultPlanError, match="fabric clock"):
            parse_plan({"faults": [
                {"kind": "kill", "links": [0], "target": "group",
                 "at_s": 1.0}]})

    def test_at_s_must_be_nonnegative(self):
        with pytest.raises(FaultPlanError, match="at_s"):
            parse_plan({"faults": [
                {"kind": "kill", "target": "group", "at_s": -1.0}]})

    def test_fabric_rejects_out_of_range_kill_rank(self):
        plan = parse_plan({"faults": [
            {"kind": "kill", "target": "rank:5", "at_s": 1.0}]})
        with pytest.raises(ValueError, match="rank"):
            ChaosFabric([("127.0.0.1", 1)], plan)


# ---------------------------------------------------------------------------
# chaos `kill` execution (one-shot, deterministic offset in the
# canonical event log, executor callback)
# ---------------------------------------------------------------------------

class TestKillFaultExecution:
    def test_at_s_kill_fires_once_and_records_event(self):
        plan = parse_plan({"faults": [
            {"kind": "kill", "target": "group", "at_s": 0.05}]})
        calls = []
        with ChaosFabric([("127.0.0.1", 1)], plan, killer=calls.append) as fab:
            _wait(lambda: calls, timeout=5.0, what="killer callback")
            time.sleep(0.3)  # a second firing would land in here
            assert calls == ["group"]
            kills = [e for e in fab.events() if e[1] == "kill"]
        assert len(kills) == 1
        detail = dict(kills[0][2:])
        assert detail["target"] == "group"
        # the canonical log records the PLAN's offset, never wall time
        assert detail["at_s"] == 0.05

    def test_killer_exceptions_do_not_kill_the_fabric(self):
        def boom(target):
            raise RuntimeError("executor failed")

        plan = parse_plan({"faults": [
            {"kind": "kill", "target": "group", "at_s": 0.05}]})
        with ChaosFabric([("127.0.0.1", 1)], plan, killer=boom) as fab:
            _wait(lambda: [e for e in fab.events() if e[1] == "kill"],
                  timeout=5.0, what="kill event despite executor error")


# ---------------------------------------------------------------------------
# the native on-disk format, read back through ps/store.py
# ---------------------------------------------------------------------------

class TestSnapshotStore:
    def test_snapshot_roundtrip_meta_and_payload(self, tmp_path):
        with ServerGroup(1, 1, dim=8, sync=False, store_dir=str(tmp_path),
                         store_interval_s=60.0) as g:
            with KVWorker(g.hosts, 8, sync_group=False,
                          timeout_ms=2000) as kv:
                kv.push_init(np.full(8, 1.0, np.float32))
                for _ in range(3):
                    kv.push(np.full(8, 1.0, np.float32))
                _snap_now(g)
                # init + 3 pushes = push clock 4
                _wait(lambda: _scan(g).snapshot_clock >= 4,
                      what="snapshot at clock 4")
                best = _scan(g).best
                assert best.valid
                assert best.version == ps_store.STORE_VERSION
                assert best.dim == 8
                assert best.push_clock == 4
                assert best.initialized
                assert not best.has_ftrl
                assert best.epoch >= 1
                meta, weights, z, n = ps_store.read_snapshot(best.path)
                assert meta.push_clock == 4
                assert z is None and n is None
                # 1.0 init, 3 pushes of grad 1.0 at lr 0.2
                np.testing.assert_allclose(
                    np.asarray(weights, np.float32), 0.4, atol=1e-6)
                kv.shutdown_servers()
            g.wait()

    def test_ftrl_snapshot_carries_accumulators(self, tmp_path):
        with ServerGroup(1, 1, dim=4, sync=False, optimizer="ftrl",
                         store_dir=str(tmp_path),
                         store_interval_s=60.0) as g:
            with KVWorker(g.hosts, 4, sync_group=False,
                          timeout_ms=2000) as kv:
                kv.push_init(np.zeros(4, np.float32))
                kv.push(np.full(4, 1.0, np.float32))
                _snap_now(g)
                _wait(lambda: _scan(g).snapshot_clock >= 2,
                      what="FTRL snapshot")
                best = _scan(g).best
                assert best.has_ftrl
                _meta, _w, zacc, nacc = ps_store.read_snapshot(best.path)
                assert zacc is not None and nacc is not None
                # one unit gradient: n accumulates grad^2
                np.testing.assert_allclose(
                    np.asarray(nacc, np.float32), 1.0, atol=1e-6)
                kv.shutdown_servers()
            g.wait()

    def test_generations_alternate_and_best_wins(self, tmp_path):
        with ServerGroup(1, 1, dim=4, sync=False, store_dir=str(tmp_path),
                         store_interval_s=60.0) as g:
            with KVWorker(g.hosts, 4, sync_group=False,
                          timeout_ms=2000) as kv:
                kv.push_init(np.zeros(4, np.float32))
                _snap_now(g)
                _wait(lambda: _scan(g).snapshot_clock >= 1,
                      what="generation 1")
                kv.push(np.full(4, 1.0, np.float32))
                _snap_now(g)
                _wait(lambda: _scan(g).snapshot_clock >= 2,
                      what="generation 2")
                rs = _scan(g)
                present = [m for m in rs.generations if m.present]
                assert len(present) == 2, "two alternating generations"
                assert all(m.valid for m in present)
                assert rs.best.push_clock == max(m.push_clock
                                                 for m in present)
                kv.shutdown_servers()
            g.wait()

    def _two_generations(self, tmp_path):
        """Arm a store with two valid generations (clocks 1 and 2,
        weights 0 and -0.2) and SIGKILL the server mid-flight."""
        with ServerGroup(1, 1, dim=4, sync=False, store_dir=str(tmp_path),
                         store_interval_s=60.0) as g:
            with KVWorker(g.hosts, 4, sync_group=False,
                          timeout_ms=2000) as kv:
                kv.push_init(np.zeros(4, np.float32))
                _snap_now(g)
                _wait(lambda: _scan(g).snapshot_clock >= 1,
                      what="generation 1")
                kv.push(np.full(4, 1.0, np.float32))
                _snap_now(g)
                _wait(lambda: _scan(g).snapshot_clock >= 2,
                      what="generation 2")
                rank_dir = g.store_rank_dir(0)
                g.procs[0].kill()
                g.procs[0].wait()
        rs = ps_store.scan_rank(rank_dir)
        assert rs.best.push_clock == 2
        return rank_dir, rs.best

    def test_torn_write_falls_back_one_generation(self, tmp_path):
        rank_dir, best = self._two_generations(tmp_path)
        with open(best.path, "r+b") as f:
            f.truncate(best.size_bytes - 6)
        rs = ps_store.scan_rank(rank_dir)
        assert rs.corrupt == 1
        bad = next(m for m in rs.generations if m.path == best.path)
        assert not bad.valid and "torn" in bad.why
        assert rs.best.push_clock == 1, "falls back one generation"
        with pytest.raises(ps_store.StoreError, match="torn"):
            ps_store.read_snapshot(best.path)
        # the native cold start reaches the same verdict: it restores
        # the surviving generation, never the torn one
        with ServerGroup(1, 1, dim=4, sync=False,
                         store_dir=str(tmp_path)) as g:
            with KVWorker(g.hosts, 4, sync_group=False,
                          timeout_ms=2000) as kv:
                np.testing.assert_allclose(kv.pull(), 0.0, atol=1e-6)
                kv.shutdown_servers()
            g.wait()

    def test_bad_crc_rejected_loudly(self, tmp_path):
        rank_dir, best = self._two_generations(tmp_path)
        with open(best.path, "r+b") as f:
            f.seek(best.size_bytes - 1)
            byte = f.read(1)
            f.seek(best.size_bytes - 1)
            f.write(bytes([byte[0] ^ 0xFF]))
        rs = ps_store.scan_rank(rank_dir)
        assert rs.corrupt == 1
        bad = next(m for m in rs.generations if m.path == best.path)
        assert not bad.valid and "CRC" in bad.why
        assert rs.best.push_clock == 1
        with pytest.raises(ps_store.StoreError, match="CRC"):
            ps_store.read_snapshot(best.path)

    def test_both_generations_corrupt_never_restored(self, tmp_path):
        rank_dir, _best = self._two_generations(tmp_path)
        for m in ps_store.scan_rank(rank_dir).generations:
            if m.present:
                with open(m.path, "r+b") as f:
                    f.seek(m.size_bytes - 1)
                    byte = f.read(1)
                    f.seek(m.size_bytes - 1)
                    f.write(bytes([byte[0] ^ 0xFF]))
        rs = ps_store.scan_rank(rank_dir)
        assert rs.best is None
        assert rs.corrupt == 2
        assert rs.recovered_clock == 0
        # a cold start on the burned store comes up EMPTY (loudly, in
        # its log) — it must not resurrect either corrupt generation
        with ServerGroup(1, 1, dim=4, sync=False,
                         store_dir=str(tmp_path)) as g:
            with KVWorker(g.hosts, 4, sync_group=False,
                          timeout_ms=2000) as kv:
                kv.push_init(np.full(4, 7.0, np.float32))
                np.testing.assert_allclose(kv.pull(), 7.0, atol=1e-6)
                kv.shutdown_servers()
            g.wait()


# ---------------------------------------------------------------------------
# kill -9 under async load: the RPO contract, audited via the push clock
# ---------------------------------------------------------------------------

class TestKillNineRecovery:
    def test_wal_rpo_is_zero(self, tmp_path):
        """Every ACKED push survives a SIGKILL when the WAL is armed:
        the group-commit fsync runs before the ack leaves the server."""
        with ServerGroup(1, 1, dim=16, sync=False, store_dir=str(tmp_path),
                         store_interval_s=60.0, store_wal=True,
                         store_wal_fsync_s=0.01) as g:
            with KVWorker(g.hosts, 16, sync_group=False,
                          timeout_ms=2000) as kv:
                kv.push_init(np.zeros(16, np.float32))
                for _ in range(12):
                    kv.push(np.full(16, 1.0, np.float32))
                g.procs[0].kill()
                g.procs[0].wait()
        rs = ps_store.scan_rank(os.path.join(str(tmp_path), "rank-0"))
        acked = 1 + 12  # init counts as clock 1
        assert rs.recovered_clock >= acked, (
            f"lost {acked - rs.recovered_clock} acked pushes with the "
            "WAL armed")
        assert rs.wal_records > 0
        # the recovered weights are EXACT: all 12 acked pushes replay
        with ServerGroup(1, 1, dim=16, sync=False, store_dir=str(tmp_path),
                         store_wal=True) as g:
            with KVWorker(g.hosts, 16, sync_group=False,
                          timeout_ms=2000) as kv:
                np.testing.assert_allclose(kv.pull(), -0.2 * 12, atol=1e-5)
                kv.shutdown_servers()
            g.wait()

    def test_run_frames_keep_the_records_bytes_and_replay(self, tmp_path):
        """The server handles a frame of row keys as rows, and a run of
        them as one range; a WAL record is older than that and keeps its
        bytes: FLAT keys, one u64 a value, whatever the frame was.  The
        segment is rebuilt here byte for byte from the format
        (``kv_protocol.h``), which is what the server wrote for the same
        pushes before it stopped writing flat keys out per frame; after
        a SIGKILL the replay lands on the weights that were acked."""
        import zlib

        from distlr_tpu.ps import wire

        vpk, rows = 4096, 3
        dim = vpk * rows            # the default frame: one run of 3 rows
        rng = np.random.default_rng(31)
        w0, g1, g2 = rng.normal(size=(3, dim)).astype(np.float32)
        g_rows = rng.normal(size=2 * vpk).astype(np.float32)
        g_flat = rng.normal(size=5).astype(np.float32)
        row_keys = np.array([0, 2], np.uint64)
        flat_keys = np.array([1, 2, 3, 9000, dim - 1], np.uint64)
        with ServerGroup(1, 1, dim=dim, sync=False, store_dir=str(tmp_path),
                         store_interval_s=60.0, store_wal=True,
                         store_wal_fsync_s=0.01) as g:
            with KVWorker(g.hosts, dim, sync_group=False,
                          timeout_ms=2000) as kv:
                kv.push_init(w0)                                   # a run
                kv.push(g1)                                        # a run
                kv.push_pull(g2)                                   # a run
                kv.push(g_rows, keys=row_keys, vals_per_key=vpk)   # rows
                kv.push(g_flat, keys=flat_keys)                    # flat
                acked = kv.pull()
                assert kv.stats(0)["run_frames"] == 5   # 3 + the fused + pull
                g.procs[0].kill()
                g.procs[0].wait()
        rank_dir = os.path.join(str(tmp_path), "rank-0")
        (clock, path), = ps_store.wal_segments(rank_dir)
        assert clock == 0

        def record(seq, flags, keys, vals):
            payload = keys.astype("<u8").tobytes() + vals.tobytes()
            return ps_store.WAL_RECORD_STRUCT.pack(
                seq, keys.size, flags, wire.OP_PUSH, 0,
                zlib.crc32(payload)) + payload

        every = np.arange(dim, dtype=np.uint64)
        of_rows = (row_keys[:, None] * vpk
                   + np.arange(vpk, dtype=np.uint64)[None, :]).reshape(-1)
        want = ps_store.WAL_SEGMENT_STRUCT.pack(
            ps_store.WAL_MAGIC, ps_store.STORE_VERSION, 1)
        want += record(1, wire.FLAG_INIT_PUSH, every, w0)
        want += record(2, 0, every, g1)
        want += record(3, 0, every, g2)
        want += record(4, 0, of_rows, g_rows)
        want += record(5, 0, flat_keys, g_flat)
        with open(path, "rb") as f:
            assert f.read() == want
        assert [r.seq for r in ps_store.iter_wal(path)] == [1, 2, 3, 4, 5]
        with ServerGroup(1, 1, dim=dim, sync=False, store_dir=str(tmp_path),
                         store_wal=True) as g:
            with KVWorker(g.hosts, dim, sync_group=False,
                          timeout_ms=2000) as kv:
                assert kv.pull().tobytes() == acked.tobytes()
                assert kv.stats(0)["total_pushes"] == 5
                kv.shutdown_servers()
            g.wait()

    def test_snapshot_only_rpo_bounded_by_interval(self, tmp_path):
        """Snapshot-only loss is bounded by the acks issued inside the
        final snapshot interval (+ scheduling slack)."""
        interval = 0.2
        with ServerGroup(1, 1, dim=8, sync=False, store_dir=str(tmp_path),
                         store_interval_s=interval) as g:
            with KVWorker(g.hosts, 8, sync_group=False,
                          timeout_ms=2000) as kv:
                kv.push_init(np.zeros(8, np.float32))
                ack_times = []
                for _ in range(30):
                    kv.push(np.full(8, 1.0, np.float32))
                    ack_times.append(time.monotonic())
                    time.sleep(0.02)
                t_kill = time.monotonic()
                g.procs[0].kill()
                g.procs[0].wait()
        rs = ps_store.scan_rank(os.path.join(str(tmp_path), "rank-0"))
        acked = 1 + len(ack_times)
        lost = max(0, acked - rs.recovered_clock)
        window = 2.0 * interval  # one interval + one of writer slack
        in_window = sum(1 for t in ack_times if t_kill - t <= window)
        assert lost <= in_window + 1, (
            f"lost {lost} acked pushes; only {in_window} were issued "
            f"inside the final {window:.1f}s window")
        assert rs.corrupt == 0


# ---------------------------------------------------------------------------
# supervisor audit trail (satellite: reseeded-from-store / store-stale /
# store-corrupt-fallback)
# ---------------------------------------------------------------------------

class TestSupervisorStoreEvents:
    def test_reseeded_from_store_when_disk_is_ahead(self, tmp_path):
        with ServerGroup(1, 1, dim=8, sync=False, store_dir=str(tmp_path),
                         store_interval_s=60.0, store_wal=True,
                         store_wal_fsync_s=0.01) as g:
            sup = ServerSupervisor(g, poll_interval=0.05,
                                   snapshot_interval=30.0)
            sup.start()
            kv = KVWorker(g.hosts, 8, sync_group=False, timeout_ms=2000)
            kv.push_init(np.zeros(8, np.float32))
            for _ in range(6):
                kv.push(np.full(8, 1.0, np.float32))
            kv.close()
            pid0 = g.procs[0].pid
            g.procs[0].kill()
            _wait(lambda: g.procs[0].pid != pid0
                  and g.procs[0].poll() is None, what="respawn")
            _wait(lambda: "reseeded-from-store" in _names(sup),
                  what="reseeded-from-store audit event")
            # WAL recovery: the respawn serves the exact pre-kill state
            with KVWorker(g.hosts, 8, sync_group=False,
                          timeout_ms=2000) as kv2:
                np.testing.assert_allclose(kv2.pull(), -0.2 * 6, atol=1e-5)
            sup.stop()

    def test_store_stale_falls_back_to_ram_snapshot(self, tmp_path):
        with ServerGroup(1, 1, dim=8, sync=False, store_dir=str(tmp_path),
                         store_interval_s=600.0) as g:
            sup = ServerSupervisor(g, poll_interval=0.05,
                                   snapshot_interval=0.1)
            sup.start()
            kv = KVWorker(g.hosts, 8, sync_group=False, timeout_ms=2000)
            kv.push_init(np.zeros(8, np.float32))
            for _ in range(3):
                kv.push(np.full(8, 1.0, np.float32))
            _snap_now(g)  # disk pinned at clock 4
            _wait(lambda: _scan(g).snapshot_clock >= 4, what="disk at 4")
            for _ in range(8):
                kv.push(np.full(8, 1.0, np.float32))
            kv.close()
            time.sleep(0.4)  # let the RAM snapshot overtake the disk
            pid0 = g.procs[0].pid
            g.procs[0].kill()
            _wait(lambda: g.procs[0].pid != pid0
                  and g.procs[0].poll() is None, what="respawn")
            _wait(lambda: "store-stale" in _names(sup),
                  what="store-stale audit event")
            # recorded after the re-seed's push, which follows the audit
            _wait(lambda: "reseeded" in _names(sup), what="reseeded event")
            sup.stop()

    def test_store_corrupt_fallback_is_audited(self, tmp_path):
        with ServerGroup(1, 1, dim=8, sync=False, store_dir=str(tmp_path),
                         store_interval_s=60.0) as g:
            sup = ServerSupervisor(g, poll_interval=0.05,
                                   snapshot_interval=30.0)
            sup.start()
            kv = KVWorker(g.hosts, 8, sync_group=False, timeout_ms=2000)
            kv.push_init(np.zeros(8, np.float32))
            kv.push(np.full(8, 1.0, np.float32))
            _snap_now(g)
            _wait(lambda: _scan(g).snapshot_clock >= 2, what="snapshot")
            kv.close()
            best = _scan(g).best
            pid0 = g.procs[0].pid
            g.procs[0].kill()
            g.procs[0].wait()
            # corrupt the only generation before the supervisor reseeds
            with open(best.path, "r+b") as f:
                f.seek(best.size_bytes - 1)
                byte = f.read(1)
                f.seek(best.size_bytes - 1)
                f.write(bytes([byte[0] ^ 0xFF]))
            _wait(lambda: g.procs[0].pid != pid0
                  and g.procs[0].poll() is None, what="respawn")
            _wait(lambda: "store-corrupt-fallback" in _names(sup),
                  what="store-corrupt-fallback audit event")
            sup.stop()


# ---------------------------------------------------------------------------
# ps-ctl store: offline disaster inspection
# ---------------------------------------------------------------------------

class TestStoreInspection:
    def test_inspect_store_doc_shape(self, tmp_path):
        with ServerGroup(1, 1, dim=4, sync=False, store_dir=str(tmp_path),
                         store_interval_s=60.0) as g:
            with KVWorker(g.hosts, 4, sync_group=False,
                          timeout_ms=2000) as kv:
                kv.push_init(np.zeros(4, np.float32))
                _snap_now(g)
                _wait(lambda: _scan(g).snapshot_clock >= 1, what="snapshot")
                kv.shutdown_servers()
            g.wait()
        doc = ps_store.inspect_store(str(tmp_path), now=time.time())
        assert "0" in doc["ranks"]
        rank = doc["ranks"]["0"]
        assert rank["recovered_clock"] >= 1
        assert rank["corrupt_generations"] == 0
        assert rank["dim"] == 4
        json.dumps(doc)  # the CLI payload must be JSON-able

    def test_ps_ctl_store_cli_offline(self, tmp_path):
        with ServerGroup(1, 1, dim=4, sync=False, store_dir=str(tmp_path),
                         store_interval_s=60.0) as g:
            with KVWorker(g.hosts, 4, sync_group=False,
                          timeout_ms=2000) as kv:
                kv.push_init(np.zeros(4, np.float32))
                _snap_now(g)
                _wait(lambda: _scan(g).snapshot_clock >= 1, what="snapshot")
                kv.shutdown_servers()
            g.wait()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-m", "distlr_tpu.launch", "ps-ctl",
             "store", "--store-dir", str(tmp_path)],
            capture_output=True, text=True, timeout=120, env=env,
            cwd=REPO)
        assert out.returncode == 0, out.stderr
        line = next(ln for ln in out.stdout.splitlines()
                    if ln.startswith("PSCTL "))
        doc = json.loads(line[len("PSCTL "):])
        assert doc["ranks"]["0"]["recovered_clock"] >= 1


# ---------------------------------------------------------------------------
# the scaled-down acceptance drill: whole group SIGKILLed mid-push via
# a chaos `kill` fault, cold restart from --store-dir, client resumes
# ---------------------------------------------------------------------------

class TestDisasterDrill:
    def test_after_ops_kill_fires_at_exact_op_and_rank_recovers(
            self, tmp_path):
        plan = parse_plan({"faults": [
            {"kind": "kill", "links": [0], "target": "rank:0",
             "after_ops": 4}]})
        with ServerGroup(1, 1, dim=8, sync=False, via_chaos=plan,
                         store_dir=str(tmp_path), store_interval_s=60.0,
                         store_wal=True, store_wal_fsync_s=0.01) as g:
            sup = ServerSupervisor(g, poll_interval=0.05,
                                   snapshot_interval=30.0)
            sup.start()
            pid0 = g.procs[0].pid
            kv = KVWorker(g.hosts, 8, sync_group=False, timeout_ms=2000)
            kv.push_init(np.zeros(8, np.float32))  # op 1
            acked = 0
            try:
                for _ in range(10):
                    kv.push(np.full(8, 1.0, np.float32))
                    acked += 1
                    time.sleep(0.02)
                pytest.fail("the kill fault never severed the client")
            except OSError:
                pass
            kv.close()
            kills = [e for e in g.chaos.events() if e[1] == "kill"]
            assert len(kills) == 1, "kill faults are one-shot"
            detail = dict(kills[0][2:])
            assert detail["op"] == 4
            assert detail["target"] == "rank:0"
            _wait(lambda: g.procs[0].pid != pid0
                  and g.procs[0].poll() is None, what="respawn")
            _wait(lambda: "reseeded-from-store" in _names(sup),
                  what="reseed audit")
            # the WAL covers every acked push; the op-4 push raced the
            # SIGKILL so the applied clock may run one ahead of acks
            rs = _scan(g)
            applied = rs.recovered_clock - 1  # minus the init push
            assert acked <= applied <= acked + 1
            with KVWorker(g.hosts, 8, sync_group=False,
                          timeout_ms=2000) as kv2:
                np.testing.assert_allclose(kv2.pull(), -0.2 * applied,
                                           atol=1e-5)
            sup.stop()

    def test_whole_group_power_loss_client_resumes(self, tmp_path):
        """The acceptance drill, scaled down: a 2-rank async WAL group
        is SIGKILLed whole mid-push by a time-triggered chaos kill, the
        supervisor cold-restarts every rank from --store-dir, and the
        SAME client (retry policy, no restart) resumes pushing.

        The audit has two legs.  RPO: the recovered push clock covers
        every push the SERVER acked before the cut.  Weights: every
        client-acked push lands exactly once — minus the pushes the
        retry policy ABSORBED as outcome-unknown around the cut (its
        documented at-most-once semantics: never re-issued once a byte
        was delivered, counted in push_outcome_unknown_total)."""
        from distlr_tpu.obs.registry import get_registry

        def _absorbed():
            fam = get_registry().get("distlr_ps_push_outcome_unknown_total")
            if fam is None:
                return 0.0
            return sum(c.value for _v, c in fam.children())

        plan = parse_plan({"faults": [
            {"kind": "kill", "target": "group", "at_s": 0.5}]})
        lr, grad = 0.2, 0.1
        with ServerGroup(2, 1, dim=32, sync=False, via_chaos=plan,
                         store_dir=str(tmp_path), store_interval_s=0.5,
                         store_wal=True, store_wal_fsync_s=0.01) as g:
            sup = ServerSupervisor(g, poll_interval=0.05,
                                   snapshot_interval=0.5)
            sup.start()
            pids = [p.pid for p in g.procs]
            kv = KVWorker(g.hosts, 32, sync_group=False, timeout_ms=2000,
                          retry=RetryPolicy(attempts=10, backoff_ms=50))
            base_absorbed = _absorbed()
            kv.push_init(np.zeros(32, np.float32))

            def _kills():
                return [e for e in g.chaos.events() if e[1] == "kill"]

            def _push_until(done, budget_s):
                nonlocal acked, unknown
                deadline = time.monotonic() + budget_s
                while not done() and time.monotonic() < deadline:
                    try:
                        kv.push(np.full(32, grad, np.float32))
                        acked += 1
                    except OSError:
                        unknown += 1
                        time.sleep(0.05)
                    time.sleep(0.005)

            acked, unknown = 0, 0
            _push_until(_kills, 10.0)  # the power cut lands mid-stream
            assert _kills(), "the time-triggered kill never fired"
            survived = acked
            absorbed_at_cut = _absorbed() - base_absorbed
            _wait(lambda: all(p.pid != old and p.poll() is None
                              for p, old in zip(g.procs, pids)),
                  what="every rank respawned")
            # RPO leg: the WAL covered every pre-cut server ack.  The
            # client's count may run ahead by the absorbed pushes (ack
            # never reached it) — those are the only allowed gap.
            clocks = [_scan(g, r).recovered_clock
                      for r in range(g.num_servers)]
            assert min(clocks) >= 1 + survived - absorbed_at_cut, (
                f"recovered clocks {clocks} lost server-acked pushes "
                f"({survived} client acks, {absorbed_at_cut:.0f} "
                "absorbed)")
            # the SAME client (no restart) must resume: 20 more acks
            _push_until(lambda: acked >= survived + 20, 10.0)
            kv.close()
            kills = _kills()
            assert len(kills) == 1
            assert dict(kills[0][2:])["target"] == "group"
            assert acked >= survived + 20, (
                f"client never resumed: {acked} acks, {unknown} unknown")
            assert "reseeded-from-store" in _names(sup)
            absorbed = _absorbed() - base_absorbed
            with KVWorker(g.hosts, 32, sync_group=False,
                          timeout_ms=2000) as kv2:
                w = kv2.pull()
            lo = -lr * grad * (acked + unknown) - 1e-4
            hi = -lr * grad * (acked - absorbed) + 1e-4
            assert np.all(w >= lo) and np.all(w <= hi), (
                f"weights {w[0]:.4f} outside [{lo:.4f}, {hi:.4f}] for "
                f"{acked} acked / {unknown} unknown / {absorbed:.0f} "
                "absorbed pushes")
            # each shard's slice moves as a unit, so each is uniform
            for r in range(g.num_servers):
                sl = w[slice(*g.key_range(r))]
                assert np.allclose(sl, sl[0], atol=1e-5), \
                    f"rank {r}'s recovered slice is not uniform"
            sup.stop()
