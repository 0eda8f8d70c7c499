"""Server-group lifecycle management.

Replaces the reference launcher's server-spawning half
(``examples/local.sh:36-41``: S ``distlr`` processes with
``DMLC_ROLE=server``) with a context-managed group of native
``distlr_kv_server`` processes, one per key range.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import threading
import time

import numpy as np

from distlr_tpu.obs.registry import get_registry
from distlr_tpu.ps import wire
from distlr_tpu.ps.build import build_native, sanitizer_environ, server_binary
from distlr_tpu.utils.logging import get_logger

log = get_logger(__name__)

_reg = get_registry()
_SPAWNS = _reg.counter(
    "distlr_ps_server_spawns_total",
    "native KV server processes spawned (incl. supervisor respawns)",
    labelnames=("rank",),
)
_UP = _reg.gauge(
    "distlr_ps_server_up",
    "1 while this server rank's process is managed and running",
    labelnames=("rank",),
)
_SUP_EVENTS = _reg.counter(
    "distlr_ps_supervisor_events_total",
    "supervisor audit-trail events (respawned/reseeded/seeded-zeros/"
    "gave-up/respawn-failed/reseeded-from-store/store-stale/"
    "store-corrupt-fallback)",
    labelnames=("event",),
)
#: Durable-store health, scanned from each rank's on-disk state
#: (ps/store.py) by the supervisor's snapshot cycles when the group
#: runs with a --store_dir.
_STORE_SNAPSHOT_AGE = _reg.gauge(
    "distlr_ps_store_snapshot_age_seconds",
    "age of this rank's newest VALID on-disk snapshot generation (the "
    "worst-case RPO window when the WAL is off)",
    labelnames=("rank",),
)
_STORE_BYTES = _reg.gauge(
    "distlr_ps_store_bytes",
    "on-disk durable-store footprint per rank",
    labelnames=("rank", "kind"),
)
_STORE_WAL_LAG = _reg.gauge(
    "distlr_ps_store_wal_lag_records",
    "intact WAL records past this rank's newest valid snapshot — the "
    "replay depth a cold restart pays (snapshot lag, not data loss)",
    labelnames=("rank",),
)
_STORE_CORRUPT = _reg.gauge(
    "distlr_ps_store_corrupt_generations",
    "snapshot generations on disk currently rejected as torn/corrupt "
    "(>0 means the store is one failure from losing its fallback)",
    labelnames=("rank",),
)
_SNAPSHOT_SECONDS = _reg.histogram(
    "distlr_ps_supervisor_snapshot_seconds",
    "wall seconds per supervisor rolling-snapshot cycle",
)
_MEMBERSHIP_SERVERS = _reg.gauge(
    "distlr_membership_servers",
    "server ranks in the group's CURRENT layout (moves on an elastic "
    "resize, not on crashes — crash visibility is distlr_ps_server_up)",
)


@dataclasses.dataclass(frozen=True)
class ResizePlan:
    """One membership change, computed by :meth:`ServerGroup.plan_resize`
    and executed by the :class:`~distlr_tpu.ps.membership.
    MembershipCoordinator`: which old processes survive as which new
    ranks, which new ranks need spawning, which old ranks retire, and
    exactly which global key sub-ranges must MOVE (drained from their
    old owner via keyed pulls, seeded into the new owner via a forced
    keyed init-push)."""

    new_num_servers: int
    #: global key slice per NEW rank
    new_ranges: list[tuple[int, int]]
    #: new_rank -> old_rank whose process survives as it (same
    #: range_begin, so the server's local key rebase stays valid; its
    #: resident slice never crosses the wire)
    reuse: dict[int, int]
    #: new ranks that need a fresh process
    spawn: list[int]
    #: old ranks with no new identity (retired after the drain)
    retire: list[int]
    #: (old_rank, global_lo, global_hi, new_rank) — the data that moves
    moves: list[tuple[int, int, int, int]]

    @property
    def moved_keys(self) -> int:
        return sum(hi - lo for _, lo, hi, _ in self.moves)


def plan_reshard(dim: int, old_ranges: list[tuple[int, int]],
                 new_num_servers: int, *, alive: list[bool],
                 allow_reuse: bool = True) -> ResizePlan:
    """The membership planner's pure core: current layout -> equal-range
    layout over ``new_num_servers``, as a :class:`ResizePlan`.

    Extracted from :meth:`ServerGroup.plan_resize` (which now delegates
    here after its process-level validation) so fleetsim property-tests
    the SAME arithmetic against thousand-rank layouts without spawning a
    single server.  ``alive[r]`` says whether old rank ``r``'s process
    survives (a dead process can never be reused — its table is gone);
    ``allow_reuse=False`` is the FTRL / opt_segments full-rebuild mode.

    Reuse keys on a matching ``range_begin`` among alive ranks: the
    server stores local keys rebased by range_begin, so a matching start
    keeps every resident slot addressable — a grown range extends
    elastically, a shrunk one simply stops being addressed.  Every key
    of every new range is then either resident (the reused prefix) or
    covered by exactly one move; :mod:`distlr_tpu.analysis.fleetsim`
    pins that as the ``reshard_converged`` property.
    """
    if new_num_servers < 1:
        raise ValueError(
            f"new_num_servers must be >= 1, got {new_num_servers}")
    if new_num_servers > dim:
        raise ValueError(
            f"cannot shard dim={dim} over {new_num_servers} "
            "servers (empty ranges)")
    if len(alive) != len(old_ranges):
        raise ValueError(
            f"alive has {len(alive)} entries for {len(old_ranges)} ranks")
    S2 = int(new_num_servers)
    new_ranges = [(dim * r // S2, dim * (r + 1) // S2) for r in range(S2)]
    reuse: dict[int, int] = {}
    if allow_reuse:
        old_by_begin = {lo: r for r, (lo, _hi) in enumerate(old_ranges)
                        if alive[r]}
        claimed: set[int] = set()
        for nr, (lo, _hi) in enumerate(new_ranges):
            r = old_by_begin.get(lo)
            if r is not None and r not in claimed:
                reuse[nr] = r
                claimed.add(r)
    moves: list[tuple[int, int, int, int]] = []
    for nr, (lo, hi) in enumerate(new_ranges):
        res_hi = lo  # end of the resident (reused) prefix
        if nr in reuse:
            res_hi = min(old_ranges[reuse[nr]][1], hi)
        if res_hi >= hi:
            continue
        for o, (olo, ohi) in enumerate(old_ranges):
            mlo, mhi = max(olo, res_hi), min(ohi, hi)
            if mlo < mhi:
                moves.append((o, mlo, mhi, nr))
    return ResizePlan(
        new_num_servers=S2,
        new_ranges=new_ranges,
        reuse=reuse,
        spawn=[nr for nr in range(S2) if nr not in reuse],
        retire=[r for r in range(len(old_ranges))
                if r not in reuse.values()],
        moves=moves,
    )


class ServerGroup:
    """Spawn and manage S native KV server processes on localhost.

    Server rank ``r`` owns global keys ``[r*D/S, (r+1)*D/S)`` — the
    ps-lite range partition (reference ``src/main.cc:98-101``); the
    client library slices requests to match.

    Ports are ephemeral: each server binds port 0 and announces the
    kernel-chosen port as ``PORT <n>`` on stdout, which is read here —
    no pick-then-rebind race.  ``bind_any=True`` listens on 0.0.0.0 for
    multi-host (DCN) deployments.
    """

    def __init__(
        self,
        num_servers: int,
        num_workers: int,
        dim: int,
        *,
        learning_rate: float = 0.2,
        sync: bool = True,
        last_gradient: bool = False,
        ports: list[int] | None = None,
        bind_any: bool = False,
        binary: str | None = None,
        max_dim: int | None = None,
        via_chaos=None,
        optimizer: str = "sgd",
        ftrl_alpha: float = 0.1,
        ftrl_beta: float = 1.0,
        ftrl_l1: float = 0.0,
        ftrl_l2: float = 0.0,
        compress: bool = True,
        trace_journal_dir: str | None = None,
        prof_journal_dir: str | None = None,
        prof_window_s: float | None = None,
        epoch: int = 1,
        opt_segments: list[tuple[int, str]] | None = None,
        store_dir: str | None = None,
        store_interval_s: float = 5.0,
        store_wal: bool = False,
        store_wal_fsync_s: float = 0.1,
    ):
        if optimizer not in ("sgd", "ftrl", "signsgd"):
            raise ValueError(
                f"optimizer must be sgd|ftrl|signsgd, got {optimizer!r}")
        if not 1 <= epoch <= wire.AUX_MAX:
            # membership epochs ride the u16 MsgHeader::aux field
            raise ValueError(
                f"epoch must be in [1, {wire.AUX_MAX}], got {epoch}")
        if opt_segments:
            # per-namespace optimizers (GLOBAL (end, opt) pairs, ascending,
            # covering [0, dim)): each rank gets the intersection with its
            # key range as a LOCAL --opt_segments map
            if optimizer == "signsgd" or last_gradient:
                raise ValueError(
                    "opt_segments is incompatible with optimizer='signsgd' "
                    "and last_gradient (uniform-group semantics)")
            prev = 0
            for end, opt in opt_segments:
                if opt not in ("sgd", "ftrl"):
                    raise ValueError(
                        f"segment optimizer must be sgd|ftrl, got {opt!r}")
                if end <= prev:
                    raise ValueError(
                        f"opt_segments ends must ascend, got {opt_segments}")
                prev = end
            if prev != dim:
                raise ValueError(
                    f"opt_segments must cover [0, dim={dim}), got end {prev}")
        if store_wal and not store_dir:
            raise ValueError(
                "store_wal requires store_dir (the WAL lives in the "
                "same per-rank store directory)")
        if store_wal and sync:
            # mirrors the native server's own exit-2 validation: a sync
            # round's merge buffer has no per-push replay semantics
            raise ValueError(
                "store_wal requires an async (sync=False) group — "
                "sync-round merge state has no per-push replay semantics")
        if store_dir and store_interval_s <= 0:
            raise ValueError(
                f"store_interval_s must be positive, got {store_interval_s}")
        if store_wal and store_wal_fsync_s <= 0:
            raise ValueError(
                f"store_wal_fsync_s must be positive, got {store_wal_fsync_s}")
        if optimizer != "sgd" and last_gradient:
            # Q1 is a reference-SGD parity quirk; there is no "last
            # worker's FTRL step / majority vote / W" reference behavior
            # to mirror.
            raise ValueError(
                f"optimizer={optimizer!r} is incompatible with "
                "last_gradient (Q1 compat is an SGD parity quirk)"
            )
        build_native()
        self._binary = binary or server_binary()
        self.num_servers = num_servers
        self.num_workers = num_workers
        self.dim = dim
        self.ports: list[int] = ports or []
        self.procs: list[subprocess.Popen] = []
        #: membership epoch new spawns (incl. supervisor respawns) carry
        #: (kv_protocol.h kEpoch); the coordinator bumps it per resize.
        #: 1 = the static default — spawn command lines stay byte-
        #: identical to every earlier round's.
        self.epoch = int(epoch)
        #: global key slice per rank — the ps-lite equal partition at
        #: spawn, REWRITTEN by an elastic resize (commit_resize); every
        #: range consumer reads this, never re-derives dim*r/S
        self.ranges: list[tuple[int, int]] = [
            (dim * r // num_servers, dim * (r + 1) // num_servers)
            for r in range(num_servers)
        ]
        self._opt_segments = list(opt_segments or [])
        #: per-rank chaos links when via_chaos is set (rank order; the
        #: fabric's own list keeps creation order, which diverges from
        #: rank order after a resize)
        self._chaos_links: list = []
        # Fault-injection hook: a FaultPlan (distlr_tpu.chaos) interposes
        # one ChaosFabric link per server rank between clients and the
        # native processes — `hosts` then names the PROXIED ports, so
        # every KVWorker riding this group sees the plan's faults.  The
        # supervisor's per-rank probes (`_probe_rank`) keep addressing
        # the real server ports: supervision is control-plane and must
        # diagnose the chaos, not drown in it.
        self._chaos_plan = via_chaos
        self.chaos = None  # the live ChaosFabric once start() ran
        self._args = dict(
            lr=learning_rate,
            sync=int(sync),
            last_gradient=int(last_gradient),
            bind_any=int(bind_any),
            # elasticity/corruption cap (server --max_dim); None = the
            # server's default (2^31, always clamped to >= its slice dim)
            max_dim=max_dim,
            # server-side update rule (the pluggable optimizer point the
            # lr flag already parameterized): "sgd", "ftrl" (per-
            # coordinate FTRL-Proximal with z/n accumulators — the
            # sparse-CTR production optimizer the online-learning loop
            # trains through), or "signsgd" (1-bit majority-vote
            # aggregation — the kCodecSign wire codec's server half)
            optimizer=optimizer,
            ftrl_alpha=ftrl_alpha,
            ftrl_beta=ftrl_beta,
            ftrl_l1=ftrl_l1,
            ftrl_l2=ftrl_l2,
            # False spawns --compress=0: the server hides its codec
            # capabilities and answers kHello like a pre-codec binary —
            # how the graceful-fallback tests simulate an old server
            compress=bool(compress),
            # distributed tracing (ISSUE 8): when set, each rank logs
            # per-handler spans for trace-stamped ops to
            # <dir>/kvserver-<rank>.jsonl — the native half of the span
            # journals `launch trace-agg` merges.  None keeps the spawn
            # command line byte-identical to every earlier round's.
            trace_journal_dir=trace_journal_dir,
            # continuous profiling (ISSUE 9): each rank journals per-
            # handler thread-CPU windows to <dir>/kvserver-<rank>.jsonl
            # in the Python samplers' profwindow schema — the native
            # tracks of `launch prof-agg`'s fleet flamegraph.  None keeps
            # the spawn command line byte-identical.
            prof_journal_dir=prof_journal_dir,
            prof_window_s=prof_window_s,
            # durable store (ISSUE 20): each rank persists crash-
            # consistent snapshots (+ optional push WAL) of its slice
            # under <store_dir>/rank-<r>/ and self-recovers from them at
            # spawn — including supervisor respawns, which then skip the
            # RAM re-seed when the disk state is at least as new.  None
            # keeps the spawn command line byte-identical (RAM-only,
            # the prior behavior).
            store_dir=store_dir,
            store_interval_s=store_interval_s,
            store_wal=store_wal,
            store_wal_fsync_s=store_wal_fsync_s,
        )
        # serializes respawn() against stop() (supervisor thread vs
        # teardown) and marks teardown so a racing respawn becomes a no-op
        self._lock = threading.Lock()
        self._stopped = False

    @property
    def hosts(self) -> str:
        """Client connection spec, server-rank order.  With a
        ``via_chaos`` plan attached this names the fault-injecting
        proxy ports — the drop-in property that puts every client
        behind the plan; :attr:`direct_hosts` bypasses it."""
        if self.chaos is not None:
            return ",".join(f"127.0.0.1:{lk.port}"
                            for lk in self._chaos_links)
        return self.direct_hosts

    @property
    def direct_hosts(self) -> str:
        """The native server processes' own ports (chaos-free path)."""
        return ",".join(f"127.0.0.1:{p}" for p in self.ports)

    @property
    def has_ftrl(self) -> bool:
        """Whether ANY coordinate of the group runs FTRL (the uniform
        optimizer or an opt_segments namespace) — gates the supervisor's
        opt-state snapshot/restore and the drain's opt-state migration."""
        return (self._args["optimizer"] == "ftrl"
                or any(opt == "ftrl" for _, opt in self._opt_segments))

    def key_range(self, rank: int) -> tuple[int, int]:
        """Global key slice ``[lo, hi)`` owned by server ``rank`` in the
        CURRENT layout."""
        return self.ranges[rank]

    def store_rank_dir(self, rank: int) -> str:
        """Rank ``rank``'s durable-store directory (requires a group
        ``store_dir``) — where its snapshot generations and WAL
        segments live."""
        if not self._args["store_dir"]:
            raise ValueError("group has no store_dir")
        return os.path.join(self._args["store_dir"], f"rank-{rank}")

    def _local_opt_segments(self, lo: int, hi: int) -> str:
        """--opt_segments value for a rank owning global [lo, hi): the
        global per-namespace map intersected and rebased to local keys."""
        parts = []
        for end, opt in self._opt_segments:
            start = max(0, min(end, hi) - lo)
            if start > 0 and (not parts or start > int(parts[-1].split(":")[0])):
                parts.append(f"{start}:{opt}")
            if end >= hi:
                break
        return ",".join(parts)

    def _spawn(self, rank: int, port: int, *,
               key_range: tuple[int, int] | None = None,
               epoch: int | None = None) -> tuple[subprocess.Popen, int]:
        lo, hi = key_range if key_range is not None else self.key_range(rank)
        cmd = [
            self._binary,
            f"--port={port}",
            f"--num_workers={self.num_workers}",
            f"--dim={hi - lo}",
            f"--lr={self._args['lr']}",
            f"--sync={self._args['sync']}",
            f"--last_gradient={self._args['last_gradient']}",
            f"--bind_any={self._args['bind_any']}",
        ]
        if self._args["max_dim"] is not None:
            cmd.append(f"--max_dim={self._args['max_dim']}")
        epoch = self.epoch if epoch is None else epoch
        if epoch != 1:
            # non-default only: static groups keep byte-identical spawns
            cmd.append(f"--epoch={epoch}")
        if self._opt_segments:
            segs = self._local_opt_segments(lo, hi)
            if segs:
                cmd.append(f"--opt_segments={segs}")
        if self._args["optimizer"] == "ftrl":
            # only non-default optimizers touch the command line, so sgd
            # spawns stay byte-identical to every earlier round's
            cmd += [
                f"--optimizer={self._args['optimizer']}",
                f"--ftrl_alpha={self._args['ftrl_alpha']}",
                f"--ftrl_beta={self._args['ftrl_beta']}",
                f"--ftrl_l1={self._args['ftrl_l1']}",
                f"--ftrl_l2={self._args['ftrl_l2']}",
            ]
        elif self._args["optimizer"] != "sgd":
            cmd.append(f"--optimizer={self._args['optimizer']}")
        elif self.has_ftrl:
            # sgd group default + FTRL opt_segments: the segments' FTRL
            # coordinates must still run the CONFIGURED hyperparameters
            # — without these flags they would silently train on the
            # native defaults
            cmd += [
                f"--ftrl_alpha={self._args['ftrl_alpha']}",
                f"--ftrl_beta={self._args['ftrl_beta']}",
                f"--ftrl_l1={self._args['ftrl_l1']}",
                f"--ftrl_l2={self._args['ftrl_l2']}",
            ]
        if not self._args["compress"]:
            # non-default only: default spawns stay byte-identical
            cmd.append("--compress=0")
        if self._args["trace_journal_dir"]:
            d = self._args["trace_journal_dir"]
            os.makedirs(d, exist_ok=True)
            cmd.append("--trace_journal="
                       + os.path.join(d, f"kvserver-{rank}.jsonl"))
        if self._args["prof_journal_dir"]:
            d = self._args["prof_journal_dir"]
            os.makedirs(d, exist_ok=True)
            cmd.append("--prof_journal="
                       + os.path.join(d, f"kvserver-{rank}.jsonl"))
            if self._args["prof_window_s"] is not None:
                cmd.append(f"--prof_window={self._args['prof_window_s']}")
        if self._args["store_dir"]:
            # per-rank subdirectory: ranks own disjoint key slices, so
            # their snapshot/WAL files must never collide.  The server
            # RECOVERS from whatever is already there before announcing
            # PORT — a cold group restart with the same store_dir is the
            # whole-fleet disaster-recovery path.
            d = self.store_rank_dir(rank)
            os.makedirs(d, exist_ok=True)
            cmd.append(f"--store_dir={d}")
            if self._args["store_interval_s"] != 5.0:
                cmd.append(f"--store_interval={self._args['store_interval_s']}")
            if self._args["store_wal"]:
                cmd.append("--store_wal=1")
                if self._args["store_wal_fsync_s"] != 0.1:
                    cmd.append(
                        f"--store_wal_fsync={self._args['store_wal_fsync_s']}")
        # DISTLR_NATIVE_VARIANT spawns ride the sanitizer environment
        # (suppressions wired in, caller's log_path preserved); the
        # standard build passes env=None — the spawn stays byte-
        # identical to every earlier round's.
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                                env=sanitizer_environ())
        # The server prints "PORT <n>" once listening; blocking on that
        # line doubles as the readiness wait.
        line = proc.stdout.readline().strip()
        if not line.startswith("PORT "):
            proc.terminate()
            raise RuntimeError(
                f"KV server rank {rank} failed to start (got {line!r})"
            )
        _SPAWNS.labels(rank=rank).inc()
        _UP.labels(rank=rank).set(1)
        return proc, int(line.split()[1])

    def start(self) -> "ServerGroup":
        fixed_ports = list(self.ports)
        self.ports = []
        self._stopped = False
        for rank in range(self.num_servers):
            try:
                proc, port = self._spawn(rank, fixed_ports[rank] if fixed_ports else 0)
            except RuntimeError:
                self.stop()
                raise
            self.procs.append(proc)
            self.ports.append(port)
        if self._chaos_plan is not None and self.chaos is None:
            from distlr_tpu.chaos.proxy import ChaosFabric  # noqa: PLC0415

            # one proxy link per rank, targeting the REAL ports — a
            # supervisor respawn reuses the original port, so the link
            # stays valid across server deaths.  The group owns the
            # pids, so it is also the kill-fault executor (ISSUE 20:
            # plan kind "kill" SIGKILLs a rank or the whole group).
            self.chaos = ChaosFabric(self.direct_hosts, self._chaos_plan,
                                     killer=self._chaos_kill)
            self._chaos_links = list(self.chaos.links)
        _MEMBERSHIP_SERVERS.set(self.num_servers)
        return self

    def _chaos_kill(self, target: str) -> None:
        """Kill-fault executor for the embedded chaos fabric (plan kind
        ``kill``, ISSUE 20): SIGKILL one rank's native server
        (``"rank:N"``) or every rank (``"group"``).  A supervised group
        respawns the victims and re-seeds them — from the durable store
        when ``store_dir`` is armed — which is exactly the power-loss
        drill the DR acceptance test runs."""
        with self._lock:
            if target == "group":
                victims = list(self.procs)
            else:
                rank = int(target.split(":", 1)[1])
                if rank >= len(self.procs):
                    log.warning("chaos kill target %r: no such rank",
                                target)
                    return
                victims = [self.procs[rank]]
        for proc in victims:
            if proc.poll() is None:
                proc.kill()

    def respawn(self, rank: int) -> bool:
        """Restart a dead server process on its ORIGINAL port (so the
        group's ``hosts`` string — already baked into every client —
        stays valid).  The new process starts UNINITIALIZED: the caller
        (ServerSupervisor) must re-seed its key slice via a forced init
        push.  Returns False if the group is being torn down or the rank
        is still alive."""
        with self._lock:
            if self._stopped:
                return False
            old = self.procs[rank]
            if old.poll() is None:
                return False
            if old.stdout:
                old.stdout.close()
            proc, port = self._spawn(rank, self.ports[rank])
            if port != self.ports[rank]:
                # Another process stole the port between death and respawn;
                # clients hold the old hosts string, so this replacement is
                # unreachable — fail the respawn, not the supervisor thread.
                proc.terminate()
                if proc.stdout:
                    proc.stdout.close()
                proc.wait()
                raise RuntimeError(
                    f"respawned server rank {rank} bound port {port}, "
                    f"expected {self.ports[rank]} (port stolen while down)"
                )
            self.procs[rank] = proc
            return True

    # -- elastic membership (the live-resharding round) --------------------
    def plan_resize(self, new_num_servers: int) -> ResizePlan:
        """Compute the membership change from the current layout to
        ``new_num_servers`` equal ranges — WITHOUT touching anything.

        A surviving old process is REUSED as the new rank whose range
        starts where its own did (the server stores local keys rebased
        by range_begin, so a matching start keeps every resident slot
        addressable; a grown range extends elastically, a shrunk one
        simply stops being addressed).  Doubling reuses every old rank
        and moves half the table; halving reuses every even rank and
        drains the odd ones.  Groups with per-coordinate optimizer
        state (FTRL — uniform or via opt_segments) never reuse: the
        kOptState wire only seeds FULL ranges, so their resharding is a
        full rebuild (every new rank fresh, weights AND z/n migrated).
        """
        if self._args["sync"]:
            raise ValueError(
                "elastic resize supports async (Hogwild) groups only — "
                "a sync BSP round cannot straddle a membership change")
        if self._args["store_dir"]:
            raise ValueError(
                "elastic resize of a durable (store_dir) group is not "
                "supported: the per-rank on-disk slices would no longer "
                "match the new layout — stop the group, clear or migrate "
                "the store, and restart at the new size")
        return plan_reshard(
            self.dim, self.ranges, new_num_servers,
            alive=[p.poll() is None for p in self.procs],
            allow_reuse=not self.has_ftrl and not self._opt_segments,
        )

    def spawn_for_resize(self, plan: ResizePlan,
                         epoch: int) -> dict[int, tuple]:
        """Spawn the plan's fresh ranks at the NEW epoch (ephemeral
        ports).  Returns ``{new_rank: (proc, port)}`` — staged, not yet
        part of the layout; :meth:`commit_resize` installs them, or the
        caller terminates them on an aborted migration."""
        staged: dict[int, tuple] = {}
        try:
            for nr in plan.spawn:
                staged[nr] = self._spawn(nr, 0,
                                         key_range=plan.new_ranges[nr],
                                         epoch=epoch)
        except Exception:
            for proc, _port in staged.values():
                proc.terminate()
                if proc.stdout:
                    proc.stdout.close()
                proc.wait()
            raise
        return staged

    def commit_resize(self, plan: ResizePlan, staged: dict[int, tuple],
                      epoch: int) -> None:
        """Install the new layout: reused processes take their new rank
        ids, staged spawns join, retiring processes terminate, and
        (under a chaos plan) the per-rank proxy links follow — new
        ranks get fresh links, so the plan's faults keep applying to
        the grown fleet."""
        with self._lock:
            old_count = self.num_servers
            new_procs: list[subprocess.Popen] = []
            new_ports: list[int] = []
            new_links: list = []
            for nr in range(plan.new_num_servers):
                if nr in plan.reuse:
                    r = plan.reuse[nr]
                    new_procs.append(self.procs[r])
                    new_ports.append(self.ports[r])
                    if self.chaos is not None:
                        new_links.append(self._chaos_links[r])
                else:
                    proc, port = staged[nr]
                    new_procs.append(proc)
                    new_ports.append(port)
                    if self.chaos is not None:
                        new_links.append(
                            self.chaos.add_upstream("127.0.0.1", port))
            retiring = [(r, self.procs[r]) for r in plan.retire]
            retiring_links = ([self._chaos_links[r] for r in plan.retire]
                              if self.chaos is not None else [])
            self.procs = new_procs
            self.ports = new_ports
            self.ranges = list(plan.new_ranges)
            self.num_servers = plan.new_num_servers
            self._chaos_links = new_links
            self.epoch = int(epoch)
        # teardown of the retired ranks happens outside the lock (the
        # supervisor is paused during a resize; nothing else spawns)
        for _r, proc in retiring:
            if proc.poll() is None:
                proc.terminate()
        for _r, proc in retiring:
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            if proc.stdout:
                proc.stdout.close()
        for lk in retiring_links:
            lk.stop()
        for rank in range(plan.new_num_servers):
            _UP.labels(rank=rank).set(1)
        for rank in range(plan.new_num_servers, old_count):
            _UP.labels(rank=rank).set(0)
        _MEMBERSHIP_SERVERS.set(self.num_servers)

    def alive(self) -> list[bool]:
        """Process-level liveness, one flag per server rank."""
        return [p.poll() is None for p in self.procs]

    def health(self, *, timeout_ms: int = 2000) -> list[dict]:
        """Protocol-level health: per-server kStats counters, probed over
        a dedicated short-lived connection (safe while the sync barrier
        is wedged — stats replies are never deferred).  This is the
        failure-detection hook the reference lacks (SURVEY.md §5.3: its
        only outcome for a dead worker is an eternal deadlock)."""
        from distlr_tpu.ps.client import KVWorker  # noqa: PLC0415  (cycle)

        # direct_hosts: a health probe is control-plane — it must
        # diagnose an injected partition (via the workers' counters),
        # not time out inside it
        with KVWorker(self.direct_hosts, self.dim, client_id=0xFFFF,
                      timeout_ms=timeout_ms) as probe:
            # every kStats read mirrors the native counters into the
            # registry (KVWorker.stats): the server process has no scrape
            # surface of its own, so a health probe doubles as its
            # exporter (total_pushes/total_pulls/pending_sync_pushes/...)
            return [probe.stats(rank) for rank in range(self.num_servers)]

    def global_pushes(self, *, timeout_ms: int = 2000) -> float:
        """Server-side view of the group's monotonic push clock (see
        :meth:`distlr_tpu.ps.client.KVWorker.global_pushes`): mean
        ``total_pushes`` across ranks, probed over a dedicated
        connection.  The probe doubles as a ``health()`` cycle, so the
        ``distlr_ps_server_stat`` gauges refresh too."""
        stats = self.health(timeout_ms=timeout_ms)
        return sum(s["total_pushes"] for s in stats) / max(len(stats), 1)

    def wait(self) -> None:
        """Block until every server process of the CURRENT layout exits
        — they do after a client's ``shutdown_servers()``.  This is the
        foreground mode ``launch ps-server`` uses on a dedicated server
        host.  A Ctrl-C propagates (the context manager tears the group
        down) so an interrupted run stays distinguishable from a clean
        one.  Elastic groups swap the process list mid-wait
        (commit_resize): a RETIRED rank's exit must not end the wait,
        so the loop re-checks whether the layout moved under it and
        waits the new ranks too.  Respawns (supervisor, or the ps-ctl
        RESTORE verb) replace list ELEMENTS in place instead — so the
        loop also re-checks liveness of the current processes before
        concluding the group is done."""
        while True:
            snapshot = self.procs
            for p in list(snapshot):
                p.wait()
            if self.procs is not snapshot:
                continue  # resized mid-wait: wait the new layout too
            with self._lock:
                if self._stopped or all(p.poll() is not None
                                        for p in self.procs):
                    return
            # an exited rank was respawned in place while we waited —
            # the group is still serving; go around again

    def stop(self) -> None:
        with self._lock:
            self._stopped = True
        if self.chaos is not None:
            self.chaos.stop()
            self.chaos = None
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            if p.stdout:
                p.stdout.close()
        for rank in range(len(self.procs)):
            _UP.labels(rank=rank).set(0)
        self.procs.clear()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


class ServerSupervisor:
    """Server-side crash recovery for ASYNC (Hogwild) groups: a daemon
    thread that snapshots the group's weights on an interval, polls
    process liveness, respawns dead ranks on their original ports
    (:meth:`ServerGroup.respawn`), and re-seeds each respawned rank's key
    slice from the latest snapshot via a forced keyed init push.

    This closes the server half of §5.3 failure recovery (the worker
    half — timeouts, kStats probes, in-place worker restarts — already
    exists): the reference's only outcome for ANY dead process is an
    eternal deadlock (``/root/reference/src/main.cc:67-78``, SURVEY.md
    §5.3).  Recovery semantics are Hogwild-grade by design: updates the
    dead rank absorbed after the last snapshot are lost (bounded by
    ``snapshot_interval``), which is the same staleness class async
    training already tolerates.  Sync (BSP) groups are REFUSED: a mid-round
    merge buffer and pending barrier votes cannot be reconstructed — the
    sync recovery path is job-level ``checkpoint_dir`` + ``resume``.

    Workers riding the group still see one failed op per server death
    (their TCP stream to the old process breaks); pair the supervisor
    with ``run_ps_workers(..., max_restarts>0)`` so those workers rejoin
    — the SIGKILL test in ``tests/test_ps_robustness.py`` exercises the
    combination end-to-end.
    """

    def __init__(self, group: ServerGroup, *, poll_interval: float = 0.2,
                 snapshot_interval: float = 1.0, max_respawns: int = 3,
                 timeout_ms: int = 5000):
        if group._args["sync"]:
            raise ValueError(
                "ServerSupervisor supports async groups only: a sync "
                "server's mid-round BSP merge state cannot be "
                "reconstructed — use checkpoint_dir + resume for sync runs"
            )
        self._group = group
        self._poll_interval = poll_interval
        self._snapshot_interval = snapshot_interval
        self._max_respawns = max_respawns
        self._timeout_ms = timeout_ms
        # Keyed rolling snapshot: one full-dim buffer, but captured and
        # tracked PER KEY RANGE (valid flag, last-seen push counter,
        # capture time per rank).  A range whose server-side
        # total_pushes counter hasn't moved since its last capture is
        # skipped — no pull, no bytes — so snapshot cost scales with
        # write traffic, not key-space size (a full-vector pull per
        # interval is 4 MB at D=1M but quadratically painful at the
        # key-space sizes keyed PS exists for).
        self._snapshot: np.ndarray | None = None
        self._snapshot_at = 0.0
        self._snap_valid = [False] * group.num_servers
        self._snap_pushes = [-1] * group.num_servers
        self._snap_at = [0.0] * group.num_servers
        # FTRL groups: the z/n per-coordinate accumulators ride the same
        # rolling snapshot (pulled via kOptState next to each weight
        # capture) and are restored on re-seed — without them a
        # respawned FTRL rank silently degrades to a warm restart: its
        # per-coordinate learning rates reset to the aggressive t=0
        # schedule and every L1 dual is forgotten.
        self._ftrl = group.has_ftrl
        self._opt_z: np.ndarray | None = None
        self._opt_n: np.ndarray | None = None
        self._respawns = [0] * group.num_servers
        self._needs_reseed: set[int] = set()
        self._stop = threading.Event()
        # elastic resize coordination: while paused the loop idles (a
        # retiring rank's exit must not read as a crash, and respawn
        # must not race commit_resize's procs swap)
        self._paused = threading.Event()
        self._thread: threading.Thread | None = None
        #: (monotonic time, rank, event) audit trail — "respawned",
        #: "reseeded", "seeded-zeros", "gave-up", "respawn-failed";
        #: durable-store groups add "reseeded-from-store" (disk state
        #: at least as new as the RAM snapshot — re-seed skipped),
        #: "store-stale" (RAM newer; re-seeded over the disk recovery)
        #: and "store-corrupt-fallback" (a snapshot generation was
        #: rejected; recovery used the surviving generation/WAL)
        self.events: list[tuple[float, int, str]] = []

    def _record_event(self, when: float, rank: int, event: str) -> None:
        self.events.append((when, rank, event))
        _SUP_EVENTS.labels(event=event).inc()

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "ServerSupervisor":
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="ps-server-supervisor")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def pause(self) -> None:
        """Idle the supervision loop (elastic resize window): retiring
        ranks' exits must not respawn, and the procs/ranges swap must
        not race a poll cycle.  In-flight cycles finish first — calls
        only return semantics, the loop checks per cycle."""
        self._paused.set()

    def resume(self) -> None:
        self._paused.clear()

    def reset_layout(self) -> None:
        """Re-bind to the group's CURRENT layout after a resize: per-
        rank snapshot/respawn state re-initializes (every range must be
        re-captured — rank ids now mean different key slices), the
        full-dim snapshot buffer survives (dim never changes)."""
        n = self._group.num_servers
        self._snap_valid = [False] * n
        self._snap_pushes = [-1] * n
        self._snap_at = [0.0] * n
        self._respawns = [0] * n
        self._needs_reseed.clear()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- internals --------------------------------------------------------
    def _probe_rank(self, rank: int):
        from distlr_tpu.ps.client import KVWorker  # noqa: PLC0415  (cycle)

        # A fresh SINGLE-RANK connection per use: the supervisor's ops
        # must not share a stream with anything, a server death poisons
        # open streams, and — critically — per-rank connections keep
        # every rank's snapshot/reseed independent.  A group-wide
        # connection would make one dead rank fail the whole cycle and
        # silently freeze the HEALTHY ranks' slices, unbounding the
        # advertised snapshot_interval loss guarantee.  The server
        # stores its range rebased to local keys, so a 1-host client of
        # dim (hi-lo) addresses exactly that slice.
        lo, hi = self._group.key_range(rank)
        host = f"127.0.0.1:{self._group.ports[rank]}"
        return KVWorker(host, hi - lo, client_id=0xFFFE,
                        timeout_ms=self._timeout_ms, sync_group=False)

    def _try_snapshot(self) -> None:
        with _SNAPSHOT_SECONDS.time():
            self._try_snapshot_inner()

    def _try_snapshot_inner(self) -> None:
        if self._snapshot is None:
            self._snapshot = np.zeros(self._group.dim, np.float32)
        if self._ftrl and self._opt_z is None:
            self._opt_z = np.zeros(self._group.dim, np.float32)
            self._opt_n = np.zeros(self._group.dim, np.float32)
        for r in range(self._group.num_servers):
            # a capture is as old as its first read: pushes that land
            # while it is pulled are in it or not, so stamping it after
            # the pull would promise updates the slice may not hold
            began = time.monotonic()
            try:
                with self._probe_rank(r) as kv:
                    # An UNINITIALIZED server serves zeros from
                    # HandlePull; a snapshot taken before this rank's
                    # init (worker push or supervisor re-seed) would
                    # become "authoritative" and a crash within
                    # snapshot_interval would re-seed zeros over real
                    # (possibly checkpoint-restored) weights.
                    s = kv.stats(0, rank=r)
                    if not s["initialized"]:
                        continue
                    if (self._snap_valid[r]
                            and s["total_pushes"] == self._snap_pushes[r]):
                        # untouched since its last capture: the stored
                        # slice is still the live state — refresh its
                        # timestamp without moving any bytes
                        self._snap_at[r] = began
                        continue
                    vals = kv.pull()
                    lo, hi = self._group.key_range(r)
                    self._snapshot[lo:hi] = vals
                    if self._ftrl:
                        # same cycle, not atomic with the weight pull:
                        # updates landing between the two pulls make z/n
                        # marginally newer than w — FTRL re-derives w
                        # from z on the next touch of each coordinate,
                        # so the inconsistency self-heals per coordinate
                        # (the same bounded-staleness class the
                        # snapshot itself already accepts)
                        from distlr_tpu.ps.client import PSRejectedError  # noqa: PLC0415

                        try:
                            z, n = kv.pull_opt_state()
                        except PSRejectedError:
                            # has_ftrl is GROUP-wide; an opt_segments
                            # rank hosting no FTRL slice rejects the op
                            # — its weights capture above still counts
                            # (a generic except here would invalidate
                            # the whole rank and zero-reseed its slice
                            # on every crash)
                            pass
                        else:
                            self._opt_z[lo:hi] = z
                            self._opt_n[lo:hi] = n
                    # The counter was read BEFORE the pull, so it may
                    # undercount what the pull captured — the safe
                    # direction (worst case: one redundant re-pull next
                    # cycle, never a stale slice treated as current).
                    self._snap_pushes[r] = s["total_pushes"]
                    self._snap_valid[r] = True
                    self._snap_at[r] = began
            except Exception:
                # this rank is down or wedged; the respawn pass handles
                # it — its previously captured slice stays authoritative,
                # and OTHER ranks' captures proceed regardless
                continue
        self._snapshot_at = time.monotonic()
        self._refresh_store_metrics()

    def _refresh_store_metrics(self) -> None:
        """Mirror each rank's on-disk store health into the registry
        (``distlr_ps_store_*``) — piggybacks on the snapshot cadence so
        the scan cost rides an interval that already exists."""
        if not self._group._args["store_dir"]:
            return
        from distlr_tpu.ps import store as ps_store  # noqa: PLC0415

        now = time.time()
        for r in range(self._group.num_servers):
            try:
                rs = ps_store.scan_rank(self._group.store_rank_dir(r))
            except OSError:
                continue
            best = rs.best
            if best is not None:
                _STORE_SNAPSHOT_AGE.labels(rank=r).set(
                    max(0.0, now - best.wall_time))
            _STORE_BYTES.labels(rank=r, kind="snapshot").set(
                rs.snapshot_bytes)
            _STORE_BYTES.labels(rank=r, kind="wal").set(rs.wal_bytes)
            _STORE_WAL_LAG.labels(rank=r).set(
                max(0, rs.recovered_clock - rs.snapshot_clock))
            _STORE_CORRUPT.labels(rank=r).set(rs.corrupt)

    def _reseed(self, rank: int) -> bool:
        lo, hi = self._group.key_range(rank)
        if self._group._args["store_dir"]:
            # The respawned process already self-recovered from its
            # on-disk store (LoadStore runs before the PORT announce).
            # Pushing the RAM snapshot over it would REGRESS the rank
            # whenever the disk is newer — which it usually is: the
            # native store interval plus the WAL beat the supervisor's
            # pull-based capture.  Prefer whichever clock is ahead.
            from distlr_tpu.ps import store as ps_store  # noqa: PLC0415

            rs = ps_store.scan_rank(self._group.store_rank_dir(rank))
            now = time.monotonic()
            if rs.corrupt:
                # a generation was rejected (torn/corrupt) — recovery
                # still proceeded from the surviving generation/WAL,
                # but the fallback must be LOUD, never silent
                self._record_event(now, rank, "store-corrupt-fallback")
            disk_clock = rs.recovered_clock
            best = rs.best
            has_disk = disk_clock > 0 or (best is not None
                                          and best.initialized)
            ram_clock = (self._snap_pushes[rank]
                         if self._snap_valid[rank] else -1)
            if has_disk and disk_clock >= ram_clock:
                self._record_event(now, rank, "reseeded-from-store")
                log.warning(
                    "supervisor: server %d recovered from its store "
                    "(push_clock=%d >= RAM snapshot %d); skipping re-seed",
                    rank, disk_clock, ram_clock)
                # force the next snapshot cycle to re-pull this range
                self._snap_pushes[rank] = -1
                return True
            if has_disk:
                # disk exists but the RAM snapshot is ahead (e.g. a very
                # long store interval): reseed below, audited
                self._record_event(now, rank, "store-stale")
        if self._snapshot is not None and self._snap_valid[rank]:
            vals, event = self._snapshot[lo:hi], "reseeded"
        else:
            # died before the first snapshot: zeros keep the server
            # *initialized* (pulls return a defined value) even though
            # the slice's training progress is lost
            vals, event = np.zeros(hi - lo, np.float32), "seeded-zeros"
        try:
            with self._probe_rank(rank) as kv:
                kv.push_init(vals, force=True)
                if self._ftrl and self._snap_valid[rank]:
                    from distlr_tpu.ps.client import PSRejectedError  # noqa: PLC0415

                    # restore the FTRL accumulators captured with this
                    # slice — the respawn keeps its per-coordinate
                    # learning-rate schedule and L1 duals instead of
                    # degrading to a warm restart.  (seeded-zeros case:
                    # a fresh server's z/n are already zeros.)
                    try:
                        kv.push_init_opt_state(self._opt_z[lo:hi],
                                               self._opt_n[lo:hi],
                                               force=True)
                    except PSRejectedError:
                        pass  # opt_segments rank with no FTRL slice
        except Exception as e:
            # retried next poll (_needs_reseed): an unseeded-but-alive
            # server would otherwise install the first gradient push AS
            # the weights (the server's first-push-init branch)
            log.warning("supervisor: re-seed of server %d failed: %s", rank, e)
            return False
        self._record_event(time.monotonic(), rank, event)
        # The respawned process restarted its push counter; forget the
        # old count so the next snapshot cycle always re-pulls this range
        # (a coincidental count match must not skip it).
        self._snap_pushes[rank] = -1
        return True

    def _run(self) -> None:
        # eager first snapshot so an early death has something to restore
        self._try_snapshot()
        while not self._stop.wait(self._poll_interval):
            now = time.monotonic()
            if self._paused.is_set():
                # elastic resize in flight: the coordinator owns the
                # group until resume() — see pause()
                continue
            if self._group._stopped:
                # intentional teardown (group.stop(), e.g. run_ps_workers'
                # on_error): SIGTERMed ranks exit nonzero but are not
                # crashes — respawning/logging here would burn the budget
                # and emit spurious gave-up errors during shutdown
                continue
            procs = list(self._group.procs)
            if not procs or all(p.poll() == 0 for p in procs):
                # group retired (or torn down): every process exited
                # voluntarily — rank 0's shutdown_servers at the end of a
                # clean run, NOT a crash.  Respawning here would misread
                # the job's own shutdown as a failure and spin up
                # uninitialized servers on the old ports.
                continue
            dead = [
                r for r, p in enumerate(procs)
                if p.poll() is not None and p.returncode != 0
            ]
            for r in dead:
                # mark down at DETECTION: a gave-up or respawn-failed
                # rank must scrape as 0, not hold the spawn-time 1 —
                # this gauge exists to signal exactly that outage
                # (_spawn sets it back to 1 on a successful respawn)
                _UP.labels(rank=r).set(0)
            for rank in list(self._needs_reseed):
                # a previously-respawned rank whose re-seed failed (e.g. a
                # second rank was still down, so the probe could not
                # connect): alive but uninitialized — retry until seeded
                if rank not in dead and self._reseed(rank):
                    self._needs_reseed.discard(rank)
            for rank in dead:
                if self._respawns[rank] >= self._max_respawns:
                    if not any(
                        r == rank and ev == "gave-up" for _, r, ev in self.events
                    ):
                        log.error("supervisor: server %d exceeded %d respawns; "
                                  "leaving it down", rank, self._max_respawns)
                        self._record_event(now, rank, "gave-up")
                    continue
                self._respawns[rank] += 1
                try:
                    if not self._group.respawn(rank):
                        continue  # torn down, or raced a still-alive rank
                except RuntimeError as e:  # spawn failure / stolen port
                    log.warning("supervisor: respawn of server %d failed: %s",
                                rank, e)
                    self._record_event(now, rank, "respawn-failed")
                    continue
                log.warning("supervisor: server %d died; respawned (%d/%d)",
                            rank, self._respawns[rank], self._max_respawns)
                self._record_event(now, rank, "respawned")
                if not self._reseed(rank):
                    self._needs_reseed.add(rank)
            if now - self._snapshot_at >= self._snapshot_interval:
                # Runs even while some rank is dead or awaiting re-seed:
                # captures are per-rank (dead -> connect fails, skipped;
                # respawned-but-unseeded -> uninitialized, skipped), so a
                # crashed or given-up rank must not freeze the healthy
                # ranks' slices — that would quietly unbound the
                # snapshot_interval loss guarantee.
                self._try_snapshot()
