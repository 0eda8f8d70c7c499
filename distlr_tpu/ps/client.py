"""Python KV worker — ctypes binding over the native client library.

API mirror of ps-lite's ``KVWorker<float>`` as used by the reference
(``Push``/``Pull``/``Wait``, call sites ``src/lr.cc:116-132``,
``src/main.cc:135-148``), so the async/PS training loop reads like the
reference worker while the gradient math runs in JAX on the chip.
"""

from __future__ import annotations

import contextlib
import ctypes
import dataclasses
import random
import time
import weakref

import numpy as np

from distlr_tpu.obs import dtrace
from distlr_tpu.obs.registry import family_total, get_registry
from distlr_tpu.obs.tracing import get_tracer
from distlr_tpu.ps import wire
from distlr_tpu.ps.build import build_native, client_lib
from distlr_tpu.utils.logging import get_logger

log = get_logger(__name__)

_lib = None

_reg = get_registry()
#: Per-op wall latency of the blocking native client calls.  In sync mode
#: a push's latency INCLUDES the BSP barrier wait (the deferred reply is
#: the barrier), which is exactly what a straggler investigation needs.
_OP_SECONDS = _reg.histogram(
    "distlr_ps_client_op_seconds", "wall seconds per native KV op",
    labelnames=("op",),
)
_OPS_TOTAL = _reg.counter(
    "distlr_ps_client_ops_total", "native KV ops by outcome",
    labelnames=("op", "status"),
)
_BYTES_TOTAL = _reg.counter(
    "distlr_ps_client_bytes_total",
    "key+value payload bytes moved by native KV ops",
    labelnames=("op", "direction"),
)
_DENSE_FRAMES = _reg.counter(
    "distlr_ps_dense_frames_total",
    "default-key (keys=None) ops that succeeded, by how the dense key "
    "space crossed the wire: rows = runs of vals_per_key values under "
    "one u64 row key, flat = one u64 key beside every value (no "
    "vals_per_key divides dim and the handle's range boundaries)",
    labelnames=("op", "encoding"),
)
_KEY_FRAMES = _reg.counter(
    "distlr_ps_client_key_frames_total",
    "explicit-key ops that succeeded, by what the op did with its keys "
    "before the native call: held = nothing (the very array "
    "KVWorker.hold checked once and this connection keeps read-only, "
    "used over the row space it was checked against), checked = "
    "KVWorker._validate_keys' passes over them (a caller's own array, a "
    "copy of a held one, one made writeable again or held at another "
    "vals_per_key)",
    labelnames=("op", "keys"),
)
_PAYLOAD_FRAMES = _reg.counter(
    "distlr_ps_payload_frames_total",
    "value-carrying frames (one a server) of keyed ops that succeeded, "
    "by how the float32 values crossed: mapped = in the connection's "
    "shared mapping (a same-host server, connected directly, slices of "
    "64 KiB or more), inline = on the socket (a proxy or another host "
    "in between, an older server, coded, opt-state and small frames)",
    labelnames=("op", "carrier"),
)
_CHUNKED_PULLS = _reg.counter(
    "distlr_ps_client_chunked_pulls_total",
    "pull_chunked calls (serving-tier bounded reads)",
)
_CHUNKS = _reg.counter(
    "distlr_ps_client_chunks_total",
    "individual bounded pull ops issued by pull_chunked",
)
_RETRIES = _reg.counter(
    "distlr_ps_retries_total",
    "KV ops re-issued in place after a transient transport failure "
    "(RetryPolicy path: reconnect + re-issue, no process restart)",
    labelnames=("op",),
)
_RECONNECTS = _reg.counter(
    "distlr_ps_reconnects_total",
    "native KV connections rebuilt in place (KVWorker.reconnect)",
)
_PUSH_UNKNOWN = _reg.counter(
    "distlr_ps_push_outcome_unknown_total",
    "gradient pushes whose delivery could not be determined after a "
    "transport failure — counted and absorbed (the Hogwild staleness "
    "class), NEVER re-issued (a maybe-applied push re-issued is a "
    "silent double-apply)",
)
_REROUTES = _reg.counter(
    "distlr_membership_reroutes_total",
    "client routing re-negotiations after an epoch fence (the group "
    "layout changed mid-run: layout re-fetched from the membership "
    "coordinator, handle rebuilt against the new ranks — no restart)",
)
_EPOCH_MISMATCHES = _reg.counter(
    "distlr_membership_epoch_mismatches_total",
    "KV ops bounced by a server's membership-epoch fence (each one "
    "triggers a routing re-negotiation, or — for a gradient push whose "
    "frames already left — an absorbed unknown-outcome push)",
)
_CLIENT_EPOCH = _reg.gauge(
    "distlr_membership_client_epoch",
    "membership epoch this process's most recently (re)connected "
    "epoch-announced KV client is at (0 = no epoch announced)",
)
#: Push-byte accounting (ISSUE 7): raw = what the same frame costs as
#: f32 with no codec (the key frame AS SENT + 4 bytes/value — so the
#: ratio is the codec's own saving; the bytes a dense op's row keys save
#: are an uncompressed push's too), wire = what actually left the kernel
#: (headers + keys + coded payload, summed over servers).  Both count
#: DELIVERED pushes exactly once: a failed attempt contributes nothing,
#: its successful re-issue counts once, and an absorbed unknown-outcome
#: push counts zero — so the ratio can never be inflated by retries.
_PUSH_RAW = _reg.counter(
    "distlr_ps_push_bytes_raw_total",
    "dense-f32-equivalent bytes of delivered gradient pushes "
    "(what the same pushes would have cost uncompressed)",
)
_PUSH_WIRE = _reg.counter(
    "distlr_ps_push_bytes_wire_total",
    "actual wire bytes of delivered gradient pushes "
    "(headers + keys + coded value payload)",
)
_COMPRESS_RATIO = _reg.gauge(
    "distlr_ps_push_compress_ratio",
    "cumulative push-byte compression ratio raw/wire (1.0-ish = dense "
    "f32; the codec x accumulation win reads directly off this gauge)",
)


def _compress_ratio() -> float:
    # derived from the counters themselves, when the gauge is read — no
    # shadow totals to drift if the registry is ever reset or the counters
    # relabeled, and nothing for a push to refresh
    wire_total = family_total("distlr_ps_push_bytes_wire_total")
    if wire_total > 0:
        return family_total("distlr_ps_push_bytes_raw_total") / wire_total
    return 0.0


_COMPRESS_RATIO.set_function(_compress_ratio)


def _op_failed(op: str, exc: BaseException) -> None:
    """An op that raised, by outcome.  Timeouts are distinguished from
    hard failures (a wedged barrier vs a dead peer read very differently
    on a dashboard)."""
    status = "timeout" if isinstance(exc, PSTimeoutError) else "error"
    _OPS_TOTAL.labels(op=op, status=status).inc()


@contextlib.contextmanager
def _observe_op(op: str, *, sent: int = 0, received: int = 0):
    """Record one op's latency, outcome, and payload bytes: the ops that
    are no keyed exchange (a barrier vote, the opt-state pair).  A keyed
    op's are :meth:`KVWorker._keyed`'s."""
    t0 = time.perf_counter()
    try:
        yield
    except Exception as e:
        _op_failed(op, e)
        raise
    _OP_SECONDS.labels(op=op).observe(time.perf_counter() - t0)
    _OPS_TOTAL.labels(op=op, status="ok").inc()
    if sent:
        _BYTES_TOTAL.labels(op=op, direction="sent").inc(sent)
    if received:
        _BYTES_TOTAL.labels(op=op, direction="received").inc(received)


class _OpAccount:
    """One handle's shares of one keyed op's series: registry cells
    (``obs/registry.py``), bound at the handle's first use of each and
    updated with no lock as the op returns.  A ``KVWorker`` is used by
    one thread at a time, so a share has one writer; W lock-step workers
    whose pushes are answered at the same instant each count into their
    own, and a read of the series (a scrape, ``family_total``, ``value``)
    counts every live share in.  A failed op counts nothing here
    (:func:`_op_failed`)."""

    #: share -> (family, its labels beside ``op``; None: no labels)
    SHARES = {
        "seconds": (_OP_SECONDS, {}),
        "ok": (_OPS_TOTAL, {"status": "ok"}),
        "rows": (_DENSE_FRAMES, {"encoding": "rows"}),
        "flat": (_DENSE_FRAMES, {"encoding": "flat"}),
        "held": (_KEY_FRAMES, {"keys": "held"}),
        "checked": (_KEY_FRAMES, {"keys": "checked"}),
        "sent": (_BYTES_TOTAL, {"direction": "sent"}),
        "received": (_BYTES_TOTAL, {"direction": "received"}),
        "mapped": (_PAYLOAD_FRAMES, {"carrier": "mapped"}),
        "inline": (_PAYLOAD_FRAMES, {"carrier": "inline"}),
        "raw": (_PUSH_RAW, None),
        "wire": (_PUSH_WIRE, None),
    }

    def __init__(self, op: str):
        self.op = op
        self.bound: list = []

    def __getattr__(self, share: str):
        # a share's first use (later ones find the attribute)
        try:
            family, labels = self.SHARES[share]
        except KeyError:
            raise AttributeError(share) from None
        series = (family._default() if labels is None
                  else family.labels(op=self.op, **labels))
        cell = series.cell()
        self.bound.append(cell)
        setattr(self, share, cell)
        return cell


def _retire_accounts(accounts: dict) -> None:
    """A handle that is gone: its shares are the series' own counts."""
    for account in accounts.values():
        for cell in account.bound:
            cell.retired = True


#: the third of what :meth:`KVWorker._resolve_keys` gives, which is also
#: the share of :class:`_OpAccount` that counts the op, says how its keys
#: came: the default key set in one of these two encodings, or a caller's
#: own keys, ``"held"`` or ``"checked"``
_DENSE_ENCODINGS = ("rows", "flat")

#: a keyed op's six phases (:meth:`KVWorker._record_op`)
_XCHG = ("xchg_enter", "xchg_send", "xchg_await", "xchg_recv", "xchg_wake",
         "xchg_account")

#: Order of the counters a server stats probe returns (kv_protocol.h).
#: The ``cpu_*`` tail is the continuous-profiling extension: cumulative
#: per-handler THREAD CPU seconds (CLOCK_THREAD_CPUTIME_ID around each
#: dispatch) — fractional, so every ``*_seconds`` counter stays a float
#: in the stats dict while the others stay ints.  A pre-extension server
#: replies only the first six, one from before the BSP tail eleven, one
#: from before ``run_frames`` fifteen, one from before
#: ``lock_wait_seconds`` sixteen, one from before the release's fan-out
#: seventeen, one from before a push's phases nineteen, one from before
#: ``mapped_frames`` twenty-four, one from before ``ftrl_steps``
#: twenty-five, one from before ``ftrl_packed_steps`` twenty-seven; the
#: probe reports what arrived.
STATS_FIELDS = (
    "dim",
    "initialized",
    "pending_sync_pushes",
    "barrier_waiters",
    "total_pushes",
    "total_pulls",
    "cpu_push_seconds",
    "cpu_pull_seconds",
    "cpu_stats_seconds",
    "cpu_barrier_seconds",
    # the membership round's additive slot: this rank's layout epoch
    # (kv_protocol.h kEpoch) — a probe of a migrating group reads the
    # flip rank by rank
    "epoch",
    # the BSP barrier's additive tail (zeros from an async server):
    # rounds released; seconds released pushes were held, arrival to own
    # reply; seconds from a round's first arrival to its last; thread-CPU
    # seconds of the release, its writers' included (also inside
    # cpu_push_seconds)
    "sync_rounds",
    "sync_hold_seconds",
    "sync_spread_seconds",
    "cpu_release_seconds",
    # of the operations total_pushes and total_pulls count, those whose
    # keys were one ascending consecutive run, handled as the range of
    # slots it is (a fused push-pull stands in both totals, so twice
    # here): every default-key op of a dense worker, no scattered frame
    "run_frames",
    # wall seconds the push handlers stood waiting for the server's one
    # lock, over total_pushes: near zero where pushes arrive apart, what
    # a merge pays first where W workers push at the same instant
    "lock_wait_seconds",
    # the BSP release's fan-out (zeros from an async server): a round's
    # value-carrying replies are written side by side, one thread each;
    # those written by a thread other than the releasing one (W - 1 a
    # round of W fused pushes, 0 for header-only rounds), and the wall
    # seconds of the releases, last merge done to last reply written
    "release_fanned_replies",
    "release_wall_seconds",
    # a push's life on the server, phase by phase: wall seconds on the
    # monotonic clock (this host's perf_counter too).  Over
    # total_pushes: header read to keys and values read and decoded;
    # the lock held to the push's own arithmetic done (the BSP merge;
    # an async push's apply and its reply's copy).  Over the pushes of
    # released rounds: merge done to the round's release begun, the wait
    # for the later arrivals (0 for the last voter).  Over sync_rounds:
    # the release's begin to the mean applied and the merge cleared.
    # Over value-carrying replies: one reply's write begun to written,
    # whichever thread wrote it.  An async server reads zeros in
    # sync_wait_seconds and release_apply_seconds.
    "recv_seconds",
    "merge_seconds",
    "sync_wait_seconds",
    "release_apply_seconds",
    "reply_write_seconds",
    # of the operations total_pushes and total_pulls count, those whose
    # values crossed in their connection's shared mapping and not through
    # the socket (kv_protocol.h kCodecMapped; a fused push-pull twice, as
    # in run_frames): over the rise of the two totals, the share of a
    # job's value-carrying traffic that stayed out of the kernel
    "mapped_frames",
    # where the per-coordinate FTRL-Proximal step runs (an async push's
    # apply, a BSP release's apply of the round's mean; zeros from a
    # server with no FTRL coordinate): the coordinates a step ran on (a
    # zero gradient entry steps nothing and counts nothing), and of
    # those the steps whose |z| <= l1 branch left the weight exactly 0.0
    "ftrl_steps",
    "ftrl_zeroed",
    # of ftrl_steps, those an async keyed push of single-value rows took
    # four at a time (kv_loops.h FtrlStepPacked: a group of four keys
    # strictly ascending, no zero entry, one optimizer); over ftrl_steps,
    # the share of the rule's work done four lanes wide
    "ftrl_packed_steps",
)

#: kStats counters of the native servers, refreshed by every kStats read
#: (:func:`mirror_server_stats`: the native process cannot scrape itself
#: — the Python side mirrors its protocol counters into the registry).
#: Every name of STATS_FIELDS has a series here, and but for the
#: handlers' ``cpu_*`` this one only.
_SERVER_STAT = _reg.gauge(
    "distlr_ps_server_stat",
    "latest kStats read (a health probe, or any KVWorker.stats call of "
    "this process) of each native server counter, the stat label one of "
    "STATS_FIELDS; ftrl_steps and ftrl_zeroed: the coordinates the "
    "server's FTRL-Proximal step ran on, and of those the steps that "
    "left the weight exactly 0.0 (zeros from a server with no FTRL "
    "coordinate); its newest, ftrl_packed_steps: of ftrl_steps, those "
    "an asynchronous keyed push took four coordinates at a time",
    labelnames=("rank", "stat"),
)
#: Per-handler thread-CPU seconds of the native server ranks, mirrored
#: from the kStats CPU extension by every kStats read — the series a
#: fleet flamegraph's Python edge lines up against the C++ side with.
_SERVER_CPU = _reg.gauge(
    "distlr_kv_server_cpu_seconds",
    "cumulative per-handler thread CPU seconds inside the native KV "
    "server (CLOCK_THREAD_CPUTIME_ID around each dispatch: payload "
    "read + decode + apply, never socket wait), from the latest "
    "health probe",
    labelnames=("rank", "handler"),
)
#: one rank's gauge children, looked up once: a worker's staleness probe
#: reads kStats tens of times a second
_MIRRORED: dict[int, list] = {}


def mirror_server_stats(rank: int, stats: dict) -> None:
    """One kStats reply into the registry, where it was parsed: every
    counter as ``distlr_ps_server_stat{rank, stat}``, a handler's
    ``cpu_*`` also as ``distlr_kv_server_cpu_seconds{rank, handler}``
    (``cpu_release_seconds`` is no handler: the release's cycles are
    inside the push's).  The one function behind :meth:`KVWorker.stats`,
    and so behind ``ServerGroup.health()``."""
    children = _MIRRORED.get(rank)
    if children is None:
        children = _MIRRORED[rank] = []
        for name in STATS_FIELDS:
            mine = [_SERVER_STAT.labels(rank=rank, stat=name)]
            if (name.startswith("cpu_") and name.endswith("_seconds")
                    and name != "cpu_release_seconds"):
                mine.append(_SERVER_CPU.labels(
                    rank=rank, handler=name[len("cpu_"):-len("_seconds")]))
            children.append((name, mine))
    for name, mine in children:
        val = stats.get(name)
        if val is not None:
            for child in mine:
                child.set(val)


# The field list IS a wire mirror: its length must track kStatsVals and
# its v1 prefix kStatsValsV1 (distlr_tpu.ps.wire, lint-checked against
# the header) — the exact drift class that pinned kStats lengths wrong
# in earlier rounds.
assert len(STATS_FIELDS) == wire.STATS_VALS
assert STATS_FIELDS[wire.STATS_VALS_V1 - 1] == "total_pulls"


class PSTimeoutError(TimeoutError):
    """A KV op hit the receive timeout — in sync mode, the named
    straggler failure: a dead/slow worker holding the BSP barrier
    (SURVEY.md §5.3; the reference deadlocks forever here)."""


class PSRejectedError(OSError):
    """The server answered an explicit kError rejection: the op is
    unsupported for its configuration (e.g. an FTRL opt-state op
    against an sgd server) — deterministic, so the retry driver
    raises it immediately instead of burning its attempt/deadline
    budget re-issuing an op that can never succeed."""


class PSEpochError(OSError):
    """A server's membership-epoch fence bounced the op: the group
    layout this client routed by is stale (ranks joined or retired —
    kv_protocol.h kEpoch).  Unlike :class:`PSRejectedError` this is
    transient BY DESIGN: re-fetch the layout from the membership
    coordinator, reconnect, and the op is legal again.  A client built
    with a ``route`` provider handles it automatically; ``epoch`` is
    the epoch the server reported."""

    def __init__(self, msg: str, epoch: int = 0):
        super().__init__(msg)
        self.epoch = int(epoch)


class FaultRateTracker:
    """Sliding-window transport-fault counter -> adaptive backoff scale.

    A static backoff base is tuned for the QUIET network: under a fault
    storm (a flapping switch, a long partition's edge) every worker
    re-hammers the servers at the same quiet-network cadence, which both
    prolongs the storm and burns retry budget.  This tracker observes
    the worker's own recent transport faults and scales the policy's
    backoff BASE linearly with the fault count in the window —
    ``1 + 0.5 * faults``, capped at ``max_scale`` — so a noisy period
    automatically backs off harder and a quiet one decays back to the
    configured base as old faults age out.  The scaled base still
    respects the policy's ``backoff_max_ms`` cap.
    """

    def __init__(self, window_s: float = 30.0, max_scale: float = 8.0):
        if window_s <= 0:
            raise ValueError(f"window_s must be positive, got {window_s}")
        if max_scale < 1.0:
            raise ValueError(f"max_scale must be >= 1, got {max_scale}")
        self.window_s = float(window_s)
        self.max_scale = float(max_scale)
        self._faults: list[float] = []

    def _prune(self, now: float) -> None:
        cutoff = now - self.window_s
        # faults append in time order, so the stale prefix is contiguous
        drop = 0
        for t in self._faults:
            if t >= cutoff:
                break
            drop += 1
        if drop:
            del self._faults[:drop]

    def record(self, now: float | None = None) -> None:
        """One observed transport fault (call at failure time)."""
        now = time.monotonic() if now is None else now
        self._prune(now)
        self._faults.append(now)

    def scale(self, now: float | None = None) -> float:
        """Current backoff-base multiplier in [1, max_scale]."""
        now = time.monotonic() if now is None else now
        self._prune(now)
        return min(self.max_scale, 1.0 + 0.5 * len(self._faults))


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """In-place recovery policy for transient KV transport faults.

    With a policy attached, a :class:`KVWorker` answers a reset, delay,
    or short partition by reconnecting the poisoned native handle and
    re-issuing the op — bounded attempts, jittered exponential backoff,
    and a per-op wall deadline — instead of surfacing the failure to the
    restart/resume ladder.  Only IDEMPOTENT ops are ever re-issued
    (pull, chunked/keyed pulls, stats, barrier votes — the server rolls
    a dead connection's vote out of the count, so a reconnect re-vote is
    exactly one live vote).  A gradient push is re-issued ONLY when the
    native client proves no byte of it reached any server's kernel
    (:func:`kv_op_delivery_began`); otherwise its outcome is unknown and
    it is counted in ``distlr_ps_push_outcome_unknown_total`` and
    absorbed — a retried pull / lost push is the same bounded-staleness
    class Hogwild training already tolerates (arXiv:1508.05711), while a
    double-applied gradient would silently bias the trajectory.

    Sync (BSP) pushes are NEVER retried regardless of policy: the
    deferred reply IS the barrier, and the timeout is the named
    straggler signal — retrying it would mix gradients across rounds.
    """

    #: total tries per op, including the first issue (>= 1)
    attempts: int = 4
    #: base of the exponential backoff between tries
    backoff_ms: float = 50.0
    #: backoff cap (jitter applies after the cap)
    backoff_max_ms: float = 2000.0
    #: +/- fraction of each backoff drawn uniformly (0 = fixed ladder)
    jitter: float = 0.2
    #: wall deadline per op across all tries; crossing it surfaces the
    #: last failure even when attempts remain
    deadline_s: float = 60.0
    #: RNG seed for the jitter draw (None = nondeterministic)
    seed: int | None = None
    #: Scale the backoff BASE by the observed recent fault rate
    #: (:class:`FaultRateTracker`) instead of keeping it static per run:
    #: a fault storm backs off up to ``adaptive_max_scale`` x harder
    #: (still capped by ``backoff_max_ms``), a quiet window decays back.
    adaptive: bool = False
    adaptive_window_s: float = 30.0
    adaptive_max_scale: float = 8.0

    def __post_init__(self):
        if self.attempts < 1:
            raise ValueError(f"attempts must be >= 1, got {self.attempts}")
        if self.backoff_ms < 0 or self.backoff_max_ms < self.backoff_ms:
            raise ValueError(
                "need 0 <= backoff_ms <= backoff_max_ms, got "
                f"{self.backoff_ms}/{self.backoff_max_ms}")
        if not 0.0 <= self.jitter < 1.0:
            raise ValueError(f"jitter must be in [0, 1), got {self.jitter}")
        if self.deadline_s <= 0:
            raise ValueError(
                f"deadline_s must be positive, got {self.deadline_s}")
        if self.adaptive_window_s <= 0:
            raise ValueError(
                f"adaptive_window_s must be positive, "
                f"got {self.adaptive_window_s}")
        if self.adaptive_max_scale < 1.0:
            raise ValueError(
                f"adaptive_max_scale must be >= 1, "
                f"got {self.adaptive_max_scale}")

    @classmethod
    def from_config(cls, cfg) -> "RetryPolicy | None":
        """The policy a :class:`~distlr_tpu.config.Config` asks for, or
        None when retries are off (``ps_retry_attempts == 0``) — the ONE
        construction every consumer (PS workers, the online trainer,
        serving pulls) shares, so a new knob like ``ps_retry_adaptive``
        reaches all of them at once."""
        if cfg.ps_retry_attempts <= 0:
            return None
        return cls(
            attempts=cfg.ps_retry_attempts,
            backoff_ms=cfg.ps_retry_backoff_ms,
            backoff_max_ms=cfg.ps_retry_backoff_max_ms,
            deadline_s=cfg.ps_retry_deadline_s,
            adaptive=bool(getattr(cfg, "ps_retry_adaptive", False)),
        )

    def backoff_s(self, retry_index: int, rng: random.Random,
                  scale: float = 1.0) -> float:
        """Sleep before re-issue number ``retry_index`` (0-based).
        ``scale`` multiplies the BASE (the adaptive fault-rate path);
        the ``backoff_max_ms`` cap applies after scaling, so adaptivity
        can saturate but never exceed the configured ceiling."""
        base = min(self.backoff_ms * scale * (2.0 ** retry_index),
                   self.backoff_max_ms)
        if self.jitter:
            base *= 1.0 + self.jitter * (2.0 * rng.random() - 1.0)
        return max(base, 0.0) / 1000.0


def _load():
    global _lib
    if _lib is None:
        build_native()
        lib = ctypes.CDLL(client_lib())
        lib.kv_connect.restype = ctypes.c_void_p
        lib.kv_connect.argtypes = [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint32]
        for name in ("kv_push_vpk", "kv_pull_vpk", "kv_push_pull_vpk"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_int
            fn.argtypes = (
                [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                 ctypes.c_uint64, ctypes.c_uint64]
                if name != "kv_push_pull_vpk" else
                [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                 ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64]
            )
        lib.kv_push_init_vpk.restype = ctypes.c_int
        lib.kv_push_init_vpk.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_uint64, ctypes.c_int, ctypes.c_uint64,
        ]
        lib.kv_barrier.restype = ctypes.c_int
        lib.kv_barrier.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
        lib.kv_wait.restype = ctypes.c_int
        lib.kv_wait.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.kv_shutdown_servers.restype = ctypes.c_int
        lib.kv_shutdown_servers.argtypes = [ctypes.c_void_p]
        lib.kv_set_timeout_ms.restype = ctypes.c_int
        lib.kv_set_timeout_ms.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.kv_set_push_visit_all.restype = ctypes.c_int
        lib.kv_set_push_visit_all.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.kv_timed_out.restype = ctypes.c_int
        lib.kv_timed_out.argtypes = [ctypes.c_void_p]
        lib.kv_op_rejected.restype = ctypes.c_int
        lib.kv_op_rejected.argtypes = [ctypes.c_void_p]
        lib.kv_op_delivery_began.restype = ctypes.c_int
        lib.kv_op_delivery_began.argtypes = [ctypes.c_void_p]
        lib.kv_negotiate_codec.restype = ctypes.c_int
        lib.kv_negotiate_codec.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.kv_negotiate_trace.restype = ctypes.c_int
        lib.kv_negotiate_trace.argtypes = [ctypes.c_void_p]
        lib.kv_set_trace.restype = ctypes.c_int
        lib.kv_set_trace.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64,
        ]
        lib.kv_clock_offset.restype = ctypes.c_double
        lib.kv_clock_offset.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
        # what a keyed op reads as it returns: a few words each, through
        # a handle that keeps the GIL (PyDLL), because releasing it for
        # nanoseconds, where W lock-step workers return at once, hands
        # it to a peer and stands in line for it again
        keeps_gil = ctypes.PyDLL(client_lib())
        lib.kv_last_wire_sent = keeps_gil.kv_last_wire_sent
        lib.kv_last_wire_sent.restype = ctypes.c_uint64
        lib.kv_last_wire_sent.argtypes = [ctypes.c_void_p]
        lib.kv_last_exchange = keeps_gil.kv_last_exchange
        lib.kv_last_exchange.restype = None
        lib.kv_last_exchange.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.kv_last_carried = keeps_gil.kv_last_carried
        lib.kv_last_carried.restype = None
        lib.kv_last_carried.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.kv_negotiate_epoch.restype = ctypes.c_int
        lib.kv_negotiate_epoch.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.kv_negotiate_mapping.restype = ctypes.c_int
        lib.kv_negotiate_mapping.argtypes = [ctypes.c_void_p]
        lib.kv_set_epoch.restype = ctypes.c_int
        lib.kv_set_epoch.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.kv_epoch_mismatch.restype = ctypes.c_int
        lib.kv_epoch_mismatch.argtypes = [ctypes.c_void_p]
        lib.kv_group_epoch.restype = ctypes.c_int
        lib.kv_group_epoch.argtypes = [ctypes.c_void_p]
        lib.kv_pull_opt_state.restype = ctypes.c_int
        lib.kv_pull_opt_state.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_uint64,
        ]
        lib.kv_push_init_opt_state.restype = ctypes.c_int
        lib.kv_push_init_opt_state.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_uint64, ctypes.c_int,
        ]
        lib.kv_stats.restype = ctypes.c_int
        lib.kv_stats.argtypes = [  # out buffer is float64 (see kv_protocol.h)
            ctypes.c_void_p, ctypes.c_uint32, ctypes.c_void_p, ctypes.c_uint64,
        ]
        lib.kv_last_error.restype = ctypes.c_char_p
        lib.kv_last_error.argtypes = [ctypes.c_void_p]
        lib.kv_close.argtypes = [ctypes.c_void_p]
        _lib = lib
    return _lib


class KVWorker:
    """Blocking Push/Pull/Wait client over a range-sharded server group."""

    def __init__(self, hosts: str | None, dim: int, client_id: int = 0, *,
                 timeout_ms: int = 0, sync_group: bool = True,
                 retry: RetryPolicy | None = None,
                 compress: str = "none", trace: bool | None = None,
                 epoch: int | None = None, route=None,
                 route_timeout_s: float = 30.0):
        from distlr_tpu.compress import CODEC_IDS  # noqa: PLC0415  (cycle-free, numpy-only)

        if compress not in CODEC_IDS:
            raise ValueError(
                f"compress must be one of {tuple(CODEC_IDS)}, "
                f"got {compress!r}")
        lib = _load()
        self._lib = lib
        self.dim = dim
        #: membership routing (the elastic-fleet round): ``route`` is a
        #: zero-arg callable returning the coordinator's current layout
        #: ``{"hosts", "epoch", "status", ...}`` (see
        #: :mod:`distlr_tpu.ps.membership` — ``layout_client`` wraps a
        #: ``launch ps-ctl`` endpoint into one).  With it set, an epoch
        #: fence mid-op re-fetches the layout and rebuilds the handle in
        #: place — a resharding costs a re-route, never a restart.
        #: ``epoch`` announces the layout epoch to every server so the
        #: fence can protect this client; both default from the route
        #: provider when one is given.
        self._route = route
        self._route_timeout_s = float(route_timeout_s)
        self._epoch = int(epoch) if epoch else 0
        self._epoch_armed = False
        self._warned_no_epoch = False
        if route is not None:
            # the coordinator is AUTHORITATIVE: a caller-supplied hosts
            # list may predate a resize, and a stale list announced with
            # the current epoch would pass every fence while range-
            # slicing against the wrong layout — silent misrouting.
            layout = self._fetch_active_layout()
            if hosts is not None and hosts != layout["hosts"]:
                log.info("route provider overrides stale hosts %s -> %s",
                         hosts, layout["hosts"])
            hosts = layout["hosts"]
            if not self._epoch:
                self._epoch = int(layout.get("epoch") or 0)
        if hosts is None:
            raise ValueError("KVWorker needs hosts or a route provider")
        self.num_servers = hosts.count(",") + 1
        # connection state kept for reconnect(): a poisoned handle is
        # rebuilt in place with exactly these parameters
        self._hosts = hosts
        self._client_id = client_id
        self._timeout_ms = int(timeout_ms)
        self._sync_group = bool(sync_group)
        self.retry = retry
        self._retry_rng = random.Random(retry.seed if retry else None)
        self._fault_rate = (FaultRateTracker(retry.adaptive_window_s,
                                             retry.adaptive_max_scale)
                            if retry is not None and retry.adaptive else None)
        #: requested wire codec name ("none" = dense f32, never negotiated)
        self.compress = compress
        #: codec actually in force after the kHello capability handshake
        #: ("none" when any server of the group lacks it — graceful
        #: fallback, re-derived on every reconnect).  None until the
        #: first handshake so the initial outcome — including a
        #: fallback — always logs (the change-only guard in
        #: :meth:`_build_handle` would otherwise swallow a first-connect
        #: downgrade the operator explicitly asked to see).
        self.compress_active: str | None = None
        self._codec_id = CODEC_IDS[compress]
        #: ask for distributed-trace stamping (ISSUE 8): when True the
        #: kHello handshake additionally checks kCapTrace, and ops
        #: issued under a SAMPLED dtrace context carry the 16-byte
        #: trace trailer (plus a client-side ``ps.<op>`` span).  False
        #: (and the ``--trace-sample 0`` path) negotiates nothing and
        #: leaves the wire byte-identical.  The default ``None`` follows
        #: the process: tracing armed (``dtrace.configure`` ran with a
        #: non-zero sample) => negotiate — so trainers, serving pulls,
        #: and the online trainer all participate without per-site
        #: wiring, and untraced processes stay wire-identical.
        if trace is None:
            trace = dtrace.is_configured() and dtrace.sample_rate() > 0
        self._trace = bool(trace)
        #: whether every server of the group parses trace trailers
        #: (re-derived on every reconnect, like compress_active)
        self.trace_active = False
        # one-time sparse-gradient sanity check on the first sign push
        self._sign_zero_checked = False
        # how default-key ops address the key space (lazy): (keys, vpk)
        self._dense_rows: tuple[np.ndarray, int] | None = None
        # the key frames this connection keeps (:meth:`hold`), by id: the
        # array itself and the row space it was checked against
        self._held: dict[int, tuple[np.ndarray, int]] = {}
        # where kv_last_exchange writes an op's four instants, and
        # kv_last_carried its value-carrying frames (mapped, inline)
        self._xchg = (ctypes.c_double * 4)()
        self._carried = (ctypes.c_uint64 * 2)()
        # the instants of the attempt that answered the op now returning,
        # and when its native call was back in Python (_record_op)
        self._answered: tuple | None = None
        # this handle's shares of each keyed op's series, by op
        self._accounts: dict[str, _OpAccount] = {}
        weakref.finalize(self, _retire_accounts, self._accounts)
        #: connections of the current handle whose values cross in a
        #: shared mapping (kv_protocol.h "values in a mapping"):
        #: re-derived at every (re)connect from what the servers
        #: advertise and whether each socket reaches its server directly
        self.mapped_connections = 0
        self._h = None
        if route is None:
            self._h = self._build_handle()
        else:
            # a route-provided client may be constructed mid-migration
            # (or mid-partition, behind a chaos plan): poll through
            # connect/negotiation failures the same way a reroute does,
            # bounded by route_timeout_s
            deadline = time.monotonic() + self._route_timeout_s
            while True:
                try:
                    self._h = self._build_handle()
                    break
                except OSError as e:
                    if time.monotonic() >= deadline:
                        raise
                    log.debug("route-provided connect failed (%s); "
                              "re-fetching layout", e)
                    time.sleep(0.05)
                    self._apply_layout(self._fetch_active_layout())
        # dense default key set 0..D-1, like the reference app (src/lr.cc:117-121)
        self._all_keys = np.arange(dim, dtype=np.uint64)

    def _build_handle(self):
        """Connect + configure + (when asked) negotiate a NEW native
        handle — shared by the constructor and :meth:`reconnect` so a
        rebuilt connection always re-runs the capability handshake
        (codec state lives per handle)."""
        lib = self._lib
        h = lib.kv_connect(self._hosts.encode(), self.dim, self._client_id)
        if not h:
            raise ConnectionError(
                f"could not connect to KV servers at {self._hosts}")
        try:
            if self._timeout_ms and lib.kv_set_timeout_ms(
                    h, self._timeout_ms) != 0:
                raise OSError("failed to set KV socket timeout")
            if not self._sync_group:
                # Async group: no BSP barrier to vote in, so keyed pushes
                # may skip servers whose key slice is empty (saves S-1
                # round trips per sparse push).  MUST stay True for sync
                # groups.
                lib.kv_set_push_visit_all(h, 0)
            if self._codec_id:
                got = lib.kv_negotiate_codec(h, self._codec_id)
                if got < 0:
                    raise OSError(
                        "codec negotiation failed: "
                        + lib.kv_last_error(h).decode())
                active = self.compress if got == self._codec_id else "none"
                if active != getattr(self, "compress_active", None):
                    if active == "none":
                        log.warning(
                            "KV group at %s does not advertise codec %r; "
                            "falling back to dense f32 pushes",
                            self._hosts, self.compress)
                    else:
                        log.info("negotiated %r gradient pushes with %s",
                                 active, self._hosts)
                self.compress_active = active
            else:
                self.compress_active = "none"
            if self._trace:
                got = lib.kv_negotiate_trace(h)
                if got < 0:
                    raise OSError("trace negotiation failed: "
                                  + lib.kv_last_error(h).decode())
                if not got and not self.trace_active:
                    log.info(
                        "KV group at %s predates trace propagation; "
                        "degrading to client-only spans", self._hosts)
                self.trace_active = got == 1
                if self.trace_active:
                    hosts = self._hosts.split(",")
                    for s in range(self.num_servers):
                        # the hello doubles as a clock probe: journal
                        # each server's offset so trace-agg can align
                        # its span journal onto this host's clock
                        dtrace.record_clock(
                            hosts[s], lib.kv_clock_offset(h, s))
            else:
                self.trace_active = False
            if self._epoch:
                got = lib.kv_negotiate_epoch(h, self._epoch)
                if got < 0:
                    raise OSError("epoch negotiation failed: "
                                  + lib.kv_last_error(h).decode())
                if got == 0:
                    # mixed-fleet degradation, like codec/trace: no
                    # fencing — this client behaves like a pre-epoch one
                    if not self._warned_no_epoch:
                        log.warning(
                            "KV group at %s predates membership epochs; "
                            "epoch fencing disabled for this client",
                            self._hosts)
                        self._warned_no_epoch = True
                    self._epoch_armed = False
                elif got != self._epoch:
                    raise PSEpochError(
                        f"group at {self._hosts} is at membership epoch "
                        f"{got}; this client's layout says {self._epoch} "
                        "— re-fetch routing from the coordinator",
                        epoch=got)
                else:
                    self._epoch_armed = True
                    _CLIENT_EPOCH.set(self._epoch)
            # the values' carrier: nothing asks for it and nothing can
            # turn it off.  A same-host server reached directly shares a
            # mapping with each connection whose slice is large enough to
            # use one; every refusal is the socket, silently
            got = lib.kv_negotiate_mapping(h)
            if got < 0:
                raise OSError("mapping negotiation failed: "
                              + lib.kv_last_error(h).decode())
            self.mapped_connections = got
        except Exception:
            lib.kv_close(h)
            raise
        return h

    def reconnect(self) -> None:
        """Rebuild the native handle in place — same hosts, dim,
        client_id, timeout, group-mode flags, and (re-negotiated) wire
        codec — the escape from a poisoned connection (one receive
        failure fails every later op on that stream until reconnect;
        kv_client.cc).  Callers running their own recovery loop use this
        instead of recreating the whole object; a :class:`RetryPolicy`
        calls it automatically.

        The new connections are established (and the codec handshake
        completed) BEFORE the old ones close, so a failed reconnect
        (servers still down) leaves the worker on its previous —
        poisoned but intact — handle and raises an ``OSError``; closing
        the old stream is also what makes the servers roll back any of
        its pending barrier votes or deferred pushes (DropConnection),
        which is exactly why a post-reconnect re-vote counts once."""
        h = self._build_handle()
        old, self._h = self._h, h
        if old:
            self._lib.kv_close(old)
        _RECONNECTS.inc()

    # -- membership re-routing (elastic fleet) -----------------------------
    def _fetch_active_layout(self) -> dict:
        """Poll the route provider until it reports an ACTIVE layout —
        a client landing mid-migration waits the drain out here instead
        of bouncing ops off the fence — bounded by ``route_timeout_s``."""
        deadline = time.monotonic() + self._route_timeout_s
        delay = 0.05
        last: Exception | None = None
        while True:
            layout = None
            try:
                layout = self._route()
            except Exception as e:  # noqa: BLE001 — coordinator may be mid-flip
                last = e
            if (layout is not None
                    and layout.get("status", "active") == "active"):
                return layout
            if time.monotonic() >= deadline:
                raise OSError(
                    "membership layout fetch timed out after "
                    f"{self._route_timeout_s:g}s"
                    + (f" (last error: {last})" if last else
                       " (coordinator still migrating)"))
            time.sleep(min(delay, max(0.0, deadline - time.monotonic())))
            delay = min(delay * 2, 0.5)

    def _renegotiate_route(self) -> None:
        """The epoch-fence recovery: re-fetch the layout from the
        membership coordinator and rebuild the native handle against
        the new ranks — the same in-place move ``reconnect()`` makes
        for a poisoned stream, plus new hosts and a new announced
        epoch.  Polls through a migration window (the coordinator
        reports ``status: migrating`` until the drain completes);
        bounded by ``route_timeout_s``."""
        deadline = time.monotonic() + self._route_timeout_s
        last: Exception | None = None
        while True:
            layout = self._fetch_active_layout()
            self._apply_layout(layout)
            try:
                self.reconnect()
            except PSEpochError as e:
                # coordinator lag: the fetched layout is ALREADY stale
                # (a second resize raced this one) — poll again
                last = e
            except OSError as e:
                last = e  # new ranks may still be binding; poll again
            else:
                _REROUTES.inc()
                dtrace.instant("ps.reroute", tags={
                    "epoch": self._epoch, "servers": self.num_servers})
                log.info("membership re-route: now at epoch %d over %d "
                         "server(s)", self._epoch, self.num_servers)
                return
            if time.monotonic() >= deadline:
                raise OSError(
                    f"membership re-route failed after "
                    f"{self._route_timeout_s:g}s: {last}")
            time.sleep(0.05)

    def _apply_layout(self, layout: dict) -> None:
        hosts = layout["hosts"]
        epoch = int(layout.get("epoch") or 0)
        if "dim" in layout and int(layout["dim"]) != self.dim:
            raise OSError(
                f"membership layout changed the key-space dim "
                f"({self.dim} -> {layout['dim']}): not a reshard — "
                "this client cannot follow")
        self._hosts = hosts
        self.num_servers = hosts.count(",") + 1
        self._epoch = epoch
        # range boundaries moved: the cached dense row encoding must
        # re-derive.  A held key frame stays: what its check guards
        # (ascending, under ``dim // vpk``) knows no boundary, and ``dim``
        # is as it was
        self._dense_rows = None

    # -- in-place retry (RetryPolicy) -------------------------------------
    def _run_with_retry(self, op: str, fn, *, idempotent: bool,
                        on_failure=None):
        """THE retry driver — one loop for both op classes (the
        idempotent and push paths used to be near-identical twins; PR 5
        debt).  On a transient transport failure: reconnect the poisoned
        handle, back off (jittered exponential), and re-issue — bounded
        by the policy's attempts and per-op wall deadline.  With no
        policy this is a plain call (fail-fast semantics).

        ``idempotent=False`` marks a gradient-carrying op, with two
        extra rules the delivery-proof semantics demand:

        * sync (BSP) groups never retry it at all — the deferred reply
          IS the barrier and the timeout is the named straggler signal;
        * a re-issue is allowed only while the native client proves no
          byte of the failed op reached any server's kernel
          (``kv_op_delivery_began == 0``).  Once delivery began the
          outcome is unknown: it is counted
          (``distlr_ps_push_outcome_unknown_total``), the handle is
          reconnected best-effort, and the ``on_failure`` hook resolves
          the op (the fused push_pull re-pulls its weights
          idempotently); without a hook the push is absorbed as
          lost-or-applied-once (returns -1) — the bounded-staleness
          class Hogwild training already tolerates, where a re-issued
          maybe-applied push would be a silent double-apply.

        ``on_failure`` fires only on the unknown-delivery outcome; the
        idempotent path never reaches it (re-issue is always legal
        there).

        A membership change (the elastic fleet resharding under this
        op) is its own recovery class, live even WITHOUT a retry policy
        when a ``route`` provider is set.  It surfaces two ways — an
        epoch fence (:class:`PSEpochError`) from a still-running rank,
        or plain transport exhaustion against a RETIRED rank (a
        resharded layout closes old processes; a dead socket cannot
        reply a fence) — and both recover identically: re-fetch the
        layout from the coordinator, rebuild the handle, re-issue
        (bounded; a reshard is not a fault and burns no retry budget).
        A gradient push caught by the fence is absorbed through the
        same unknown-outcome path as a transport failure: the fenced
        rank applied nothing, but a peer whose epoch flipped a moment
        later may have applied its slice — re-issuing would
        double-apply it.

        PROTOCOL ASSERTION (checked, not just prose): this ladder is
        modeled step for step in
        :mod:`distlr_tpu.analysis.protocol.spec` (the delivery-proof
        rule, the absorb-never-reissue rule, the reroute layer), and
        ``make verify-protocol`` exhaustively searches the
        interleavings — reverting the absorption rule is the
        ``reissue-straddling-push`` mutant, rediscovered as a
        double-apply counterexample in tier-1.
        """
        if not idempotent and self._sync_group:
            return fn()  # BSP pushes: fail fast, no retry, no re-route
        if self.retry is None and self._route is None:
            return fn()
        max_reroutes = 8 if self._route is not None else 0
        for reroute in range(max_reroutes + 1):
            try:
                return self._retry_ladder(op, fn, idempotent=idempotent,
                                          on_failure=on_failure)
            except PSRejectedError:
                # explicit protocol rejection: deterministic caller
                # error, identical on every re-issue — never retried
                raise
            except PSEpochError:
                _EPOCH_MISMATCHES.inc()
                if reroute >= max_reroutes:
                    # no coordinator to ask (or it keeps handing out
                    # already-stale layouts): surface the fence
                    raise
                if not idempotent:
                    _PUSH_UNKNOWN.inc()
                    with contextlib.suppress(OSError):
                        self._renegotiate_route()
                    if on_failure is not None:
                        return on_failure()
                    return -1
                self._renegotiate_route()  # raises OSError on timeout
            except OSError:
                if (not idempotent
                        and self._lib.kv_op_delivery_began(self._h)):
                    # Without a RetryPolicy the ladder is a plain call,
                    # so the delivery-proof absorb decision lands HERE:
                    # frames reached a kernel, the outcome is unknown —
                    # re-issuing after the re-route would be a silent
                    # double-apply.  (With a policy the ladder already
                    # absorbed this case; OSErrors escaping it carry
                    # delivery_began == false.)
                    _PUSH_UNKNOWN.inc()
                    with contextlib.suppress(OSError):
                        self._renegotiate_route()
                    if on_failure is not None:
                        return on_failure()
                    return -1
                if reroute >= max_reroutes:
                    raise
                # transport exhaustion with a route provider: possibly a
                # retired rank — recover routing and re-issue (legal:
                # nothing of this op was delivered anywhere).
                self._renegotiate_route()
        raise AssertionError("unreachable")

    def _retry_ladder(self, op: str, fn, *, idempotent: bool, on_failure):
        """The transport-fault half of :meth:`_run_with_retry`: bounded
        reconnect/backoff/re-issue attempts under the
        :class:`RetryPolicy` (a plain single call without one).
        :class:`PSEpochError` and exhaustion propagate to the
        membership layer above."""
        pol = self.retry
        if pol is None:
            return fn()
        deadline = time.monotonic() + pol.deadline_s
        last: Exception | None = None
        for attempt in range(pol.attempts):
            if attempt:
                # adaptive policies scale the backoff BASE by the
                # observed recent fault rate (FaultRateTracker): a storm
                # backs off harder, a quiet window decays to the static
                # base — backoff_max_ms still caps either way
                scale = (self._fault_rate.scale()
                         if self._fault_rate is not None else 1.0)
                nap = pol.backoff_s(attempt - 1, self._retry_rng, scale)
                time.sleep(min(nap, max(0.0, deadline - time.monotonic())))
                try:
                    self.reconnect()
                except PSEpochError:
                    # the group resharded while this op was backing off:
                    # the membership layer recovers routing, not the
                    # transport ladder
                    raise
                except OSError as e:
                    # servers unreachable (e.g. mid-partition): burn the
                    # attempt on the reconnect and keep backing off
                    self._record_fault()
                    last = e
                    if time.monotonic() >= deadline:
                        break
                    continue
                if time.monotonic() >= deadline:
                    # deadline crossed during backoff/reconnect: surface
                    # the last failure rather than re-issuing an op that
                    # could block a further full receive timeout
                    break
                _RETRIES.labels(op=op).inc()
            try:
                return fn()
            except (PSRejectedError, PSEpochError):
                raise  # both handled a layer up, neither is a fault
            except OSError as e:
                self._record_fault()
                if not idempotent and self._lib.kv_op_delivery_began(self._h):
                    _PUSH_UNKNOWN.inc()
                    with contextlib.suppress(OSError):
                        # best-effort: later ops retry their own reconnect
                        self.reconnect()
                    if on_failure is not None:
                        return on_failure()
                    return -1
                last = e
                if time.monotonic() >= deadline:
                    break
        assert last is not None
        raise last

    def _record_fault(self) -> None:
        if self._fault_rate is not None:
            self._fault_rate.record()

    def _with_retry(self, op: str, fn):
        """Idempotent ops (pull/chunked/keyed/stats/barrier/push_init):
        re-issue is always legal — the server rolls a dead connection's
        state back (DropConnection), so a reconnect re-issue counts once."""
        return self._run_with_retry(op, fn, idempotent=True)

    def _push_with_retry(self, op: str, fn, *, on_unknown=None):
        """Gradient-carrying ops (push/push_pull): delivery-proof retry
        semantics — see :meth:`_run_with_retry`."""
        return self._run_with_retry(op, fn, idempotent=False,
                                    on_failure=on_unknown)

    @contextlib.contextmanager
    def _trace_op(self, op: str):
        """Distributed-trace hook around one KV op: when a SAMPLED
        dtrace context is current and the group negotiated kCapTrace,
        record a client-side ``ps.<op>`` span (its duration includes
        any retry backoff — exactly the wall this op cost its caller)
        and stamp the native handle so the request frames carry the
        trace trailer and the server's handler span parents under this
        one.  The stamp is one-shot and consumed by the FIRST attempt;
        a retry re-issue goes unstamped rather than mis-attributing a
        later op.  With no context (or trace off): zero work, zero
        wire delta."""
        ctx = dtrace.current()
        if ctx is None or not ctx.sampled:
            yield
            return
        with dtrace.span(f"ps.{op}", tags={"servers": self.num_servers}) as sp:
            if self.trace_active:
                # pre-trace groups skip the stamp: client-only spans —
                # the mixed-fleet degradation, never a desync
                self._lib.kv_set_trace(self._h, ctx.trace_id, sp.span_id)
            yield

    def set_timeout(self, timeout_ms: int) -> None:
        """Receive timeout for every op; 0 = block forever (reference
        semantics — a sync-mode straggler then deadlocks the job exactly
        like ps-lite, SURVEY.md §5.3).  The value is remembered so a
        later :meth:`reconnect` re-applies what is in force NOW, not the
        constructor-time value."""
        if self._lib.kv_set_timeout_ms(self._h, int(timeout_ms)) != 0:
            raise OSError("failed to set KV socket timeout")
        self._timeout_ms = int(timeout_ms)

    def acknowledged(self, op: str) -> int:
        """How many ``op`` (``"push"``, ``"pull"``, ...) this handle has
        had answered so far: its own share of ``distlr_ps_client_ops_total
        {op, status="ok"}`` (:class:`_OpAccount`), which :meth:`_keyed`
        counts as the op returns.  One connection carries one operation
        at a time, so read on the connection's thread between two ops it
        is the op sequence's own count."""
        account = self._accounts.get(op)
        return 0 if account is None else int(account.ok.value)

    def _keyed(self, op: str, native, args: tuple, how: str, *,
               sent: int = 0, received: int = 0,
               raw: int | None = None) -> int:
        """One attempt of a keyed op: ``native(handle, *args)``, and, where
        it is answered, the op's accounts in ONE pass that hands nothing
        over: no call that releases the interpreter (the three getters
        keep it: :func:`_load`), no lock (the counts go to this handle's
        own shares, :class:`_OpAccount`), no walk of a family.  W
        lock-step workers are answered at the same instant, and whatever
        one of them gives away here the others' returns stand behind.

        Latency and outcome (``distlr_ps_client_op_seconds``,
        ``_ops_total``; a failed attempt: :func:`_op_failed`, and nothing
        else); ``how`` its keys came (:meth:`_resolve_keys`): the encoding
        a default-key op resolved to (``"rows"``/``"flat"``,
        ``distlr_ps_dense_frames_total``), or what an op that passed its
        own keys did with them (``"held"``/``"checked"``,
        ``distlr_ps_client_key_frames_total``);
        payload bytes ``sent`` and ``received``
        (``distlr_ps_client_bytes_total``); the value-carrying frames by
        carrier (``kv_last_carried``,
        ``distlr_ps_payload_frames_total{op, carrier}``).  ``raw``: the op
        is a gradient push of that many dense-f32-equivalent bytes; what
        it sent is what left the kernel (``kv_last_wire_sent``, only
        known after the call: a coded push), and both go to the push-byte
        accounts.  The attempt's instants (``kv_last_exchange``, on
        ``time.perf_counter``'s clock) are kept, with the first instant
        Python read after the call, for :meth:`_record_op`, which the op
        calls as it returns; nothing is kept where no reply was read (a
        pull of no keys: an instant not reached is 0)."""
        account = self._accounts.get(op)
        if account is None:
            account = self._accounts[op] = _OpAccount(op)
        began = time.perf_counter()
        try:
            ts = native(self._h, *args)
            back = time.perf_counter()
            self._check(ts, op)
        except Exception as e:
            _op_failed(op, e)
            raise
        lib, h = self._lib, self._h
        lib.kv_last_carried(h, self._carried)
        lib.kv_last_exchange(h, self._xchg)
        mapped, inline = self._carried
        account.mapped.inc(mapped)
        account.inline.inc(inline)
        t0, t1, t2, t3 = self._xchg
        self._answered = ((t0, t1, t2, t3, back)
                          if 0.0 < t0 <= t1 <= t2 <= t3 <= back else None)
        if raw is not None:
            sent = lib.kv_last_wire_sent(h)
            account.raw.inc(raw)
            account.wire.inc(sent)
        getattr(account, how).inc()
        if sent:
            account.sent.inc(sent)
        if received:
            account.received.inc(received)
        account.ok.inc()
        account.seconds.observe(time.perf_counter() - began)
        return ts

    def _record_op(self, entered: float) -> None:
        """The keyed op that is about to return, entered at ``entered``
        (its first instruction in Python), as six spans one after
        another under the span open on this thread (a loop's ``push`` or
        ``pull``, the comm thread's ``wire``), which they cover but for
        its own entry and exit:

        * ``xchg_enter``: to the native call's start: the retry, trace
          and counter scopes, the frame looked up and, without ``out=``,
          the reply's buffer; for explicit keys that are no held frame
          (:meth:`hold`) also :meth:`_validate_keys`' passes over them
          (each gives the interpreter up: 0.1 ms alone over 88,000 keys,
          0.4-0.5 among four workers), and in a push the conversion of
          values that are not contiguous float32 yet;
        * ``xchg_send``: to the last request byte handed over (on the
          socket: to the kernel; in a mapping: the values copied into
          the request area and the header in the kernel);
        * ``xchg_await``: to the first reply header read: the servers'
          read, merge, wait for the round and release up to the first
          reply (a mapped reply's values are in the reply area by then);
        * ``xchg_recv``: to the last value in the caller's buffer;
        * ``xchg_wake``: to the first instant Python read after the
          native call: the call's exit and the wait for the interpreter,
          which another thread may hold;
        * ``xchg_account``: to here: the reply checked, the op's
          counters and byte accounts (:meth:`_keyed`), the scopes'
          exits, the tracer's lock, taken once for the six.

        The native client noted the middle four instants.  On a retried
        op they are those of the attempt that was answered, and
        ``xchg_enter`` holds what went before it.  Nothing where no
        attempt was (an op that raised, a push of unknown outcome, a
        pull of no keys)."""
        answered, self._answered = self._answered, None
        if answered is None:
            return
        get_tracer().completed_run(_XCHG, (entered, *answered))

    def _check(self, ts: int, what: str) -> int:
        if ts < 0:
            err = self._lib.kv_last_error(self._h).decode()
            if self._lib.kv_timed_out(self._h):
                raise PSTimeoutError(f"KV {what} timed out: {err}")
            if self._lib.kv_epoch_mismatch(self._h):
                raise PSEpochError(f"KV {what} fenced: {err}",
                                   epoch=self._lib.kv_group_epoch(self._h))
            if self._lib.kv_op_rejected(self._h):
                raise PSRejectedError(f"KV {what} rejected: {err}")
            raise IOError(f"KV {what} failed: {err}")
        return ts

    def _validate_keys(self, keys: np.ndarray, vpk: int = 1) -> np.ndarray:
        """The native range-slicer requires strictly ascending in-range
        keys (it binary-searches range boundaries); reject violations
        here rather than returning silently-wrong slices.  With
        ``vpk > 1`` keys are row ids over a ``dim // vpk`` row space."""
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        space = self.dim // vpk
        if keys.size:
            kmax = int(keys.max())  # unsigned max, not last element
            if kmax >= space:
                raise ValueError(
                    f"key {kmax} out of range (dim={self.dim}"
                    + (f", vals_per_key={vpk} -> {space} rows)" if vpk > 1
                       else ")"))
            if keys.size > 1 and not (keys[1:] > keys[:-1]).all():
                raise ValueError("keys must be strictly ascending")
        return keys

    def hold(self, keys: np.ndarray, vals_per_key: int = 1) -> np.ndarray:
        """Take a key set into this connection's keeping: checked here,
        once (:meth:`_validate_keys`, its errors), and returned as the
        connection's own contiguous uint64 array, **read-only**.  An op
        handed that very array, read-only still, with the same
        ``vals_per_key`` (:meth:`_resolve_keys` looks at the object, its
        flag and the row space, and at no key) makes no pass over it: for
        a caller whose key sets are fixed before its rounds begin, as a
        worker's windows are when its shard is localised.  Anything else
        (a copy, a slice, the array made writeable again, another
        ``vals_per_key``, another handle) is checked as any caller's
        keys are.  A reconnect and a re-route keep the frame
        (:meth:`_apply_layout`); it lives as long as the handle.  The
        record is one dict entry and no native call: safe from several
        threads at once, which the handle's ops are not."""
        vpk = int(vals_per_key)
        frame = self._validate_keys(np.array(keys, dtype=np.uint64), vpk)
        frame.flags.writeable = False
        self._held[id(frame)] = (frame, self.dim // vpk)
        return frame

    def supports_vals_per_key(self, vpk: int) -> bool:
        """Whether ``vals_per_key=vpk`` ops can be range-sliced over this
        server group: every range boundary (``dim*s/S``) must be a
        multiple of vpk so no row straddles two servers.  Callers for
        whom this is False should send expanded per-lane keys instead."""
        if vpk <= 1:
            return True
        if self.dim % vpk != 0:
            return False
        return all((self.dim * s // self.num_servers) % vpk == 0
                   for s in range(1, self.num_servers))

    def _resolve_keys(self, keys, vpk: int, vals: np.ndarray | None = None):
        """THE resolver of a keyed op's ``(keys, vals_per_key, how)``.
        ``keys=None`` addresses the whole dense key space 0..D-1 and
        crosses the wire in the row encoding :meth:`_dense_row_encoding`
        gives (``how`` says which: ``"rows"`` or ``"flat"``); explicit
        keys are sent as given, validated here (``"checked"``) unless
        they are a frame this connection holds (``"held"``): the very
        array :meth:`hold` returned, read-only still, over the row space
        it was checked against, on which no pass is made.  The
        default set is FLAT ids by contract — combining it with a
        caller's ``vals_per_key > 1`` would silently reinterpret flat
        ids as row ids, so that combination is rejected rather than
        returning garbage.  ``vals``, where the op carries any, is held
        to the size the keys address."""
        if keys is not None:
            held = self._held.get(id(keys))
            if (held is not None and held[0] is keys
                    and held[1] == self.dim // vpk
                    and not keys.flags.writeable):
                how = "held"
            else:
                keys, how = self._validate_keys(keys, vpk), "checked"
        elif vpk != 1:
            raise ValueError(
                "vals_per_key > 1 requires explicit row keys (the "
                "dense default key set is flat ids, not rows)")
        else:
            keys, vpk = self._dense_row_encoding()
            how = "rows" if vpk > 1 else "flat"
        if vals is not None and vals.shape[0] != keys.shape[0] * vpk:
            raise ValueError(
                f"{vals.shape[0]} vals vs "
                + (f"the {self.dim} default keys" if how in _DENSE_ENCODINGS
                   else f"{keys.shape[0]} keys x vals_per_key {vpk}"))
        return keys, vpk, how

    def _dense_row_encoding(self) -> tuple[np.ndarray, int]:
        """How a default-key op addresses the dense key space: as runs
        of ``vpk`` values under one u64 row key, with the largest
        ``vpk`` (<= the protocol cap) that divides ``dim`` and every
        range boundary this handle has, so no run straddles two
        servers.  The key frame shrinks from ``dim`` u64s to
        ``dim/vpk`` — at D=1M over two servers vpk = 4,000: 1 KB of
        keys a server where the flat set is 4 MB, beside 2 MB of
        values.  The server walks the rows as they stand and, these
        keys being one consecutive run, handles the frame as the range
        of slots it is (kv_protocol.h; kStats ``run_frames``): what it
        applies and replies is what the flat keys would have got, bit
        for bit.  ``(all flat keys, 1)``
        when no divisor aligns (a prime D above the cap, three servers
        over a D that 3 does not divide).  Cached; a re-route drops the
        cache (:meth:`_apply_layout`)."""
        if self._dense_rows is None:
            best = 1
            for v in range(min(wire.MAX_VALS_PER_KEY, self.dim), 1, -1):
                if self.dim % v == 0 and self.supports_vals_per_key(v):
                    best = v
                    break
            keys = (np.arange(self.dim // best, dtype=np.uint64)
                    if best > 1 else self._all_keys)
            self._dense_rows = (keys, best)
        return self._dense_rows

    def _frame_now(self, frame):
        """``frame`` (what :meth:`_resolve_keys` gave) as THIS attempt
        sends it.  A default-key op derives its encoding again: a
        re-route between two attempts (an epoch fence, a retired rank)
        moves the range boundaries the rows were cut to, and rows of the
        old layout would straddle the new servers.  Explicit keys are
        the caller's and stay."""
        return (self._resolve_keys(None, 1) if frame[2] in _DENSE_ENCODINGS
                else frame)

    def _push_frame(self, keys: np.ndarray | None, vpk: int,
                    vals: np.ndarray):
        """A gradient push's ``(keys, vpk, how)``
        (:meth:`_resolve_keys`), after the codec's one-time check of
        what it is asked to code."""
        if self.compress_active == "signsgd" and not self._sign_zero_checked:
            # 1-bit signSGD has no abstention: an exact zero votes -1,
            # so a mostly-zero gradient (sparse data pushed full-width)
            # silently walks every untouched weight +lr per round.  One
            # representative check on the first coded push, then free.
            self._sign_zero_checked = True
            if vals.size and np.count_nonzero(vals) < vals.size // 2:
                log.warning(
                    "signsgd push is mostly exact zeros (%d of %d "
                    "coordinates): zero votes decode -1 and drift "
                    "untouched weights by +lr per round — push touched "
                    "keys only, or use compress='int8' for sparse "
                    "gradients", vals.size - np.count_nonzero(vals),
                    vals.size)
        return self._resolve_keys(keys, vpk, vals)

    def push(self, vals: np.ndarray, keys: np.ndarray | None = None,
             *, vals_per_key: int = 1) -> int:
        """Blocking push; in sync mode returns only after ALL workers
        pushed (the server's deferred reply = BSP barrier).

        ``vals_per_key=R``: keys are R-lane ROW ids (each owns flat
        slots ``[k*R, (k+1)*R)``) and ``vals`` holds ``len(keys)*R``
        floats row-major — one u64 of key per R values on the wire
        instead of R expanded keys (the blocked CTR path's encoding;
        requires :meth:`supports_vals_per_key`).

        With a negotiated codec (``compress=``) the value payload
        crosses the wire coded; delivered pushes tick the
        ``distlr_ps_push_bytes_{raw,wire}_total`` counters exactly once
        each (a retried attempt counts only on its successful issue)."""
        entered = time.perf_counter()
        vals = np.ascontiguousarray(vals, dtype=np.float32).reshape(-1)
        frame = self._push_frame(keys, int(vals_per_key), vals)

        def _issue():
            keys, vpk, how = self._frame_now(frame)
            return self._keyed(
                "push", self._lib.kv_push_vpk,
                (keys.ctypes.data_as(ctypes.c_void_p),
                 vals.ctypes.data_as(ctypes.c_void_p), keys.shape[0], vpk),
                how, raw=keys.nbytes + vals.nbytes)

        with self._trace_op("push"):
            ts = self._push_with_retry("push", _issue)
        self._record_op(entered)
        return ts

    def push_init(self, vals: np.ndarray, keys: np.ndarray | None = None,
                  *, force: bool = False) -> int:
        """Idempotent weight-seeding push: initializes an uninitialized
        server group, no-ops otherwise (kInitPush) — safe for a restarted
        worker to re-send, unlike a plain first push.  ``force=True``
        overwrites live weights (kForceInit): checkpoint resume against a
        surviving group; restarted workers must NOT use it."""
        entered = time.perf_counter()
        vals = np.ascontiguousarray(vals, dtype=np.float32)
        frame = self._resolve_keys(keys, 1, vals)

        def _issue():
            keys, vpk, how = self._frame_now(frame)
            return self._keyed(
                "push_init", self._lib.kv_push_init_vpk,
                (keys.ctypes.data_as(ctypes.c_void_p),
                 vals.ctypes.data_as(ctypes.c_void_p), keys.shape[0],
                 1 if force else 0, vpk),
                how, sent=keys.nbytes + vals.nbytes)

        # idempotent by protocol design (kInitPush no-ops once seeded;
        # kForceInit re-sends the same vals) -> plain retry is safe
        ts = self._with_retry("push_init", _issue)
        self._record_op(entered)
        return ts

    def push_pull(self, vals: np.ndarray,
                  keys: np.ndarray | None = None,
                  *, vals_per_key: int = 1) -> np.ndarray:
        """Fused push+pull: push a gradient and receive the post-update
        weights for the same keys in ONE round trip per server (the
        reference protocol spends two per batch, ``src/lr.cc:116-132``).
        Sync mode: blocks through the BSP round like a push, and the
        returned weights are the post-round state — bit-identical to the
        pull that would have followed.  ``vals_per_key``: see
        :meth:`push`."""
        entered = time.perf_counter()
        vals = np.ascontiguousarray(vals, dtype=np.float32).reshape(-1)
        frame = self._push_frame(keys, int(vals_per_key), vals)
        out = np.empty_like(vals)

        def _issue():
            keys, vpk, how = self._frame_now(frame)
            self._keyed(
                "push_pull", self._lib.kv_push_pull_vpk,
                (keys.ctypes.data_as(ctypes.c_void_p),
                 vals.ctypes.data_as(ctypes.c_void_p),
                 out.ctypes.data_as(ctypes.c_void_p), keys.shape[0], vpk),
                how, received=out.nbytes, raw=keys.nbytes + vals.nbytes)
            return out

        def _repull():
            keys, vpk, how = frame
            return (self.pull() if how in _DENSE_ENCODINGS
                    else self.pull(keys=keys, vals_per_key=vpk))

        # Unknown push outcome: the gradient is lost-or-applied-once
        # (counted), and the PULL half is re-issued idempotently so the
        # caller still gets current weights for the same keys.
        with self._trace_op("push_pull"):
            out = self._push_with_retry("push_pull", _issue,
                                        on_unknown=_repull)
        self._record_op(entered)
        return out

    def pull(self, keys: np.ndarray | None = None,
             *, vals_per_key: int = 1,
             out: np.ndarray | None = None) -> np.ndarray:
        """Blocking pull.  ``vals_per_key=R``: keys are row ids and the
        result holds ``len(keys)*R`` floats row-major (see :meth:`push`).

        ``out``: the caller's own C-contiguous float32 buffer of at least
        that many values.  The reply is written into its head, whichever
        attempt is answered, and the head's view returned; the rest is
        not touched.  A buffer that cannot take the reply is a
        ``ValueError`` before a byte is sent.  Without it every reply is
        a new array."""
        entered = time.perf_counter()
        frame = self._resolve_keys(keys, int(vals_per_key))
        count = frame[0].shape[0] * frame[1]
        if out is None:
            out = np.empty(count, dtype=np.float32)
        elif not (isinstance(out, np.ndarray) and out.dtype == np.float32
                  and out.flags.c_contiguous and out.flags.writeable
                  and out.size >= count):
            raise ValueError(
                f"out must be a writeable C-contiguous float32 array of "
                f"at least {count} values, got "
                + (f"{out.dtype} shape {out.shape}"
                   if isinstance(out, np.ndarray) else type(out).__name__))
        else:
            out = out.reshape(-1)[:count]

        def _issue():
            keys, vpk, how = self._frame_now(frame)
            self._keyed(
                "pull", self._lib.kv_pull_vpk,
                (keys.ctypes.data_as(ctypes.c_void_p),
                 out.ctypes.data_as(ctypes.c_void_p), keys.shape[0], vpk),
                how, sent=keys.nbytes, received=out.nbytes)
            return out

        with self._trace_op("pull"):
            out = self._with_retry("pull", _issue)
        self._record_op(entered)
        return out

    def pull_chunked(self, keys: np.ndarray | None = None, *,
                     vals_per_key: int = 1,
                     chunk_rows: int = 1 << 16) -> np.ndarray:
        """Pull a large key set as a sequence of bounded keyed pulls.

        The serving-tier read path (:mod:`distlr_tpu.serve.reload`): a
        D=1M CTR table pulled as ONE dense op is answered by a 2 MB
        value frame a server in a single message; chunking caps the
        per-op frame at ``chunk_rows`` rows (keys stay the implicit
        range ids, one u64 per ``vals_per_key`` floats — the caller's
        ``vals_per_key``, not the row runs of :meth:`pull`: this
        default is a row space of its own), so a periodic weight
        refresh never monopolizes a server's receive loop against the
        trainer pushing to the same group.  ``keys=None`` pulls the full
        row space ``0..dim/vals_per_key``; an explicit ascending ``keys``
        array (hot-row serving) is chunked as given.
        """
        vpk = int(vals_per_key)
        if chunk_rows <= 0:
            raise ValueError(f"chunk_rows must be positive, got {chunk_rows}")
        if vpk > 1 and not self.supports_vals_per_key(vpk):
            raise ValueError(
                f"vals_per_key={vpk} rows straddle this group's range "
                "boundaries; pull with vals_per_key=1 instead"
            )
        _CHUNKED_PULLS.inc()
        if keys is None:
            space = self.dim // vpk
            parts = [
                self.pull(keys=np.arange(lo, min(lo + chunk_rows, space),
                                         dtype=np.uint64),
                          vals_per_key=vpk)
                for lo in range(0, space, chunk_rows)
            ]
        else:
            keys = self._validate_keys(keys, vpk)
            parts = [
                self.pull(keys=keys[lo:lo + chunk_rows], vals_per_key=vpk)
                for lo in range(0, keys.shape[0], chunk_rows)
            ]
        _CHUNKS.inc(len(parts))
        if not parts:  # empty key set (e.g. an empty hot-row working set)
            return np.empty(0, np.float32)
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    def pull_rows_into(self, table: np.ndarray, keys: np.ndarray, *,
                       vals_per_key: int = 1,
                       chunk_rows: int = 1 << 16) -> int:
        """Keyed hot-slice pull: fetch only ``keys`` rows and scatter
        them into ``table`` in place — the serving tier's working-set
        refresh (:mod:`distlr_tpu.serve.hotset`).  A hot refresh moves
        ``rows * (8 + 4*vpk)`` wire bytes instead of the full D-dim
        table's; the caller's ``table`` keeps the last full pull's
        values for every cold row (the documented staleness trade).

        ``table`` must be a C-contiguous float32 array of ``dim``
        elements (flat or ``(rows, vals_per_key)``); returns the number
        of rows pulled (0 for an empty key set).
        """
        vpk = int(vals_per_key)
        table = np.asarray(table)
        if (table.dtype != np.float32 or table.size != self.dim
                or not table.flags["C_CONTIGUOUS"]):
            raise ValueError(
                f"table must be C-contiguous float32 with {self.dim} "
                f"elements, got {table.dtype} shape {table.shape}"
            )
        keys = self._validate_keys(keys, vpk)
        if keys.size == 0:
            return 0
        vals = self.pull_chunked(keys, vals_per_key=vpk,
                                 chunk_rows=chunk_rows)
        view = table.reshape(self.dim // vpk, vpk)
        view[keys.astype(np.int64)] = vals.reshape(-1, vpk)
        return int(keys.size)

    def pull_opt_state(self) -> tuple[np.ndarray, np.ndarray]:
        """The server's FTRL per-coordinate accumulators ``(z, n)`` for
        this handle's full key range (kOptState; the supervisor's
        snapshot path).  Single-server handles only — the supervisor's
        per-rank connections — because the ``[z..., n...]`` layout
        cannot be range-sliced.  Raises against a non-FTRL server (the
        server replies kError)."""
        if self.num_servers != 1:
            raise ValueError(
                "pull_opt_state addresses ONE server per handle (got "
                f"{self.num_servers}); use a per-rank connection")
        out = np.empty(2 * self.dim, dtype=np.float32)

        def _issue():
            with _observe_op("pull_opt_state", sent=self._all_keys.nbytes,
                             received=out.nbytes):
                ts = self._lib.kv_pull_opt_state(
                    self._h,
                    self._all_keys.ctypes.data_as(ctypes.c_void_p),
                    out.ctypes.data_as(ctypes.c_void_p),
                    self._all_keys.shape[0],
                )
                self._check(ts, "pull_opt_state")
            return out[:self.dim].copy(), out[self.dim:].copy()

        return self._with_retry("pull_opt_state", _issue)

    def push_init_opt_state(self, z: np.ndarray, n: np.ndarray, *,
                            force: bool = False) -> int:
        """Seed the server's FTRL z/n accumulators (idempotent like
        :meth:`push_init`; ``force=True`` overwrites — the supervisor's
        restore path, which pairs this with a forced weight init so a
        respawned FTRL rank resumes with its full optimizer state
        instead of degrading to a warm restart)."""
        if self.num_servers != 1:
            raise ValueError(
                "push_init_opt_state addresses ONE server per handle "
                f"(got {self.num_servers}); use a per-rank connection")
        z = np.ascontiguousarray(z, dtype=np.float32).reshape(-1)
        n = np.ascontiguousarray(n, dtype=np.float32).reshape(-1)
        if z.shape[0] != self.dim or n.shape[0] != self.dim:
            raise ValueError(
                f"z/n must each hold dim={self.dim} values, got "
                f"{z.shape[0]}/{n.shape[0]}")
        buf = np.concatenate([z, n])

        def _issue():
            with _observe_op("push_init_opt_state",
                             sent=self._all_keys.nbytes + buf.nbytes):
                ts = self._lib.kv_push_init_opt_state(
                    self._h,
                    self._all_keys.ctypes.data_as(ctypes.c_void_p),
                    buf.ctypes.data_as(ctypes.c_void_p),
                    self._all_keys.shape[0],
                    1 if force else 0,
                )
                return self._check(ts, "push_init_opt_state")

        # idempotent by protocol design (seed-only, like push_init)
        return self._with_retry("push_init_opt_state", _issue)

    def wait(self, ts: int) -> None:
        """No-op for API parity: push/pull already block (the reference
        pairs every Push/Pull with an immediate Wait)."""
        self._lib.kv_wait(self._h, ts)

    def barrier(self, barrier_id: int = 0) -> None:
        """Worker-group barrier via server 0 (Postoffice::Barrier
        equivalent, reference src/main.cc:150).  ``barrier_id`` is the
        generation: a late vote for an already-released generation
        returns immediately (restart safety — kv_protocol.h)."""
        if not 0 <= barrier_id <= wire.AUX_MAX:
            # the wire field is u16 (MsgHeader::aux); silent truncation
            # could alias a released generation and turn a real barrier
            # into a no-op
            raise ValueError(f"barrier_id must fit in uint16, got {barrier_id}")

        def _issue():
            with _observe_op("barrier"):
                self._check(self._lib.kv_barrier(self._h, barrier_id),
                            "barrier")

        # Retry-safe: closing the failed connection makes server 0 roll
        # its pending vote out of the count (DropConnection), and a
        # released generation answers re-votes immediately — so a
        # reconnect re-vote counts exactly once.
        self._with_retry("barrier", _issue)

    def stats(self, server: int = 0, *, rank: int | None = None) -> dict:
        """Health/progress counters of one server (never deferred, so it
        works mid-barrier — the supervisor's straggler detector).  Use a
        dedicated KVWorker for probing: ops on this connection must not
        be in flight concurrently.  Every read refreshes the registry's
        mirror of that server (:func:`mirror_server_stats`) under
        ``rank``: the group's rank of this handle's ``server``, which is
        ``server`` itself unless the handle addresses a part of the
        group (the supervisor's one-rank probes)."""
        out = np.zeros(len(STATS_FIELDS), dtype=np.float64)

        def _issue():
            n = self._lib.kv_stats(
                self._h, server, out.ctypes.data_as(ctypes.c_void_p),
                out.shape[0],
            )
            self._check(n, "stats")
            return {
                name: float(v) if name.endswith("_seconds") else int(v)
                for name, v in zip(STATS_FIELDS, out[:n])
            }

        got = self._with_retry("stats", _issue)
        mirror_server_stats(server if rank is None else rank, got)
        return got

    def global_pushes(self, *, per_worker_scale: bool = True) -> float:
        """The group's monotonic global push clock: the sum of every
        server rank's ``total_pushes`` kStats counter, divided by the
        server count (``per_worker_scale``) so one dense worker batch —
        which lands on ALL ranges — ticks the clock by exactly 1.

        This is the unit Hogwild staleness bounds are stated in
        (pushes-behind, arXiv:1508.05711): sampling the clock at pull
        time and again at push time measures how many peer updates the
        in-flight gradient is stale against.  Keyed pushes may skip
        ranges they don't touch, so for sparse traffic the clock ticks
        by the touched fraction — the per-key-range average, which is
        the quantity the per-range convergence bound actually sees.
        Stats replies are never deferred, so the clock works mid-barrier.
        """
        total = sum(self.stats(r)["total_pushes"]
                    for r in range(self.num_servers))
        return total / self.num_servers if per_worker_scale else float(total)

    def set_epoch(self, epoch: int) -> None:
        """ADMIN: flip every server of this handle to membership epoch
        ``epoch`` (kv_protocol.h kEpoch SET) — the coordinator's fence
        arm.  Ordinary clients never call this; they ANNOUNCE via the
        constructor's ``epoch=`` and recover through ``route=``."""
        if self._lib.kv_set_epoch(self._h, int(epoch)) != 0:
            raise OSError("epoch set failed: "
                          + self._lib.kv_last_error(self._h).decode())

    def group_epoch(self) -> int:
        """Newest membership epoch any server reported to this handle
        (0 = never epoch-negotiated)."""
        return int(self._lib.kv_group_epoch(self._h))

    def shutdown_servers(self) -> None:
        self._lib.kv_shutdown_servers(self._h)

    def namespace(self, base: int, dim: int) -> "KVNamespace":
        """A namespace-scoped view of this worker: ops address only the
        ``[base, base + dim)`` flat-slot slice (see
        :class:`KVNamespace`)."""
        return KVNamespace(self, base, dim)

    def close(self) -> None:
        if self._h:
            self._lib.kv_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def parse_namespace_optimizers(spec) -> dict[str, str]:
    """Per-namespace server optimizers from an extended ``--namespaces``
    spec: ``"v1:ftrl,v2:sgd"`` -> ``{"v1": "ftrl", "v2": "sgd"}``.
    Entries without a ``:opt`` suffix are omitted (they ride the
    group-wide ``--ps-optimizer``); bare specs return ``{}``.  Only
    ``sgd`` and ``ftrl`` are legal per-namespace (sign votes only mean
    majority-vote through a UNIFORM signsgd group)."""
    if not isinstance(spec, str):
        return {}
    opts: dict[str, str] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part or ":" not in part:
            continue
        mid, _, opt = part.partition(":")
        mid, opt = mid.strip(), opt.strip()
        if opt not in ("sgd", "ftrl"):
            raise ValueError(
                f"namespace optimizer must be sgd|ftrl, got {part!r}")
        opts[mid] = opt
    return opts


def namespace_layout(models, per_model_dim: int) -> dict[str, tuple[int, int]]:
    """Pack equal-width model namespaces into one flat key space:
    ``{model_id: (base, per_model_dim)}`` in spec order — namespace
    ``i`` owns flat slots ``[i*D, (i+1)*D)``.  The TOTAL dim (what the
    hosting :class:`~distlr_tpu.ps.ServerGroup` is spawned with) is
    ``len(models) * per_model_dim``; spawn with ``num_servers`` such
    that range boundaries stay vals_per_key-aligned per namespace
    (equal-width namespaces + a server count dividing the model count,
    or one server, always are).  Entries may carry a per-namespace
    optimizer suffix (``"v1:ftrl,v2:sgd"`` — see
    :func:`parse_namespace_optimizers`); the layout strips it, so
    clients can repeat the server's spec verbatim.

    The layout is EQUAL-WIDTH ONLY.  A spec that asks for per-model
    dims (``"v1=8192,v2=1024"`` or a ``{model: dim}`` mapping) is
    rejected loudly instead of silently hashing every model into the
    same width: heterogeneous widths need a packed layout (per-model
    bases derived from a cumulative-sum table, plus range boundaries
    re-aligned per namespace) — the ROADMAP's packed-``namespace_
    layout`` follow-on.  Equal explicit dims are accepted as a
    self-documenting spelling of the uniform case."""
    explicit_dims: dict[str, int] = {}
    if isinstance(models, dict):
        explicit_dims = {str(m): int(d) for m, d in models.items()}
        models = list(models)
    elif isinstance(models, str):
        parsed = []
        for part in models.split(","):
            part = part.strip()
            if not part:
                continue
            mid, eq, dim = part.partition("=")
            mid = mid.partition(":")[0].strip()
            parsed.append(mid)
            if eq:
                try:
                    explicit_dims[mid] = int(dim)
                except ValueError:
                    raise ValueError(
                        f"bad namespace dim in {part!r} "
                        "(want <model>=<int>)") from None
        models = parsed
    models = list(models)
    if not models:
        raise ValueError("namespace layout needs at least one model id")
    if len(set(models)) != len(models):
        raise ValueError(f"duplicate model ids in {models}")
    if explicit_dims:
        widths = sorted(set(explicit_dims.values()))
        if len(widths) > 1 or (per_model_dim and
                               widths != [int(per_model_dim)]):
            raise ValueError(
                "heterogeneous-dim namespaces are not supported by the "
                f"equal-width layout (asked for {explicit_dims}, "
                f"uniform width {per_model_dim}): per-model widths need "
                "the packed namespace_layout follow-on (cumulative-sum "
                "bases + per-namespace range alignment) tracked in "
                "ROADMAP.md 'Carried minor debts' — until then give "
                "every model the same dim")
        per_model_dim = widths[0]
    if per_model_dim <= 0:
        raise ValueError(
            f"per_model_dim must be positive, got {per_model_dim}")
    return {m: (i * per_model_dim, per_model_dim)
            for i, m in enumerate(models)}


class KVNamespace:
    """A model namespace inside one KV server group's key space.

    Multi-tenant serving (ISSUE 10): one native server group hosts many
    model namespaces by folding a tenant/version id into the KEYED key
    space — namespace ``i`` owns a contiguous flat-slot slice, and this
    view offsets every row key by the namespace base CLIENT-SIDE, the
    same additive move ``vals_per_key`` made (the wire still carries
    plain ascending keyed ops; pre-namespace servers need no change and
    can never desynchronize).  The underlying :class:`KVWorker` is
    connected with the group's TOTAL dim; this view presents the
    namespace's ``dim`` through the same op surface the serving
    reloader and the online trainer already consume.

    Seeding: the group's ``initialized`` flag is global (first
    ``kInitPush`` wins), so the FIRST namespace's idempotent seed
    initializes the group and later namespaces' plain ``push_init``
    calls no-op (their slices stay at the allocation zeros — exactly
    what the zero-seeding online trainer expects).  A namespace seeding
    NON-zero initial weights into an already-initialized group must
    pass ``force=True`` (keyed ``kForceInit`` overwrites only this
    namespace's keys).
    """

    def __init__(self, kv: KVWorker, base: int, dim: int):
        if dim <= 0:
            raise ValueError(f"namespace dim must be positive, got {dim}")
        if base < 0 or base + dim > kv.dim:
            raise ValueError(
                f"namespace [{base}, {base + dim}) outside the group's "
                f"key space [0, {kv.dim})")
        self.kv = kv
        self.base = int(base)
        self.dim = int(dim)

    @property
    def num_servers(self) -> int:
        return self.kv.num_servers

    @property
    def compress_active(self):
        return self.kv.compress_active

    def supports_vals_per_key(self, vpk: int) -> bool:
        """vals_per_key rows work inside this namespace when they work
        group-wide AND the namespace slice is row-aligned (base/dim
        multiples of vpk) — otherwise row ids would shift lanes across
        the base offset."""
        if vpk <= 1:
            return True
        return (self.base % vpk == 0 and self.dim % vpk == 0
                and self.kv.supports_vals_per_key(vpk))

    # -- key translation ---------------------------------------------------
    def _wire_keys(self, keys, vpk: int) -> np.ndarray:
        """Namespace-local row keys -> group row keys.  ``keys=None`` is
        the namespace's full row space (an EXPLICIT key frame — the
        dense default set is a whole-group concept)."""
        if self.base % vpk != 0 or self.dim % vpk != 0:
            raise ValueError(
                f"vals_per_key={vpk} does not align with namespace "
                f"base={self.base}/dim={self.dim}")
        rows = self.dim // vpk
        shift = self.base // vpk
        if keys is None:
            return np.arange(shift, shift + rows, dtype=np.uint64)
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        if keys.size:
            kmax = int(keys.max())
            if kmax >= rows:
                raise ValueError(
                    f"key {kmax} outside namespace row space "
                    f"[0, {rows}) (vals_per_key={vpk})")
        return keys + np.uint64(shift)

    # -- scoped ops --------------------------------------------------------
    def pull(self, keys=None, *, vals_per_key: int = 1) -> np.ndarray:
        vpk = int(vals_per_key)
        return self.kv.pull(keys=self._wire_keys(keys, vpk),
                            vals_per_key=vpk)

    def pull_chunked(self, keys=None, *, vals_per_key: int = 1,
                     chunk_rows: int = 1 << 16) -> np.ndarray:
        vpk = int(vals_per_key)
        return self.kv.pull_chunked(self._wire_keys(keys, vpk),
                                    vals_per_key=vpk,
                                    chunk_rows=chunk_rows)

    def pull_rows_into(self, table: np.ndarray, keys: np.ndarray, *,
                       vals_per_key: int = 1,
                       chunk_rows: int = 1 << 16) -> int:
        """Keyed hot-slice pull into a NAMESPACE-sized table (the
        hot-set reloader's refresh, filtered to this namespace)."""
        vpk = int(vals_per_key)
        table = np.asarray(table)
        if (table.dtype != np.float32 or table.size != self.dim
                or not table.flags["C_CONTIGUOUS"]):
            raise ValueError(
                f"table must be C-contiguous float32 with {self.dim} "
                f"elements, got {table.dtype} shape {table.shape}")
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        if keys.size == 0:
            return 0
        vals = self.pull_chunked(keys, vals_per_key=vpk,
                                 chunk_rows=chunk_rows)
        view = table.reshape(self.dim // vpk, vpk)
        view[keys.astype(np.int64)] = vals.reshape(-1, vpk)
        return int(keys.size)

    def push(self, vals: np.ndarray, keys=None, *,
             vals_per_key: int = 1) -> int:
        vpk = int(vals_per_key)
        return self.kv.push(vals, keys=self._wire_keys(keys, vpk),
                            vals_per_key=vpk)

    def push_init(self, vals: np.ndarray, keys=None, *,
                  force: bool = False) -> int:
        """Seed THIS namespace's slice (see the class docstring for the
        multi-namespace init semantics)."""
        return self.kv.push_init(vals, keys=self._wire_keys(keys, 1),
                                 force=force)

    # -- pass-through ------------------------------------------------------
    def stats(self, server: int = 0, *, rank: int | None = None) -> dict:
        return self.kv.stats(server, rank=rank)

    def global_pushes(self, **kw) -> float:
        return self.kv.global_pushes(**kw)

    def wait(self, ts: int) -> None:
        self.kv.wait(ts)

    def reconnect(self) -> None:
        self.kv.reconnect()

    def close(self) -> None:
        self.kv.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
