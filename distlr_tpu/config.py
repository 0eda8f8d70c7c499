"""Typed configuration with reference-compatible environment-variable shim.

The reference has no CLI parser: every knob is an environment variable read
via ``ps::Environment::Get()->find`` with *no defaults* (missing vars crash
— see reference ``src/main.cc:26-27,129-131,153-155`` and the complete
contract in ``examples/local.sh:12-33``).  This module gives the same knobs
a typed home with sane defaults, plus :meth:`Config.from_env` so a
``local.sh``-style invocation (env-only) still works.

Env-var compatibility table (reference ``examples/local.sh`` defaults):

=================  ==========================  =======================
Variable            Reference default           Config field
=================  ==========================  =======================
``SYNC_MODE``       1 (sync)                    ``sync_mode``
``LEARNING_RATE``   0.2                         ``learning_rate``
``DATA_DIR``        ./a9a-data                  ``data_dir``
``NUM_FEATURE_DIM`` 123                         ``num_feature_dim``
``NUM_ITERATION``   100                         ``num_iteration``
``BATCH_SIZE``      -1 (full shard)             ``batch_size``
``TEST_INTERVAL``   10                          ``test_interval``
``RANDOM_SEED``     10 (never read by ref, Q2)  ``random_seed``
``C``               (hardcoded 1 in ref)        ``l2_c``
=================  ==========================  =======================

Cluster-shape vars (``DMLC_NUM_WORKER`` etc.) map onto mesh / process
configuration; see :mod:`distlr_tpu.parallel.mesh` and
:mod:`distlr_tpu.launch`.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Mapping


def _env(env: Mapping[str, str], name: str, cast, default):
    raw = env.get(name)
    if raw is None or raw == "":
        return default
    try:
        return cast(raw)
    except (TypeError, ValueError) as e:
        raise ValueError(f"bad value for env var {name}={raw!r}: {e}") from e


def _bool_from_int(raw: str) -> bool:
    # Reference semantics: SYNC_MODE is sync iff the string is exactly "1"
    # (strcmp in src/main.cc:26).
    return raw.strip() == "1"


@dataclasses.dataclass
class Config:
    """The trainers' and the PS plane's options, and the observability
    ones every process shares.  What only one daemon reads (``launch
    serve``, ``route``, ``autopilot``, ``obs-agg``) is a flag of that
    subcommand and goes straight to the constructor that uses it.

    Defaults reproduce the reference launcher's defaults
    (``examples/local.sh:12-19``) so `Config()` trains the same workload
    ``local.sh`` does.
    """

    # ---- algorithm (reference env contract) ----
    sync_mode: bool = True            # SYNC_MODE ("1" = BSP, else async/PS)
    learning_rate: float = 0.2        # LEARNING_RATE (server-side SGD eta)
    data_dir: str = "./a9a-data"      # DATA_DIR (train/ test/ models/ subdirs)
    num_feature_dim: int = 123        # NUM_FEATURE_DIM (D)
    num_iteration: int = 100          # NUM_ITERATION (outer epochs)
    # BATCH_SIZE (-1 = full shard).  A dense PS worker on a jax device
    # keeps its shard resident either way: with B > 0 a round's batch is
    # the window [k B, k B + B) of it in file order (a short last batch
    # padded and masked), and so does a keyed sparse_lr worker whose step
    # is worth the trip (its shard localised at load); a shuffled or
    # wrap_final_batch iterator, a shard the device has no room for and
    # the other keyed models stream a batch a step from the host.
    batch_size: int = -1
    test_interval: int = 10           # TEST_INTERVAL (eval every k epochs)
    random_seed: int = 10             # RANDOM_SEED (unused by ref — Q2)
    l2_c: float = 1.0                 # L2 coefficient C (hardcoded 1 in ref lr.h:10)

    # ---- model ----
    model: str = "binary_lr"          # binary_lr | softmax | sparse_lr
    #                                 | sparse_softmax | blocked_lr
    num_classes: int = 2              # softmax only
    nnz_max: int | None = None        # sparse_lr: cap per-row nonzeros (pad width)
    # blocked_lr: lanes per table row (params = num_feature_dim, rows =
    # num_feature_dim / block_size) — see data/hashing.hash_group_blocks.
    block_size: int = 8
    # blocked_lr: number of conjunction groups the raw fields hash into.
    # 0 = ceil(ctr_fields / block_size) consecutive chunks (the default
    # layout).  G > that splits the fields near-equally into G groups of
    # <= block_size lanes each (data/hashing.split_field_groups): one
    # extra row gather per extra group buys tuple spaces small enough to
    # recur: on low-cardinality iid fields the single-group layout loses
    # accuracy that several narrower groups keep.
    block_groups: int = 0
    # blocked_lr from disk: number of raw categorical fields per row in
    # raw-CTR shards (data/hashing.write_raw_ctr_shards).  0 = read it
    # from the data dir's ctr_meta.json manifest at load time.
    ctr_fields: int = 0
    # Seed of the load-time feature hash (hash_group_blocks); train and
    # test splits of one run always share it, so it only matters for
    # reproducing a specific bucket assignment across runs.
    hash_seed: int = 0
    dtype: str = "float32"            # accumulation dtype
    compute_dtype: str = "bfloat16"   # matmul dtype on TPU (MXU-friendly)
    # Device-resident storage dtype of DENSE feature matrices. The dense
    # D=1M step streams the whole feature matrix from HBM twice:
    # "bfloat16" halves the bytes, "int8" quarters them (symmetric
    # per-dataset quantization; the scale folds into the model as
    # feature_scale; 2x the max resident dataset).  Dense models only;
    # sparse vals stay float32.
    # "int8_dot" additionally keeps BOTH matmul operands int8 (native
    # int8 x int8 -> int32 MXU contraction with dynamic per-step scales
    # for w and the residual) instead of converting the (B, D) tile to
    # bfloat16, so the VPU convert of the tile is not in the way (rates
    # not measured on today's code).
    # Dense models (binary_lr and softmax), single-device or
    # feature-sharded; sparse/blocked reject.
    feature_dtype: str = "float32"    # float32 | bfloat16 | int8 | int8_dot

    # ---- parity / compat with reference quirks (SURVEY.md §3.5) ----
    # "reference" reproduces documented quirks (Q1 last-gradient sync update,
    # Q2 identical srand(0) init, Q4 L2/B scaling); "correct" is the fixed
    # math. Each quirk is individually gated below; compat_mode sets defaults.
    compat_mode: str = "correct"      # correct | reference
    # Q4: divide the L2 term by batch size (reference does; correct doesn't).
    l2_scale_by_batch: bool | None = None
    # Q1: sync server applies last worker's gradient instead of the mean.
    sync_last_gradient: bool | None = None
    # Q2: init weights with C rand() after srand(0), uniform [0,1).
    reference_rng_init: bool | None = None
    # Q5: the final batch of each epoch wraps to the shard head (duplicate
    # samples) instead of being padded+masked (data_iter.h:44-56).
    wrap_final_batch: bool | None = None

    # ---- parallelism ----
    num_workers: int = 1              # data-parallel shards (DMLC_NUM_WORKER)
    num_servers: int = 1              # PS mode server count (DMLC_NUM_SERVER)
    mesh_shape: dict | None = None    # e.g. {"data": 8} / {"data": 4, "model": 2}
    feature_shards: int = 1           # model-axis sharding of the feature dim

    # ---- PS / async mode ----
    # Dense PS protocol optimization: replace the reference's two round
    # trips per batch (pull -> grad -> push, src/lr.cc:116-132) with ONE
    # fused push_pull (the reply carries the post-update weights), and in
    # async mode additionally double-buffer — compute batch k+1's
    # gradient while batch k's round trip is in flight (self-staleness
    # bounded by 1 in-flight push; Hogwild-legal).  In sync mode the
    # fused op blocks on the loop's own thread and trajectories are
    # bit-identical (BSP rounds are totally ordered, so the fused reply
    # equals the next pull), unless ps_max_delay says the next round may
    # run under it; set False for the reference-faithful op sequence.
    # Keyed models (sparse/blocked) ignore this (their pull and push key
    # sets differ per batch).
    ps_pipeline: bool = True
    # Bounded-delay consistency, tau = 1 (Li et al., OSDI 2014, 3.4: a
    # worker may start round k+1 before its push of round k is
    # acknowledged, but not before round k-tau is), on two planes.  0 =
    # none (every trajectory as pinned).  1 on the sync (BSP) dense job:
    # a worker computes round k on the weights after round k-2 while its
    # push of round k-1 stands at the servers' barrier: the servers still
    # merge W pushes and apply one mean update a round, and every
    # worker's round k still runs on the same weights, bit for bit, so
    # the run has a trajectory (another one than lock step's).  1 on the
    # asynchronous keyed sparse_lr job over a resident, windowed shard:
    # the comm thread pushes round k's gradient and then pulls round
    # k+2's keys while the loop computes round k+1, so the weights under
    # round k reflect this worker's own pushes through round k-2 whole
    # and none later (one own push behind, stated and held; peers'
    # pushes as they arrive); a streamed, shuffled or too-large shard is
    # refused by load_data, not serialized silently.  Either way one
    # connection carries one operation at a time and nothing is in
    # flight at an eval, a checkpoint or fit's return.  Refused with:
    # the asynchronous dense job, keyed BSP, blocked_lr and
    # sparse_softmax (numpy steps), ps_pipeline off, ps_accum_max > 1,
    # sync_last_gradient, a coded wire.  tau >= 2 would need a server
    # that holds two open rounds, or a third vector a keyed worker.
    # 0 | 1
    ps_max_delay: int = 0
    # Per-op receive timeout. A dead peer otherwise deadlocks the sync
    # BSP barrier forever (the reference's named straggler failure,
    # SURVEY.md §5.3), so detection is ON by default — but with a 10 min
    # margin, because legitimate blocking gaps can be long: startup
    # parse skew before the first barrier, or peers waiting at the BSP
    # push barrier while rank 0 jit-compiles + runs a full-test-set
    # eval. Set 0 for the reference's block-forever semantics; lower it
    # for fast failure detection on small steps.
    ps_timeout_ms: int = 600_000
    # In-place retry of transient KV transport faults (async mode +
    # serving pulls; distlr_tpu.ps.client.RetryPolicy): a reset, delay,
    # or short partition costs a reconnect+retry instead of escalating
    # to the restart/resume ladder.  attempts counts total tries per op
    # (0 = off, today's fail-fast); backoff is jittered-exponential
    # between tries, bounded by the per-op deadline.  Sync (BSP)
    # gradient pushes are NEVER retried regardless — the deferred reply
    # is the barrier and the timeout is the named straggler error.
    ps_retry_attempts: int = 0
    ps_retry_backoff_ms: float = 50.0
    ps_retry_backoff_max_ms: float = 2000.0
    ps_retry_deadline_s: float = 60.0
    # Server-side optimizer applied to incoming gradient pushes.  "sgd"
    # is the reference update (w -= lr * g).  "ftrl" is per-coordinate
    # FTRL-Proximal (McMahan et al., KDD'13 — z/n accumulators, L1
    # sparsification via ftrl_l1): the production sparse-CTR optimizer
    # the online-learning loop (distlr_tpu.feedback) trains through.
    # Incompatible with the Q1 sync_last_gradient quirk (an SGD parity
    # artifact).  What an asynchronous keyed job under it is held to
    # (the benchmark's cell sparse-ps-async-keyed-ftrl-1chip): every
    # acknowledged push applied exactly once, whole, coordinate by
    # coordinate on arrival, float32 on the wire and in the servers; a
    # zero entry steps nothing; with nothing in flight n[k] is the sum
    # of the squares of every gradient acknowledged for k and w[k] the
    # closed form of (z[k], n[k]), exactly 0.0 where |z[k]| <= ftrl_l1;
    # a pull returns exactly the keys asked.  A keyed step pushes the
    # window's MEAN gradient, so ftrl_alpha and ftrl_l1 are on the
    # mean's scale, not an example's.
    ps_optimizer: str = "sgd"         # sgd | ftrl
    ftrl_alpha: float = 0.1           # per-coordinate learning-rate scale
    ftrl_beta: float = 1.0            # learning-rate smoothing
    ftrl_l1: float = 0.0              # L1 strength (sparsifies weights)
    ftrl_l2: float = 0.0              # L2 strength
    # Gradient wire codec for PS pushes (distlr_tpu.compress; negotiated
    # per connection via the kHello capability handshake — a group with
    # any pre-codec server falls back to dense f32).  "int8": block-
    # quantized values with per-block f32 scales (~3.9x fewer value
    # bytes, error <= scale/2, works under sgd and ftrl).  "signsgd":
    # 1 bit/coordinate + server-side majority-vote aggregation (the
    # server group is spawned --optimizer=signsgd; requires
    # ps_optimizer="sgd" since signSGD replaces the update rule, and a
    # signSGD-scale learning_rate — the step is lr * sign, not lr * g).
    # "none" (default) skips negotiation entirely: zero wire deltas, so
    # oracle-pinned trajectories stand.  Incompatible with the Q1
    # sync_last_gradient quirk (a dense-SGD parity artifact).
    ps_compress: str = "none"         # none | int8 | signsgd
    # AdaBatch local accumulation (distlr_tpu.compress.accum): push the
    # MEAN gradient every k batches, k growing from ps_accum_start by
    # x ps_accum_growth every ps_accum_growth_every pushes, capped at
    # ps_accum_max.  Default (1, 1) = off (push every batch, the
    # trajectory-pinned behavior).  Divides push traffic by k on top of
    # whatever the codec saves; within a span batches ride the span-
    # start weights (the span is the self-staleness bound).
    ps_accum_start: int = 1
    ps_accum_growth: float = 2.0
    ps_accum_growth_every: int = 32
    ps_accum_max: int = 1
    # Scale the retry backoff base by the observed recent transport-
    # fault rate (FaultRateTracker) instead of keeping it static: fault
    # storms back off up to 8x harder (still capped by
    # ps_retry_backoff_max_ms), quiet windows decay back.
    ps_retry_adaptive: bool = False
    # Durable server store (native --store_dir): each spawned rank
    # persists crash-consistent CRC-checked snapshots of its slice
    # (weights + FTRL z/n + epoch + push clock) under
    # <ps_store_dir>/rank-<r>/ every ps_store_interval_s seconds via
    # tmp+fsync+rename (2 generations kept; torn/corrupt generations
    # rejected loudly with fallback).  A cold restart with the same
    # store dir recovers every rank from disk at its persisted epoch —
    # RPO <= one interval.  None (default) = RAM-only, the prior
    # behavior.
    ps_store_dir: str | None = None
    ps_store_interval_s: float = 5.0
    # Segmented append-only push WAL on top of the snapshots (the
    # native server's --store_wal flag): every applied push is logged
    # and replayed over the newest valid snapshot on restart, driving
    # RPO to ~0 (bounded only by the group-commit fsync window below).
    # Requires ps_store_dir; async (sync_mode=False) servers only —
    # sync-round merge state has no per-push replay semantics.
    ps_store_wal: bool = False
    ps_store_wal_fsync_s: float = 0.1

    # ---- chaos (distlr_tpu.chaos fault injection) ----
    # Path to a JSON fault plan: local `launch ps` runs interpose the
    # deterministic fault-injection proxy between every worker and the
    # spawned server group (ServerGroup via_chaos).  None = no chaos.
    chaos_plan: str | None = None
    # Seed of the plan's jitter draws: same seed + same plan + same op
    # sequence => byte-identical fault timeline.  None = honor the plan
    # file's own "seed" field (default 0) — matching `launch chaos`;
    # setting it here overrides the plan.
    chaos_seed: int | None = None

    # ---- input pipeline ----
    # Host->device streaming depth in Trainer.fit: with prefetch=N, up
    # to N-1 batches are host-sliced and handed to device_put ahead of
    # the running step by one background thread a fit, which goes on
    # over every epoch's end (double buffering at 2 — the trajectory is
    # identical, only the host work overlaps the device step).  It says
    # how many batches the device holds, not when their bytes cross: a
    # large dense batch is put piece by piece, each when the link has
    # room for it (parallel/feed.py).  1 = strictly serial (the
    # reference's DataIter shape, include/data_iter.h:40-55).
    prefetch: int = 2

    # ---- checkpoint / obs ----
    checkpoint_dir: str | None = None
    checkpoint_interval: int = 0      # epochs; 0 = only final save
    profile_dir: str | None = None
    # HTTP /metrics endpoint (distlr_tpu.obs): None = off, 0 = ephemeral
    # OS-assigned port (announced as "METRICS host:port"), else the fixed
    # port to bind.  Serves Prometheus text at /metrics and a JSON
    # snapshot at /metrics.json for every subsystem in this process.
    obs_metrics_port: int | None = None
    obs_metrics_host: str = "127.0.0.1"
    # Fleet-observability rendezvous dir shared by every process of one
    # run: each launched process publishes its scrape endpoint as
    # <obs_run_dir>/endpoints/<role>-<rank>.json (and, when set, a
    # missing obs_metrics_port defaults to 0 — an ephemeral endpoint is
    # the whole point of joining a fleet).  `launch obs-agg` polls the
    # dir and serves the merged fleet scrape; `launch top` renders it.
    obs_run_dir: str | None = None
    # Write the run's phase spans as Chrome trace-event JSON here at the
    # end of the command (loadable in Perfetto / chrome://tracing).
    obs_trace_path: str | None = None
    # Distributed-trace sampling rate (distlr_tpu.obs.dtrace): the
    # fraction of minted traces whose spans are journaled to
    # <obs_run_dir>/spans/ and propagated across the serve line protocol
    # and the KV wire.  Tracing arms only when obs_run_dir is set (the
    # journals need the rendezvous dir); 0 disables propagation entirely
    # and leaves the KV wire byte-identical to the pre-trace protocol.
    # Unsampled traces still feed the in-memory flight-recorder ring.
    trace_sample: float = 0.01
    # Continuous profiling (distlr_tpu.obs.profile): always-on sampling
    # rate of the per-process stack profiler, armed (like tracing) only
    # when obs_run_dir is set — windows journal to
    # <obs_run_dir>/profiles/<role>-<rank>.jsonl, and an alert edge (or
    # `launch profrec`) bursts the rate once per incident.  0 disables
    # the profiler entirely.  ~19 Hz is deliberately off the round
    # numbers: a rate sharing a period with a 10/20/100 Hz loop would
    # alias and report one frame as the whole workload.
    prof_hz: float = 19.0
    # Seconds of aggregation per journaled profile window.
    prof_window_s: float = 10.0
    # Structured fleet logging (distlr_tpu.obs.log): minimum level
    # journaled to <obs_run_dir>/logs/<role>-<rank>.jsonl as JSONL
    # records stamped with the active dtrace trace/span ids.  Armed
    # (like tracing) only when obs_run_dir is set; the human-readable
    # stderr lines are unaffected either way.
    log_level: str = "info"
    # Records kept in the logger's bounded in-memory ring (the `launch
    # logs --follow`-style recent view; like the flight recorder's span
    # ring, the ring holds what the journal level filtered out).
    log_ring: int = 2048
    # Rate-limited dedupe: identical (level, logger, message-template)
    # records within this many seconds collapse into one journaled
    # record carrying a suppressed-count.  0 journals every record.
    log_dedupe_s: float = 5.0
    # Incident engine (launch obs-agg, distlr_tpu.obs.incident):
    # seconds of context collected around an alert edge into the
    # incidents/<seq>/ bundle (WARN+ logs, chaos events, autopilot
    # decisions, rollout transitions inside the window).
    incident_window_s: float = 120.0
    # Seconds the aggregator waits after the alert edge before
    # assembling the bundle — long enough for every rank's flight dump
    # (0.25 s watcher) and the profiler's burst window (burst_s, 3 s
    # default) to land on disk.
    incident_settle_s: float = 6.0
    # Incident bundles kept under <run_dir>/incidents/ before the
    # oldest is pruned (loudly, via distlr_incident_pruned_total).
    incident_max: int = 32

    def __post_init__(self):
        ref = self.compat_mode == "reference"
        if self.compat_mode not in ("correct", "reference"):
            raise ValueError(f"compat_mode must be correct|reference, got {self.compat_mode!r}")
        if self.l2_scale_by_batch is None:
            self.l2_scale_by_batch = ref
        if self.sync_last_gradient is None:
            self.sync_last_gradient = ref
        if self.reference_rng_init is None:
            self.reference_rng_init = ref
        if self.wrap_final_batch is None:
            self.wrap_final_batch = ref
        if self.model not in ("binary_lr", "softmax", "sparse_lr",
                              "sparse_softmax", "blocked_lr"):
            raise ValueError(f"unknown model {self.model!r}")
        if self.block_size < 0 or (
            self.block_size == 0 and self.model != "blocked_lr"
        ):
            raise ValueError(
                "block_size must be positive (0 = auto, blocked_lr only: "
                "resolved from raw-CTR data by suggest_block_size)"
            )
        if self.block_groups < 0 or (
            self.block_groups > 0 and self.model != "blocked_lr"
        ):
            raise ValueError(
                "block_groups is a blocked_lr option (0 = default "
                "ceil(fields/block_size) grouping; G = near-equal G-way "
                f"field split); got block_groups={self.block_groups} "
                f"with model={self.model!r}"
            )
        if self.num_feature_dim <= 0:
            raise ValueError("num_feature_dim must be positive")
        if self.batch_size == 0 or self.batch_size < -1:
            raise ValueError("batch_size must be -1 (full shard) or positive")
        if self.feature_dtype not in ("float32", "bfloat16", "int8", "int8_dot"):
            raise ValueError(
                "feature_dtype must be float32|bfloat16|int8|int8_dot, "
                f"got {self.feature_dtype!r}"
            )
        if self.feature_dtype == "int8_dot" and self.model not in (
            "binary_lr", "softmax",
        ):
            raise ValueError(
                "feature_dtype='int8_dot' (native int8 MXU contraction) "
                f"requires a dense model (binary_lr or softmax); "
                f"got model={self.model!r}"
            )
        # (int8_dot + feature_shards > 1 is supported since r4: both the
        # psum and ring feature-sharded steps feed the native int8
        # contraction — parallel/feature_parallel.partial_logits.)
        if self.model in ("sparse_lr", "sparse_softmax", "blocked_lr"
                          ) and self.feature_dtype != "float32":
            # Quantized resident feature storage is a dense-matrix
            # capability; sparse COO / blocked lane vals stay float32 in
            # every mode. Fail here so sync and PS reject identically.
            raise ValueError(
                "feature_dtype quantization applies to dense models only; "
                f"{self.model} stores feature values as float32 "
                "(set feature_dtype='float32')"
            )
        if self.prefetch < 1:
            raise ValueError("prefetch must be >= 1 (1 = no prefetch)")
        if self.ctr_fields < 0:
            raise ValueError("ctr_fields must be >= 0 (0 = read from manifest)")
        if not 0 <= self.hash_seed < 1 << 64:
            # caught here as a config error, not an OverflowError deep in
            # splitmix64's uint64 arithmetic after data already parsed
            raise ValueError(f"hash_seed must be in [0, 2^64), got {self.hash_seed}")
        if self.ps_retry_attempts < 0:
            raise ValueError(
                f"ps_retry_attempts must be >= 0 (0 = off), "
                f"got {self.ps_retry_attempts}"
            )
        if (self.ps_retry_backoff_ms < 0
                or self.ps_retry_backoff_max_ms < self.ps_retry_backoff_ms):
            raise ValueError(
                "need 0 <= ps_retry_backoff_ms <= ps_retry_backoff_max_ms, "
                f"got {self.ps_retry_backoff_ms}/{self.ps_retry_backoff_max_ms}"
            )
        if self.ps_retry_deadline_s <= 0:
            raise ValueError(
                f"ps_retry_deadline_s must be positive, "
                f"got {self.ps_retry_deadline_s}"
            )
        if self.ps_optimizer not in ("sgd", "ftrl"):
            raise ValueError(
                f"ps_optimizer must be sgd|ftrl, got {self.ps_optimizer!r}")
        if self.ps_optimizer == "ftrl" and self.sync_last_gradient:
            raise ValueError(
                "ps_optimizer='ftrl' is incompatible with "
                "sync_last_gradient (Q1 compat is an SGD parity quirk)"
            )
        if self.ftrl_alpha <= 0:
            raise ValueError(
                f"ftrl_alpha must be positive, got {self.ftrl_alpha}")
        if self.ftrl_beta < 0 or self.ftrl_l1 < 0 or self.ftrl_l2 < 0:
            raise ValueError(
                "ftrl_beta/ftrl_l1/ftrl_l2 must be >= 0, got "
                f"{self.ftrl_beta}/{self.ftrl_l1}/{self.ftrl_l2}"
            )
        if self.ps_compress not in ("none", "int8", "signsgd"):
            raise ValueError(
                f"ps_compress must be none|int8|signsgd, "
                f"got {self.ps_compress!r}")
        if self.ps_compress != "none" and self.sync_last_gradient:
            raise ValueError(
                "ps_compress is incompatible with sync_last_gradient "
                "(Q1 compat pins the dense-SGD wire trajectory)"
            )
        if self.ps_compress == "signsgd" and self.ps_optimizer != "sgd":
            raise ValueError(
                "ps_compress='signsgd' replaces the server update rule "
                "(the group runs --optimizer=signsgd); it is incompatible "
                f"with ps_optimizer={self.ps_optimizer!r}"
            )
        if self.ps_max_delay not in (0, 1):
            raise ValueError(
                f"ps_max_delay must be 0 or 1, got {self.ps_max_delay!r}: "
                "one connection carries one operation at a time; a lock-"
                "step server holds one open round, and a keyed worker's "
                "two vectors hold the round under the step and the next, "
                "so a worker can be one of its own pushes ahead of what "
                "its weights reflect and no more")
        if self.ps_max_delay:
            keyed = self.model in ("sparse_lr", "sparse_softmax",
                                   "blocked_lr")
            why = next((why for refused, why in (
                (not self.sync_mode and not keyed,
                 "needs sync_mode for a dense model: it bounds the delay "
                 "of BSP rounds; the asynchronous dense job is pipelined "
                 "one push deep already (ps_pipeline), and carrying that "
                 "push across an epoch's end is another lineage rule"),
                (self.sync_mode and keyed,
                 f"in lock step is for dense models: a {self.model} "
                 "round pulls and pushes its own batch's rows, so no "
                 "fused reply holds the next round's weights, and a keyed "
                 "pull issued under a withheld BSP push is not written; "
                 "the keyed delayed exchange is the asynchronous "
                 "sparse_lr job's (sync_mode: false)"),
                (keyed and self.model != "sparse_lr",
                 f"is written for sparse_lr alone among the keyed models: "
                 f"a {self.model} step is numpy's on the host and holds "
                 "the interpreter, so no exchange would run under it"),
                (not self.ps_pipeline,
                 "needs ps_pipeline: the serialized pull-then-push has no "
                 "comm thread for the next round to run beside"),
                (self.ps_accum_max > 1,
                 "is incompatible with ps_accum_max > 1: a span already "
                 "runs its rounds on the span's one pull, and a delayed "
                 "span would be two kinds of staleness in one trajectory"),
                (bool(self.sync_last_gradient),
                 "is incompatible with sync_last_gradient: Q1's arrival-"
                 "order lottery has no trajectory for the delay to keep"),
                (self.ps_compress != "none",
                 f"is incompatible with ps_compress={self.ps_compress!r}: "
                 "no test holds a coded wire to the delayed reference"),
            ) if refused), None)
            if why:
                raise ValueError(f"ps_max_delay=1 {why}")
        if self.ps_accum_start < 1 or self.ps_accum_max < self.ps_accum_start:
            raise ValueError(
                "need 1 <= ps_accum_start <= ps_accum_max, got "
                f"{self.ps_accum_start}/{self.ps_accum_max} "
                "(raise --accum-max when setting --accum-start)"
            )
        if self.ps_accum_growth < 1.0:
            raise ValueError(
                f"ps_accum_growth must be >= 1, got {self.ps_accum_growth}")
        if self.ps_accum_growth_every <= 0:
            raise ValueError(
                "ps_accum_growth_every must be positive, "
                f"got {self.ps_accum_growth_every}")
        if self.ps_store_interval_s <= 0:
            raise ValueError(
                "ps_store_interval_s must be positive, "
                f"got {self.ps_store_interval_s}")
        if self.ps_store_wal_fsync_s <= 0:
            raise ValueError(
                "ps_store_wal_fsync_s must be positive, "
                f"got {self.ps_store_wal_fsync_s}")
        if self.ps_store_wal and not self.ps_store_dir:
            raise ValueError(
                "ps_store_wal requires ps_store_dir (the WAL lives in "
                "the same per-rank store directory)")
        if self.ps_store_wal and self.sync_mode:
            raise ValueError(
                "ps_store_wal requires async mode (sync_mode=False): "
                "sync-round merge state has no per-push replay semantics"
            )
        if self.chaos_seed is not None and not 0 <= self.chaos_seed < 1 << 64:
            raise ValueError(
                "chaos_seed must be None (use the plan's seed) or in "
                f"[0, 2^64), got {self.chaos_seed}")
        if self.obs_metrics_port is not None and not (
            0 <= self.obs_metrics_port < 1 << 16
        ):
            raise ValueError(
                "obs_metrics_port must be None (off) or in [0, 65536), "
                f"got {self.obs_metrics_port}"
            )
        if not 0.0 <= self.trace_sample <= 1.0:
            raise ValueError(
                f"trace_sample must be in [0, 1], got {self.trace_sample}")
        if self.prof_hz < 0:
            raise ValueError(
                f"prof_hz must be >= 0 (0 = profiler off), got "
                f"{self.prof_hz}")
        if self.prof_window_s <= 0:
            raise ValueError(
                f"prof_window_s must be positive, got {self.prof_window_s}")
        if self.log_level not in ("debug", "info", "warning", "error"):
            raise ValueError(
                "log_level must be debug|info|warning|error, got "
                f"{self.log_level!r}")
        if self.log_ring < 1:
            raise ValueError(
                f"log_ring must be >= 1, got {self.log_ring}")
        if self.log_dedupe_s < 0:
            raise ValueError(
                "log_dedupe_s must be >= 0 (0 = journal every record), "
                f"got {self.log_dedupe_s}")
        if self.incident_window_s <= 0:
            raise ValueError(
                "incident_window_s must be positive, got "
                f"{self.incident_window_s}")
        if self.incident_settle_s < 0:
            raise ValueError(
                "incident_settle_s must be >= 0, got "
                f"{self.incident_settle_s}")
        if self.incident_max < 1:
            raise ValueError(
                f"incident_max must be >= 1, got {self.incident_max}")

    # -- reference env-var shim ------------------------------------------------
    @classmethod
    def from_env(cls, env: Mapping[str, str] | None = None, **overrides: Any) -> "Config":
        """Build a Config from the reference's env-var contract.

        Unlike the reference (which segfaults on missing vars), absent vars
        fall back to the launcher defaults above.
        """
        env = os.environ if env is None else env
        kw: dict[str, Any] = dict(
            sync_mode=_env(env, "SYNC_MODE", _bool_from_int, True),
            learning_rate=_env(env, "LEARNING_RATE", float, 0.2),
            data_dir=_env(env, "DATA_DIR", str, "./a9a-data"),
            num_feature_dim=_env(env, "NUM_FEATURE_DIM", int, 123),
            num_iteration=_env(env, "NUM_ITERATION", int, 100),
            batch_size=_env(env, "BATCH_SIZE", int, -1),
            test_interval=_env(env, "TEST_INTERVAL", int, 10),
            random_seed=_env(env, "RANDOM_SEED", int, 10),
            l2_c=_env(env, "C", float, 1.0),
            num_workers=_env(env, "DMLC_NUM_WORKER", int, 1),
            num_servers=_env(env, "DMLC_NUM_SERVER", int, 1),
        )
        kw.update(overrides)
        return cls(**kw)

    def replace(self, **kw: Any) -> "Config":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)
