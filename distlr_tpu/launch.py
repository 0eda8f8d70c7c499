"""Launcher CLI — the successor of ``examples/local.sh`` + ``gen_data.py``.

The reference launches a cluster as 1 scheduler + S servers + W workers,
all the same binary parameterized by env vars (``examples/local.sh:30-49``).
Here the sync path needs exactly ONE process (the roles collapsed into an
SPMD program over the mesh), and the PS path needs server processes that
:func:`distlr_tpu.train.ps_trainer.run_ps_local` spawns itself — so the
"launcher" is a small CLI:

    python -m distlr_tpu.launch gen-data --data-dir D --num-samples N ...
    python -m distlr_tpu.launch sync     [--data-dir D ...]
    python -m distlr_tpu.launch ps       [--async] [--num-workers W ...]
    python -m distlr_tpu.launch serve    [--model-file M | --ps-hosts H ...]
    python -m distlr_tpu.launch route    --replicas host:p1,host:p2 ...

Every algorithm knob also honors the reference's env-var contract
(``SYNC_MODE``, ``LEARNING_RATE``, ``NUM_FEATURE_DIM``, ... — see
:meth:`distlr_tpu.config.Config.from_env`), so ``local.sh``-style
invocation by exported env still works; CLI flags override env.

Multi-host: ``--coordinator host:port --num-processes N --process-id i``
bootstraps ``jax.distributed`` before building the mesh, putting all
hosts' devices into one global mesh (ICI within host, DCN across).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import sys

from distlr_tpu.config import Config
from distlr_tpu.utils.logging import get_logger

log = get_logger(__name__)


def _obs_rank(args: argparse.Namespace) -> int:
    """This process's fleet rank (the <rank> of its endpoint file):
    the explicit multi-host process id when given, else the lowest
    worker rank this process runs, else 0."""
    pid = getattr(args, "process_id", None)
    if pid is not None:  # an explicit process id 0 counts too
        return pid
    ranks = getattr(args, "worker_ranks", None)
    if ranks:
        return min(int(s) for s in ranks.split(","))
    return 0


@contextlib.contextmanager
def _obs_scope(cfg: Config, role: str | None = None, rank: int = 0):
    """Command-scoped observability: start the /metrics endpoint when
    ``--metrics-port`` is set (announced as ``METRICS host:port``, the
    same scriptable contract as ``SERVING``/``HOSTS``) and dump the
    phase-span Chrome trace at command exit when ``--trace-path`` is.

    With ``--obs-run-dir`` the process additionally joins the fleet:
    the endpoint (defaulting to an ephemeral port when no explicit
    ``--metrics-port`` was given) is published as
    ``<run_dir>/endpoints/<role>-<rank>.json`` for ``launch obs-agg``
    to discover and federate — and distributed tracing arms
    (:mod:`distlr_tpu.obs.dtrace`): sampled spans journal to
    ``<run_dir>/spans/<role>-<rank>.jsonl`` for ``launch trace-agg``,
    and the flight-recorder ring dumps to ``<run_dir>/flightrec/``
    when the aggregator trips an alert (or ``launch flightrec``
    triggers on demand)."""
    server = None
    endpoint = None
    prof_armed = False
    if cfg.obs_run_dir and role is not None:
        from distlr_tpu.obs import dtrace  # noqa: PLC0415

        dtrace.configure(cfg.obs_run_dir.split(os.pathsep)[0], role, rank,
                         sample=cfg.trace_sample)
        if cfg.prof_hz > 0:
            # continuous profiling (ISSUE 9): always-on sampling at the
            # cheap default rate, bursting once per alert incident (the
            # flight recorder's trigger) or `launch profrec`; windows
            # journal to <run_dir>/profiles/<role>-<rank>.jsonl for
            # `launch prof-agg`
            from distlr_tpu.obs import profile  # noqa: PLC0415

            profile.configure(cfg.obs_run_dir.split(os.pathsep)[0], role,
                              rank, hz=cfg.prof_hz,
                              window_s=cfg.prof_window_s)
            prof_armed = True
        # structured fleet logging (ISSUE 18): every distlr_tpu.*
        # stderr logger additionally journals JSONL records — trace-id
        # stamped, deduped, ring-buffered — to <run_dir>/logs/
        # <role>-<rank>.jsonl for `launch logs` and incident bundles.
        # The human-readable stderr lines are untouched (one extra
        # handler, never a replacement).
        from distlr_tpu.obs import log as fleetlog  # noqa: PLC0415

        fleetlog.configure(cfg.obs_run_dir.split(os.pathsep)[0], role,
                           rank, level=cfg.log_level, ring=cfg.log_ring,
                           dedupe_s=cfg.log_dedupe_s)
    port = cfg.obs_metrics_port
    if port is None and cfg.obs_run_dir and role is not None:
        port = 0  # joining a fleet implies a scrape endpoint
    if port is not None:
        from distlr_tpu.obs import start_metrics_server  # noqa: PLC0415

        server = start_metrics_server(host=cfg.obs_metrics_host, port=port)
        print(f"METRICS {server.host}:{server.port}", flush=True)
        if cfg.obs_run_dir and role is not None:
            from distlr_tpu.obs import write_endpoint  # noqa: PLC0415

            # first dir when several were given (multi-dir is an obs-agg
            # scrape-side capability; a process publishes into one fleet)
            endpoint = write_endpoint(
                cfg.obs_run_dir.split(os.pathsep)[0], role, rank,
                server.host, server.port)
    try:
        yield
    finally:
        if cfg.obs_trace_path:
            from distlr_tpu.obs import get_tracer  # noqa: PLC0415

            path = get_tracer().dump_chrome_trace(cfg.obs_trace_path)
            log.info("phase trace -> %s (load in Perfetto)", path)
        if cfg.obs_run_dir and role is not None:
            from distlr_tpu.obs import dtrace  # noqa: PLC0415
            from distlr_tpu.obs import log as fleetlog  # noqa: PLC0415

            dtrace.flush()
            fleetlog.stop()  # flushes + detaches the journal tee
        if prof_armed:
            from distlr_tpu.obs import profile  # noqa: PLC0415

            profile.stop()  # flushes the final partial window
        if server is not None:
            server.stop()
        if endpoint is not None:
            # A clean exit leaves the fleet, so the aggregator forgets
            # this rank instead of alerting it down forever; a CRASH
            # never reaches this finally — the lingering endpoint file
            # is exactly what makes the outage scrape as down.
            with contextlib.suppress(OSError):
                os.unlink(endpoint)


def _add_config_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--data-dir", dest="data_dir")
    p.add_argument("--num-feature-dim", dest="num_feature_dim", type=int)
    p.add_argument("--num-iteration", dest="num_iteration", type=int)
    p.add_argument("--batch-size", dest="batch_size", type=int)
    p.add_argument("--learning-rate", dest="learning_rate", type=float)
    p.add_argument("--l2-c", dest="l2_c", type=float)
    p.add_argument("--test-interval", dest="test_interval", type=int)
    p.add_argument("--model", choices=["binary_lr", "softmax", "sparse_lr",
                                       "sparse_softmax", "blocked_lr"])
    p.add_argument("--num-classes", dest="num_classes", type=int)
    p.add_argument("--nnz-max", dest="nnz_max", type=int,
                   help="sparse_lr: cap per-row nonzeros (pad width)")
    p.add_argument("--block-size", dest="block_size",
                   type=lambda s: 0 if s == "auto" else int(s),
                   help="blocked_lr: lanes per table row (table rows = "
                   "num-feature-dim / block-size); 'auto' samples the "
                   "raw shards and picks the cheapest statistically safe "
                   "(R, groups) layout — fewest row gathers, then fewest "
                   "lanes (data.hashing.suggest_blocking; honors a "
                   "pinned --block-groups).  Resolution is data-"
                   "dependent: pin explicit values when a model must be "
                   "re-evaluated reproducibly")
    p.add_argument("--block-groups", dest="block_groups", type=int,
                   help="blocked_lr: hash the fields into this many "
                   "conjunction groups instead of ceil(fields/block-size) "
                   "chunks; extra groups cost one row gather each but "
                   "keep group tuple spaces small enough to recur "
                   "(low-cardinality iid fields lose accuracy in a "
                   "single wide group and keep it in several narrow "
                   "ones)")
    p.add_argument("--ctr-fields", dest="ctr_fields", type=int,
                   help="blocked_lr: raw categorical fields per row "
                   "(default: read from the data dir's ctr_meta.json)")
    p.add_argument("--hash-seed", dest="hash_seed", type=int,
                   help="seed of the load-time feature hash")
    p.add_argument("--compat-mode", dest="compat_mode", choices=["correct", "reference"])
    p.add_argument("--random-seed", dest="random_seed", type=int,
                   help="RNG seed for data shuffling/synthetic draws "
                   "(default 10, the reference's RANDOM_SEED contract)")
    p.add_argument("--prefetch", dest="prefetch", type=int,
                   help="host->device streaming depth in Trainer.fit "
                   "(default 2 = double buffering; 1 = strictly serial, "
                   "the reference's DataIter shape)")
    p.add_argument("--ps-timeout", dest="ps_timeout_ms", type=int,
                   help="per-op KV receive timeout, ms (default 600000; "
                   "0 = block forever — the reference semantics, where a "
                   "sync straggler deadlocks the job)")
    p.add_argument("--feature-dtype", dest="feature_dtype",
                   choices=["float32", "bfloat16", "int8", "int8_dot"],
                   help="device-resident storage dtype for dense features "
                   "(int8: symmetric per-dataset quantization; halves/quarters "
                   "the HBM stream the dense step is bound by; int8_dot: "
                   "int8 storage plus the native int8 MXU contraction — "
                   "skips the bf16 convert wall; dense models only)")
    p.add_argument("--checkpoint-dir", dest="checkpoint_dir")
    p.add_argument("--checkpoint-interval", dest="checkpoint_interval", type=int)
    p.add_argument("--profile-dir", dest="profile_dir")
    p.add_argument("--metrics-port", dest="obs_metrics_port", type=int,
                   help="serve Prometheus /metrics (+ /metrics.json) on "
                   "this port; 0 = ephemeral, announced as "
                   "'METRICS host:port' (default: off)")
    p.add_argument("--metrics-host", dest="obs_metrics_host",
                   help="bind address for --metrics-port (default 127.0.0.1)")
    p.add_argument("--obs-run-dir", dest="obs_run_dir", action="append",
                   help="fleet rendezvous dir shared by every process of "
                   "this run: publishes this process's scrape endpoint as "
                   "endpoints/<role>-<rank>.json (implies --metrics-port 0 "
                   "when none is given); `launch obs-agg` federates the "
                   "dir, `launch top` watches it.  Repeatable for obs-agg "
                   "only (aggregation of aggregators: the trainer fleet "
                   "and the serving fleet merge into one scrape); other "
                   "commands publish into the FIRST dir given")
    p.add_argument("--trace-path", dest="obs_trace_path",
                   help="write per-phase Chrome trace-event JSON here at "
                   "the end of the run (open in Perfetto)")
    p.add_argument("--trace-sample", dest="trace_sample", type=float,
                   help="distributed-trace sampling rate in [0, 1] "
                   "(default 0.01): the fraction of requests/ops whose "
                   "spans journal to <obs-run-dir>/spans/ and propagate "
                   "across the serve protocol and the KV wire; armed only "
                   "with --obs-run-dir.  0 = off — byte-identical KV "
                   "wire; the in-memory flight-recorder ring still runs")
    p.add_argument("--prof-hz", dest="prof_hz", type=float,
                   help="continuous-profiling sampling rate (default 19; "
                   "0 = profiler off): a daemon thread folds every "
                   "thread's stack into <obs-run-dir>/profiles/ windows, "
                   "tagged by the innermost dtrace span, bursting to "
                   "high Hz once per alert incident (or `launch "
                   "profrec`); armed only with --obs-run-dir")
    p.add_argument("--prof-window", dest="prof_window_s", type=float,
                   help="seconds of aggregation per journaled profile "
                   "window (default 10)")
    p.add_argument("--log-level", dest="log_level",
                   choices=["debug", "info", "warning", "error"],
                   help="minimum level of structured log records "
                   "journaled to <obs-run-dir>/logs/<role>-<rank>.jsonl "
                   "(default info); stderr output is unaffected.  "
                   "Records are stamped with the active dtrace "
                   "trace/span ids, so `launch logs --trace` can pull "
                   "one request's log+span story")
    p.add_argument("--log-ring", dest="log_ring", type=int,
                   help="records kept in the structured logger's "
                   "bounded in-memory ring (default 2048)")
    p.add_argument("--log-dedupe", dest="log_dedupe_s", type=float,
                   help="seconds identical records collapse into one "
                   "journaled record with a suppressed-count "
                   "(default 5; 0 = journal every record)")
    p.add_argument("--incident-window", dest="incident_window_s",
                   type=float,
                   help="obs-agg: seconds of context (WARN+ logs, chaos "
                   "events, autopilot decisions, rollout transitions) "
                   "collected around an alert edge into the "
                   "incidents/<seq>/ bundle (default 120)")
    p.add_argument("--incident-settle", dest="incident_settle_s",
                   type=float,
                   help="obs-agg: seconds after the alert edge before "
                   "the bundle assembles, letting flight dumps and the "
                   "profiler burst land (default 6)")
    p.add_argument("--incident-max", dest="incident_max", type=int,
                   help="obs-agg: incident bundles kept before the "
                   "oldest is pruned (default 32)")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--num-workers", dest="num_workers", type=int)
    p.add_argument("--num-servers", dest="num_servers", type=int)
    p.add_argument("--feature-shards", dest="feature_shards", type=int,
                   help="model-axis size; >1 selects the 2D feature-sharded path")
    # multi-host bootstrap
    p.add_argument("--coordinator", help="host:port of process 0 for jax.distributed")
    p.add_argument("--num-processes", dest="num_processes", type=int)
    p.add_argument("--process-id", dest="process_id", type=int)
    p.add_argument(
        "--ps-retry-attempts", dest="ps_retry_attempts", type=int,
        help="in-place retry of transient KV transport faults: total "
        "tries per op (default 0 = fail fast).  Async workers and "
        "serving pulls reconnect + re-issue with jittered exponential "
        "backoff; sync BSP pushes always stay fail-fast (the timeout is "
        "the named straggler signal)",
    )
    p.add_argument(
        "--ps-retry-backoff", dest="ps_retry_backoff_ms", type=float,
        help="base backoff between retries, ms (default 50)",
    )
    p.add_argument(
        "--ps-retry-backoff-max", dest="ps_retry_backoff_max_ms", type=float,
        help="backoff cap, ms (default 2000)",
    )
    p.add_argument(
        "--ps-retry-deadline", dest="ps_retry_deadline_s", type=float,
        help="per-op wall deadline across retries, seconds (default 60)",
    )
    p.add_argument(
        "--ps-optimizer", dest="ps_optimizer", choices=["sgd", "ftrl"],
        help="server-side update rule for gradient pushes: sgd (the "
        "reference w -= lr*g, default) or ftrl (per-coordinate "
        "FTRL-Proximal with z/n accumulators and --ftrl-l1 "
        "sparsification — the sparse-CTR production optimizer)",
    )
    p.add_argument("--ftrl-alpha", dest="ftrl_alpha", type=float,
                   help="FTRL per-coordinate learning-rate scale "
                   "(default 0.1)")
    p.add_argument("--ftrl-beta", dest="ftrl_beta", type=float,
                   help="FTRL learning-rate smoothing (default 1.0)")
    p.add_argument("--ftrl-l1", dest="ftrl_l1", type=float,
                   help="FTRL L1 strength — sparsifies server weights "
                   "(default 0)")
    p.add_argument("--ftrl-l2", dest="ftrl_l2", type=float,
                   help="FTRL L2 strength (default 0)")
    p.add_argument(
        "--ps-compress", dest="ps_compress",
        choices=["none", "int8", "signsgd"],
        help="gradient wire codec for PS pushes (negotiated per "
        "connection; groups with a pre-codec server fall back to dense "
        "f32): int8 = block-quantized values with per-block scales "
        "(~3.9x fewer value bytes, sgd/ftrl), signsgd = 1 bit/coordinate "
        "with server-side majority-vote aggregation (spawns the group "
        "--optimizer=signsgd; use a signSGD-scale --learning-rate). "
        "Default none = byte-identical wire, trajectory pins stand",
    )
    p.add_argument("--accum-start", dest="ps_accum_start", type=int,
                   help="AdaBatch local accumulation: initial batches "
                   "per push (default 1 = push every batch)")
    p.add_argument("--accum-growth", dest="ps_accum_growth", type=float,
                   help="multiply the accumulation span by this every "
                   "--accum-growth-every pushes (default 2)")
    p.add_argument("--accum-growth-every", dest="ps_accum_growth_every",
                   type=int,
                   help="pushes between accumulation-span growths "
                   "(default 32)")
    p.add_argument("--accum-max", dest="ps_accum_max", type=int,
                   help="accumulation span cap (default 1 = accumulation "
                   "off for trainers; `launch online` defaults to 64, "
                   "its PR-6 contract)")
    p.add_argument(
        "--ps-retry-adaptive", dest="ps_retry_adaptive",
        action="store_true", default=None,
        help="scale the retry backoff base by the observed recent "
        "transport-fault rate (up to 8x under a fault storm, decaying "
        "back when quiet) instead of the static per-run base",
    )
    p.add_argument(
        "--store-dir", dest="ps_store_dir",
        help="durable server store: each spawned KV rank persists "
        "crash-consistent CRC-checked snapshots of its slice (weights "
        "+ FTRL z/n + epoch + push clock) under <dir>/rank-<r>/ and "
        "SELF-RECOVERS from them at startup — restarting with the same "
        "dir is the whole-fleet disaster-recovery path (default: off, "
        "RAM-only)",
    )
    p.add_argument(
        "--store-interval", dest="ps_store_interval_s", type=float,
        help="seconds between durable-store snapshots (default 5; the "
        "worst-case RPO window without --store-wal)",
    )
    p.add_argument(
        "--store-wal", dest="ps_store_wal", action="store_true",
        default=None,
        help="segmented append-only push WAL on top of the snapshots: "
        "every applied push replays over the newest valid snapshot on "
        "restart, driving RPO to ~0 (bounded by --store-wal-fsync). "
        "Requires --store-dir; async groups only",
    )
    p.add_argument(
        "--store-wal-fsync", dest="ps_store_wal_fsync_s", type=float,
        help="seconds between WAL group-commit fsyncs (default 0.1 — "
        "the power-loss RPO bound; kill -9 alone loses nothing, the "
        "records are already in the page cache)",
    )
    p.add_argument(
        "--cpu-devices", dest="cpu_devices", type=int,
        help="run on an N-device virtual CPU mesh instead of the default "
        "backend (same as JAX_PLATFORMS=cpu with XLA_FLAGS="
        "--xla_force_host_platform_device_count=N; env twin: "
        "DISTLR_CPU_DEVICES)",
    )


def _given(**kw):
    """The keyword arguments whose flag was given.  One left out is
    ``None`` here and falls to the receiving constructor's own default,
    so a daemon option's default is written once, where it is used."""
    return {k: v for k, v in kw.items() if v is not None}


def _config_from_args(args: argparse.Namespace) -> Config:
    """A flag reaches ``Config`` by one rule: it was given and its
    ``dest`` is a field's name.  Any other flag is its subcommand's own."""
    fields = {f.name for f in dataclasses.fields(Config)}
    overrides = {k: v for k, v in _given(**vars(args)).items() if k in fields}
    if isinstance(overrides.get("obs_run_dir"), list):
        # --obs-run-dir is repeatable (obs-agg federates several fleets);
        # Config carries the pathsep-joined list, and single-dir consumers
        # (endpoint publishing) use the first entry — see _obs_scope.
        overrides["obs_run_dir"] = os.pathsep.join(overrides["obs_run_dir"])
    cfg = Config.from_env(**overrides)
    if getattr(args, "feature_shards", None):
        cfg = cfg.replace(
            mesh_shape={"data": cfg.num_workers, "model": args.feature_shards},
            feature_shards=args.feature_shards,
        )
    return cfg


def _resolve_auto_block(cfg: Config) -> Config:
    """Resolve ``--block-size auto`` for roles that consume it (sync and
    PS workers).  NOT called by ps-server: the server's parameter dim
    doesn't depend on block_size and the server host may not have a
    copy of the data dir at all."""
    if cfg.model != "blocked_lr" or cfg.block_size != 0:
        return cfg
    from distlr_tpu.data.hashing import resolve_auto_block_size  # noqa: PLC0415

    r, g = resolve_auto_block_size(cfg.data_dir, cfg.ctr_fields,
                                   cfg.num_feature_dim,
                                   num_groups=cfg.block_groups)
    if r == 1:
        log.info("block_size auto: resolved to scalar-equivalent R=1 "
                 "(no candidate layout%s passed the recurrence/row-load "
                 "gates on this data)",
                 f" at block_groups={cfg.block_groups}" if cfg.block_groups
                 else "")
    else:
        log.info("block_size auto: resolved to R=%d, %s", r,
                 f"{g} conjunction groups" if g
                 else "default field grouping")
    return cfg.replace(block_size=r, block_groups=g)


def _select_devices(args: argparse.Namespace, role: str, *,
                    distributed: bool = False) -> None:
    """Device selection of a JAX-using role: the default backend, or the
    CPU when --cpu-devices / DISTLR_CPU_DEVICES asks for it (the env twin
    is for wrappers that cannot pass flags, examples/local.sh).  Places
    the compile cache and logs the devices the role ended up on."""
    from distlr_tpu.utils import backend  # noqa: PLC0415

    n = getattr(args, "cpu_devices", None)
    if n is None:  # flag (even an explicit 0) beats the env twin
        raw = os.environ.get("DISTLR_CPU_DEVICES", "")
        try:
            n = int(raw) if raw else 0
        except ValueError:
            raise SystemExit(
                f"DISTLR_CPU_DEVICES must be an integer, got {raw!r}"
            ) from None
    if n:
        backend.use_cpu_devices(n)
    backend.configure_compile_cache()
    if distributed:  # must precede the first backend use, i.e. the log
        _maybe_init_distributed(args)
    backend.log_devices(f"launch {role}")


def _maybe_init_distributed(args: argparse.Namespace) -> None:
    if args.coordinator:
        import jax  # noqa: PLC0415

        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
        log.info(
            "joined distributed run: process %s of %s", args.process_id, args.num_processes
        )


def cmd_gen_data(args: argparse.Namespace) -> int:
    if args.ctr_raw and not args.ctr_fields:
        print("error: --ctr-raw requires --ctr-fields", file=sys.stderr)
        return 2
    if args.ctr_tuples < 0:
        print("error: --ctr-tuples must be non-negative (0 disables the "
              "tuple table)", file=sys.stderr)
        return 2
    if args.ctr_tuples and not args.ctr_raw:
        print("error: --ctr-tuples requires --ctr-raw (the pre-hashed "
              "one-hot writer has no tuple-table mode)", file=sys.stderr)
        return 2
    if args.ctr_fields:
        if args.num_classes != 2 or args.sparsity != 0.5:
            print("error: --num-classes/--sparsity do not apply to CTR shards "
                  "(--ctr-fields writes binary-label CTR data)",
                  file=sys.stderr)
            return 2
        if args.ctr_raw:
            # Raw categorical shards (hash-scheme-agnostic): the blocked_lr
            # on-disk format; scalar hashing can also be applied at load.
            from distlr_tpu.data.hashing import write_raw_ctr_shards  # noqa: PLC0415

            manifest = write_raw_ctr_shards(
                args.data_dir,
                args.num_samples,
                args.ctr_fields,
                args.ctr_vocab,
                args.num_parts,
                seed=args.seed,
                num_distinct_tuples=args.ctr_tuples or None,
            )
            log.info("wrote %d raw-CTR train shards + test to %s",
                     len(manifest["train_parts"]), args.data_dir)
            return 0
        # Hashed one-hot CTR shards (sparse_lr workloads): num-feature-dim
        # is the bucket count, --ctr-vocab the raw categorical vocabulary.
        from distlr_tpu.data.hashing import write_ctr_shards  # noqa: PLC0415

        manifest = write_ctr_shards(
            args.data_dir,
            args.num_samples,
            args.ctr_fields,
            args.ctr_vocab,
            args.num_feature_dim,
            args.num_parts,
            seed=args.seed,
        )
    else:
        from distlr_tpu.data.synthetic import write_synthetic_shards  # noqa: PLC0415

        manifest = write_synthetic_shards(
            args.data_dir,
            args.num_samples,
            args.num_feature_dim,
            args.num_parts,
            seed=args.seed,
            num_classes=args.num_classes,
            sparsity=args.sparsity,
        )
    log.info("wrote %d train shards + test to %s", len(manifest["train_parts"]), args.data_dir)
    return 0


def cmd_sync(args: argparse.Namespace) -> int:
    _select_devices(args, "sync", distributed=True)
    from distlr_tpu.train import Trainer  # noqa: PLC0415

    cfg = _resolve_auto_block(_config_from_args(args))
    with _obs_scope(cfg, "sync", _obs_rank(args)):
        trainer = Trainer(cfg).load_data()
        trainer.fit(resume=args.resume)
        path = trainer.save_model()
        # the rate is rows over the wall of fit; the rate inside steps
        # alone (StepTimer) is the device step's, named for what it is
        log.info(
            "final accuracy %.4f, %.0f samples/sec (%.0f step_samples/sec "
            "inside steps), model -> %s",
            trainer.evaluate(), trainer.fit_samples_per_sec,
            trainer.timer.samples_per_sec, path,
        )
    return 0


def cmd_eval(args: argparse.Namespace) -> int:
    """Score a saved text model against a data dir's test split — the
    load path the reference never had (its SaveModel output,
    ``src/lr.cc:73-82``, was write-only; this reads that exact format)."""
    _select_devices(args, "eval")
    from distlr_tpu.train import Trainer  # noqa: PLC0415
    from distlr_tpu.train.export import load_model_text  # noqa: PLC0415

    cfg = _resolve_auto_block(_config_from_args(args))
    with _obs_scope(cfg, "eval", _obs_rank(args)):
        trainer = Trainer(cfg).load_data(
            # quantized dtypes derive their scale from the train split; the
            # default float32 path skips the (dominant) train ingest
            test_only=cfg.feature_dtype == "float32",
        )
        w = load_model_text(args.model_file, shape=trainer.model.param_shape)
        trainer.weights = trainer._shard_weights(w)
        m = trainer.evaluate_metrics()
        print(f"accuracy: {m['accuracy']:.4f}  test_logloss: {m['logloss']:.5f}")
    return 0


def cmd_ps(args: argparse.Namespace) -> int:
    _select_devices(args, "ps")
    from distlr_tpu.train.ps_trainer import run_ps_local, run_ps_workers  # noqa: PLC0415

    cfg = _resolve_auto_block(_config_from_args(args))
    if args.asynchronous:
        cfg = cfg.replace(sync_mode=False)
    if args.hosts:
        # Multi-host: join an existing server group (launch ps-server on
        # the server host first), running this host's worker ranks.
        if args.supervise_servers:
            print("error: --supervise-servers applies to local mode (the "
                  "server host owns its processes; supervise there)",
                  file=sys.stderr)
            return 2
        if cfg.chaos_plan:
            print("error: --chaos-plan applies to local mode (it wraps "
                  "the spawned server group); to fault-inject a remote "
                  "group, run `launch chaos --upstreams ...` and point "
                  "--hosts at the proxied ports", file=sys.stderr)
            return 2
        ranks = (
            [int(s) for s in args.worker_ranks.split(",")]
            if args.worker_ranks
            else range(cfg.num_workers)
        )
        with _obs_scope(cfg, "ps", _obs_rank(args)):
            run_ps_workers(cfg, args.hosts, ranks, save=True,
                           resume=args.resume,
                           max_restarts=args.max_worker_restarts)
    else:
        if args.worker_ranks:
            print("error: --worker-ranks requires --hosts (local mode always "
                  "runs all ranks)", file=sys.stderr)
            return 2
        if args.supervise_servers and cfg.sync_mode:
            print("error: --supervise-servers requires --async (sync BSP "
                  "state cannot be reconstructed; use --checkpoint-dir + "
                  "--resume)", file=sys.stderr)
            return 2
        with _obs_scope(cfg, "ps", _obs_rank(args)):
            run_ps_local(cfg, save=True, resume=args.resume,
                         max_restarts=args.max_worker_restarts,
                         supervise_servers=args.supervise_servers)
    return 0


def _serve_row_width(cfg: Config) -> int:
    """PS row width for serving pulls: how many flat KV slots one engine
    row key owns.  MUST match the key space ``ScoringEngine.row_keys``
    feeds the hot tracker — blocked rows own ``block_size`` lanes, and
    BOTH softmax families (``ps_param_dim`` flattens the (D, K) matrix
    row-major) own ``num_classes`` slots per feature key."""
    if cfg.model == "blocked_lr":
        return cfg.block_size
    if cfg.model in ("softmax", "sparse_softmax"):
        return cfg.num_classes
    return 1


def cmd_serve(args: argparse.Namespace) -> int:
    """Online scoring front-end over a trained model (see
    :mod:`distlr_tpu.serve`): batched jitted scoring behind a TCP line
    protocol, with hot weight reload from a checkpoint dir or a LIVE
    KV server group — the latter lets a trainer and this server run
    against the same PS simultaneously (`launch ps --async` + `launch
    serve --ps-hosts ...`)."""
    import os  # noqa: PLC0415
    import signal  # noqa: PLC0415

    _select_devices(args, "serve")
    from distlr_tpu.serve import (  # noqa: PLC0415
        CheckpointWatcher,
        HotReloader,
        LivePSWatcher,
        ScoringEngine,
        ScoringServer,
    )
    from distlr_tpu.serve.tenant import (  # noqa: PLC0415
        DEFAULT_MODEL,
        valid_model_id,
    )
    from distlr_tpu.train.export import load_weights  # noqa: PLC0415
    from distlr_tpu.train.ps_trainer import ps_param_dim  # noqa: PLC0415

    cfg = _config_from_args(args)
    live_ps = bool(args.ps_hosts or args.ps_ctl)
    if not (args.model_file or cfg.checkpoint_dir or live_ps):
        print("error: serve needs a weight source: --model-file and/or "
              "--checkpoint-dir (watched) or --ps-hosts / --ps-ctl "
              "(live pull)", file=sys.stderr)
        return 2
    if args.hot_rows < 0:
        print(f"error: --hot-rows must be >= 0 (0 = off), got "
              f"{args.hot_rows}", file=sys.stderr)
        return 2
    if args.hot_rows and not live_ps:
        print("error: --hot-rows applies to live-PS reload only "
              "(--ps-hosts / --ps-ctl); checkpoint/model-file sources "
              "always load the full table", file=sys.stderr)
        return 2
    ps_route = None
    if args.ps_ctl:
        # elastic group: serving pulls follow the membership
        # coordinator's layout — a live reshard costs the watcher one
        # re-route inside a poll, never a dead reloader
        from distlr_tpu.ps.membership import layout_client  # noqa: PLC0415

        ps_route = layout_client(args.ps_ctl)
    if cfg.model == "blocked_lr" and cfg.block_size == 0:
        if cfg.data_dir and os.path.isdir(cfg.data_dir):
            cfg = _resolve_auto_block(cfg)
        else:
            print("error: blocked_lr serving needs the trained (R, groups) "
                  "pinned (--block-size/--block-groups), or a --data-dir "
                  "to re-resolve 'auto' from", file=sys.stderr)
            return 2

    # multi-tenant namespace layout: which slice of a shared PS group's
    # key space each model id owns (must match `launch ps-server
    # --namespaces` order)
    ns_layout = None
    if args.ps_namespaces:
        if not live_ps:
            print("error: --ps-namespaces applies to live-PS reload only "
                  "(--ps-hosts / --ps-ctl)", file=sys.stderr)
            return 2
        from distlr_tpu.ps import namespace_layout  # noqa: PLC0415

        ns_layout = namespace_layout(args.ps_namespaces, ps_param_dim(cfg))

    def _ns(model_id: str) -> tuple[int, int | None]:
        if ns_layout is None:
            return 0, None
        if model_id not in ns_layout:
            raise SystemExit(
                f"error: model {model_id!r} not in --ps-namespaces "
                f"{sorted(ns_layout)}")
        return ns_layout[model_id][0], ps_param_dim(cfg) * len(ns_layout)

    def _engine() -> ScoringEngine:
        return ScoringEngine(cfg, **_given(
            max_batch_size=args.serve_max_batch_size,
            idle_evict_s=args.engine_idle_evict))

    reload_every = _given(interval_s=args.reload_interval)
    reloader = None
    hot_tracker = None
    extra_reloaders = []
    retry = None
    row_width = _serve_row_width(cfg)
    try:  # an option out of range is its constructor's ValueError
        model_id = (DEFAULT_MODEL if args.model_id is None
                    else valid_model_id(args.model_id))
        engine = _engine()
        if args.model_file:
            engine.set_weights(load_weights(
                args.model_file, shape=engine.model.param_shape))
        if live_ps:
            if args.hot_rows:
                from distlr_tpu.serve import HotSetTracker  # noqa: PLC0415

                hot_tracker = HotSetTracker(args.hot_rows)
            from distlr_tpu.ps import RetryPolicy  # noqa: PLC0415

            # serving pulls are idempotent, so the full policy applies: a
            # PS blip mid-poll is retried inside the poll; an exhausted
            # policy degrades to last-good weights (HotReloader), never
            # kills the server
            retry = RetryPolicy.from_config(cfg)
            base, total = _ns(args.ps_namespace or model_id)
            source = LivePSWatcher(
                args.ps_hosts, ps_param_dim(cfg),
                vals_per_key=max(row_width, 1),
                hot_tracker=hot_tracker,
                retry=retry,
                ns_base=base, ns_total_dim=total,
                route=ps_route,
                **_given(min_coverage=args.hot_min_coverage,
                         full_refresh_every=args.hot_full_every),
            )
        elif cfg.checkpoint_dir:
            source = CheckpointWatcher(cfg.checkpoint_dir)
        else:
            source = None
        if source is not None:
            reloader = HotReloader(engine, source, **reload_every).start()
            if not engine.has_weights:
                reloader.wait_for_weights()

        # additional hosted model versions: "--extra-model id=weights"
        # loads a static engine from a model file; "--extra-model id=@ps"
        # attaches a live-PS reloader over that id's namespace of the SAME
        # group (one ScoringServer hosting several live versions — the
        # canary shape)
        engines = {model_id: engine}
        for spec in args.extra_models or []:
            mid, eq, src = spec.partition("=")
            mid, src = mid.strip(), src.strip()
            if not eq or not mid or not src:
                print(f"error: bad --extra-model {spec!r} (want id=weights "
                      "or id=@ps)", file=sys.stderr)
                return 2
            if mid in engines:
                print(f"error: duplicate model id {mid!r}", file=sys.stderr)
                return 2
            eng = _engine()
            if src == "@ps":
                if not live_ps:
                    print("error: --extra-model id=@ps needs --ps-hosts or "
                          "--ps-ctl", file=sys.stderr)
                    return 2
                base, total = _ns(mid)
                extra_src = LivePSWatcher(
                    args.ps_hosts, ps_param_dim(cfg),
                    vals_per_key=max(row_width, 1),
                    # distinct pull client per namespace watcher
                    client_id=LivePSWatcher.SERVE_CLIENT_ID - len(engines),
                    retry=retry, ns_base=base, ns_total_dim=total,
                    route=ps_route,
                )
                rl = HotReloader(eng, extra_src, **reload_every).start()
                rl.wait_for_weights()
                extra_reloaders.append(rl)
            else:
                eng.set_weights(
                    load_weights(src, shape=eng.model.param_shape))
            engines[mid] = eng

        feedback = None
        if args.feedback_spool:
            from distlr_tpu.feedback import FeedbackSink  # noqa: PLC0415

            shard_dir = args.feedback_shards or os.path.join(
                args.feedback_spool, "shards")
            feedback = FeedbackSink(
                args.feedback_spool, shard_dir, model=cfg.model,
                negative_rate=args.feedback_negative_rate,
                tracker=hot_tracker,
                **_given(capacity=args.feedback_capacity,
                         window_s=args.feedback_window,
                         shard_records=args.feedback_shard_records,
                         drift_block=args.drift_block,
                         drift_threshold=args.drift_threshold),
            )
            log.info("feedback loop ON: spool=%s shards=%s window=%.0fs "
                     "negative_rate=%.2f", args.feedback_spool, shard_dir,
                     feedback.joiner.window_s, feedback.joiner.negative_rate)

        multi = bool(args.extra_models) or args.model_id is not None
        server = ScoringServer(
            # single unnamed engine = the pre-tenant construction (flat
            # feedback shards); an explicit --model-id or extra models
            # turn model identity on
            None if multi else engine,
            engines=engines if multi else None,
            reloader=reloader,
            extra_reloaders=extra_reloaders,
            hot_tracker=hot_tracker, feedback=feedback,
            **_given(host=args.bind, port=args.port,
                     max_wait_ms=args.max_wait_ms),
        )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    with _obs_scope(cfg, "serve", _obs_rank(args)):
        # Scriptable readiness line, like ps-server's "HOSTS ..." contract.
        print(f"SERVING {server.host}:{server.port}", flush=True)
        server.serve_forever()
    return 0


def cmd_online(args: argparse.Namespace) -> int:
    """Continuous trainer (:mod:`distlr_tpu.feedback.online`): watch the
    feedback joiner's shard dir and push Hogwild updates into the same
    live PS group the serving engines hot-reload from — the closed
    loop's training leg.  Runs until SIGTERM/Ctrl-C unless
    ``--max-shards`` / ``--idle-exit`` bound it."""
    import signal  # noqa: PLC0415
    import threading  # noqa: PLC0415

    # no device selection: the online trainer computes with the NumPy
    # gradient twins and starts no JAX backend (it never takes the chip)
    from distlr_tpu.feedback import OnlineTrainer  # noqa: PLC0415

    if args.ps_accum_max is None:
        # the online loop's PR-6 contract: growing accumulation ON by
        # default (trainers default to 1 = off; the flag overrides both)
        args.ps_accum_max = 64
    cfg = _config_from_args(args)
    ns_base, ns_total = 0, None
    if args.ps_namespaces:
        # train only this tenant's namespace slice of a shared group
        from distlr_tpu.ps import namespace_layout  # noqa: PLC0415
        from distlr_tpu.serve.tenant import DEFAULT_MODEL  # noqa: PLC0415
        from distlr_tpu.train.ps_trainer import ps_param_dim  # noqa: PLC0415

        layout = namespace_layout(args.ps_namespaces, ps_param_dim(cfg))
        ns_id = args.ps_namespace or DEFAULT_MODEL
        if ns_id not in layout:
            print(f"error: namespace {ns_id!r} not in --ps-namespaces "
                  f"{sorted(layout)}", file=sys.stderr)
            return 2
        ns_base = layout[ns_id][0]
        ns_total = ps_param_dim(cfg) * len(layout)
    route = None
    if args.ps_ctl:
        # elastic fleet: follow the membership coordinator's layout —
        # a live reshard costs this trainer a re-route, not a restart
        from distlr_tpu.ps.membership import layout_client  # noqa: PLC0415

        route = layout_client(args.ps_ctl)
    if not args.hosts and route is None:
        print("error: online needs --hosts or --ps-ctl", file=sys.stderr)
        return 2
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    with _obs_scope(cfg, "online", _obs_rank(args)):
        trainer = OnlineTrainer(
            cfg, args.hosts, args.shard_dir,
            accum_start=cfg.ps_accum_start,
            accum_growth=cfg.ps_accum_growth,
            accum_growth_every=cfg.ps_accum_growth_every,
            accum_max=cfg.ps_accum_max,
            poll_interval_s=args.poll_interval,
            worker_id=args.worker_id,
            ns_base=ns_base, ns_total_dim=ns_total,
            route=route,
        )
        print(f"ONLINE shard_dir={args.shard_dir} hosts={args.hosts} "
              f"worker={args.worker_id}", flush=True)
        try:
            stats = trainer.run(stop=stop, max_shards=args.max_shards,
                                idle_exit_s=args.idle_exit)
        except KeyboardInterrupt:
            trainer._flush_push()
            stats = trainer.stats()
        finally:
            trainer.close()
        log.info("online trainer done: %d shards, %d examples, %d pushes "
                 "(k=%d)", stats["shards_consumed"], stats["examples"],
                 stats["pushes"], stats["accum_k"])
    return 0


def cmd_route(args: argparse.Namespace) -> int:
    """Serving-tier routing front-end (:mod:`distlr_tpu.serve.router`):
    load-balance the serve line protocol across engine replicas with
    health-check ejection/reinstatement, bounded per-replica in-flight
    admission control (explicit ``ERR SHED``, never a silent hang), and
    retry-once failover for the idempotent score requests.  Deliberately
    jax-free — like ``obs-agg``, it starts in well under a second and
    never competes with the replicas for a chip."""
    import signal  # noqa: PLC0415

    from distlr_tpu.serve.router import ScoringRouter  # noqa: PLC0415

    cfg = _config_from_args(args)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        router = ScoringRouter(args.replicas, quotas=args.quota, **_given(
            host=args.bind, port=args.port,
            max_inflight=args.max_inflight,
            eject_after=args.eject_after,
            health_interval_s=args.health_interval,
            probe_backoff_s=args.probe_backoff,
            probe_backoff_max_s=args.probe_backoff_max,
            backend_timeout_s=args.backend_timeout))
    except ValueError as e:
        # option and replica-list errors get the argparse-style contract
        # (bad host:port, duplicates, out-of-range knobs), not a traceback
        print(f"error: {e}", file=sys.stderr)
        return 2
    with _obs_scope(cfg, "route", _obs_rank(args)):
        # Scriptable readiness line, like serve's "SERVING host:port".
        print(f"ROUTING {router.host}:{router.port}", flush=True)
        router.serve_forever()
    return 0


def cmd_rollout(args: argparse.Namespace) -> int:
    """Canary ramp with automatic rollback (:mod:`distlr_tpu.serve.
    rollout`): drive a routing tier's weighted primary/candidate SPLIT
    through staged weights, polling the fleet's ``distlr_alert_*``
    gauges at every hold — any bound alert firing mid-ramp rolls the
    split back in one admin round trip; a clean ramp ends in PROMOTE.
    Every transition journals to ``<obs-run-dir>/rollout/``.  Jax-free,
    like route/obs-agg.  Exit codes: 0 promoted, 3 rolled back, 4
    aborted (pre-ramp alerts / registry problems)."""
    import json  # noqa: PLC0415

    from distlr_tpu.obs.federate import discover_endpoints  # noqa: PLC0415
    from distlr_tpu.serve.rollout import (  # noqa: PLC0415
        RolloutController,
        RouterAdmin,
        fleet_alert_poller,
        parse_stages,
    )

    cfg = _config_from_args(args)
    host, _, port = args.router.rpartition(":")
    if not host or not port.isdigit():
        print(f"error: --router must be host:port, got {args.router!r}",
              file=sys.stderr)
        return 2
    try:
        stages = parse_stages(args.stages)
    except ValueError as e:
        print(f"error: bad --stages: {e}", file=sys.stderr)
        return 2
    poller = None
    fleet_url = args.fleet
    if not fleet_url and cfg.obs_run_dir:
        run_dir = cfg.obs_run_dir.split(os.pathsep)[0]
        aggs = [e for e in discover_endpoints(run_dir)
                if e["role"] == "obs-agg"]
        if aggs:
            fleet_url = f"http://{aggs[-1]['host']}:{aggs[-1]['port']}"
    if fleet_url:
        names = ([n.strip() for n in args.alerts.split(",") if n.strip()]
                 if args.alerts else None)
        # scoped SLO gating (ISSUE 12 satellite): by default only alerts
        # ATTRIBUTABLE to the candidate (label-named — e.g. its own
        # shadow-PSI series) break the ramp; an alert the primary or
        # another tenant caused no longer rolls the candidate back.
        # --gate-all-alerts restores the indiscriminate fleet gate.
        # --slo <name> (ISSUE 17) narrows further to that objective's
        # burn-rate alerts (distlr_alert_slo_burn{slo=<name>}).
        poller = fleet_alert_poller(
            fleet_url, names=names,
            scope_model=None if args.gate_all_alerts else args.candidate,
            scope_slo=args.slo)
    elif not args.unwatched:
        print("error: no alert source — pass --fleet http://host:port, an "
              "--obs-run-dir with a running obs-agg, or --unwatched to "
              "ramp on the timer alone (rollback becomes manual)",
              file=sys.stderr)
        return 2
    journal_dir = args.journal_dir or (
        cfg.obs_run_dir.split(os.pathsep)[0] if cfg.obs_run_dir else None)
    with _obs_scope(cfg, "rollout", _obs_rank(args)):
        ctrl = RolloutController(
            RouterAdmin(host, int(port)), args.tenant, args.candidate,
            stages, alert_poll=poller,
            poll_interval_s=args.poll_interval,
            shadow_fraction=args.shadow,
            settle_s=args.settle,
            journal_dir=journal_dir,
        )
        try:
            outcome = ctrl.run()
        except (OSError, RuntimeError) as e:
            print(f"error: ramp failed against the router: {e}",
                  file=sys.stderr)
            return 1
    # Scriptable contract, like METRICS/SERVING/HOSTS/TRACE.
    print(f"ROLLOUT {json.dumps(outcome)}", flush=True)
    return {"promoted": 0, "rolled_back": 3}.get(outcome["outcome"], 4)


def cmd_autopilot(args: argparse.Namespace) -> int:
    """Fleet autopilot (:mod:`distlr_tpu.autopilot`): the closed
    control loop over the elastic fleet.  Polls obs-agg's
    ``/fleet.json``, reduces it to signals (cumulative percentiles +
    windowed rates), and drives whichever actuators were bound:
    ``--ps-ctl`` scales the elastic server group, ``--router`` +
    ``--replica-pool`` promotes/demotes standby serving replicas,
    ``--worker-cmd`` spawns/retires online-worker subprocesses.  Every
    decision journals to ``<journal-dir>/autopilot/decisions.jsonl``;
    a bound ``distlr_alert_*`` firing inside the rollback window
    reverts the last action (the ``launch rollout`` fail-safe,
    repurposed).  Jax-free, like route/rollout/obs-agg."""
    import json  # noqa: PLC0415
    import signal  # noqa: PLC0415

    from distlr_tpu.autopilot import (  # noqa: PLC0415
        Actuators,
        AutopilotDaemon,
        EngineActuator,
        PolicyConfig,
        PolicyEngine,
        PSActuator,
        WorkerActuator,
        fleet_fetcher,
    )
    from distlr_tpu.obs.federate import discover_endpoints  # noqa: PLC0415
    from distlr_tpu.serve.rollout import fleet_alert_poller  # noqa: PLC0415

    cfg = _config_from_args(args)
    run_dir = (cfg.obs_run_dir.split(os.pathsep)[0]
               if cfg.obs_run_dir else None)
    fleet_url = args.fleet
    if not fleet_url and run_dir:
        aggs = [e for e in discover_endpoints(run_dir)
                if e["role"] == "obs-agg"]
        if aggs:
            fleet_url = f"http://{aggs[-1]['host']}:{aggs[-1]['port']}"
    if not fleet_url:
        print("error: no fleet source — pass --fleet http://host:port or "
              "an --obs-run-dir with a running obs-agg (the autopilot is "
              "blind without /fleet.json)", file=sys.stderr)
        return 2
    if args.router and not args.replica_pool:
        print("error: --router needs --replica-pool (the standby "
              "replicas the autopilot may promote into rotation)",
              file=sys.stderr)
        return 2
    if not (args.ps_ctl or args.router or args.worker_cmd):
        print("error: nothing to actuate — bind at least one of "
              "--ps-ctl, --router (+--replica-pool), --worker-cmd",
              file=sys.stderr)
        return 2
    try:
        actuators = Actuators(
            ps=PSActuator(args.ps_ctl) if args.ps_ctl else None,
            engine=(EngineActuator(
                args.router,
                [a.strip() for a in args.replica_pool.split(",")
                 if a.strip()],
                model=args.engine_model)
                if args.router else None),
            worker=(WorkerActuator(args.worker_cmd)
                    if args.worker_cmd else None),
        )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    poller = None
    if not args.unwatched:
        names = ([n.strip() for n in args.alerts.split(",") if n.strip()]
                 if args.alerts else None)
        poller = fleet_alert_poller(fleet_url, names=names)
    try:
        # a band's flag is PolicyConfig's field of the same name
        policy = PolicyConfig(**_given(**{
            f.name: getattr(args, f"autopilot_{f.name}")
            for f in dataclasses.fields(PolicyConfig)}))
        daemon = AutopilotDaemon(
            PolicyEngine(policy),
            actuators,
            fetch=fleet_fetcher(fleet_url),
            alert_poll=poller,
            journal_dir=args.journal_dir or run_dir,
            **_given(interval_s=args.autopilot_interval_s,
                     rate_window_s=args.autopilot_rate_window_s),
        )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    with _obs_scope(cfg, "autopilot", _obs_rank(args)):
        if run_dir:
            seeded = daemon.seed_rates_from_history(run_dir)
            if seeded:
                log.info("autopilot: seeded rate window from %d "
                         "history rows", seeded)
        # Scriptable contract, like METRICS/ROLLOUT/HOSTS.
        print("AUTOPILOT " + json.dumps({
            "fleet": fleet_url,
            "actuators": [a for a, on in (
                ("ps", args.ps_ctl), ("engine", args.router),
                ("worker", args.worker_cmd)) if on],
            "journal": daemon.journal_path,
        }), flush=True)
        signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
        try:
            if args.iterations is not None:
                for _ in range(args.iterations):
                    daemon.tick_once()
                    daemon._stop.wait(daemon.interval_s)
                actuators.close()
            else:
                daemon.run_forever()
        except KeyboardInterrupt:
            return 130
        finally:
            print("AUTOPILOT-EXIT " + json.dumps(daemon.status()),
                  flush=True)
    return 0


def cmd_chaos(args: argparse.Namespace) -> int:
    """Stand a fault-injection proxy fabric in front of an EXISTING KV
    server group (:mod:`distlr_tpu.chaos`): one proxied port per
    upstream, announced as ``HOSTS <proxied>`` — point any worker /
    server / watcher command at those instead of the real ports and the
    whole run rides the JSON fault plan.  Deliberately jax-free; the
    event log (deterministic: same seed + same plan + same traffic =
    identical log) is dumped at exit when ``--events-path`` is set."""
    import json  # noqa: PLC0415
    import signal  # noqa: PLC0415

    from distlr_tpu.chaos import ChaosFabric, FaultPlanError, load_plan  # noqa: PLC0415

    cfg = _config_from_args(args)

    # kill-fault executor for a standalone fabric: the server processes
    # are someone else's children, so --pids hands over their pids in
    # rank order ("rank:N" -> pids[N], "group" -> all of them)
    killer = None
    if args.pids:
        try:
            pids = [int(p) for p in args.pids.split(",") if p.strip()]
        except ValueError:
            print(f"error: --pids must be a comma-separated pid list, "
                  f"got {args.pids!r}", file=sys.stderr)
            return 2

        def killer(target: str) -> None:
            victims = (pids if target == "group"
                       else pids[int(target.split(":", 1)[1]):][:1])
            if not victims:
                log.warning("chaos kill target %r: no such pid", target)
            for pid in victims:
                try:
                    os.kill(pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass  # already dead: a kill fault is idempotent

    try:
        plan = load_plan(args.plan, seed=args.seed)
        fabric = ChaosFabric(args.upstreams, plan, protocol=args.protocol,
                             killer=killer)
    except (OSError, FaultPlanError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        with _obs_scope(cfg, "chaos", _obs_rank(args)), fabric:
            # Scriptable contract, like ps-server: substitute these for
            # the real group's hosts in every downstream command.
            print(f"HOSTS {fabric.hosts}", flush=True)
            for lk in fabric.links:
                log.info("chaos link %d: 127.0.0.1:%d -> %s:%d",
                         lk.link, lk.port, *lk.upstream)
            while True:
                signal.pause()
    except KeyboardInterrupt:
        return 130
    finally:
        doc = fabric.events_doc()
        log.info("chaos: %d fault events injected", len(doc["events"]))
        if args.events_path:
            # schema-pinned canonical log (chaos.proxy.EVENT_SCHEMA):
            # replay tooling — the protocol conformance pass — rejects
            # headerless/unknown-schema files instead of misparsing
            with open(args.events_path, "w") as f:
                json.dump(doc, f, indent=1)
            log.info("chaos event log -> %s (schema %d)",
                     args.events_path, doc["schema"])
    return 0


def cmd_ps_server(args: argparse.Namespace) -> int:
    """Host a KV server group in the foreground (multi-host PS mode:
    the reference's ``DMLC_ROLE=server`` processes, ``local.sh:36-41``;
    rendezvous is just TCP — no scheduler role)."""
    import signal  # noqa: PLC0415

    from distlr_tpu.ps import ServerGroup  # noqa: PLC0415
    from distlr_tpu.train.ps_trainer import (  # noqa: PLC0415
        ps_param_dim,
        server_optimizer,
    )

    # A terminated foreground group must not orphan its native server
    # processes: route SIGTERM through SystemExit so the context manager
    # below runs ServerGroup.stop() (SIGINT already raises KeyboardInterrupt,
    # which ServerGroup.wait() handles).
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    if args.asynchronous:
        # fold --async into the Config BEFORE validation: ps_store_wal's
        # async-only check must see the mode the group will actually run
        args.sync_mode = False
    cfg = _config_from_args(args)
    ports = [int(s) for s in args.ports.split(",")] if args.ports else None
    if ports and len(ports) != cfg.num_servers:
        print(f"error: {len(ports)} ports for {cfg.num_servers} servers", file=sys.stderr)
        return 2
    # multi-tenant namespaces (ISSUE 10): one group hosts N model
    # namespaces as contiguous slices of an N-times-larger key space;
    # clients scope with the same layout (serve --ps-namespaces /
    # online --ps-namespaces, or KVWorker.namespace directly).  Each
    # entry may carry a per-namespace optimizer ("v1:ftrl,v2:sgd" —
    # the ISSUE-12 satellite): the group spawns with --opt_segments so
    # one fleet hosts an FTRL model generation next to an SGD one.
    layout = None
    opt_segments = None
    per_dim = ps_param_dim(cfg)
    total_dim = per_dim
    if args.namespaces:
        from distlr_tpu.ps import (  # noqa: PLC0415
            namespace_layout,
            parse_namespace_optimizers,
        )

        layout = namespace_layout(args.namespaces, per_dim)
        total_dim = per_dim * len(layout)
        try:
            ns_opts = parse_namespace_optimizers(args.namespaces)
        except ValueError as e:
            print(f"error: bad --namespaces: {e}", file=sys.stderr)
            return 2
        if ns_opts:
            default_opt = server_optimizer(cfg)
            if default_opt == "signsgd":
                print("error: per-namespace optimizers are incompatible "
                      "with signsgd groups (sign votes only mean "
                      "majority-vote through a uniform group)",
                      file=sys.stderr)
                return 2
            opt_segments = [(base + d, ns_opts.get(m, default_opt))
                            for m, (base, d) in layout.items()]
    if args.elastic and cfg.sync_mode and not args.asynchronous:
        print("error: --elastic requires --async (a sync BSP round "
              "cannot straddle a membership change)", file=sys.stderr)
        return 2
    group = ServerGroup(
        cfg.num_servers,
        cfg.num_workers,
        total_dim,
        learning_rate=cfg.learning_rate,
        sync=cfg.sync_mode and not args.asynchronous,
        last_gradient=bool(cfg.sync_last_gradient),
        ports=ports,
        bind_any=True,
        optimizer=server_optimizer(cfg),
        ftrl_alpha=cfg.ftrl_alpha,
        ftrl_beta=cfg.ftrl_beta,
        ftrl_l1=cfg.ftrl_l1,
        ftrl_l2=cfg.ftrl_l2,
        # distributed tracing (ISSUE 8): hosted server ranks journal
        # their per-handler spans next to the Python ranks' journals
        trace_journal_dir=(
            os.path.join(cfg.obs_run_dir.split(os.pathsep)[0], "spans")
            if cfg.obs_run_dir and cfg.trace_sample > 0 else None),
        # continuous profiling (ISSUE 9): hosted ranks journal per-
        # handler thread-CPU windows next to the Python samplers'
        prof_journal_dir=(
            os.path.join(cfg.obs_run_dir.split(os.pathsep)[0], "profiles")
            if cfg.obs_run_dir and cfg.prof_hz > 0 else None),
        prof_window_s=cfg.prof_window_s,
        opt_segments=opt_segments,
        # durable store (ISSUE 20): each hosted rank persists + self-
        # recovers its slice under <store-dir>/rank-<r>/ — restarting
        # this command with the same --store-dir IS the fleet-wide
        # disaster-recovery path (ranks come back at their persisted
        # epoch, so surviving clients' fencing just works)
        store_dir=cfg.ps_store_dir,
        store_interval_s=cfg.ps_store_interval_s,
        store_wal=cfg.ps_store_wal,
        store_wal_fsync_s=cfg.ps_store_wal_fsync_s,
    )
    ctl = None
    try:
        with _obs_scope(cfg, "ps-server", _obs_rank(args)), group:
            # Workers pass this (with this host's address substituted for
            # 127.0.0.1) as --hosts.
            print(f"HOSTS {group.hosts}", flush=True)
            if layout is not None:
                # scriptable layout contract, like HOSTS: clients repeat
                # the same --ps-namespaces list, this line documents the
                # flat-slot bases the group actually serves
                print("NAMESPACES "
                      + ",".join(f"{m}={b}" for m, (b, _d) in layout.items())
                      + f" per_dim={per_dim}", flush=True)
            if args.elastic or cfg.ps_store_dir:
                # the scheduler role (membership coordination): LAYOUT/
                # STATUS/RESIZE over a tiny TCP line protocol — `launch
                # ps-ctl` drives it, clients' route= providers poll it.
                # Durable groups get the endpoint too (STORE/SNAPSHOT/
                # RESTORE admin verbs), though plan_resize refuses them.
                from distlr_tpu.ps.membership import (  # noqa: PLC0415
                    MembershipCoordinator,
                    MembershipServer,
                )

                coord = MembershipCoordinator(group)
                ctl = MembershipServer(coord, host="0.0.0.0",
                                       port=args.ctl_port or 0).start()
                print(f"PSCTL {ctl.host}:{ctl.port}", flush=True)
            group.wait()
    except KeyboardInterrupt:
        return 130  # interrupted != clean worker-driven shutdown
    finally:
        if ctl is not None:
            ctl.stop()
    return 0


def cmd_ps_ctl(args: argparse.Namespace) -> int:
    """Admin CLI for an elastic group's membership coordinator
    (:mod:`distlr_tpu.ps.membership`): ``layout`` / ``status`` /
    ``resize N`` against the ``PSCTL host:port`` endpoint a ``launch
    ps-server --elastic`` announced.  Jax-free, like route/obs-agg."""
    import json  # noqa: PLC0415

    from distlr_tpu.ps.membership import ctl_request  # noqa: PLC0415

    if args.command == "store" and args.store_dir:
        # offline inspect: read the on-disk snapshots/WAL directly via
        # ps/store.py — the post-disaster path, when no coordinator is
        # alive to ask (torn/corrupt files come back described, never
        # raised: a disaster inspection must work on a half-burned store)
        import time  # noqa: PLC0415

        from distlr_tpu.ps import store as ps_store  # noqa: PLC0415

        try:
            doc = ps_store.inspect_store(args.store_dir, now=time.time())
        except ps_store.StoreError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        print(f"PSCTL {json.dumps(doc)}", flush=True)
        return 0
    if not args.ctl:
        print("error: --ctl host:port required (or `store --store-dir "
              "<dir>` for offline inspection)", file=sys.stderr)
        return 2
    if args.command == "resize":
        if args.n is None or args.n < 1:
            print("error: resize needs a target server count "
                  "(ps-ctl --ctl host:port resize N)", file=sys.stderr)
            return 2
        line = f"RESIZE {args.n}"
        if args.no_wait:
            # daemon-friendly form: the coordinator validates, replies
            # immediately with accepted=true, and drains in the
            # background — poll `status` until it reads active again
            line += " wait=0"
    else:
        line = args.command.upper()
    try:
        doc = ctl_request(args.ctl, line)
    except (OSError, ValueError) as e:
        print(f"error: ps-ctl at {args.ctl}: {e}", file=sys.stderr)
        return 1
    # Scriptable contract, like METRICS/SERVING/HOSTS/ROLLOUT.
    print(f"PSCTL {json.dumps(doc)}", flush=True)
    return 0 if doc.get("ok", True) else 3


def cmd_obs_agg(args: argparse.Namespace) -> int:
    """Fleet metrics aggregator (:mod:`distlr_tpu.obs.federate`): poll
    every endpoint published under ``--obs-run-dir``, merge the per-rank
    registries (counters sum, histograms merge bucket-wise, gauges gain
    ``role``/``rank`` identity), derive the ``distlr_alert_*`` gauges,
    and re-serve the fleet as ``/metrics`` + ``/metrics.json`` +
    ``/fleet.json``.  Deliberately jax-free: it starts in well under a
    second and can watch a wedged run without competing for the chip."""
    import signal  # noqa: PLC0415

    from distlr_tpu.obs import MetricsServer, write_metrics_snapshot  # noqa: PLC0415
    from distlr_tpu.obs.federate import (  # noqa: PLC0415
        AlertThresholds,
        FleetScraper,
        write_endpoint,
    )

    cfg = _config_from_args(args)
    if not cfg.obs_run_dir:
        print("error: obs-agg needs --obs-run-dir (the rendezvous dir the "
              "fleet's processes publish their endpoints into)",
              file=sys.stderr)
        return 2
    # Effective alert thresholds: dataclass defaults < --thresholds-file
    # JSON < explicit CLI flags.  The distlr_alert_* threshold labels are
    # rendered from this instance, so a scrape always names the values
    # that were actually in force.
    try:
        thresholds = AlertThresholds.resolve(
            args.thresholds_file,
            barrier_wait_ratio=args.alert_barrier_wait_ratio,
            barrier_min_count=args.alert_barrier_min_count,
            push_error_rate=args.alert_push_error_rate,
            weight_age_ratio=args.alert_weight_age_ratio,
            retry_rate=args.alert_retry_rate,
            scrape_stale_s=args.stale_after,
            shadow_psi=args.alert_shadow_psi,
        )
    except (OSError, ValueError) as e:
        print(f"error: bad alert thresholds: {e}", file=sys.stderr)
        return 2
    slo_spec, slo_rules = None, None
    if args.slo_file:
        from distlr_tpu.obs.slo import SLOSpecError, load_slo_file  # noqa: PLC0415
        try:
            slo_spec, slo_rules = load_slo_file(args.slo_file)
        except SLOSpecError as e:
            print(f"error: bad --slo-file: {e}", file=sys.stderr)
            return 2
        log.info("SLO engine armed: %s",
                 ", ".join(s.name for s in slo_spec))
    try:
        scraper = FleetScraper(
            cfg.obs_run_dir, interval_s=args.interval,
            stale_after_s=thresholds.scrape_stale_s,
            thresholds=thresholds,
            slo_spec=slo_spec, slo_rules=slo_rules,
            incident_window_s=cfg.incident_window_s,
            incident_settle_s=cfg.incident_settle_s,
            incident_max=cfg.incident_max,
            **_given(history_max_lines=args.obs_tsdb_history_lines,
                     tsdb_raw_points=args.obs_tsdb_raw_points,
                     tsdb_rollup_retention_s=(
                         args.obs_tsdb_rollup_retention_s)))
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.once:
        # One-shot federation: merge whatever the run dir holds right
        # now (live endpoints AND banked snapshots/ files) and emit it —
        # a fleet snapshot without a daemon.
        scraper.scrape_once()
        fleet = scraper.fleet_json()
        if args.snapshot_path:
            write_metrics_snapshot(args.snapshot_path, scraper.merged)
            log.info("fleet snapshot -> %s", args.snapshot_path)
        else:
            print(scraper.prometheus_text(), end="")
        t = fleet["totals"]
        print(f"FLEET ranks={t['ranks']} up={t['up']} stale={t['stale']} "
              f"down={t['down']}", file=sys.stderr)
        return 0

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    port = cfg.obs_metrics_port if cfg.obs_metrics_port is not None else 0
    server = MetricsServer(
        registry=scraper, host=cfg.obs_metrics_host, port=port,
        extra_json={"/fleet.json": scraper.fleet_json},
        extra_query={"/query": scraper.query_endpoint},
    ).start()
    print(f"METRICS {server.host}:{server.port}", flush=True)
    # Published under its own role so `launch top --obs-run-dir` can find
    # the aggregator; the scraper skips obs-agg endpoints when merging.
    # With several run dirs, the FIRST is the aggregator's home.
    endpoint = write_endpoint(cfg.obs_run_dir.split(os.pathsep)[0],
                              "obs-agg", 0, server.host, server.port)
    try:
        scraper.run_forever()
    except KeyboardInterrupt:
        return 130
    finally:
        scraper.stop()
        server.stop()
        with contextlib.suppress(OSError):
            # leave cleanly so `launch top` gets the "start obs-agg
            # first" error instead of polling a dead endpoint
            os.unlink(endpoint)
    return 0


def cmd_trace_agg(args: argparse.Namespace) -> int:
    """Merge every rank's distributed-trace span journal
    (``<run_dir>/spans/*.jsonl`` — Python processes AND native
    ``distlr_kv_server`` ranks, one schema) into a single Chrome/
    Perfetto trace-event file, with per-journal process naming,
    clock-skew alignment from the kHello clock probes, and the chaos
    proxy's fault instants interleaved.  Jax-free, like obs-agg."""
    from distlr_tpu.obs import dtrace  # noqa: PLC0415

    cfg = _config_from_args(args)
    if not cfg.obs_run_dir:
        print("error: trace-agg needs --obs-run-dir (the run dir whose "
              "spans/ journals to merge; repeatable)", file=sys.stderr)
        return 2
    run_dirs = cfg.obs_run_dir.split(os.pathsep)
    doc = dtrace.write_merged_trace(run_dirs, args.out)
    meta = doc["otherData"]
    if not meta["journals"]:
        print(f"error: no span journals under "
              f"{', '.join(os.path.join(d, 'spans') for d in run_dirs)} — "
              "did the fleet run with --obs-run-dir and a non-zero "
              "--trace-sample?", file=sys.stderr)
        return 1
    # Scriptable contract, like METRICS/SERVING/HOSTS.
    print(f"TRACE {args.out} journals={len(meta['journals'])} "
          f"spans={meta['spans']} traces={len(meta['trace_ids'])}",
          flush=True)
    log.info("merged trace -> %s (load in Perfetto); journals: %s",
             args.out, ", ".join(meta["journals"]))
    return 0


def cmd_prof_agg(args: argparse.Namespace) -> int:
    """Merge every rank's continuous-profiling journal
    (``<run_dir>/profiles/*.jsonl`` — Python samplers AND native
    ``distlr_kv_server`` per-handler CPU windows, one schema) into a
    fleet-wide collapsed-stack file (``flamegraph.pl``/inferno input,
    track-prefixed) plus a speedscope-compatible JSON with one track
    per ``<role>-<rank>`` journal.  Jax-free, like obs-agg/trace-agg."""
    from distlr_tpu.obs import profile  # noqa: PLC0415

    cfg = _config_from_args(args)
    if not cfg.obs_run_dir:
        print("error: prof-agg needs --obs-run-dir (the run dir whose "
              "profiles/ journals to merge; repeatable)", file=sys.stderr)
        return 2
    run_dirs = cfg.obs_run_dir.split(os.pathsep)
    tracks = profile.merge_run_dirs(run_dirs)
    if not tracks:
        print(f"error: no profile journals under "
              f"{', '.join(os.path.join(d, 'profiles') for d in run_dirs)}"
              " — did the fleet run with --obs-run-dir and a non-zero "
              "--prof-hz?", file=sys.stderr)
        return 1
    collapsed = args.out + ".collapsed"
    speedscope = args.out + ".speedscope.json"
    n_lines = profile.write_collapsed(tracks, collapsed)
    profile.write_speedscope(tracks, speedscope)
    samples = sum(t["samples"] for t in tracks.values())
    # Scriptable contract, like METRICS/SERVING/HOSTS/TRACE.
    print(f"PROF {args.out} tracks={len(tracks)} stacks={n_lines} "
          f"samples={samples}", flush=True)
    log.info("fleet profile -> %s (flamegraph.pl/inferno) + %s "
             "(speedscope.app); tracks: %s",
             collapsed, speedscope, ", ".join(sorted(tracks)))
    return 0


def cmd_profrec(args: argparse.Namespace) -> int:
    """Trigger an on-demand profile burst: every sampler configured on
    the run dir switches to high-Hz capture once and journals exactly
    one burst window — the profiler-only twin of ``launch flightrec``
    (alert incidents trigger both automatically, under one incident
    sequence number)."""
    from distlr_tpu.obs import profile  # noqa: PLC0415

    cfg = _config_from_args(args)
    if not cfg.obs_run_dir:
        print("error: profrec needs --obs-run-dir", file=sys.stderr)
        return 2
    for d in cfg.obs_run_dir.split(os.pathsep):
        path = profile.trigger(d, reason=args.reason)
        print(f"PROFREC {path}", flush=True)
    log.info("profile-burst trigger dropped; samplers burst within one "
             "watcher poll")
    return 0


def cmd_flightrec(args: argparse.Namespace) -> int:
    """Trigger an on-demand flight-recorder dump: every process
    configured on the run dir (``--obs-run-dir`` at launch) writes its
    in-memory ring of recent spans/events — sampled or not — to
    ``<run_dir>/flightrec/<role>-<rank>-<seq>.json`` within one watcher
    poll (~0.25 s).  The alert-triggered path is automatic (obs-agg
    drops the same trigger when a ``distlr_alert_*`` gauge fires); this
    verb is the manual twin for live debugging."""
    from distlr_tpu.obs import dtrace  # noqa: PLC0415

    cfg = _config_from_args(args)
    if not cfg.obs_run_dir:
        print("error: flightrec needs --obs-run-dir", file=sys.stderr)
        return 2
    for d in cfg.obs_run_dir.split(os.pathsep):
        path = dtrace.trigger(d, alert=args.reason)
        print(f"FLIGHTREC {path}", flush=True)
    log.info("flight-recorder trigger dropped; processes dump within "
             "one watcher poll")
    return 0


def cmd_top(args: argparse.Namespace) -> int:
    """Live ANSI dashboard over the fleet scrape (`launch top`)."""
    from distlr_tpu.obs.federate import discover_endpoints  # noqa: PLC0415
    from distlr_tpu.obs.top import run_top, run_top_replay  # noqa: PLC0415

    if args.replay:
        # offline incident scrubbing: render the aggregator's banked
        # scrape history (<run_dir>/history.jsonl) frame by frame —
        # the metrics-timeline complement of the flight recorder
        color = False if args.no_color else None
        return run_top_replay(args.replay, interval=args.replay_interval,
                              color=color, rate_window=args.rate_window)
    url = args.fleet
    if not url:
        if not args.obs_run_dir:
            print("error: top needs --fleet http://host:port or "
                  "--obs-run-dir (to discover a running obs-agg)",
                  file=sys.stderr)
            return 2
        aggs = [e for e in discover_endpoints(args.obs_run_dir)
                if e["role"] == "obs-agg"]
        if not aggs:
            print(f"error: no obs-agg endpoint under {args.obs_run_dir} — "
                  "start `python -m distlr_tpu.launch obs-agg --obs-run-dir "
                  f"{args.obs_run_dir}` first", file=sys.stderr)
            return 2
        url = f"http://{aggs[-1]['host']}:{aggs[-1]['port']}"
    color = False if args.no_color else None
    return run_top(url, interval=args.interval, iterations=args.iterations,
                   color=color, rate_window=args.rate_window)


def cmd_fleet_query(args: argparse.Namespace) -> int:
    """One tsdb expression against a running obs-agg (`launch
    fleet-query`): hits the aggregator's ``/query`` endpoint and prints
    the JSON result — ``rate()``, ``increase()``,
    ``histogram_quantile()``, ``avg_over_time()`` + label matchers and
    arithmetic over the embedded fleet time-series store.  Exit codes:
    0 value, 1 no data in the window, 2 bad query/unreachable."""
    import json  # noqa: PLC0415
    import urllib.error  # noqa: PLC0415
    import urllib.parse  # noqa: PLC0415
    import urllib.request  # noqa: PLC0415

    from distlr_tpu.obs.federate import discover_endpoints  # noqa: PLC0415

    url = args.fleet
    if not url:
        if not args.obs_run_dir:
            print("error: fleet-query needs --fleet http://host:port or "
                  "--obs-run-dir (to discover a running obs-agg)",
                  file=sys.stderr)
            return 2
        run_dir = (args.obs_run_dir[0]
                   if isinstance(args.obs_run_dir, list) else args.obs_run_dir)
        aggs = [e for e in discover_endpoints(run_dir)
                if e["role"] == "obs-agg"]
        if not aggs:
            print(f"error: no obs-agg endpoint under {run_dir} — start "
                  "`python -m distlr_tpu.launch obs-agg` first",
                  file=sys.stderr)
            return 2
        url = f"http://{aggs[-1]['host']}:{aggs[-1]['port']}"
    qs = urllib.parse.urlencode({"expr": args.expr, "window": args.window})
    try:
        with urllib.request.urlopen(f"{url.rstrip('/')}/query?{qs}",
                                    timeout=args.timeout) as r:
            doc = json.load(r)
    except urllib.error.HTTPError as e:
        try:
            doc = json.load(e)
        except ValueError:
            doc = {"error": str(e)}
        print(f"error: {doc.get('error', e)}", file=sys.stderr)
        return 2
    except (OSError, ValueError) as e:
        print(f"error: aggregator unreachable at {url}: {e}",
              file=sys.stderr)
        return 2
    print(json.dumps(doc))
    return 0 if doc.get("value") is not None else 1


def cmd_logs(args: argparse.Namespace) -> int:
    """Query the fleet's structured log journals (`launch logs`): merge
    ``<run_dir>/logs/*.jsonl`` across every rank into one time-ordered
    stream, filtered by level/substring/time, tailed, or — with
    ``--trace <id>`` — narrowed to one request's records, interleaved
    with that trace's spans from the span journals (the log+span story
    of a single request).  Exit 1 when nothing matched."""
    import json  # noqa: PLC0415
    import time  # noqa: PLC0415

    from distlr_tpu.obs import dtrace  # noqa: PLC0415
    from distlr_tpu.obs import log as fleetlog  # noqa: PLC0415

    cfg = _config_from_args(args)
    if not cfg.obs_run_dir:
        print("error: logs needs --obs-run-dir (where the fleet "
              "journals records)", file=sys.stderr)
        return 2
    dirs = cfg.obs_run_dir.split(os.pathsep)
    events: list[dict] = list(fleetlog.read_records(
        dirs, level=args.level, grep=args.grep, trace=args.trace))
    if args.trace:
        # interleave the trace's spans: records say WHAT was logged,
        # spans say WHERE in the request the process was
        want = args.trace.lower().lstrip("0")
        for d in dirs:
            spans_dir = os.path.join(d, "spans")
            if not os.path.isdir(spans_dir):
                continue
            for name in sorted(os.listdir(spans_dir)):
                if not name.endswith(".jsonl"):
                    continue
                for r in dtrace.read_journal(
                        os.path.join(spans_dir, name)):
                    if r.get("type") != "span" or \
                            str(r.get("trace", "")).lstrip("0") != want:
                        continue
                    events.append({
                        "ts": float(r.get("ts", 0.0)) / 1e6,
                        "kind": "span", "src": name[:-len(".jsonl")],
                        "name": r.get("name"),
                        "dur_ms": round(float(r.get("dur", 0.0)) / 1e3, 3),
                        "trace": r.get("trace"), "span": r.get("span"),
                    })
        events.sort(key=lambda e: e.get("ts", 0.0))
    if args.tail and len(events) > args.tail:
        events = events[-args.tail:]
    for ev in events:
        if args.json:
            print(json.dumps(ev))
            continue
        ts = time.strftime("%H:%M:%S", time.localtime(ev.get("ts", 0.0)))
        ts += f".{int((ev.get('ts', 0.0) % 1) * 1000):03d}"
        if ev.get("kind") == "span":
            print(f"{ts} SPAN {ev['src']}] {ev['name']} "
                  f"({ev['dur_ms']} ms)", flush=True)
        else:
            who = f"{ev.get('role', '?')}-{ev.get('rank', '?')}"
            sup = f" (x{ev['suppressed']} suppressed)" \
                if ev.get("suppressed") else ""
            tr = f" trace={ev['trace']}" if ev.get("trace") else ""
            print(f"{ts} {str(ev.get('level', '?')).upper():7s} {who} "
                  f"{ev.get('logger')}] {ev.get('msg')}{sup}{tr}",
                  flush=True)
    return 0 if events else 1


def cmd_fleetsim(args: argparse.Namespace) -> int:
    """Deterministic fleet scenarios (`launch fleetsim`): the ISSUE-19
    discrete-event simulator driving the REAL autopilot / router /
    reshard / SLO policies at thousand-rank scale.  Thin shim over
    ``python -m distlr_tpu.analysis.fleetsim`` so operators reach it
    from the same entry point as the fleet it models."""
    from distlr_tpu.analysis.fleetsim.__main__ import (  # noqa: PLC0415
        main as fleetsim_main,
    )

    argv: list[str] = []
    if args.full:
        argv.append("--full")
    for name in args.scenario or ():
        argv.extend(["--scenario", name])
    if args.seed:
        argv.extend(["--seed", str(args.seed)])
    if args.fuzz:
        argv.extend(["--fuzz", str(args.fuzz)])
    if args.replay:
        argv.extend(["--replay", args.replay])
    if args.history:
        argv.extend(["--history", args.history])
    if args.json:
        argv.append("--json")
    if args.list:
        argv.append("--list")
    return fleetsim_main(argv)


def cmd_incident(args: argparse.Namespace) -> int:
    """Incident bundles (`launch incident`): list the bundles under
    ``<run_dir>/incidents/``, show one's facts, re-render its
    POSTMORTEM.md, or — with ``--trigger`` — fire the PR 8/9 dump
    machinery manually and assemble a bundle for a drill."""
    import json  # noqa: PLC0415
    import time  # noqa: PLC0415

    from distlr_tpu.obs import incident  # noqa: PLC0415

    cfg = _config_from_args(args)
    if not cfg.obs_run_dir:
        print("error: incident needs --obs-run-dir", file=sys.stderr)
        return 2
    dirs = cfg.obs_run_dir.split(os.pathsep)
    if args.trigger:
        log.info("manual incident trigger (%s): dumping rings, waiting "
                 "%.1fs settle for bursts", args.trigger,
                 cfg.incident_settle_s)
        path = incident.manual_trigger(
            dirs, args.trigger, window_s=cfg.incident_window_s,
            settle_s=cfg.incident_settle_s)
        if path is None:
            print("error: bundle for this trigger seq already exists",
                  file=sys.stderr)
            return 1
        print(f"INCIDENT {path}", flush=True)
        return 0
    if args.action == "list":
        incidents = incident.list_incidents(dirs[0])
        for doc in incidents:
            when = time.strftime(
                "%H:%M:%S", time.localtime(doc.get("detected_ts", 0)))
            n = sum((doc.get("events") or {}).values())
            print(f"{doc['seq']:04d}  {when}  {doc.get('reason', '?'):24s} "
                  f"events={n:<4d} {doc['path']}", flush=True)
        return 0 if incidents else 1
    seq = args.seq
    if seq is None:
        seq = incident.latest_seq(dirs[0])
    if seq is None:
        print(f"error: no incident bundles under {dirs[0]}/incidents",
              file=sys.stderr)
        return 1
    if args.action == "show":
        doc = incident.load(dirs[0], seq)
        if doc is None:
            print(f"error: no bundle for seq {seq}", file=sys.stderr)
            return 1
        print(json.dumps(doc, indent=1))
        return 0
    # render
    path = incident.render(dirs[0], seq)
    if path is None:
        print(f"error: no bundle for seq {seq}", file=sys.stderr)
        return 1
    print(f"INCIDENT {path}", flush=True)
    return 0


def build_parser() -> argparse.ArgumentParser:
    """Every subcommand's flags, and ``fn`` set to its ``cmd_*``."""
    parser = argparse.ArgumentParser(prog="distlr_tpu.launch", description=__doc__)
    sub = parser.add_subparsers(dest="cmd", required=True)

    g = sub.add_parser("gen-data", help="write seeded synthetic libsvm shards")
    g.add_argument("--data-dir", required=True)
    g.add_argument("--num-samples", type=int, default=10000)
    g.add_argument("--num-feature-dim", type=int, default=123)
    g.add_argument("--num-parts", type=int, default=4)
    g.add_argument("--seed", type=int, default=0)
    g.add_argument("--num-classes", type=int, default=2)
    g.add_argument("--sparsity", type=float, default=0.5)
    g.add_argument("--ctr-fields", type=int, default=0,
                   help="if >0: write hashed one-hot CTR shards with this "
                   "many categorical fields (sparse_lr workloads; "
                   "--num-feature-dim becomes the bucket count)")
    g.add_argument("--ctr-vocab", type=int, default=100_000,
                   help="raw categorical vocabulary size for --ctr-fields")
    g.add_argument("--ctr-raw", action="store_true",
                   help="with --ctr-fields: write RAW categorical shards "
                   "(hash-scheme-agnostic; the blocked_lr on-disk format) "
                   "instead of pre-hashed one-hot rows")
    g.add_argument("--ctr-tuples", type=int, default=0,
                   help="with --ctr-raw: draw rows from this many distinct "
                   "field-value tuples (correlated fields — the "
                   "tuple-recurrent regime the blocked path learns on) "
                   "instead of i.i.d. fields")
    g.set_defaults(fn=cmd_gen_data)

    s = sub.add_parser("sync", help="synchronous SPMD training (one process)")
    _add_config_flags(s)
    s.set_defaults(fn=cmd_sync)

    e = sub.add_parser("eval", help="score a saved text model on the test split")
    _add_config_flags(e)
    e.add_argument("--model-file", dest="model_file", required=True,
                   help="text model file (the reference SaveModel format; "
                        "what sync/ps runs write to models/part-00N)")
    e.set_defaults(fn=cmd_eval)

    p = sub.add_parser("ps", help="parameter-server training (native KV servers)")
    _add_config_flags(p)
    p.add_argument("--async", dest="asynchronous", action="store_true",
                   help="Hogwild mode (SYNC_MODE=0 equivalent)")
    p.add_argument("--hosts", help="join existing servers (comma-separated "
                   "host:port, rank order) instead of spawning local ones")
    p.add_argument("--worker-ranks", dest="worker_ranks",
                   help="with --hosts: this host's ranks, e.g. 0,1 (default: all)")
    p.add_argument("--max-worker-restarts", dest="max_worker_restarts",
                   type=int, default=0,
                   help="async mode: restart a failed worker in place up to "
                   "N times (sync recovery is --checkpoint-dir + --resume)")
    p.add_argument("--supervise-servers", dest="supervise_servers",
                   action="store_true",
                   help="async local mode: respawn dead server ranks and "
                   "re-seed them from a rolling snapshot (pair with "
                   "--max-worker-restarts)")
    p.add_argument("--chaos-plan", dest="chaos_plan",
                   help="local mode: JSON fault plan (distlr_tpu.chaos) "
                   "injected between every worker and the spawned server "
                   "group — delay/jitter, throttling, resets at op/byte "
                   "offsets, timed partitions; pair with "
                   "--ps-retry-attempts so faults cost a retry, not a "
                   "restart")
    p.add_argument("--chaos-seed", dest="chaos_seed", type=int,
                   help="seed of the plan's jitter draws (same seed + "
                   "same plan = identical fault timeline; default: the "
                   "plan file's own \"seed\", else 0 — same rule as "
                   "`launch chaos`)")
    p.add_argument("--no-ps-pipeline", dest="ps_pipeline",
                   action="store_false", default=None,
                   help="disable the fused/pipelined dense PS protocol "
                   "(fall back to the reference's serialized two-round-"
                   "trips-per-batch sequence)")
    p.add_argument("--ps-max-delay", dest="ps_max_delay", type=int,
                   choices=[0, 1],
                   help="bounded-delay consistency of the sync (BSP) dense "
                   "job: 0 (default) = lock step; 1 = a worker computes "
                   "round k on the weights after round k-2 while its push "
                   "of round k-1 stands at the servers' barrier (the "
                   "servers still apply one mean update a round; nothing "
                   "is in flight at an eval, a checkpoint or the end)")
    p.set_defaults(fn=cmd_ps)

    r = sub.add_parser(
        "serve",
        help="online scoring server (batched jit scoring + hot weight reload)",
    )
    _add_config_flags(r)
    r.add_argument("--model-file", dest="model_file",
                   help="initial weights: text model file (models/part-00N) "
                        "or an orbax checkpoint dir")
    r.add_argument("--ps-hosts", dest="ps_hosts",
                   help="pull live weights from this running KV server "
                   "group (comma-separated host:port, rank order) — serve "
                   "WHILE `launch ps --async` trains against the same group")
    r.add_argument("--ps-ctl", dest="ps_ctl",
                   help="elastic group: the membership coordinator's "
                   "PSCTL host:port — serving pulls follow layout epochs "
                   "across live reshards (optional next to --ps-hosts; "
                   "alone, the layout is fetched from the coordinator)")
    r.add_argument("--port", type=int, help="listen port (default: "
                   "ephemeral, announced as 'SERVING host:port')")
    r.add_argument("--bind", help="listen address (default 127.0.0.1)")
    r.add_argument("--serve-max-batch-size", dest="serve_max_batch_size",
                   type=int, help="top batch bucket / microbatch flush size")
    r.add_argument("--max-wait-ms", dest="max_wait_ms", type=float,
                   help="microbatch window: max ms a request waits for "
                   "co-batching company")
    r.add_argument("--reload-interval", dest="reload_interval", type=float,
                   help="weight-source poll period, seconds (the serving "
                   "staleness bound; jittered ±20%% so replicas "
                   "desynchronize)")
    r.add_argument("--hot-rows", dest="hot_rows", type=int, default=0,
                   help="with --ps-hosts: track the request traffic's hot "
                   "working set (capacity N row keys) and reload only that "
                   "slice via keyed pulls instead of the full D-dim table; "
                   "falls back to a full refresh when coverage drops "
                   "(default 0 = always full)")
    r.add_argument("--hot-min-coverage", dest="hot_min_coverage", type=float,
                   help="full-refresh fallback: minimum fraction of recent "
                   "request keys the hot set must cover (default 0.95)")
    r.add_argument("--hot-full-every", dest="hot_full_every", type=int,
                   help="also force a full refresh every N polls, bounding "
                   "cold-row staleness (default 10; 0 = coverage-driven "
                   "only)")
    r.add_argument("--engine-idle-evict", dest="engine_idle_evict",
                   type=float,
                   help="release an engine's DEVICE weight table after "
                   "this many idle seconds (host copy kept; the next "
                   "request lazily re-loads) — a cold model version "
                   "stops pinning HBM.  Default 0 = never evict")
    r.add_argument("--feedback-spool", dest="feedback_spool",
                   help="turn the feedback loop ON: journal every scored "
                   "request into this bounded spool dir, accept LABEL "
                   "lines, emit joined training shards, and run the "
                   "score-drift detector (distlr_tpu.feedback)")
    r.add_argument("--feedback-shards", dest="feedback_shards",
                   help="joined-shard output dir the online trainer "
                   "watches (default <feedback-spool>/shards)")
    r.add_argument("--feedback-window", dest="feedback_window", type=float,
                   help="delayed-label join window, seconds (default 60)")
    r.add_argument("--feedback-negative-rate", dest="feedback_negative_rate",
                   type=float, default=0.1,
                   help="probability a never-labeled request becomes a "
                   "label-0 example at window expiry (default 0.1; 0 = "
                   "drop all never-labeled)")
    r.add_argument("--feedback-shard-records", dest="feedback_shard_records",
                   type=int,
                   help="joined examples per emitted shard (default 1024)")
    r.add_argument("--feedback-capacity", dest="feedback_capacity", type=int,
                   help="in-memory spool bound; past it the least-"
                   "important oldest requests shed (default 100000)")
    r.add_argument("--drift-block", dest="drift_block", type=int,
                   help="served scores per drift-PSI comparison block "
                   "(default 512)")
    r.add_argument("--drift-threshold", dest="drift_threshold", type=float,
                   help="block-to-block PSI above which "
                   "distlr_alert_score_drift fires (default 0.25)")
    r.add_argument("--model-id", dest="model_id",
                   help="model id this server's PRIMARY engine answers as "
                   "(MODEL/@-addressing; feedback records carry it so "
                   "online training stays per-tenant).  Default "
                   "'default' = pre-tenant unaddressed behavior")
    r.add_argument("--extra-model", dest="extra_models", action="append",
                   metavar="ID=WEIGHTS|ID=@ps",
                   help="host an ADDITIONAL model version on this server "
                   "(repeatable): id=path loads a static engine from a "
                   "model file / orbax dir; id=@ps attaches a live-PS "
                   "reloader over that id's namespace of the --ps-hosts "
                   "group (needs --ps-namespaces)")
    r.add_argument("--ps-namespaces", dest="ps_namespaces",
                   help="comma-separated model ids the PS group hosts as "
                   "key-space namespaces (MUST repeat `launch ps-server "
                   "--namespaces` verbatim — order defines the slices)")
    r.add_argument("--ps-namespace", dest="ps_namespace",
                   help="which namespace the primary engine serves "
                   "(default: --model-id)")
    r.set_defaults(fn=cmd_serve)

    on = sub.add_parser(
        "online",
        help="continuous trainer: consume joined feedback shards as they "
             "appear and push Hogwild updates into the live PS the "
             "serving engines hot-reload from (the closed loop)",
    )
    _add_config_flags(on)
    on.add_argument("--hosts",
                    help="the live ASYNC KV server group (comma-separated "
                    "host:port, rank order) — the same group `launch serve "
                    "--ps-hosts` pulls from; optional with --ps-ctl "
                    "(the layout is fetched from the coordinator)")
    on.add_argument("--ps-ctl", dest="ps_ctl",
                    help="elastic group: the membership coordinator's "
                    "PSCTL host:port — this trainer follows layout "
                    "epochs (a live reshard costs one re-route, never "
                    "a restart)")
    on.add_argument("--shard-dir", dest="shard_dir", required=True,
                    help="joined-shard dir the serving tier's feedback "
                    "sink writes (serve --feedback-shards)")
    on.add_argument("--worker-id", dest="worker_id", type=int, default=0,
                    help="this trainer's id among the online workers "
                    "sharing one shard dir (distinct PS client_id + log "
                    "identity; shards are claimed exclusively via the "
                    ".claim rename protocol, so any number of `launch "
                    "online` processes can share the dir)")
    on.add_argument("--poll-interval", dest="poll_interval", type=float,
                    default=0.5,
                    help="shard-dir scan period while idle, seconds "
                    "(default 0.5)")
    on.add_argument("--max-shards", dest="max_shards", type=int, default=0,
                    help="exit after consuming N shards (0 = run forever; "
                    "scripts/benches)")
    on.add_argument("--idle-exit", dest="idle_exit", type=float,
                    help="exit after this many seconds with no new shards "
                    "(default: wait forever)")
    on.add_argument("--ps-namespaces", dest="ps_namespaces",
                    help="comma-separated model ids the PS group hosts as "
                    "key-space namespaces (repeat `launch ps-server "
                    "--namespaces` verbatim); this trainer pushes only "
                    "into its own namespace slice")
    on.add_argument("--ps-namespace", dest="ps_namespace",
                    help="which namespace this trainer trains (default: "
                    "--model-id / serve_model_id); point --shard-dir at "
                    "the same tenant's shard subdir")
    on.set_defaults(fn=cmd_online)

    rt = sub.add_parser(
        "route",
        help="serving-tier front-end: load-balance the serve protocol over "
             "engine replicas with health checks, admission control "
             "(explicit load shed), and retry-once failover",
    )
    _add_config_flags(rt)
    rt.add_argument("--replicas", required=True,
                    help="comma-separated host:port of running `launch "
                    "serve` replicas (rank order); replicas may die, "
                    "reload, and rejoin under live traffic")
    rt.add_argument("--port", type=int, help="listen port (default: "
                    "ephemeral, announced as 'ROUTING host:port')")
    rt.add_argument("--bind", help="listen address (default 127.0.0.1)")
    rt.add_argument("--max-inflight", dest="max_inflight", type=int,
                    help="admission control: per-replica in-flight request "
                    "budget; past it requests shed with an explicit "
                    "'ERR SHED' reply (default 64)")
    rt.add_argument("--eject-after", dest="eject_after", type=int,
                    help="consecutive transport failures before a replica "
                    "is ejected from rotation (default 3)")
    rt.add_argument("--health-interval", dest="health_interval", type=float,
                    help="active STATS probe period for idle in-rotation "
                    "replicas, seconds (default 1)")
    rt.add_argument("--probe-backoff", dest="probe_backoff", type=float,
                    help="base of the exponential reinstatement-probe "
                    "backoff for ejected replicas, seconds (default 0.5)")
    rt.add_argument("--probe-backoff-max", dest="probe_backoff_max",
                    type=float,
                    help="cap of the reinstatement-probe backoff, seconds "
                    "(default 30)")
    rt.add_argument("--backend-timeout", dest="backend_timeout", type=float,
                    help="per-exchange socket timeout toward replicas, "
                    "seconds (default 30)")
    rt.add_argument("--quota", dest="quota", metavar="MODEL=RATE[:BURST],..",
                    help="per-tenant token-bucket admission quotas "
                    "(requests/s; burst defaults to 2*rate): a tenant "
                    "over budget gets an explicit 'ERR SHED tenant' "
                    "reply and its own distlr_tenant_shed_total counter, "
                    "distinct from capacity sheds")
    rt.set_defaults(fn=cmd_route)

    ro = sub.add_parser(
        "rollout",
        help="canary ramp with automatic rollback: stage a tenant's "
             "traffic onto a candidate model version via the router's "
             "SPLIT admin line, roll back the moment any bound "
             "distlr_alert_* gauge fires, PROMOTE on a clean ramp; "
             "every transition journals to <obs-run-dir>/rollout/",
    )
    _add_config_flags(ro)
    ro.add_argument("--router", required=True,
                    help="the routing front-end's host:port (what "
                    "`launch route` announced as ROUTING)")
    ro.add_argument("--tenant", required=True,
                    help="model id whose traffic is being ramped (the "
                    "PRIMARY)")
    ro.add_argument("--candidate", required=True,
                    help="model id taking the ramped traffic (must be "
                    "registered in the router's --replicas spec)")
    ro.add_argument("--stages", default="0.05:10,0.25:10,0.5:10,1.0:10",
                    help="comma-separated weight:hold_s ramp stages, "
                    "ascending to 1.0 (default "
                    "'0.05:10,0.25:10,0.5:10,1.0:10')")
    ro.add_argument("--shadow", type=float, default=0.0,
                    help="also mirror this fraction of the tenant's "
                    "traffic to the candidate during the ramp "
                    "(distlr_tenant_shadow_psi feeds the alert inputs; "
                    "default 0 = no shadow)")
    ro.add_argument("--settle", type=float, default=0.0,
                    help="with --shadow: observe the shadow for this "
                    "many seconds BEFORE the first split stage "
                    "(default 0)")
    ro.add_argument("--fleet",
                    help="obs-agg URL (http://host:port) whose "
                    "/fleet.json alerts gate the ramp; default: "
                    "discovered from --obs-run-dir")
    ro.add_argument("--alerts",
                    help="comma-separated alert gauge names to bind "
                    "(default: every distlr_alert_*)")
    ro.add_argument("--gate-all-alerts", dest="gate_all_alerts",
                    action="store_true",
                    help="roll back on ANY bound firing alert, "
                    "attributed or not (the pre-scoping behavior). "
                    "Default: only alerts attributable to the CANDIDATE "
                    "— label-named, e.g. its shadow-PSI series — gate "
                    "the ramp; the aggregator-unreachable synthetic "
                    "always gates")
    ro.add_argument("--slo",
                    help="gate the ramp on one SLO's burn-rate alerts "
                    "only (distlr_alert_slo_burn{slo=NAME} from an "
                    "obs-agg running with --slo-file); composes with "
                    "candidate attribution via the SLO spec's labels")
    ro.add_argument("--unwatched", action="store_true",
                    help="ramp on the stage timers alone, with NO alert "
                    "gate (rollback becomes manual) — tests/dev only")
    ro.add_argument("--poll-interval", dest="poll_interval", type=float,
                    default=0.5,
                    help="alert poll period during holds, seconds "
                    "(default 0.5)")
    ro.add_argument("--journal-dir", dest="journal_dir",
                    help="journal transitions under DIR/rollout/ "
                    "(default: the first --obs-run-dir)")
    ro.set_defaults(fn=cmd_rollout)

    ap = sub.add_parser(
        "autopilot",
        help="fleet autopilot: closed-loop scaling daemon — polls "
             "obs-agg's /fleet.json and drives ps-ctl RESIZE, router "
             "ADDREPLICA/DELREPLICA over a standby pool, and online-"
             "worker subprocesses through banded hysteresis with "
             "rollback-on-alert; every decision journals to "
             "<journal-dir>/autopilot/decisions.jsonl",
    )
    _add_config_flags(ap)
    ap.add_argument("--fleet",
                    help="obs-agg URL (http://host:port) polled for "
                    "/fleet.json; default: discovered from "
                    "--obs-run-dir")
    ap.add_argument("--ps-ctl", dest="ps_ctl",
                    help="elastic group coordinator host:port (what "
                    "`launch ps-server --elastic` announced as PSCTL): "
                    "binds the ps actuator (non-blocking RESIZE wait=0)")
    ap.add_argument("--router",
                    help="routing front-end host:port (ROUTING): binds "
                    "the engine actuator; needs --replica-pool")
    ap.add_argument("--replica-pool", dest="replica_pool",
                    help="comma-separated host:port of PRE-STARTED "
                    "standby `launch serve` replicas the autopilot may "
                    "promote into rotation (idle standbys evict their "
                    "weights, so parked capacity is cheap)")
    ap.add_argument("--engine-model", dest="engine_model",
                    default="default",
                    help="router model id whose replica set is scaled "
                    "(default 'default')")
    ap.add_argument("--worker-cmd", dest="worker_cmd",
                    help="online-worker command template with a "
                    "{worker_id} placeholder, e.g. \"python -m "
                    "distlr_tpu.launch online ... --worker-id "
                    "{worker_id}\": binds the worker actuator "
                    "(spawn/SIGTERM-retire; the .claim shard protocol "
                    "makes churn exactly-once)")
    ap.add_argument("--alerts",
                    help="comma-separated alert gauge names that gate "
                    "rollback (default: every distlr_alert_*; bind "
                    "explicit names when routine shed/latency alerts "
                    "are expected during scale-up)")
    ap.add_argument("--unwatched", action="store_true",
                    help="no alert gate: never roll an action back "
                    "(tests/dev only)")
    ap.add_argument("--journal-dir", dest="journal_dir",
                    help="journal decisions under DIR/autopilot/ "
                    "(default: the first --obs-run-dir)")
    ap.add_argument("--iterations", type=int,
                    help="run N ticks then exit cleanly (default: "
                    "until SIGTERM/Ctrl-C)")
    ap.add_argument("--interval", dest="autopilot_interval_s", type=float,
                    help="tick period, seconds (default 2)")
    ap.add_argument("--hysteresis-ticks", dest="autopilot_hysteresis_ticks",
                    type=int,
                    help="consecutive breached ticks before a band may "
                    "act (default 2)")
    ap.add_argument("--cooldown", dest="autopilot_cooldown_s", type=float,
                    help="per-actuator seconds after an action during "
                    "which that actuator holds (default 10)")
    ap.add_argument("--rollback-window", dest="autopilot_rollback_window_s",
                    type=float,
                    help="seconds after an action inside which a firing "
                    "bound alert reverts it (default 60)")
    ap.add_argument("--ps-min", dest="autopilot_ps_min", type=int,
                    help="server-count floor (default 1)")
    ap.add_argument("--ps-max", dest="autopilot_ps_max", type=int,
                    help="server-count ceiling (default 8)")
    ap.add_argument("--engine-min", dest="autopilot_engine_min", type=int,
                    help="in-rotation replica floor (default 1)")
    ap.add_argument("--engine-max", dest="autopilot_engine_max", type=int,
                    help="in-rotation replica ceiling (default 8)")
    ap.add_argument("--worker-min", dest="autopilot_worker_min", type=int,
                    help="online-worker floor (default 1)")
    ap.add_argument("--worker-max", dest="autopilot_worker_max", type=int,
                    help="online-worker ceiling (default 8)")
    ap.add_argument("--staleness-high", dest="autopilot_staleness_high",
                    type=float,
                    help="staleness_pushes_p99 above which the ps band "
                    "scales up (default 64)")
    ap.add_argument("--push-rate-high", dest="autopilot_push_rate_high",
                    type=float,
                    help="fleet pushes/s PER SERVER above which the ps "
                    "band scales up (default 200)")
    ap.add_argument("--push-rate-low", dest="autopilot_push_rate_low",
                    type=float,
                    help="fleet pushes/s per server below which the ps "
                    "band scales down (default 20)")
    ap.add_argument("--shed-rate-high", dest="autopilot_shed_rate_high",
                    type=float,
                    help="router sheds/s above which the engine band "
                    "scales up (default 0.5)")
    ap.add_argument("--route-p99-high", dest="autopilot_route_p99_high_ms",
                    type=float,
                    help="route p99 ms above which the engine band "
                    "scales up (default 250)")
    ap.add_argument("--req-rate-low", dest="autopilot_req_rate_low",
                    type=float,
                    help="requests/s PER REPLICA below which (with zero "
                    "shed) the engine band scales down (default 5)")
    ap.add_argument("--lag-high", dest="autopilot_lag_high", type=float,
                    help="pending feedback shards above which the "
                    "worker band scales up (default 4)")
    ap.add_argument("--lag-low", dest="autopilot_lag_low", type=float,
                    help="pending feedback shards below which the "
                    "worker band scales down (default 1)")
    ap.add_argument("--rate-window", dest="autopilot_rate_window_s",
                    type=float,
                    help="horizon of the windowed push/shed/req rates, "
                    "seconds (default 10)")
    ap.set_defaults(fn=cmd_autopilot)

    v = sub.add_parser("ps-server", help="host a KV server group (multi-host PS)")
    _add_config_flags(v)
    v.add_argument("--async", dest="asynchronous", action="store_true")
    v.add_argument("--ports", help="fixed ports, comma-separated (default: ephemeral)")
    v.add_argument("--namespaces",
                   help="host N model namespaces in one group (comma-"
                   "separated model ids, order defines the key-space "
                   "slices): the group's dim becomes N x the per-model "
                   "dim and the layout is announced as 'NAMESPACES "
                   "id=base,...' — clients repeat the same list via "
                   "--ps-namespaces.  An id may carry a per-namespace "
                   "optimizer suffix ('v1:ftrl,v2:sgd'): that slice's "
                   "keys run the named update rule (sgd|ftrl), so one "
                   "group hosts different model generations")
    v.add_argument("--elastic", action="store_true",
                   help="async only: run the membership coordinator "
                   "(scheduler role) next to the group — announced as "
                   "'PSCTL host:port'; `launch ps-ctl` resizes the "
                   "group live, clients with a route provider follow "
                   "epoch flips without restarts")
    v.add_argument("--ctl-port", dest="ctl_port", type=int,
                   help="with --elastic: fixed ps-ctl port (default: "
                   "ephemeral)")
    v.set_defaults(fn=cmd_ps_server)

    pc = sub.add_parser(
        "ps-ctl",
        help="admin CLI against an elastic group's membership "
             "coordinator (`launch ps-server --elastic`): show the "
             "layout, poll a migration, or live-reshard the group",
    )
    pc.add_argument("--ctl",
                    help="the coordinator endpoint (what ps-server "
                    "announced as PSCTL host:port); optional only for "
                    "`store --store-dir` offline inspection")
    pc.add_argument("command",
                    choices=["layout", "status", "resize",
                             "store", "snapshot", "restore"],
                    help="layout = the routing contract clients follow; "
                    "status = migration state + last-resize stats; "
                    "resize = live-reshard to N server ranks (blocks "
                    "until the drain completes); store = inspect the "
                    "durable store's snapshots/WAL per rank; snapshot = "
                    "force every rank to snapshot NOW (SIGUSR1); "
                    "restore = force every rank back to its on-disk "
                    "state (SIGKILL + respawn through native recovery)")
    pc.add_argument("--store-dir", dest="store_dir",
                    help="store only: inspect this on-disk store "
                    "directly (no live coordinator needed — the "
                    "post-disaster path)")
    pc.add_argument("n", nargs="?", type=int,
                    help="target server count (resize only)")
    pc.add_argument("--no-wait", dest="no_wait", action="store_true",
                    help="resize only: return the moment the "
                    "coordinator ACCEPTS the reshard (RESIZE n wait=0) "
                    "instead of blocking through the drain; poll "
                    "`status` until it reads active — what the "
                    "autopilot's ps actuator does")
    pc.set_defaults(fn=cmd_ps_ctl)

    c = sub.add_parser(
        "chaos",
        help="fault-injection proxy in front of an existing KV server "
             "group: deterministic delay/throttle/reset/partition/kill "
             "from a JSON plan; workers connect to the proxied HOSTS",
    )
    _add_config_flags(c)
    c.add_argument("--upstreams", required=True,
                   help="the REAL server group, comma-separated host:port "
                   "in rank order (what `launch ps-server` printed)")
    c.add_argument("--plan", required=True,
                   help="JSON fault plan (see distlr_tpu/chaos/plan.py "
                   "for the schema; malformed plans are rejected loudly "
                   "at startup)")
    c.add_argument("--seed", type=int, default=None,
                   help="jitter seed (default: the plan's own, else 0); "
                   "same seed + same plan + same traffic = identical "
                   "fault-event log")
    c.add_argument("--events-path", dest="events_path",
                   help="write the deterministic fault-event log here as "
                   "JSON at exit")
    c.add_argument("--pids", default=None,
                   help="comma-separated pids of the upstream server "
                   "processes in RANK order — arms plan kind 'kill' "
                   "(SIGKILL of rank:N / the whole group at a "
                   "deterministic op or clock offset, the DR drill's "
                   "power-loss primitive); without it kill faults only "
                   "record their event and warn")
    c.add_argument("--protocol", choices=["kv", "serve"], default="kv",
                   help="client->server framing the proxy parses: 'kv' "
                   "(native PS links, the default) or 'serve' (the "
                   "serving tier's line protocol — front a router or "
                   "engine replicas so op-offset faults land per request "
                   "line)")
    c.set_defaults(fn=cmd_chaos)

    a = sub.add_parser(
        "obs-agg",
        help="fleet metrics aggregator: merge every rank's /metrics into "
             "one scrape + /fleet.json (+ derived distlr_alert_* gauges)",
    )
    _add_config_flags(a)
    a.add_argument("--interval", type=float, default=2.0,
                   help="scrape period, seconds (default 2)")
    a.add_argument("--stale-after", dest="stale_after", type=float,
                   help="seconds without a successful scrape before a rank "
                   "counts stale->down and distlr_alert_scrape_stale fires "
                   "(default 10; overrides a thresholds-file value)")
    a.add_argument("--thresholds-file", dest="thresholds_file",
                   help="JSON object overriding AlertThresholds fields "
                   "(barrier_wait_ratio, barrier_min_count, "
                   "push_error_rate, scrape_stale_s, weight_age_ratio); "
                   "explicit CLI flags win over the file, and the "
                   "distlr_alert_* threshold labels reflect the effective "
                   "values")
    a.add_argument("--alert-barrier-wait-ratio",
                   dest="alert_barrier_wait_ratio", type=float,
                   help="barrier-wait p99 alert fires above this multiple "
                   "of the median step time (default 2)")
    a.add_argument("--alert-barrier-min-count",
                   dest="alert_barrier_min_count", type=int,
                   help="minimum barrier-wait observations before the "
                   "stall alert may fire (default 8)")
    a.add_argument("--alert-push-error-rate", dest="alert_push_error_rate",
                   type=float,
                   help="PS push error+timeout rate above which "
                   "distlr_alert_ps_push_errors fires (default 0.01)")
    a.add_argument("--alert-weight-age-ratio", dest="alert_weight_age_ratio",
                   type=float,
                   help="async weight age alert fires above this multiple "
                   "of the median step time (default 10)")
    a.add_argument("--alert-retry-rate", dest="alert_retry_rate", type=float,
                   help="distlr_alert_ps_retry_rate fires above this "
                   "fleet share of KV op attempts that are in-place "
                   "retry re-issues (default 0.05) — degradation the "
                   "resilience layer is absorbing, visible before errors")
    a.add_argument("--alert-shadow-psi", dest="alert_shadow_psi",
                   type=float,
                   help="distlr_alert_shadow_psi fires per (tenant, "
                   "candidate) when the shadow-scored candidate's score "
                   "distribution diverges from its primary's past this "
                   "PSI (default 0.25) — the candidate-attributed "
                   "evidence `launch rollout`'s scoped gate binds")
    a.add_argument("--once", action="store_true",
                   help="scrape+merge once and exit: print the fleet "
                   "Prometheus text (or write --snapshot-path) instead of "
                   "serving — how capture scripts bank a fleet snapshot")
    a.add_argument("--snapshot-path", dest="snapshot_path",
                   help="with --once: write the merged fleet registry here "
                   "(.json = JSON snapshot, else Prometheus text)")
    a.add_argument("--slo-file", dest="slo_file",
                   help="SLO spec JSON: objectives over tsdb SLI "
                   "expressions, compiled into error-budget gauges "
                   "(distlr_slo_*) and multi-window burn-rate alerts "
                   "(distlr_alert_slo_burn{slo,window}) evaluated every "
                   "scrape — see docs/CONFIG.md and the README's 'SLOs "
                   "& error budgets'")
    a.add_argument("--obs-tsdb-raw-points", dest="obs_tsdb_raw_points",
                   type=int,
                   help="embedded tsdb raw-ring size per series, in "
                   "scrape frames (default 512)")
    a.add_argument("--obs-tsdb-rollup-retention-s",
                   dest="obs_tsdb_rollup_retention_s", type=float,
                   help="seconds of 10s/60s rollup history kept per "
                   "series (default 3600); evictions count into "
                   "distlr_tsdb_points_dropped_total")
    a.add_argument("--obs-tsdb-history-lines",
                   dest="obs_tsdb_history_lines", type=int,
                   help="lines per on-disk history.jsonl segment before "
                   "rotation (default 2000; one rotated segment kept)")
    a.set_defaults(fn=cmd_obs_agg)

    fq = sub.add_parser(
        "fleet-query",
        help="evaluate one time-series expression (rate / increase / "
             "histogram_quantile / *_over_time + label matchers and "
             "arithmetic) against a running obs-agg's embedded tsdb "
             "and print the JSON result",
    )
    fq.add_argument("expr",
                    help="the expression, e.g. "
                    "'rate(route_requests{role=route})' or "
                    "'histogram_quantile(0.99, "
                    "distlr_route_request_seconds)'")
    fq.add_argument("--obs-run-dir", dest="obs_run_dir",
                    help="fleet run dir: discovers the running obs-agg's "
                    "endpoint file")
    fq.add_argument("--fleet", help="aggregator URL (http://host:port) — "
                    "overrides --obs-run-dir discovery")
    fq.add_argument("--window", type=float, default=60.0,
                    help="trailing evaluation window, seconds (default "
                    "60)")
    fq.add_argument("--timeout", type=float, default=5.0,
                    help="HTTP timeout, seconds (default 5)")
    fq.set_defaults(fn=cmd_fleet_query)

    ta = sub.add_parser(
        "trace-agg",
        help="merge every rank's distributed-trace span journal "
             "(Python + native KV servers) into one Chrome/Perfetto "
             "trace with clock-skew alignment and chaos-fault markers",
    )
    _add_config_flags(ta)
    ta.add_argument("--out", default="merged_trace.json",
                    help="output Chrome trace-event JSON path (default "
                    "merged_trace.json; open in Perfetto)")
    ta.set_defaults(fn=cmd_trace_agg)

    pa = sub.add_parser(
        "prof-agg",
        help="merge every rank's continuous-profiling journal (Python "
             "samplers + native KV-server CPU windows) into a fleet "
             "collapsed-stack file and a speedscope JSON, one track per "
             "rank",
    )
    _add_config_flags(pa)
    pa.add_argument("--out", default="fleet_profile",
                    help="output stem: writes <out>.collapsed "
                    "(flamegraph.pl/inferno) and <out>.speedscope.json "
                    "(speedscope.app); default fleet_profile")
    pa.set_defaults(fn=cmd_prof_agg)

    pr = sub.add_parser(
        "profrec",
        help="trigger an on-demand profile burst: every sampler on the "
             "run dir captures at high Hz once and journals one burst "
             "window (the profiler-only twin of flightrec)",
    )
    _add_config_flags(pr)
    pr.add_argument("--reason", default="manual",
                    help="reason string recorded in the trigger + burst "
                    "windows (default 'manual')")
    pr.set_defaults(fn=cmd_profrec)

    fr = sub.add_parser(
        "flightrec",
        help="trigger an on-demand flight-recorder dump: every process "
             "on the run dir writes its ring of recent (even unsampled) "
             "spans to <run_dir>/flightrec/",
    )
    _add_config_flags(fr)
    fr.add_argument("--reason", default="manual",
                    help="reason string recorded in the trigger + dumps "
                    "(default 'manual')")
    fr.set_defaults(fn=cmd_flightrec)

    t = sub.add_parser(
        "top",
        help="live terminal dashboard over a fleet scrape (per-rank step "
             "rate, op latencies, staleness, firing alerts)",
    )
    t.add_argument("--obs-run-dir", dest="obs_run_dir",
                   help="fleet run dir: discovers the running obs-agg's "
                   "endpoint file")
    t.add_argument("--fleet", help="aggregator URL (http://host:port) — "
                   "overrides --obs-run-dir discovery")
    t.add_argument("--interval", type=float, default=1.0,
                   help="refresh period, seconds (default 1)")
    t.add_argument("--iterations", type=int,
                   help="render N frames then exit (default: until Ctrl-C)")
    t.add_argument("--no-color", dest="no_color", action="store_true",
                   help="plain text frames (no ANSI colors/clears)")
    t.add_argument("--rate-window", dest="rate_window", type=int, default=10,
                   help="frames of history behind the windowed req/s and "
                   "push/s columns (default 10 scrapes)")
    t.add_argument("--replay", dest="replay",
                   help="scrub a PAST incident offline: render this "
                   "banked scrape history (<run_dir>/history.jsonl, "
                   "written by the aggregator) frame by frame instead of "
                   "polling a live fleet")
    t.add_argument("--replay-interval", dest="replay_interval", type=float,
                   default=0.0,
                   help="seconds between replayed frames (default 0 = "
                   "as fast as the terminal draws)")
    t.set_defaults(fn=cmd_top)

    lg = sub.add_parser(
        "logs",
        help="query the fleet's structured log journals: tail/grep/"
             "level-filter across every rank, or follow one request "
             "with --trace",
    )
    _add_config_flags(lg)
    lg.add_argument("--level", choices=["debug", "info", "warning",
                                        "error"],
                    help="minimum record level (default: all journaled)")
    lg.add_argument("--grep", help="only records whose message contains "
                    "this substring")
    lg.add_argument("--trace", help="only this trace id's records, "
                    "interleaved with its spans (one request's story)")
    lg.add_argument("--tail", type=int, default=0,
                    help="print only the last N events (default 0 = all)")
    lg.add_argument("--json", action="store_true",
                    help="one JSON object per line instead of text")
    lg.set_defaults(fn=cmd_logs)

    inc = sub.add_parser(
        "incident",
        help="incident bundles: list/show/render the postmortem bundles "
             "obs-agg assembles on alert edges, or --trigger a manual "
             "drill bundle",
    )
    _add_config_flags(inc)
    inc.add_argument("action", nargs="?", default="list",
                     choices=["list", "show", "render"],
                     help="list bundles, show one's facts as JSON, or "
                     "(re-)render its POSTMORTEM.md (default: list)")
    inc.add_argument("--seq", type=int,
                     help="bundle sequence (default: the newest)")
    inc.add_argument("--trigger", metavar="REASON",
                     help="fire the flight-recorder/profiler dump "
                     "machinery now and assemble a manual bundle with "
                     "this reason")
    inc.set_defaults(fn=cmd_incident)

    fs = sub.add_parser(
        "fleetsim",
        help="deterministic discrete-event fleet scenarios property-"
             "testing the real autopilot/router/reshard/SLO policies "
             "(replay ids: fleetsim:<scenario>:<seed>)",
    )
    fs.add_argument("--full", action="store_true",
                    help="deep tier: add the multi-seed fuzz sweep")
    fs.add_argument("--scenario", action="append", metavar="NAME",
                    help="run only this scenario (repeatable)")
    fs.add_argument("--seed", type=int, default=0,
                    help="RNG seed (default 0, the pinned digest seed)")
    fs.add_argument("--fuzz", type=int, default=0, metavar="N",
                    help="additionally run seeds 1..N per scenario")
    fs.add_argument("--replay", metavar="REPLAY_ID",
                    help="re-run one pinned replay id and print its "
                    "byte-stable verdict")
    fs.add_argument("--history", metavar="PATH",
                    help="bank the simulated fleet.json frames for "
                    "`launch top --replay PATH` (single scenario)")
    fs.add_argument("--json", action="store_true",
                    help="one JSON result doc per run instead of prose")
    fs.add_argument("--list", action="store_true",
                    help="list scenarios and mutants, then exit")
    fs.set_defaults(fn=cmd_fleetsim)
    return parser


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
