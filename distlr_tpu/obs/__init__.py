"""Unified observability: metrics registry, phase tracing, exporters.

The cross-cutting layer every subsystem reports through (ISSUE 2): one
process-wide :class:`MetricsRegistry` of labeled counters/gauges/
histograms (PS server lifecycle + supervisor events, PS client op
latency/bytes, trainer step rate and staleness, serving occupancy and
request latency), a :func:`trace_phase` span API whose per-phase
breakdown explains where step time went (Chrome trace-event dumps load
in Perfetto), and exporters: Prometheus text + JSON snapshot over a
stdlib HTTP endpoint (``--metrics-port`` / ``Config.obs_metrics_port``).

Metric namespace (see README "Observability" for the full table):

* ``distlr_ps_server_*``  — ServerGroup/ServerSupervisor lifecycle
* ``distlr_ps_client_*``  — native KV client ops, latency, bytes
* ``distlr_train_*``      — step/sample counters, rates, staleness
  (seconds gauge AND the ``_staleness_pushes`` Hogwild histogram)
* ``distlr_serve_*``      — request/engine/batcher series
* ``distlr_phase_seconds``— per-phase histogram behind the tracer
* ``distlr_fleet_*`` / ``distlr_alert_*`` — fleet-scrape meta-series
  and derived alert gauges (:mod:`distlr_tpu.obs.federate`, served by
  ``launch obs-agg`` and rendered live by ``launch top``)
* ``distlr_trace_*``      — distributed-trace span/journal/flight-
  recorder accounting (:mod:`distlr_tpu.obs.dtrace`, merged by
  ``launch trace-agg``)
* ``distlr_prof_*``       — continuous-profiling sampler/window/burst
  accounting (:mod:`distlr_tpu.obs.profile`, merged by
  ``launch prof-agg``)
* ``distlr_jax_*``        — JAX runtime introspection: jit compile
  counts + live device-buffer bytes (:mod:`distlr_tpu.obs.jaxrt`)
* ``distlr_kv_server_*``  — native-server runtime mirrored from the
  kStats probe (per-handler thread-CPU seconds)

The complete generated reference is ``docs/METRICS.md``
(:mod:`distlr_tpu.obs.metrics_doc`; a tier-1 lint keeps it in sync).
"""

from distlr_tpu.obs.exporters import (  # noqa: F401
    MetricsServer,
    install_snapshot_atexit,
    snapshot_env_paths,
    start_metrics_server,
    write_metrics_snapshot,
)
from distlr_tpu.obs.federate import (  # noqa: F401
    AlertThresholds,
    FleetMergeError,
    FleetScraper,
    discover_endpoints,
    evaluate_alerts,
    merge_snapshots,
    write_endpoint,
)
from distlr_tpu.obs.registry import (  # noqa: F401
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
)
from distlr_tpu.obs.tracing import (  # noqa: F401
    PhaseTracer,
    get_tracer,
    trace_phase,
)

# One-shot processes (a benchmark run) bank their metrics via
# DISTLR_METRICS_SNAPSHOT=<path> instead of holding a port.
install_snapshot_atexit()
