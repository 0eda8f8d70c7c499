"""JAX runtime introspection -> the metrics registry (ISSUE 9).

The sampling profiler (:mod:`distlr_tpu.obs.profile`) sees Python
frames; what it cannot see is the JAX runtime underneath them — a
recompile storm (every new batch shape costs a fresh XLA compile) reads
as "time in ``jit`` dispatch", and HBM pressure is invisible entirely.
This module exports the runtime signals that close that gap:

* **compile / trace-cache misses** — :class:`JitCacheProbe` wraps one
  jitted callable's executable cache (``_cache_size()``) and diffs it
  per tick into ``distlr_jax_compiles_total{site,bucket}``: a steadily
  ticking counter IS the recompile storm (the serving engine labels the
  batch bucket that triggered each one, so "bucket 1024 keeps
  recompiling" is one scrape away).
* **compile seconds and persistent-cache outcomes** — what
  ``jax.monitoring`` reports for the whole process, whichever call site
  compiled: ``distlr_jax_compile_seconds_total`` (a hit in the
  persistent cache counts its retrieval) and
  ``distlr_jax_compile_cache_total{result}``.  The listeners are
  registered when this module is imported, which every JAX role does
  before its first compile; :func:`compile_totals` reads them back
  (``chip_smoke.py`` prints a leg's share from it).
* **live device buffers** — :func:`sample_device_bytes` sums
  ``jax.live_arrays()`` into ``distlr_jax_device_buffer_bytes`` /
  ``distlr_jax_live_buffers`` gauges.  Walking every live array has a
  real cost, so call sites use :func:`maybe_sample_device_bytes` —
  throttled to one walk per ``min_interval_s`` process-wide.

This module imports jax and therefore lives OUTSIDE the jax-free core
of ``obs`` — only jax-using call sites (engine, trainers) import it;
the router, obs-agg, prof-agg, and top stay jax-free.
"""

from __future__ import annotations

import threading
import time

import jax

from distlr_tpu.obs.registry import get_registry

_reg = get_registry()
_COMPILES = _reg.counter(
    "distlr_jax_compiles_total",
    "XLA compiles (jit executable-cache misses) by instrumented call "
    "site; the serving engine labels the padded-batch bucket that "
    "triggered each one",
    labelnames=("site", "bucket"),
)
_COMPILE_SECONDS = _reg.counter(
    "distlr_jax_compile_seconds_total",
    "seconds in XLA backend compilation, process-wide, from "
    "jax.monitoring (a persistent-cache hit counts its retrieval)",
)
_COMPILE_CACHE = _reg.counter(
    "distlr_jax_compile_cache_total",
    "persistent compilation cache lookups by result (hit / miss), "
    "process-wide, from jax.monitoring",
    labelnames=("result",),
)
_DEVICE_BYTES = _reg.gauge(
    "distlr_jax_device_buffer_bytes",
    "bytes held by live jax arrays at the last introspection walk "
    "(device HBM on accelerators; host RAM on the CPU backend)",
)
_LIVE_BUFFERS = _reg.gauge(
    "distlr_jax_live_buffers",
    "live jax arrays at the last introspection walk",
)

_lock = threading.Lock()
_last_walk = 0.0

_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_SECONDS.inc(duration)


def _on_event(event: str, **_) -> None:
    result = _CACHE_EVENTS.get(event)
    if result is not None:
        _COMPILE_CACHE.labels(result=result).inc()


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def compile_totals() -> dict:
    """``{"seconds", "hits", "misses"}`` of this process so far."""
    return {
        "seconds": _COMPILE_SECONDS.value,
        "hits": int(_COMPILE_CACHE.labels(result="hit").value),
        "misses": int(_COMPILE_CACHE.labels(result="miss").value),
    }


class JitCacheProbe:
    """Diff one jitted callable's executable-cache size into the
    compile counter.  ``tick()`` after a call (or a batch of calls)
    attributes any cache growth since the last tick to the given
    bucket — cache sizes are cumulative, so throttled ticking never
    loses a compile, it only coarsens the attribution."""

    def __init__(self, jitfn, site: str):
        self._fn = jitfn
        self.site = str(site)
        self._tick_lock = threading.Lock()
        self._seen = self._size()

    def _size(self) -> int:
        return int(self._fn._cache_size())

    def tick(self, bucket: str | int = "-") -> int:
        """Record compiles since the last tick under ``bucket``;
        returns the delta.  Locked: the serve probe is process-shared,
        and two scoring threads ticking after one recompile must not
        both claim the same cache-size delta."""
        with self._tick_lock:
            size = self._size()
            delta = size - self._seen
            if delta <= 0:
                return 0
            self._seen = size
        _COMPILES.labels(site=self.site, bucket=str(bucket)).inc(delta)
        return delta


def sample_device_bytes() -> int:
    """Walk ``jax.live_arrays()`` now and publish the gauges; returns
    the byte total."""
    global _last_walk
    try:
        arrays = jax.live_arrays()
        total = sum(int(a.nbytes) for a in arrays)
        n = len(arrays)
    except Exception:  # noqa: BLE001 — introspection must never fail work
        return 0
    _DEVICE_BYTES.set(total)
    _LIVE_BUFFERS.set(n)
    with _lock:
        _last_walk = time.monotonic()
    return total


def maybe_sample_device_bytes(min_interval_s: float = 5.0) -> None:
    """Throttled :func:`sample_device_bytes` — the form hot loops call:
    one live-array walk per interval process-wide, however many call
    sites tick it."""
    with _lock:
        due = time.monotonic() - _last_walk >= min_interval_s
    if due:
        sample_device_bytes()
