"""Where the program runs, and where it keeps compiled programs.

One rule: the program runs on JAX's default backend.  The CPU is chosen
only explicitly — ``JAX_PLATFORMS=cpu`` in the environment, or
``--cpu-devices N`` / ``DISTLR_CPU_DEVICES=N`` (:func:`use_cpu_devices`).
Nothing decides to carry on on the CPU because it found no accelerator:
a measurement that is only meaningful on the chip calls
:func:`require_tpu` and exits non-zero anywhere else.

A chip belongs to one process at a time.  The roles that take it are
``launch sync``, ``eval``, ``serve`` and ``ps`` (dense models); a second
such role on a one-chip host needs ``--cpu-devices`` or its own chip.
Asking for ``jax.devices("cpu")`` does not avoid this: JAX initialises
every platform it can find on the first backend call, the accelerator
included.
"""

from __future__ import annotations

import os

from distlr_tpu.utils.logging import get_logger

log = get_logger(__name__)

#: set by the operator to place the compile cache; JAX reads it itself
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

# the path is part of the cache key's directory, so it must not move
# between runs: derived from the package's location, never a temp dir
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def use_cpu_devices(n: int) -> None:
    """Run this process on ``n`` virtual CPU devices (``--cpu-devices``).

    Call before the first backend use: the device count is read when the
    CPU client is created."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    import jax  # noqa: PLC0415

    jax.config.update("jax_platforms", "cpu")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory and
    return it.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads
    it and nothing is set here; otherwise the cache lives in
    ``<checkout>/.jax_cache``.  Call before the first compile — JAX
    resolves the cache once per process."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax  # noqa: PLC0415

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` of the default backend, as JAX
    reports it.  Initialises the backend."""
    import jax  # noqa: PLC0415

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def log_devices(role: str) -> None:
    """One start-up line naming the devices a JAX-using role runs on."""
    d = device_summary()
    log.info("%s runs on platform=%s device_kind=%s devices=%d",
             role, d["platform"], d["kind"], d["count"])


def require_tpu(what: str) -> dict:
    """Exit non-zero unless the default backend is a TPU: the full-size
    mode of a benchmark measures the chip and says nothing anywhere else."""
    d = device_summary()
    if d["platform"] != "tpu":
        raise SystemExit(
            f"{what}: full-size mode measures the TPU; found "
            f"platform={d['platform']} device_kind={d['kind']} "
            f"devices={d['count']}. Run it on the chip, or use the "
            "script's --smoke mode.")
    return d


def start_benchmark(what: str, *, full_size: bool) -> dict:
    """What every benchmark main does first: place the compile cache and
    take the devices — a TPU or a non-zero exit for a full-size run,
    wherever JAX lands for a ``--smoke`` one.  Returns the
    fields every row carries so that it says where it ran:
    ``{"backend", "device_kind", "devices"}``."""
    configure_compile_cache()
    d = require_tpu(what) if full_size else device_summary()
    return {"backend": d["platform"], "device_kind": d["kind"],
            "devices": d["count"]}
