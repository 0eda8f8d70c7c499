"""Build/locate the native PS components (server binary + client .so).

The reference gets its runtime from a prebuilt submodule + vendored
libzmq; here the native pieces live in-tree (``ps/native``) and build on
demand with ``make`` — no external deps beyond a C++17 toolchain.

**Sanitizer matrix** (``DISTLR_NATIVE_VARIANT={tsan,asan,ubsan}``): the
same sources build instrumented twins (``make -C ps/native
sanitizers``), and setting the env var makes THIS module hand out the
instrumented artifacts — so every existing consumer (``ServerGroup``
spawns, the ctypes client, the chaos/elastic/compress e2e suites) runs
against sanitizer binaries with zero per-site changes:

* ``tsan``  — TSan server binary AND TSan client library.  Loading an
  instrumented ``.so`` into an uninstrumented Python requires the TSan
  runtime preloaded (``LD_PRELOAD=$(g++ -print-file-name=libtsan.so)``);
  :func:`client_lib` fails with exactly that instruction when missing
  rather than letting ``dlopen`` die on a static-TLS error.
* ``asan`` / ``ubsan`` — instrumented SERVER binaries (the client stays
  standard: dlopen-ing the ASan runtime into an uninstrumented host
  process is unsupported by the runtime itself).

Checked-in suppression files (``ps/native/*.supp``, empty to start) are
appended to the sanitizer options of every spawned server via
:func:`sanitizer_environ`, so a report is a failure until it is fixed
or explicitly audited.
"""

from __future__ import annotations

import os
import re

from distlr_tpu.utils.native_build import ensure_built

#: sanitizer variant -> (make target, server suffix, options env var)
_VARIANTS = {
    "tsan": ("tsan", "_tsan", "TSAN_OPTIONS"),
    "asan": ("asan", "_asan", "ASAN_OPTIONS"),
    "ubsan": ("ubsan", "_ubsan", "UBSAN_OPTIONS"),
}


def native_dir() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "native")


def native_variant() -> str:
    """The active sanitizer variant ("" = the standard build)."""
    v = os.environ.get("DISTLR_NATIVE_VARIANT", "").strip().lower()
    if v in ("", "none"):
        return ""
    if v not in _VARIANTS:
        raise ValueError(
            f"DISTLR_NATIVE_VARIANT must be one of {tuple(_VARIANTS)} "
            f"(or unset), got {v!r}")
    return v


def server_binary(variant: str | None = None) -> str:
    """The KV server binary of ``variant`` (default: the active one) —
    which is what routes every ServerGroup spawn (and the e2e suites
    riding them) onto the instrumented build."""
    v = native_variant() if variant is None else variant
    suffix = _VARIANTS[v][1] if v else ""
    return os.path.join(native_dir(), f"distlr_kv_server{suffix}")


def _tsan_runtime_preloaded() -> bool:
    return "libtsan" in os.environ.get("LD_PRELOAD", "")


def client_lib() -> str:
    """The ctypes client library.  Variant ``tsan`` hands out the
    TSan-instrumented twin — the reader/retry paths Python drives from
    many threads finally under a sanitizer — and requires the TSan
    runtime preloaded into this process.  ``asan``/``ubsan`` keep the
    standard client (server-side instrumentation only)."""
    if native_variant() == "tsan":
        if not _tsan_runtime_preloaded():
            import shutil  # noqa: PLC0415

            gxx = shutil.which("g++") or "g++"
            raise RuntimeError(
                "DISTLR_NATIVE_VARIANT=tsan needs the TSan runtime "
                "preloaded into this Python process: relaunch with "
                f"LD_PRELOAD=$({gxx} -print-file-name=libtsan.so) "
                "(dlopen-ing the instrumented client without it dies on "
                "a static-TLS allocation error)")
        return os.path.join(native_dir(), "libdistlr_kv_tsan.so")
    return os.path.join(native_dir(), "libdistlr_kv.so")


def suppressions_file() -> str | None:
    """The checked-in suppression file of the active variant (None for
    the standard build)."""
    v = native_variant()
    if not v:
        return None
    return os.path.join(native_dir(), f"{v}.supp")


def sanitizer_environ(base: dict | None = None) -> dict | None:
    """Environment for spawning native processes under the active
    variant.  Caller options like ``log_path``/``exitcode`` survive
    (tests point log_path at a tmp dir and scan it), but HOST-ONLY
    noise controls are stripped so the native processes stay strictly
    checked: ``suppressions=`` is forced to the checked-in per-variant
    file (a jax host process may run with extra host-noise entries; a
    server must only ever see the audited native file), and
    ``report_mutex_bugs=`` is dropped (the pytest harness disables
    mutex-misuse reports for ITSELF because uninstrumented
    jaxlib/Eigen teardown false-positives there — servers keep them).
    ASan leak checking is off by default (the matrix hunts memory
    ERRORS; exit-time leak inventory of a SIGTERMed server is a
    different project).  Returns None for the standard build — spawn
    with the inherited environment, byte-identical to every earlier
    round."""
    v = native_variant()
    if not v:
        return None
    env = dict(os.environ if base is None else base)
    var = _VARIANTS[v][2]
    # sanitizer runtimes accept ':' as well as whitespace between
    # options — tokenize on both, or a colon-joined string would smuggle
    # a host relaxation past the strip inside one "token"
    tokens = [t for t in re.split(r"[\s:]+", env.get(var, "")) if t
              and not t.startswith(("suppressions=", "report_mutex_bugs="))]
    supp = suppressions_file()
    if supp and os.path.exists(supp):
        tokens.append(f"suppressions={supp}")
    if v == "asan" and not any(t.startswith("detect_leaks=")
                               for t in tokens):
        tokens.append("detect_leaks=0")
    if tokens:
        env[var] = " ".join(tokens)
    return env


def _outputs(variant: str) -> list[str]:
    outs = [server_binary(""), os.path.join(native_dir(), "libdistlr_kv.so")]
    if variant:
        outs.append(server_binary(variant))
        if variant == "tsan":
            outs.append(os.path.join(native_dir(), "libdistlr_kv_tsan.so"))
    return outs


def build_native(force: bool = False, variant: str | None = None) -> None:
    """Idempotently ``make`` the native components plus a sanitizer
    variant's (default: the active ``DISTLR_NATIVE_VARIANT``).  Freshness
    is the stamp rule of :mod:`distlr_tpu.utils.native_build`: no-op when
    every artifact was built from the sources as they are now, rebuild
    otherwise."""
    if variant is None:
        variant = native_variant()
    ensure_built(native_dir(), _outputs(variant), force=force)
