"""One freshness rule for the in-tree native artifacts.

The PS server/client (``ps/native``) and the libsvm parser
(``data/native``) build on demand with ``make``.  File times cannot say
whether an artifact matches the sources beside it — a checkout, a copy
or a container layer resets them — so every artifact carries a stamp
file ``<artifact>.stamp`` holding a hash of the sources, the Makefile
(which holds the flags) and the compiler environment it was built from.
A missing artifact, a missing stamp or a different hash means rebuild;
a failed build is an error, never a silently stale binary.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import subprocess
import threading

_lock = threading.Lock()


def source_digest(src_dir: str) -> str:
    """sha256 over every ``*.cc`` / ``*.h`` / ``Makefile`` in ``src_dir``
    (names and contents) plus the ``CXX`` / ``CXXFLAGS`` overrides the
    Makefiles honour."""
    h = hashlib.sha256()
    for name in sorted(os.listdir(src_dir)):
        if name.endswith((".cc", ".h")) or name == "Makefile":
            h.update(name.encode() + b"\0")
            with open(os.path.join(src_dir, name), "rb") as f:
                h.update(f.read() + b"\0")
    for var in ("CXX", "CXXFLAGS"):
        h.update(f"{var}={os.environ.get(var, '')}\0".encode())
    return h.hexdigest()


def read_stamp(artifact: str) -> str | None:
    """The source hash ``artifact`` was built from (None: never stamped)."""
    try:
        with open(artifact + ".stamp") as f:
            return f.read().strip()
    except OSError:
        return None


def _stale(artifacts: list[str], digest: str) -> list[str]:
    return [a for a in artifacts
            if not os.path.exists(a) or read_stamp(a) != digest]


@contextlib.contextmanager
def _file_lock(src_dir: str):
    """Serialize concurrent builds across processes (fcntl advisory lock;
    worker processes on one host may race the same outputs)."""
    import fcntl  # noqa: PLC0415  (POSIX-only, like the native build itself)

    with open(os.path.join(src_dir, ".build.lock"), "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(f, fcntl.LOCK_UN)


def ensure_built(src_dir: str, artifacts: list[str], *,
                 force: bool = False) -> None:
    """Rebuild every artifact (a ``make`` target of ``src_dir``, named by
    its file) that is not stamped with the current source hash; all of
    them under ``force``.  Raises RuntimeError with the compiler output
    when the build fails."""
    digest = source_digest(src_dir)
    with _lock:
        if not force and not _stale(artifacts, digest):
            return
        with _file_lock(src_dir):
            # re-check: another process may have built while we waited
            stale = artifacts if force else _stale(artifacts, digest)
            if not stale:
                return
            # -B: make's own mtime test is the rule this module replaces
            proc = subprocess.run(
                ["make", "-B", "-C", src_dir,
                 *(os.path.basename(a) for a in stale)],
                capture_output=True, text=True,
            )
            if proc.returncode != 0:
                raise RuntimeError(
                    f"native build failed in {src_dir}:\n"
                    f"{proc.stdout}\n{proc.stderr}")
            for a in stale:
                tmp = f"{a}.stamp.{os.getpid()}"
                with open(tmp, "w") as f:
                    f.write(digest + "\n")
                os.replace(tmp, a + ".stamp")
