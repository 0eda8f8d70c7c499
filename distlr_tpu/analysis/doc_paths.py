"""Document lint: a path or a ``make`` target a document names exists.

Narrow on purpose, so that it needs no list of exceptions.  In the code
of each of :data:`DOCS` (its code spans and fenced blocks), a word that
begins with one of this repo's top-level directories must exist (a
trailing ``:line`` or ``::test`` is cut, a glob may match, and
``distlr_tpu/sync`` may name ``sync.py``), and every
``make [-C dir] target`` must be a target of that ``Makefile``.  Paths
of the reference (``src/lr.cc``), of a run directory and of HTTP routes
begin with none of those directories.  History, plans and the survey
(``CHANGES.md``, ``ROADMAP.md``, ``PERF.md``, ``SURVEY.md``) are not linted.
"""

from __future__ import annotations

import glob
import os
import re

from distlr_tpu.analysis.report import Finding, repo_root

DOCS = ("README.md", "examples/README.md", "PARITY.md", "docs/ANALYSIS.md",
        "docs/CONFIG.md", "docs/INCIDENTS.md", "docs/METRICS.md")
TOP_DIRS = ("distlr_tpu/", "chipbench/", "benchmarks/", "tests/", "docs/",
            "examples/")

_FENCE = re.compile(r"^[ \t]*```.*?^[ \t]*```", re.M | re.S)
_SPAN = re.compile(r"`([^`]+)`")
_PATH = re.compile(r"(?<![\w./\-])(?:%s)[\w./*\-]*"
                   % "|".join(map(re.escape, TOP_DIRS)))
_MAKE = re.compile(
    r"(?<![\w\-])make((?:[ \t]+-C[ \t]+\S+)?)((?:[ \t]+[a-z][\w\-]*)+)")
_TARGET = re.compile(r"^([A-Za-z0-9_.\-]+)\s*:(?!=)", re.M)


def _exists(root: str, token: str) -> bool:
    path = os.path.join(root, token.rstrip("/."))
    return bool(os.path.exists(path) or os.path.exists(path + ".py")
                or glob.glob(path))


def _make_targets(root: str, directory: str) -> set[str]:
    try:
        with open(os.path.join(root, directory, "Makefile")) as f:
            return set(_TARGET.findall(f.read()))
    except OSError:
        return set()


def _code(text: str):
    """``(line, code)`` of every fenced block and every code span (a span
    that wraps is one line of code)."""
    prose = _FENCE.sub(lambda m: "\n" * m.group(0).count("\n"), text)
    for m in _FENCE.finditer(text):
        yield text.count("\n", 0, m.start()) + 1, m.group(0)
    for m in _SPAN.finditer(prose):
        yield prose.count("\n", 0, m.start()) + 1, " ".join(m.group(1).split())


def check_doc(doc: str, root: str | None = None) -> list[Finding]:
    root = root or repo_root()
    with open(os.path.join(root, doc)) as f:
        text = f.read()
    findings = []
    for ln, code in _code(text):
        missing = [(m, "missing-path", m.group(0))
                   for m in _PATH.finditer(code)
                   if not _exists(root, m.group(0))]
        for m in _MAKE.finditer(code):
            directory = m.group(1).split()[-1] if m.group(1) else "."
            targets = _make_targets(root, directory)
            missing += [(m, "missing-target", f"{directory}:{t}")
                        for t in m.group(2).split() if t not in targets]
        findings += [
            Finding("docs", f"{key}:{doc}:{what}",
                    f"{doc} names {what}, which does not exist",
                    ((doc, ln + code.count("\n", 0, m.start())),))
            for m, key, what in missing]
    return findings


def check(root: str | None = None) -> list[Finding]:
    return [f for doc in DOCS for f in check_doc(doc, root)]
