"""distlr-lint — the repo's jax-free static-analysis subsystem.

One runner (``python -m distlr_tpu.analysis``, ``make lint``;
``--only <pass>`` runs one in isolation, ``--list-passes`` lists
them), and its passes, each tier-1-enforced the way the PR-8 metrics-doc
lint made metric drift impossible:

* **wire parity** (:mod:`distlr_tpu.analysis.wire_parity`) — parse
  ``ps/native/kv_protocol.h`` (op codes, flag bits, capability bits,
  stats counts, quant block, frame sizes, magic) and cross-check every
  Python mirror site against it.  A constant that exists on one side
  only, disagrees in value, or is re-inlined as a raw literal instead
  of a :mod:`distlr_tpu.ps.wire` name fails the build with
  ``file:line`` on both sides.
* **concurrency** (:mod:`distlr_tpu.analysis.concurrency`) — an AST
  pass building a per-class shared-state registry (attributes written
  under a ``with self.<lock>`` in one method but touched lock-free in
  another, on classes whose instances cross threads) plus a
  cross-module lock-acquisition-order graph with cycle detection.
  Hogwild-INTENTIONAL races are named and justified in
  ``analysis/concurrency_baseline.toml``; anything unsuppressed fails.
* **config/CLI/docs parity** (:mod:`distlr_tpu.analysis.config_doc`) —
  every :class:`~distlr_tpu.config.Config` field has a ``launch`` flag
  and a docs mention and vice versa (``docs/CONFIG.md`` is generated,
  like ``docs/METRICS.md``).
* **metrics doc** — the PR-8 :mod:`distlr_tpu.obs.metrics_doc` drift
  lint, folded under this runner so ``make lint`` is the single entry
  point (``tests/test_metrics_doc.py`` stays as the tier-1 shim).
* **document paths** (:mod:`distlr_tpu.analysis.doc_paths`) — a path
  under one of this repo's directories, or a ``make`` target, that a
  document's code names must exist.
* **protocol model checking** (:mod:`distlr_tpu.analysis.protocol`) —
  the SEMANTIC pass: an executable small-step spec of the KV state
  machine, exhaustive interleaving search with invariant checks,
  mutant rediscovery of the named historical bugs, and trace
  conformance of real runs' journals.  Full-depth entry point:
  ``make verify-protocol``.
* **schedcheck** (:mod:`distlr_tpu.analysis.schedcheck`) — the
  IMPLEMENTATION pass: the real fleet classes (batcher, joiner,
  spool, router, reloader, membership coordinator, shadow mirror,
  chaos link) execute under a cooperative deterministic scheduler via
  the :mod:`distlr_tpu.sync` facade — preemption-bounded exhaustive
  DFS + seeded fuzzing per scenario, deadlock detection with wait-for
  cycles, and mutant rediscovery of the PR-6 joiner and PR-13
  ChaosLink teardown races as replayable ≤ 20-step schedules.
  Full-depth entry point: ``make verify-sched-full``.

The native half of the same story is the sanitizer matrix
(``make -C distlr_tpu/ps/native sanitizers``, ``DISTLR_NATIVE_VARIANT``
— see :mod:`distlr_tpu.ps.build` and ``docs/ANALYSIS.md``): TSan/ASan/
UBSan builds of the server AND the client library that the existing
chaos/elastic/compress e2e suites run against unchanged.

Everything here is deliberately jax-free and import-light: lint must
run in CI images (and pre-commit hooks) that never built jaxlib.
"""

from distlr_tpu.analysis.report import Finding  # noqa: F401
