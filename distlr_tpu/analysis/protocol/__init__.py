"""Protocol model checking — the semantic half of distlr-lint.

PR 13 made the repo lint itself *syntactically* (wire-constant parity,
lock discipline, doc drift).  This package makes it verify itself
*semantically*: every serious bug in the repo's history — the barrier
double-vote early release (PR 5), the re-issued straddling push that
PR 12 had to absorb as ``push_outcome_unknown`` — was a protocol
INTERLEAVING bug that chaos testing stumbled onto rather than analysis
ruled out.  Three parts:

* **executable spec** (:mod:`~distlr_tpu.analysis.protocol.spec`) — a
  small-step state machine of the KV protocol: client handles with
  per-connection negotiation (kHello capability intersection, epoch
  announce), server tables + barrier vote sets with
  generation/connection rollback, the retry ladder with
  ``kv_op_delivery_began`` semantics, and membership resize
  (spawn -> fence -> drain -> commit -> activate).  Written against
  :mod:`distlr_tpu.ps.wire` — the ONE Python protocol mirror — so the
  wire-parity pass covers it for free.
* **explicit-state model checker**
  (:mod:`~distlr_tpu.analysis.protocol.checker`) — exhaustive BFS over
  interleavings of small configurations (2 clients x 2 servers, one
  resize, one injected fault from the chaos fault alphabet) with state
  hashing and invariant checks.  Counterexamples pretty-print as
  step-by-step schedules.  Mutant mode
  (:mod:`~distlr_tpu.analysis.protocol.mutants`) reverts the named
  historical fixes and must rediscover each as a counterexample — a
  spec that cannot find known bugs is not verifying anything.
* **trace conformance**
  (:mod:`~distlr_tpu.analysis.protocol.conformance`) — replay a real
  run's artifacts (dtrace span journals, the chaos proxy's canonical
  event log, ``distlr_kv_server --trace_journal`` spans) through the
  model's observable rules, so every existing chaos/elastic e2e
  doubles as a conformance witness.  Violations cite ``file:line``.

Entry points: the ``protocol`` pass of ``python -m distlr_tpu.analysis``
(bounded exploration + mutant rediscovery + fixture conformance, fast
enough for tier-1), ``make verify-protocol`` /
``python -m distlr_tpu.analysis.protocol`` (full-depth, prints
schedules).  Everything here
is jax-free and import-light, like the rest of ``analysis/``.
"""

from distlr_tpu.analysis.protocol.checker import CheckResult, explore  # noqa: F401
from distlr_tpu.analysis.protocol.spec import Scenario, Spec  # noqa: F401
