# Top-level developer entry points.
#
#   make lint             # distlr-lint: wire parity, concurrency,
#                         # config/docs parity, metrics doc, protocol
#                         # model checking (jax-free)
#   make lint-docs        # regenerate docs/CONFIG.md + docs/METRICS.md
#   make verify-protocol  # KV-protocol model checking to closure:
#                         # exhaustive interleaving search + mutant
#                         # rediscovery (counterexample schedules
#                         # printed) + fixture trace conformance
#   make verify-sched     # schedcheck: the REAL fleet classes under
#                         # controlled interleavings — fast-tier DFS
#                         # + fuzz per scenario + both historical-race
#                         # mutants rediscovered as replayable
#                         # schedules
#   make verify-sched-full# deep tier (higher preemption bound / run
#                         # budgets; the pytest `slow` twin)
#   make verify-fleetsim  # fleetsim: thousand-rank discrete-event
#                         # scenarios driving the real autopilot /
#                         # router / reshard / SLO policies — pinned
#                         # digests + all three policy-bug mutants
#   make verify-fleetsim-full # + the multi-seed fuzz sweep per
#                         # scenario (the pytest `slow` twin)
#   make sanitizers       # build the native TSan/ASan/UBSan matrix
#   make sanitizer-smoke  # fast TSan-client + TSan-server e2e
#
# The lint passes are tier-1-enforced through tests/test_analysis.py
# (the protocol pass through tests/test_protocol_model.py); these
# targets are the same runners for hands/CI hooks.  See
# docs/ANALYSIS.md for pass semantics and the suppression policy.

PY ?= python

lint:
	$(PY) -m distlr_tpu.analysis

lint-docs:
	$(PY) -m distlr_tpu.analysis --write-docs

verify-protocol:
	$(PY) -m distlr_tpu.analysis.protocol

verify-protocol-full:
	$(PY) -m distlr_tpu.analysis.protocol --full

verify-sched:
	$(PY) -m distlr_tpu.analysis.schedcheck
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_schedcheck.py \
	  -m 'not slow' -q -p no:cacheprovider

verify-sched-full:
	$(PY) -m distlr_tpu.analysis.schedcheck --full --fuzz 200

verify-fleetsim:
	$(PY) -m distlr_tpu.analysis.fleetsim
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fleetsim.py \
	  -m 'not slow' -q -p no:cacheprovider

verify-fleetsim-full:
	$(PY) -m distlr_tpu.analysis.fleetsim --full

sanitizers:
	$(MAKE) -C distlr_tpu/ps/native sanitizers

sanitizer-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_sanitizer_matrix.py \
	  -m 'not slow' -q -p no:cacheprovider

.PHONY: lint lint-docs verify-protocol verify-protocol-full \
	verify-sched verify-sched-full verify-fleetsim \
	verify-fleetsim-full sanitizers sanitizer-smoke
