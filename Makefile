# Local equivalents of the CI jobs (see .github/workflows/ci.yml).
# CI runs these targets rather than raw pytest lines, so the marker
# selection below is the single source of truth for which tests land in
# which job: pytest.ini's addopts excludes $(SLOW_MARKER) from the default
# tier-1 run, and `test-slow` selects exactly that marker. `test-all` is
# the explicit union of the two jobs — NOT a `-m ""` override — so a test
# carrying the slow marker can never be silently skipped by both.
PY := python
export PYTHONPATH := src

SLOW_MARKER := slow

.PHONY: test test-slow test-all test-pallas bench-smoke bench scenarios \
	baselines baselines-check trace traces advisor docs-check

# The suite runs on the CPU: conftest.py initialises a backend in every
# worker, and on a TPU host the workers would contend for the chip.
test:            ## default tier-1 ($(SLOW_MARKER) excluded via pytest.ini)
	JAX_PLATFORMS=cpu $(PY) -m pytest -x -q

test-slow:       ## full-fidelity runs only (the CI slow job)
	JAX_PLATFORMS=cpu $(PY) -m pytest -q -m "$(SLOW_MARKER)"

test-all:        ## everything: tier-1 plus the slow suite, explicitly
	JAX_PLATFORMS=cpu $(PY) -m pytest -x -q
	JAX_PLATFORMS=cpu $(PY) -m pytest -q -m "$(SLOW_MARKER)"

test-pallas:     ## pallas interpret-mode equivalence (the CI pallas job)
	JAX_PLATFORMS=cpu $(PY) -m pytest -x -q tests/test_backend.py -k pallas

scenarios:       ## run every named scenario in the library end to end
	$(PY) -m benchmarks.run --only scenarios
	$(PY) -m benchmarks.run --only trace

trace:           ## bundled-trace fit + replay gates + calibration (CI job)
	$(PY) -m benchmarks.run --only trace $(if $(ARTIFACTS),--artifacts $(ARTIFACTS))

traces:          ## regenerate tests/traces/ from the seeded generators
	$(PY) tests/traces/generate.py

advisor:         ## bottleneck attribution + what-if advisor (CI job)
	$(PY) -m benchmarks.run --only advisor $(if $(ARTIFACTS),--artifacts $(ARTIFACTS))

docs-check:      ## run every fenced python block in docs/ + check links (CI job)
	$(PY) scripts/docs_check.py

baselines:       ## (re)record tests/baselines/ fingerprints — review the diff!
	$(PY) tests/test_baselines.py
	$(PY) tests/test_trace_baselines.py
	$(PY) tests/test_advisor_baselines.py

baselines-check: ## fail on any library-scenario fingerprint drift (CI job)
	$(PY) tests/test_baselines.py --check
	$(PY) tests/test_trace_baselines.py --check
	$(PY) tests/test_advisor_baselines.py --check
	$(PY) tests/traces/generate.py --check

bench-smoke:     ## the CI benchmark smoke sections (ARTIFACTS= to persist)
	$(PY) -m benchmarks.run --only table1
	$(PY) -m benchmarks.run --only multitenant
	$(PY) -m benchmarks.run --only lifecycle
	$(PY) -m benchmarks.run --only wfq
	$(PY) -m benchmarks.run --only batching $(if $(ARTIFACTS),--artifacts $(ARTIFACTS))
	$(PY) -m benchmarks.run --only scenarios $(if $(ARTIFACTS),--artifacts $(ARTIFACTS))
	$(PY) -m benchmarks.run --only topology $(if $(ARTIFACTS),--artifacts $(ARTIFACTS))
	$(PY) -m benchmarks.run --only advisor $(if $(ARTIFACTS),--artifacts $(ARTIFACTS))

bench:           ## all benchmark sections
	$(PY) -m benchmarks.run
