# Developer entry points. `make test` is the tier-1 verify command from
# ROADMAP.md; `make test-fast` deselects the paper-scale tests marked
# @pytest.mark.slow so the quick suite stays under a few minutes.
PY := PYTHONPATH=src python

.PHONY: test test-fast test-priv test-comm test-async test-serve \
	test-byz test-hier test-cov

test:
	$(PY) -m pytest -x -q

test-fast:
	$(PY) -m pytest -q -m "not slow"

# quick iteration on the DP delta pipeline + property suite only
# (tests/test_privacy.py, tests/test_property.py, DESIGN.md §9)
test-priv:
	$(PY) -m pytest -q tests/test_privacy.py tests/test_property.py

# quick iteration on the delta-compression transport only
# (tests/test_compression.py + the codec properties, DESIGN.md §10)
test-comm:
	$(PY) -m pytest -q tests/test_compression.py tests/test_property.py

# quick iteration on the fault-tolerant asynchronous federation layer
# (availability simulator, fedbuff, degraded modes — DESIGN.md §11)
test-async:
	$(PY) -m pytest -q tests/test_availability.py tests/test_scan_engine.py

# quick iteration on the serving engine (prefix cache, continuous
# batching, int8 inference — DESIGN.md §12)
test-serve:
	$(PY) -m pytest -q tests/test_serving.py

# quick iteration on the Byzantine attack/defense layer (adversarial
# client simulator, krum/geomedian/norm-bound, stage pipeline —
# DESIGN.md §13)
test-byz:
	$(PY) -m pytest -q tests/test_adversary.py tests/test_property.py

# quick iteration on the client→edge→server hierarchy + the federation
# bugfix regression tests that rode along (DESIGN.md §14)
test-hier:
	$(PY) -m pytest -q tests/test_hierarchy.py tests/test_fedavg.py

# tier-1 suite under pytest-cov (the CI job uploads coverage.xml as a
# non-gating artifact; requires pytest-cov from requirements-dev.txt)
test-cov:
	$(PY) -m pytest -x -q --cov=repro --cov-report=term \
		--cov-report=xml:coverage.xml
