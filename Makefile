# Tier-1 verify + benchmark entry points. PYTHONPATH is set per-target so
# `make test` matches the ROADMAP.md command exactly.
PY ?= python

.PHONY: test test-fast lint bench-smoke bench example trace

test:
	PYTHONPATH=src $(PY) -m pytest -x -q

# ruff (config in pyproject.toml) + guard against committed bytecode
lint:
	ruff check src tests benchmarks examples
	@if git ls-files | grep -E '(\.pyc$$|__pycache__)'; then \
		echo "ERROR: tracked bytecode files (see above)"; exit 1; \
	else echo "no tracked bytecode"; fi

test-fast:
	PYTHONPATH=src $(PY) -m pytest -x -q -m "not slow"

# quick structural checks: tenancy arena + batched-kernel parity/traffic
bench-smoke:
	PYTHONPATH=src $(PY) -m benchmarks.tenancy_bench --smoke
	PYTHONPATH=src $(PY) -m benchmarks.retrieval_bench --smoke

# the full paper-table benchmark sweep
bench:
	PYTHONPATH=src $(PY) -m benchmarks.run

example:
	PYTHONPATH=src $(PY) examples/multi_user_agent.py

trace:
	PYTHONPATH=src $(PY) -m repro.launch.serve_tenants --smoke --tenants 6 \
		--capacity 512 --steps 30 --clusters 8 --cache-kb 256
