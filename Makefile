PY ?= python
export PYTHONPATH := src

.PHONY: lint analysis baseline test test-fast

# repo-aware static checkers (jit-purity, time-unit flow, EQ-event
# exhaustiveness, frozen-spec/fixed-shape) + ruff/mypy when installed
lint: analysis
	@command -v ruff >/dev/null && ruff check . || echo "ruff not installed; skipped"
	@command -v mypy >/dev/null && mypy || echo "mypy not installed; skipped"

analysis:
	$(PY) -m repro.analysis.check

# re-pin current findings (each new pin needs a written justification)
baseline:
	$(PY) -m repro.analysis.check --fix-baseline

test:
	$(PY) -m pytest -x -q

test-fast:
	$(PY) -m pytest -x -q -m "not slow"
