# Developer loop targets. `make lint test` is the pre-push gate — the
# same two jobs .github/workflows/ci.yml runs.

PY ?= python

.PHONY: lint lint-fast test baseline lint-all lint-hot-report chip-smoke

# --format github under Actions so findings annotate the PR diff;
# --time-budget keeps the gate honest about staying per-push fast
# (the call-graph engine must never turn lint into a coffee break);
# --fail-dead-roots keeps the SYNC001 seed-root list from rotting (a
# root pattern matching zero functions fails the build, not a report)
lint:           ## ratcheted static analysis (fails on non-baselined findings)
	$(PY) tools/ptlint.py --time-budget 10 --fail-dead-roots \
		--format $(if $(GITHUB_ACTIONS),github,json)

lint-fast:      ## pre-commit loop: findings scoped to git-changed files
	$(PY) tools/ptlint.py --changed-only --time-budget 10

lint-all:       ## every finding, baseline ignored (burn-down worklist)
	$(PY) tools/ptlint.py --no-baseline

lint-hot-report: ## derived SYNC001 hot set + dead seed roots (non-blocking)
	$(PY) tools/ptlint.py --hot-report

baseline:       ## rewrite tools/ptlint_baseline.json (should only shrink)
	$(PY) tools/ptlint.py --update-baseline

test:           ## tier-1 test suite (CPU)
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# chip-smoke only PRINTS the two commands: they need a TPU and are run
# through the chip tool, one foreground process per call — nothing in
# `make test` or CI tries to reach a chip
chip-smoke:     ## how to prove the main paths still start on the chip
	@echo "one chip:   python chip_smoke.py"
	@echo "four chips: python chip_smoke.py --chips 4"
