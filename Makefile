# Tier-1 verification + smoke benchmarks + docs checks.
#   make check      - lint + tier-1 pytest + benchmark smoke pass + docs checks
#   make test       - tier-1 pytest only
#   make bench      - full benchmark pass (CSV to stdout)
#   make perf-smoke - gated smoke bench: finished/compile-count gates armed,
#                     telemetry pass on, JSON (with meta.perf + meta.compile
#                     + meta.telemetry) to BENCH_smoke.json, trace artifacts
#                     under traces/ (validated by tools/trace_report.py)
#   make trace-demo - run examples/telemetry_quickstart.py: one flap run,
#                     trace export + report under traces/demo/
#   make docs-check - core doctests + markdown relative-link checker
#   make lint-jax   - repo-specific jax tracer-discipline linter (R1-R5,
#                     tools/jaxlint) over src/repro/{net,core,kernels}
#   make lint       - lint-jax + ruff (curated pyflakes/bugbear set from
#                     pyproject.toml; skipped with a notice if ruff is
#                     not installed — CI installs it via requirements-dev)
PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
export PYTHONPATH

.PHONY: check test bench bench-smoke perf-smoke trace-demo docs-check \
	lint lint-jax

test:
	python -m pytest -x -q

lint-jax:
	python -m tools.jaxlint

lint: lint-jax
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check src tests benchmarks tools; \
	else \
		echo "# ruff not installed -- skipping ruff pass (jaxlint ran)"; \
	fi

bench-smoke:
	python -m benchmarks.run --smoke --json BENCH_smoke.json

# the CI perf gate: every family sweep must stay ONE compiled program
# (--max-compiles bounds the whole run: 8 family programs + 3 telemetry
# programs + 2 scale-out scaling rows + 5 bake-off programs — the four
# 8-policy family sweeps and the recovery pulse — + 2 correlated-failure
# recovery programs (pair + fat-tree, telemetry riding the carry) — with
# headroom) and every gated flow must finish (check_finished fails loudly
# inside the benches; the recovery blackout scenarios strand flows BY
# DESIGN and route through allow_unfinished into meta.degraded instead);
# the bake-off section also writes the BAKEOFF_ranking.json artifact; the
# telemetry pass adds meta.telemetry recovery rows + traces/ artifacts,
# and the exported traces must survive their own reader (trace_report
# exits non-zero on a round-trip or Perfetto-structure failure).
# JAX_PLATFORMS=cpu with --devices 2 forces a 2-device host mesh so the
# scale-out section's sharded-vs-unsharded digest gate runs on a real
# multi-device mesh.
# --audit traces every family's closed jaxpr (no compiles) and fails on
# dtype/effect/telemetry violations or drift from the golden fingerprints
# in tests/golden/program_fingerprints.json (meta.audit + AUDIT_report.json).
perf-smoke:
	JAX_PLATFORMS=cpu python -m benchmarks.run --smoke --devices 2 \
	  --json BENCH_smoke.json \
	  --telemetry --trace-dir traces --max-compiles 23 --audit
	python tools/trace_report.py --summary traces/*.jsonl
	python tools/trace_report.py --summary traces/recovery_*.jsonl \
	  --max-recovery-ticks 200
	python tools/trace_report.py --check-perfetto traces/*.trace.json

trace-demo:
	python examples/telemetry_quickstart.py

bench:
	python -m benchmarks.run

docs-check:
	python -m pytest --doctest-modules src/repro/core -q
	python tools/check_links.py

check: lint test perf-smoke docs-check
