# Tier-1 verify, benchmarks and lint in one invocation each.
# All targets run from the repo root with PYTHONPATH=src.

PY        ?= python
PYTHONPATH := src

.PHONY: test bench bench-quick bench-cpals lint quickstart clean ratchet anchor

test:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m pytest -x -q

bench:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m benchmarks.run

bench-quick:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m benchmarks.run --quick

bench-plan:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m benchmarks.bench_plan

bench-ingest:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m benchmarks.bench_ingest

bench-methods:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m benchmarks.bench_methods

bench-api:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m benchmarks.bench_api --json BENCH_api.json

bench-serve:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m benchmarks.bench_serve --json BENCH_serve.json

# tracing overhead gates (disabled < 1%, enabled < 5%) — exits non-zero on
# a gate failure; the CI test job runs exactly this target.
bench-obs:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m benchmarks.bench_obs --json BENCH_obs.json

# quick per-routine CP-ALS breakdown on the scaled paper tensors — covers
# every registered workspace impl (incl. linearized) x fused epilogue; the
# CI quick-bench job runs exactly this target.
bench-cpals:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m benchmarks.bench_cpals_routines --quick --json BENCH_cpals.json

# perf ratchet: latest BENCH_history record vs the last anchor (>10% time
# regression fails).  `make anchor` promotes the latest records to the new
# accepted floor after a deliberate perf change lands.
ratchet:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m benchmarks.ratchet

anchor:
	PYTHONPATH=$(PYTHONPATH) $(PY) -m benchmarks.ratchet --anchor

# no third-party linter is baked into the image; byte-compile every tree
# (syntax + tabs/indentation errors) and import the package graph.
lint:
	$(PY) -m compileall -q src tests benchmarks examples
	PYTHONPATH=$(PYTHONPATH) $(PY) -c "import repro.api, repro.api.cli, repro.core, repro.dist, repro.ingest, repro.plan, repro.serve, repro.methods, repro.kernels, repro.launch.mesh, repro.launch.steps, repro.obs, repro.obs.report, repro.obs.exposition, repro.obs.recorder, repro.obs.aggregate, repro.checkpoint, repro.utils.roofline"

quickstart:
	PYTHONPATH=$(PYTHONPATH) $(PY) examples/quickstart.py

# remove generated artifacts: bytecode caches (src/tests/benchmarks/examples),
# benchmark JSONs, and the pytest cache.  The ingest/dataset cache under
# .cache/ is intentionally kept (delete it explicitly to force cold runs).
clean:
	find src tests benchmarks examples -type d -name __pycache__ -prune -exec rm -rf {} +
	rm -rf .pytest_cache
	rm -f BENCH_*.json
