PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-cov fuzz bench bench-decode bench-paged bench-control bench-smoke lint

# tier-1 verify (ROADMAP.md)
test:
	$(PYTHON) -m pytest -x -q

# tier-1 with line coverage gate (needs pytest-cov from requirements-dev.txt)
test-cov:
	$(PYTHON) -m pytest -q --cov=repro --cov-fail-under=70

# seeded hypothesis fuzz of the BlockAllocator properties (~2 min in CI)
fuzz:
	HYPOTHESIS_PROFILE=ci-fuzz $(PYTHON) -m pytest -q tests/test_paging_properties.py --hypothesis-seed=0

# serving throughput + vectorized simulator; writes BENCH_serving.json
bench:
	$(PYTHON) benchmarks/serving_throughput.py

# cached decode vs stateless re-prefill; writes BENCH_decode.json
bench-decode:
	$(PYTHON) benchmarks/decode_throughput.py

# paged vs dense slot caches at equal KV bytes; writes BENCH_paged.json
bench-paged:
	$(PYTHON) benchmarks/decode_throughput.py --cache-layout paged

# closed-loop vs static-once DTO-EE over the live engine, threshold-aware
# packing vs FIFO, simulator event-harvest A/B; writes BENCH_control.json
bench-control:
	$(PYTHON) benchmarks/control_loop.py

# CI-sized benches: tiny workloads, assert the cached/stateless/monolithic
# outputs agree (paged == dense bitwise with >= 2x in-flight at equal KV
# bytes; fifo == threshold packing token-identical with no extra padding;
# closed loop reconfigures with accuracy pinned) and the JSON schemas hold.
# Also emits a Perfetto trace of a small serve and gates it on the
# check_trace.py span invariants.  Outputs land in bench-artifacts/ so CI
# can upload them per PR.
bench-smoke:
	mkdir -p bench-artifacts
	$(PYTHON) benchmarks/decode_throughput.py --smoke --out bench-artifacts/BENCH_decode_smoke.json
	$(PYTHON) benchmarks/decode_throughput.py --smoke --cache-layout paged --out bench-artifacts/BENCH_paged_smoke.json
	$(PYTHON) benchmarks/control_loop.py --smoke --out bench-artifacts/BENCH_control_smoke.json
	$(PYTHON) -m repro.launch.serve --reduced --slots 1 --requests-per-slot 8 --gen-len 2 \
		--trace-out bench-artifacts/trace_smoke.json \
		--stats-report bench-artifacts/serve_report_smoke.json
	$(PYTHON) tools/check_trace.py bench-artifacts/trace_smoke.json

# syntax check of every tree (no third-party linter baked into the image;
# swap in ruff/pyflakes here once available)
lint:
	$(PYTHON) -m compileall -q src tests benchmarks examples
