PYTHON ?= python
PYTHONPATH := src

.PHONY: test test-fast lint bench-smoke bench bench-batch bench-serving \
	bench-compiled bench-obs bench-cluster bench-stats bench-compile \
	examples

# tier-1: the full suite (slow markers included)
test:
	PYTHONPATH=$(PYTHONPATH) $(PYTHON) -m pytest -x -q

# sub-60s inner loop: everything not marked slow
test-fast:
	PYTHONPATH=$(PYTHONPATH) $(PYTHON) -m pytest -x -q -m "not slow"

# static checks (pyflakes: undefined names, unused imports, shadowing)
lint:
	$(PYTHON) -m pyflakes src/repro tests benchmarks examples

# tiny-configuration pass over the benchmark drivers — catches API drift
# (the drivers import and exercise the CobraSession/compile/run surface)
bench-smoke:
	PYTHONPATH=$(PYTHONPATH) $(PYTHON) -m benchmarks.run --smoke \
		exp_crossover exp_wilos exp_opt_time bench_runtime

# full benchmark harness (all modules, paper-scale configurations)
bench:
	PYTHONPATH=$(PYTHONPATH) $(PYTHON) -m benchmarks.run

# batch-size sweep {1, 8, 64}: serving throughput + the ExecutionContext
# plan-flip point (which batch size makes the memo search switch winners);
# trajectory lands in BENCH_runtime.json
bench-batch:
	PYTHONPATH=$(PYTHONPATH) $(PYTHON) -m benchmarks.run bench_runtime

# serving-level SiteCache metrics: cross-batch hit rate, observed
# distinct-binding fractions, and mutating-workload (W_A) throughput under
# write-set-aware sharing — the bench_runtime driver emits them alongside
# the batch sweep, so this is an alias of bench-batch; the serving section
# lands in BENCH_runtime.json (uploaded as the existing CI artifact)
bench-serving: bench-batch

# compiled execution tier: interpreter-vs-compiled wall throughput on the
# P0-style loop-heavy workload at batch 64 + one-time lowering latency;
# the `compiled` section lands in BENCH_runtime.json (the full bench-batch
# run emits it too — this target runs ONLY that section)
bench-compiled:
	PYTHONPATH=$(PYTHONPATH) REPRO_BENCH_ONLY=compiled \
		$(PYTHON) -m benchmarks.run bench_runtime

# observability overhead: no-op tracer vs recording tracer on the P0
# batch-64 serving loop (bit-identical outputs/simulated clock either
# way); the `obs` section lands in BENCH_runtime.json and the traced
# run's span tree in BENCH_trace_sample.jsonl (uploaded as a CI artifact)
bench-obs:
	PYTHONPATH=$(PYTHONPATH) REPRO_BENCH_ONLY=obs \
		$(PYTHON) -m benchmarks.run bench_runtime

# sharded serving cluster: simulated W_E/SCAN throughput at 1 vs 2 vs 4
# shard workers, deadline-driven batch formation (burst reaches the
# batch-64 SCAN plan flip with no fixed-size batching; sparse stays on
# the per-iteration plan), and skewed-vs-uniform affinity routing with
# the triage hot-shard flag; the `cluster` section lands in
# BENCH_runtime.json (the full bench-batch run emits it too)
bench-cluster:
	PYTHONPATH=$(PYTHONPATH) REPRO_BENCH_ONLY=cluster \
		$(PYTHON) -m benchmarks.run bench_runtime

# histogram statistics subsystem: the histogram-vs-scalar selectivity
# plan flip (bit-identical outputs either way), per-site q-error before
# and after the feedback controller's targeted re-analyze, and ANALYZE
# overhead (histograms vs scalar cardinalities) at three table sizes;
# the `stats` section lands in BENCH_runtime.json (the full bench-batch
# run and the bench-smoke CI pass emit it too)
bench-stats:
	PYTHONPATH=$(PYTHONPATH) REPRO_BENCH_ONLY=stats \
		$(PYTHON) -m benchmarks.run bench_runtime

# optimizer throughput: delta-driven vs exhaustive memo saturation on the
# synthetic 10x-scale program (>=5x cold-compile saturation speedup with
# the identical winning plan and bit-identical batch outputs — the bench
# RAISES on plan divergence), the node-budget greedy fallback, and
# cross-program MemoPool hits on a serving-fleet cold start; the
# `compile` section lands in BENCH_runtime.json (the full bench-batch run
# and the bench-smoke CI pass emit it too)
bench-compile:
	PYTHONPATH=$(PYTHONPATH) REPRO_BENCH_ONLY=compile \
		$(PYTHON) -m benchmarks.run bench_runtime

examples:
	$(PYTHON) examples/quickstart.py
	$(PYTHON) examples/serve_programs.py
