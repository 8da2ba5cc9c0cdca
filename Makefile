# Tier-1 verification and common dev entry points.
PY ?= python

.PHONY: test test-full test-kernels test-serve lint-ir bench-dp bench-smoke dryrun-executors

# tier-1 suite (the ROADMAP invocation, pinned here)
test:
	PYTHONPATH=src $(PY) -m pytest -x -q

# no fail-fast; full report
test-full:
	PYTHONPATH=src $(PY) -m pytest -q

# Pallas kernel suite alone (the CI kernels job).  The backend decides:
# interpret mode on the CPU, compiled on a TPU; tests/test_tpu_compile.py
# compiles the kernels for a described v5e chip without one
test-kernels:
	PYTHONPATH=src $(PY) -m pytest -q -m kernels

# serving subsystem alone: continuous-batching engine bit-identity,
# paged-cache eviction/resume, and streaming-schedule trace audits
test-serve:
	PYTHONPATH=src $(PY) -m pytest -q -m serve

# static IR audit (repro.analysis): every registered schedule × use_kernel
# on/off at K=2 — comm-safety, buffer, scale, donation, dtype and VMEM rules
# over the real loss+grad traces; machine-readable report in
# experiments/lint_ir.json, non-zero exit on any error finding
lint-ir:
	PYTHONPATH=src $(PY) -m repro.analysis --json experiments/lint_ir.json

bench-dp:
	PYTHONPATH=src $(PY) -m benchmarks.run --only dp_bench

# fast self-asserting benchmarks (CI): DP scheduler timings + vectorized
# cost-matrix check, the interleaved-schedule bubble assertions (incl.
# interleaved-1f1b strictly beating plain 1f1b), the 1F1B-family compiled
# peak-memory assertions (1f1b AND interleaved-1f1b flat in D vs
# contiguous's growth), the fused-attention HBM-linearity assertions
# (no quadratic score matrix / repeated-KV buffers in fwd or bwd jaxprs,
# via the repro.analysis rules, plus the analyzer's own self-assert cell),
# and the serving assertion (continuous batching >= 2x sequential tokens/s
# at batch 4 under Poisson arrivals)
bench-smoke:
	PYTHONPATH=src $(PY) -m benchmarks.run --only dp_bench
	PYTHONPATH=src $(PY) benchmarks/interleave_bench.py --assert-only
	PYTHONPATH=src $(PY) benchmarks/memory_bench.py --quick
	PYTHONPATH=src $(PY) benchmarks/kernel_bench.py --assert-only
	PYTHONPATH=src $(PY) benchmarks/serve_bench.py --assert-only

# rolled vs unrolled tick-executor trace/lower wall-time report
dryrun-executors:
	PYTHONPATH=src $(PY) -m repro.launch.dryrun --compare-executors \
	    --arch gpt3-1b --shape train_4k --terapipe-pipe 8 --terapipe-slices 16
