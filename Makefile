# Tier-1 verify + perf-trajectory artifacts.  `make test` is what CI runs.

PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
export PYTHONPATH

.PHONY: test test-mesh dev-deps bench bench-select bench-decode \
	serve-smoke serve-smoke-faults serve-smoke-overload \
	serve-smoke-mesh check-regression

dev-deps:
	-pip install -r requirements-dev.txt

test:
	python -m pytest -x -q

# Mesh tier-1: the shard_map parity tests (sequence-sharded selection,
# tensor-parallel decode) need >1 device — force an 8-way simulated
# CPU mesh so plain CI runners exercise the sharded paths.  The same
# tests SKIP (not fail) under `make test` on a single device.
test-mesh:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m pytest -x -q tests/test_mesh_serving.py \
		tests/test_config_api.py

# BENCH_kernel.json: dense-grid vs compacted-grid kernel timings +
# tile-visit / fetch-byte counts — the perf trajectory across PRs.
bench:
	python -m benchmarks.run kernel --json-dir results/bench

# BENCH_select.json: dense-selection vs chunked-selection pipeline
# (interpret mode) — wall time, traced-HLO quadratic-buffer scan, and
# occupancy-bound stats; CI uploads it so the trajectory accumulates.
bench-select:
	python -m benchmarks.run select --json-dir results/bench

# BENCH_decode.json: dense decode vs the SATA decode plan + gather
# kernel (tok/s, fetch bytes, replan-interval traffic tradeoff —
# including the summary-backend × re-plan-mode rows pricing int8
# summaries and the sketch re-plan — paged-vs-contiguous parity + HBM,
# prefill handoff) — the serving row of the perf trajectory.
bench-decode:
	python -m benchmarks.run decode --json-dir results/bench

# End-to-end serving smoke: the SATA decode route on the paged KV pool
# (half the contiguous HBM reservation; exercises admission control,
# stalls, and preemption) — asserts completion + fetch reduction.
# The --shared-prefix scenario then drives the prefix cache: requests
# sharing a prompt prefix map its cached pages (hit-rate > 0, prefill
# tokens saved, CoW on append) with outputs bitwise equal to the
# cache-disabled run.  The --retire scenario serves a workload whose
# live prefixes overflow the pool: cascade token retirement reclaims
# the coldest blocks' pages mid-stream and completes without the
# preemptions the retire-off twin needs.
serve-smoke:
	python examples/serve_topk.py --paged
	python examples/serve_topk.py --summary int8 --replan-mode sketch
	python examples/serve_topk.py --shared-prefix
	python examples/serve_topk.py --retire

# Fault-injection smoke: seeded squeeze/preempt/defer schedule plus a
# hard pool squeeze (forces >=2 host-swap preemptions) and a mid-serve
# crash, with the allocator invariant audit on throughout.  Asserts the
# restored outputs are bitwise equal to the fault-free run with zero
# re-prefilled tokens and zero cold re-plans.
serve-smoke-faults:
	python examples/serve_topk.py --faults 0

# Overload-resilience smoke: seeded load spikes the QoS degradation
# ladder absorbs as per-slot quality rungs (ladder-off needs >=2
# preemptions; ladder-on completes every request with zero requeues and
# zero timeouts), a corrupted swap payload quarantined at the checksum
# gate, and a child process killed mid-serve resumed from checkpoint
# with bitwise-equal outputs.
serve-smoke-overload:
	python examples/serve_topk.py --overload 0

# Cross-replica prefix-index smoke: two serve replicas share one
# prefix digest index — replica 0 publishes its shared-prefix pages,
# replica 1 migrates them into its own pool instead of re-prefilling
# (asserts cross-replica hits, migrated pages, and bitwise-equal
# outputs across replicas).  The forced device count keeps the smoke
# on the same simulated mesh the `mesh` CI job uses.
serve-smoke-mesh:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python examples/serve_topk.py --replicas 2

# Bench-regression gate (the CI step behind `make bench*`): regenerate
# the three artifacts into results/bench_fresh and compare against the
# COMMITTED baselines in results/bench.  Contract (details in
# benchmarks/check_regression.py): deterministic counters and
# bitwise-parity (max_err 0.0) fields are gated EXACTLY; wall-time
# ratios are tolerance-banded after normalizing by the suite median
# (cancels machine speed); dropped rows fail, new rows pass.  To bless
# a new baseline after an intended change: `make bench bench-select
# bench-decode` and commit the regenerated results/bench JSONs.
check-regression:
	python -m benchmarks.run kernel select decode \
		--json-dir results/bench_fresh
	python -m benchmarks.check_regression \
		--baseline-dir results/bench --fresh-dir results/bench_fresh
