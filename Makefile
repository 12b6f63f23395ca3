# Convenience targets. The native C++ data engine has its own Makefile
# (native/Makefile); this one is for repo-level workflows.

.PHONY: t1 lint check native obs-smoke chaos-smoke shard-smoke elastic-smoke comm-cost pallas-bench table-capacity quality-gate quality-smoke perf-gate agg-scale async-smoke watch-smoke churn-soak

# tier-1 verify: the ROADMAP.md pipeline, DOTS_PASSED count included
t1:
	@bash scripts/t1.sh

# static analysis: fedrec-lint (project invariants, docs/ANALYSIS.md) +
# the generic layer (ruff when installed; builtin GL rules always)
lint:
	@bash scripts/lint.sh

# the one local PR gate: lint, then tier-1
check:
	@bash scripts/check.sh

# observability smoke: 2-round CPU training + serve_load, then assert the
# artifact trio (metrics.jsonl / trace.json / prometheus.txt) renders
obs-smoke:
	@bash scripts/obs_smoke.sh

# robustness smoke: seeded FaultPlan (dropout + nan + scale-poison) under
# trimmed-mean aggregation — completes, reproduces bit-identically, and the
# recovery leg quarantines + rolls back instead of aborting
chaos-smoke:
	@bash scripts/chaos_smoke.sh

# sharding smoke: a REAL 2-process gloo CPU world (2x4 fake devices, one
# global 8-device mesh) running the sharded-catalog train step — asserts
# survival, rows/device = padded/8, bit-identity with the replicated
# table, and fsdp at-rest sharding with cross-process-identical losses
shard-smoke:
	@bash scripts/shard_smoke.sh

# elastic-federation smoke: a 4-process gloo world under epoch-based
# membership loses one peer to a chaos kill, shrinks-and-continues at
# world 3, reintegrates the supervisor-respawned peer at world 4,
# finishes every round + the final eval, and the membership counters
# match the script (exactly one shrink, one rejoin, worlds 4 -> 3 -> 4)
elastic-smoke:
	@bash scripts/elastic_smoke.sh

# catalog-capacity benchmark: rows-per-device x devices frontier
# (replicated vs sharded) + a measured sharded-gather exactness/latency
# leg on the local backend; banks benchmarks/table_capacity.json
table-capacity:
	@python benchmarks/table_capacity.py

# quality-regression gate: seeded CPU run -> sliced-eval digest; banks a
# provenance-stamped benchmarks/quality_gate.json on first run, then
# fails (naming the slice) when any slice's AUC regresses beyond the
# noise-aware threshold vs the banked baseline
quality-gate:
	@python benchmarks/quality_gate.py

# model-quality smoke: sliced-eval telemetry end to end (2-round CPU run
# with obs.quality on -> Quality report section + slice gauges), a store
# drift-probe leg (corrupted table push -> non-zero serve.drift_* BEFORE
# the swap), and a forced-regression gate-failure leg
quality-smoke:
	@bash scripts/quality_smoke.sh

# perf-regression gate: seeded CPU measurement of the flagship step +
# host pipeline (steps/s, batch-build/h2d ms, dispatch gaps, analytic
# FLOPs); banks a provenance-stamped benchmarks/perf_gate.json on first
# run, then fails (naming the lane) on any noise-adjusted regression vs
# the banked baseline — the perf analog of quality-gate
perf-gate:
	@python benchmarks/perf_gate.py

# aggregation-scale frontier: round time vs cohort size (1k/10k/100k
# logical clients) for flat vs hierarchical vs async aggregation on the
# real fedrec_tpu.agg kernels; proves hierarchical round time sub-linear
# in cohort size at 10k+ and the async quorum cut beating the flat
# barrier; banks benchmarks/agg_scale.json on first run, then checks
agg-scale:
	@python benchmarks/agg_scale.py

# buffered-async smoke: an agg.server commit authority + 4 async workers
# (one chaos-delayed 4s) — asserts the global commits at quorum 3 while
# the straggler is still sleeping, the late contribution folds into the
# NEXT commit (late_folds >= 1), and the delayed worker's marginal
# commit gate is ~0 in the fleet report (the barrier would have charged
# it the full straggle)
async-smoke:
	@bash scripts/async_smoke.sh

# partition-tolerance soak: 104 wire workers against a live commit
# authority + membership service through a seeded churn schedule (10%
# kills, half rejoining, a full partition window on one cohort's edge,
# in-flight push duplication on another, an authority kill/respawn from
# its state sidecars mid-run) — asserts monotone commit liveness, zero
# acked-push loss via ledger reconciliation, bounded folded staleness,
# duplicate detection without re-folding, incarnation-2 recovery, and
# the fleet watch layer naming the partitioned edge; banks
# benchmarks/churn_soak.json
churn-soak:
	@python benchmarks/churn_soak.py

# continuous-watch smoke: a forced SLO breach (tight round-time objective
# the JIT compile round blows through) must fire AND resolve through the
# alert lifecycle, an unmeetable SLO must hold `fedrec-obs alerts`/`tail
# --once` at exit 1, and the obs.slo-disabled path must leave zero watch
# footprint (no alert records, no alert.* instruments)
watch-smoke:
	@bash scripts/watch_smoke.sh

# communication-cost benchmark: measured per-codec wire buffers of the
# flagship trees + the bytes-per-round x time-to-AUC tradeoff runs (CPU);
# banks benchmarks/comm_cost.json
comm-cost:
	@python benchmarks/comm_cost.py

# attention/fused-kernel microbenchmark: XLA dense vs pallas vs chunked at
# H in {50,1024,2048,4096} plus the fused hot-path legs (B in {256,1024} +
# the gather+encode leg); refuses to run off-TPU (interpret mode measures
# nothing)
pallas-bench:
	@python benchmarks/pallas_bench.py

native:
	$(MAKE) -C native
