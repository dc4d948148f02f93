# Developer loop targets. `make lint test` is the pre-push gate — the
# same two jobs .github/workflows/ci.yml runs.

PY ?= python

.PHONY: lint lint-fast test baseline lint-all lint-hot-report bench-smoke \
	chip-smoke

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

# bench-smoke: prefix-share hit rate + mixed-length bucketed run + the
# fused-vs-unfused comparison; the bucketed leg FAILS on any prefill
# recompile after warmup, and the fused leg FAILS unless piggybacked
# admission stalls decode strictly less than the standalone baseline
# (both deterministic schedule/shape accounting, not timing). The
# pallas leg forces the ragged kernel through the served path in
# interpret mode (the CPU parity configuration — tests/
# test_ragged_attention.py is the full parity suite, run by `make test`).
# Observability legs: the prefix-share run writes its per-request trace
# timelines to /tmp/paddle_tpu_trace.json (Perfetto-loadable;
# trace_report.py summarizes it as a non-blocking artifact), and the
# tracing-overhead leg FAILS unless traced tok/s >= 0.97x untraced with
# zero post-warmup recompiles (the always-on-cheap gate).
# Fault-tolerance leg: --chaos injects a seeded mid-stream fail-on-rid
# poison and FAILS unless the quarantine contains it — the culprit
# alone FAILED, every innocent bit-identical to the fault-free run,
# zero post-warmup recompiles, allocator drained clean.
# Quantized leg: --quantized runs the fp/w8/int8-KV/w8+int8-KV matrix
# and FAILS on any post-warmup recompile, any warm-vs-cold token
# mismatch, int8 KV gather bytes > 0.55x fp, or quantized-vs-fp
# greedy divergence below the documented floor.
# Router leg: --router serves the mixed workload as SSE streams over a
# real socket through 2 Router replicas + the asyncio HTTP frontend,
# then hangs the victim's replica mid-stream; FAILS unless every
# stranded request fails over to the survivor with streams
# bit-identical to the single-engine reference (pre-failover part a
# strict prefix), zero post-warmup recompiles on both replicas.
# Restart leg: --restart is the same chaos shape with auto_restart on;
# FAILS unless the dead slot is respawned through the supervisor's
# readiness gate, rejoins rotation, serves a post-restart request, and
# recompiles stay 0 on every engine incarnation (breaker shut).
# TP leg: --tp forces 4 host devices at module import and serves the
# mixed workload single-device then through a TP=4 mesh engine
# (Megatron-sharded weights + head-sharded KV pool, serving/tp.py);
# FAILS unless TP output is bit-identical to single-device, recompiles
# stay 0 on both engines, and a TP=2-sharded replica pair survives the
# --restart chaos shape (failover + supervisor respawn of the sharded
# slot through its readiness gate).
# Composition leg: --tp --speculative --attention-impl pallas turns on
# EVERY fast path at once — the shard_map-wrapped ragged kernel, its
# suffix-slab spec verify and tree speculation on the TP=4 mesh
# (interpret mode on the 4 forced host devices); FAILS unless greedy
# output is bit-identical to the mesh-off plain-decode reference,
# recompiles stay 0, and the snapshot fast-path stamps (mesh
# attention_impl / spec_backend) report the kernel actually ran.
# Load legs: --load is the closed-loop generator (Poisson arrivals,
# multi-turn sessions, shared system prompts) emitting goodput and
# p99-under-load as tracked JSON fields (timing-based, not gated);
# --load --router runs the same generator through a 2-replica Router
# (multi-replica goodput scaling, per-replica routing counts).
# Speculative leg: --speculative runs the shared-prefix workload
# plain then with self-speculative draft-and-verify decode; FAILS
# unless spec output is bit-identical to the plain greedy reference,
# accepted tokens/step > 1, and post-warmup recompiles stay 0 (the
# spec config rides every memo/warmup key); emits spec_accept_rate /
# spec_tokens_per_step / decode_tok_s_spec as tracked JSON fields.
# Disaggregated leg: --disagg serves the mixed workload through a
# monolithic reference engine, then through Router(disaggregated=True)
# with one prefill-role and one decode-role replica (per-request
# KVSnapshot export/import), fp AND w8+int8-KV; FAILS unless the
# disaggregated streams are bit-identical to the monolithic run, the
# decode replica ran ZERO prefill chunks, every past-the-boundary
# request migrated exactly once, the int8 leg holds the documented
# fp-match floor, recompiles stay 0 on both replicas and both pools
# drain clean; emits migration count/bytes and handoff latency.
# SLO leg: --slo FAILS unless sampled device timing holds tok/s >=
# 0.97x the sampling-off legs with zero recompiles, an injected
# latency fault (4s hangs short of the watchdog) drives an itl_ms_p99
# BREACH visible end-to-end (engine health -> router rollup ->
# /health detail without flipping the 200 -> slo_breaches_total in
# the merged /metrics) that CLEARS after the fault heals, and a
# /debug/profile capture window completes with device-wall spans in
# the merged trace.
bench-smoke:    ## tiny serving benches (non-blocking CI job)
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --prefix-share \
		--n-requests 6 --max-new 4 --trace /tmp/paddle_tpu_trace.json
	$(PY) tools/trace_report.py /tmp/paddle_tpu_trace.json
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --bucketed \
		--n-requests 8 --max-new 4
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --fused \
		--n-requests 8 --max-new 6 --fused-units 2
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --chaos \
		--n-requests 8 --max-new 6
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --quantized \
		--n-requests 8 --max-new 6
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --router \
		--n-requests 8 --max-new 6
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --restart \
		--n-requests 8 --max-new 6
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --tp \
		--n-requests 6 --max-new 6
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --tp --speculative \
		--spec-tree 2,1,1 --attention-impl pallas \
		--n-requests 6 --max-new 6
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --slo \
		--n-requests 8 --max-new 6
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --speculative \
		--spec-tree 2,1,1,1 --n-requests 6 --max-new 6
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --disagg \
		--n-requests 6 --max-new 6
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --load \
		--sessions 4 --turns 2 --max-new 4
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --load --router \
		--sessions 4 --turns 2 --max-new 4
	JAX_PLATFORMS=cpu $(PY) bench_serving.py \
		--attention-impl pallas --n-requests 4 --max-new 4
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --trace-overhead \
		--n-requests 8 --max-new 6
