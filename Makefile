# Developer entry points mirroring the reference's Makefile targets
# (SURVEY §4: make test-unit / test-integration-hermetic / bench-*).
# No linter is baked into this image; py_compile stands in for `make format`.

PY ?= python

.PHONY: test test-fast test-unit test-dist test-chaos bench-flowcontrol \
	bench-router-sse bench-decisions bench-sched bench-sched-offload \
	bench-scaleout bench-slo bench-overload bench-kvobs bench-multiturn \
	bench-timeline bench-fleet-chaos bench-shadow bench-rebalance \
	bench-forecast bench-autoscale bench-tails bench-pd-pipeline \
	dryrun render-chart \
	compile-check \
	verify-metrics verify-decisions verify-hotpath verify-threadsafe \
	verify-vectorized verify-slo verify-debug verify-fleet

# Full hermetic suite (virtual 8-device CPU mesh; no TPU or cluster needed —
# the reference needs envtest + kind for the equivalent coverage).
test: verify-metrics verify-decisions verify-hotpath verify-threadsafe verify-vectorized verify-slo verify-debug
	$(PY) -m pytest tests/ -q

# Everything except the spawned-process distributed tests (the slow tail)
# and the slow-marked multi-process fleet drills (those ride
# make test-chaos / make verify-fleet).
test-fast: verify-metrics verify-decisions verify-hotpath verify-threadsafe verify-vectorized verify-debug
	$(PY) -m pytest tests/ -q -m "not slow" \
		--deselect tests/test_multihost.py \
		--deselect tests/test_multihost_pd.py

# Static registry lint: duplicate family names / high-cardinality labels /
# missing pinned families across the router, engine, and sidecar metrics
# registries (also hooked into pytest via tests/test_observability.py).
verify-metrics:
	$(PY) scripts/verify_metrics.py

# Decision flight-recorder coverage lint: every registered
# filter/scorer/picker type must appear in a recorded decision
# (also hooked into pytest via tests/test_decisions.py).
verify-decisions:
	$(PY) scripts/verify_decisions.py

# Scheduling hot-path lint: no router module may call chain_block_hashes
# directly — everything goes through the prefix-hash memo
# (also hooked into pytest via tests/test_hashmemo.py).
verify-hotpath:
	$(PY) scripts/verify_hotpath.py

# Thread-safety declaration lint: every registered filter/scorer/picker
# must declare its THREAD_SAFE audit result — undeclared plugins would be
# silently trampolined onto the event loop, defeating the scheduler-pool
# offload (also hooked into pytest via tests/test_schedpool.py).
verify-threadsafe:
	$(PY) scripts/verify_threadsafe.py

# Vectorized-kernel coverage lint: every registered filter/scorer/picker
# must define its columnar batch kernel or be explicitly declared
# scalar-fallback — a silently-lost kernel costs the whole vectorized
# hot-path win with no error anywhere (also hooked into pytest via
# tests/test_vectorized.py).
verify-vectorized:
	$(PY) scripts/verify_vectorized.py

# SLO-ledger terminal-path check: success, shed, retry-exhausted, deadline,
# and mid-stream abort must ALL stamp an slo_met outcome on the decision
# record — absent rows overcount attainment (also hooked into pytest via
# tests/test_slo.py).
verify-slo:
	$(PY) scripts/verify_slo.py

# Debug-surface lint: every registered /debug route (gateway + fleet
# supervisor) must answer JSON and have a row in docs/observability.md's
# "Debug surfaces" index table — the debug-plane twin of verify-metrics'
# docs-sync lint (also hooked into pytest via tests/test_kvobs.py).
verify-debug:
	$(PY) scripts/verify_debug.py

# Fleet failover drill: boot a 2-worker fleet, SIGKILL the datalayer
# leader, and fail unless the supervisor promotes the follower and it is
# SERVING snapshots (its epoch advancing) within the bound, with the
# ex-leader rejoining as a follower (also hooked into pytest via
# tests/test_fleet.py, slow-marked).
verify-fleet:
	$(PY) scripts/verify_fleet.py

# Recorder-overhead microbench on the flow-control dispatch path (CPU-only;
# writes benchmarks/DECISIONS_MICRO.json — target <3%, kill-switch ~0%).
bench-decisions:
	$(PY) bench.py --sched-microbench --micro-only

# Pool-scale scheduling hot-path sweep (8/32/128 endpoints × 16/64/128
# blocks, recorder on/off, memoized vs pre-memo legacy emulation); writes
# benchmarks/SCHED_HOTPATH.json — target ≥30% lower cost at 128×64.
bench-sched:
	$(PY) bench.py --sched-microbench --sweep-only

# Concurrent-scheduling offload bench (CPU-only): event-loop stall p50/p99
# + streamed-token inter-arrival gap while 32 concurrent 128-endpoint
# scheduling cycles churn, offload on vs off; plus offloaded per-cycle cost
# and inline-vs-offload pick parity. Writes benchmarks/SCHED_OFFLOAD.json —
# target ≥5x lower p99 loop stall with offload on.
bench-sched-offload:
	$(PY) bench.py --sched-offload

# Multi-process scale-out bench (CPU-only): aggregate scheduling throughput
# under saturation churn in 1/2/4 worker processes over disjoint flow
# shards (the fleet's own flow_shard partitioner), plus cross-shard pick
# parity vs a single-process run (scheduling.pickSeed). Writes
# benchmarks/SCHED_SCALEOUT.json — target ≥2.5x aggregate cycles/sec at 4
# workers with bit-identical picks.
bench-scaleout:
	$(PY) bench.py --sched-scaleout

# SLO observability bench (CPU-only): per-chunk ledger-hook cost vs the 5ms
# token cadence (kill-switch ~0%) plus a rate ramp past saturation showing
# goodput vs raw throughput divergence and predictor MAE by load band.
# Writes benchmarks/SLO_OBS.json — the baseline ROADMAP item 5 (goodput-max
# admission) will be judged against.
bench-slo:
	$(PY) bench.py --slo-ramp

# Overload-control bench (CPU-only): the --slo-ramp machinery driven at
# 1x/2x/4x measured capacity with the goodput-max overload controller ON
# (predictive admission + degrade ladder + Retry-After shedding) and again
# with the kill-switch OFF (the PR 6 goodput collapse shape). Writes
# benchmarks/OVERLOAD.json — target: goodput at 2x/4x within 30% of 1x and
# overload wasted-token fraction < 0.15, with every shed explained.
bench-overload:
	$(PY) bench.py --overload-ramp

# KV-cache observability bench (CPU-only): the cache ledger's per-request
# hook cost vs the scheduling-cycle floor (kill-switch ~0%), then a
# shared-prefix workload (cold round, warm round) through a real gateway +
# sim engines reporting hit-prediction MAE warm vs cold and the actual hit
# ratio the engines confirmed. Writes benchmarks/KV_OBS.json — the
# measurement groundwork ROADMAP item 2's prefill classifier is judged
# against.
bench-kvobs:
	$(PY) bench.py --kv-obs

# Fleet flight recorder bench (CPU-only): sampler tick cost vs the
# scheduling-cycle floor (kill-switch ~0%), an overload-ramp replay whose
# 4x band must trip exactly ONE burn-rate incident (dedup/cooldown) with
# the shed excursion + a shed DecisionRecord in its snapshot, and a
# 2-worker fleet whose merged /debug/timeline gap-marks a worker restart.
# Writes benchmarks/TIMELINE.json.
bench-timeline:
	$(PY) bench.py --timeline

# Traffic forecaster & capacity observatory (CPU-only): observe() micro
# cost vs the scheduling-cycle floor + a compressed diurnal+burst replay
# judging forecast skill vs persistence (docs/forecast.md).
bench-forecast:
	$(PY) bench.py --forecast

# Tail-latency attribution observatory (CPU-only): the per-request
# waterfall lifecycle cost vs the scheduling-cycle floor (kill-switch
# ~0%), two injected-skew scenarios (one slow transfer pair via the
# per-peer sim pull map; one delay-chaos endpoint) where /debug/tails
# must attribute >= 60% of the tail cohort's excess to the injected
# stage with the correct culprit named, and a kill-switch parity arm
# (zero stamps, identical /debug/decisions). Writes
# benchmarks/TAILS.json (docs/tails.md).
bench-tails:
	$(PY) bench.py --tails

# Multi-turn conversation scenario (CPU-only): N users x M turns with a
# shared system prompt and per-user history growth through the full
# gateway -> sidecar -> P/D sim topology, session-sticky via
# x-session-token. Compares warm-turn TTFT with the session-aware prefill
# classifier (skip the P/D hop) against the always-disagg baseline,
# best-of-N reps per the shared-box precedent. Writes
# benchmarks/MULTITURN.json — targets: warm-turn TTFT p50 >= 25% better,
# cold turns within noise, classifier precision >= 0.9 judged against the
# CacheLedger's engine-confirmed actual hit depths.
bench-multiturn:
	$(PY) bench.py --multi-turn

# Pipelined P/D disaggregation bench (CPU-only): chunk-streamed KV
# handoff (decode pulls chunk k while prefill computes chunk k+1) vs the
# serial 2-phase protocol, on a sim pair whose per-peer pull map prices
# the transfer >= 0.5x the prefill cost. Writes benchmarks/PD_PIPELINE.json
# — gates: pipelined TTFT p50 >= 25% below serial at token parity, the
# pipeline_enabled: false arm bit-identical to the pre-pipeline protocol.
bench-pd-pipeline:
	$(PY) bench.py --pd-pipeline

# Shadow-policy evaluation bench (CPU-only): the live-path hook cost vs
# the scheduling-cycle floor (kill-switch ~0%), then a skewed transfer
# topology (per-peer sim pull maps: 2 fast pairs, N slow) where the
# transfer-pair shadow policy's estimated regret is validated against a
# live A/B arm running transfer-aware-pair-scorer for real — sign
# agreement + the documented error band, every divergent pick explained
# at /debug/decisions?divergent=1. Writes benchmarks/SHADOW.json.
bench-shadow:
	$(PY) bench.py --shadow

# Self-balancing pool bench (CPU-only): an open-loop ramp whose
# prefill:decode mix swings hard prefill-heavy -> hard decode-heavy
# mid-run through the full gateway -> sidecar -> P/D sim topology.
# Three arms: a balanced-mix static baseline, the static-split
# kill-switch arm (the drowning role's attainment collapses per phase),
# and the rebalancer arm (drain-cycle role flips hold BOTH roles'
# attainment within the acceptance band of the balanced baseline) —
# every flip drains with zero client-visible errors and is explained at
# /debug/rebalance. Writes benchmarks/REBALANCE.json.
bench-rebalance:
	$(PY) bench.py --rebalance

# Kill-the-leader chaos bench (CPU-only): a 3-worker fleet with
# confirmed-index replication under live traffic — SIGKILL the datalayer
# leader and gate on failover window <= bound, zero non-balancer client
# errors, post-promotion divergence ~0, exactly one divergence incident
# with the outage gap-marked on the merged timeline; then the
# SCHED_SCALEOUT churn cell re-run with the replication stream live vs
# off (gate: >=0.9x aggregate throughput). Writes
# benchmarks/FLEET_CHAOS.json.
bench-fleet-chaos:
	$(PY) bench.py --fleet-chaos

# Guarded elastic-fleet actuator bench (CPU-only): a diurnal ramp
# through four arms on the same trace — predictive (forecast-qualified
# spawns land BEFORE saturation and attainment holds through the
# plateau), reactive (the late trigger sheds into the cold-start
# window), chaos (six drills: spawn failure, retry, burn-rate rollback
# + freeze, advice flap, stuck drain force-finalized by the watchdog,
# leadership flip mid-action — zero client errors throughout), and the
# kill-switch arm (zero ticks, zero actions, bit-identical gateway).
# Writes benchmarks/AUTOSCALE.json.
bench-autoscale:
	$(PY) bench.py --autoscale

test-unit: test-fast

# The multi-process jax.distributed suites only.
test-dist:
	$(PY) -m pytest tests/test_multihost.py tests/test_multihost_pd.py -q

# Fault-injection suite with a fixed seed: chaos decisions hash
# (CHAOS_SEED, fault kind, request id), so reruns are bit-identical; the
# fleet leader-kill drill (3 workers, election + divergence recovery +
# /debug/fleet role table) rides along via tests/test_fleet.py, and the
# actuator's lifecycle drills (spawn_fail / stall_drain / slow_start)
# via tests/test_autoscale.py.
test-chaos: verify-metrics
	CHAOS_SEED=11 $(PY) -m pytest tests/test_resilience.py \
		tests/test_engine_robustness.py tests/test_fleet.py -q -k chaos
	CHAOS_SEED=11 $(PY) -m pytest tests/test_autoscale.py -q \
		-k TestLifecycleChaos

# The chip benchmark is `python3 chipbench/run.py` (BENCHMARK.json, PERF.md);
# the bench-* targets here are SimEngine scenarios on the CPU.
bench-flowcontrol:
	$(PY) scripts/flowcontrol_bench.py

bench-router-sse:
	$(PY) scripts/profile_router_sse.py

# Driver-contract checks without hardware.
dryrun:
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('ok')"

compile-check:
	$(PY) -c "import jax, __graft_entry__ as g; fn, a = g.entry(); \
		jax.jit(fn)(*a); print('ok')"

render-chart:
	$(PY) scripts/render_chart.py deploy/charts/tpu-stack

# Syntax sweep (no linter in this image).
format:
	$(PY) -m compileall -q llm_d_inference_scheduler_tpu scripts tests
