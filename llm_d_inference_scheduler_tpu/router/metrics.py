"""Router-side Prometheus metrics (reference: pkg/epp/metrics/metrics.go:88-460).

One process-global registry; families mirror the reference's names where the
concept carries over.
"""

from __future__ import annotations

from prometheus_client import CollectorRegistry, Counter, Gauge, Histogram

REGISTRY = CollectorRegistry()

# One decode chunk of an engine (jetstream:decode_step_duration_seconds) and
# the longest gap of a relayed stream, which is one chunk when nothing stops:
# they tell the chunks deployments run apart (0.1-0.3 s) and reach a stream
# that stops for seconds.
PERIOD_BUCKETS = (.01, .025, .05, .075, .1, .125, .15, .2, .25, .3, .4, .5,
                  .65, .8, 1, 1.5, 2.5, 5, 10, 30)
# An event loop's lag (schedpool.LoopLagMonitor: the overshoot of a 100 ms
# sleep), the gateway's and the engine server's.
LOOP_LAG_BUCKETS = (.0005, .001, .0025, .005, .01, .025, .05, .1, .25, .5, 1,
                    2.5, 5, 10)

REQUEST_TOTAL = Counter(
    "inference_extension_request_total", "Requests handled",
    ("model", "target_model"), registry=REGISTRY)
REQUEST_ERROR_TOTAL = Counter(
    "inference_extension_request_error_total", "Request errors",
    ("model", "error_code"), registry=REGISTRY)
REQUEST_DURATION = Histogram(
    "inference_extension_request_duration_seconds", "End-to-end request latency",
    ("model",), registry=REGISTRY)
TTFT_SECONDS = Histogram(
    "inference_extension_time_to_first_token_seconds", "TTFT observed at the router",
    ("model",), registry=REGISTRY,
    buckets=(.005, .01, .025, .05, .1, .25, .5, 1, 2.5, 5, 10, 30))
INPUT_TOKENS = Histogram(
    "inference_extension_input_tokens", "Prompt tokens per request",
    ("model",), registry=REGISTRY, buckets=(1, 8, 32, 128, 512, 2048, 8192, 32768))
OUTPUT_TOKENS = Histogram(
    "inference_extension_output_tokens", "Completion tokens per request",
    ("model",), registry=REGISTRY, buckets=(1, 8, 32, 128, 512, 2048, 8192))
RUNNING_REQUESTS = Gauge(
    "inference_extension_running_requests", "In-flight requests at the router",
    ("model",), registry=REGISTRY)
SCHEDULER_E2E_SECONDS = Histogram(
    "inference_extension_scheduler_e2e_duration_seconds", "Scheduling latency",
    registry=REGISTRY,
    buckets=(.0001, .0005, .001, .0025, .005, .01, .025, .05, .1))
PLUGIN_DURATION_SECONDS = Histogram(
    "inference_extension_plugin_duration_seconds", "Per-plugin latency",
    ("extension_point", "plugin"), registry=REGISTRY,
    buckets=(.0001, .0005, .001, .005, .01, .05, .1, .5))
DISAGG_DECISION_TOTAL = Counter(
    "disagg_decision_total", "Disaggregation decisions",
    ("decision_type",), registry=REGISTRY)
POOL_READY_ENDPOINTS = Gauge(
    "inference_pool_ready_pods", "Endpoints in the pool", registry=REGISTRY)
POOL_AVG_KV_CACHE = Gauge(
    "inference_pool_average_kv_cache_utilization", "Mean pool KV utilization",
    registry=REGISTRY)
POOL_AVG_QUEUE = Gauge(
    "inference_pool_average_queue_size", "Mean pool queue depth", registry=REGISTRY)
FLOW_CONTROL_QUEUE_SIZE = Gauge(
    "inference_extension_flow_control_queue_size", "Queued flow-control requests",
    registry=REGISTRY)
FLOW_CONTROL_QUEUE_SECONDS = Histogram(
    "inference_extension_flow_control_queue_duration_seconds",
    "Time spent queued in flow control", registry=REGISTRY,
    buckets=(.001, .005, .01, .05, .1, .5, 1, 5, 30))
PREFIX_HIT_RATIO = Histogram(
    "inference_extension_prefix_indexer_hit_ratio", "Prefix-cache hit ratio",
    registry=REGISTRY, buckets=(0, .1, .25, .5, .75, .9, 1))
# Predicted-latency subsystem (reference metrics.go: predicted ttft/tpot +
# slo-violation counters).
PREDICTED_TTFT_MS = Histogram(
    "inference_extension_predicted_time_to_first_token_ms",
    "Predicted TTFT at scheduling time", registry=REGISTRY,
    buckets=(1, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000, 10000))
PREDICTED_TPOT_MS = Histogram(
    "inference_extension_predicted_time_per_output_token_ms",
    "Predicted TPOT at scheduling time", registry=REGISTRY,
    buckets=(.1, .5, 1, 2.5, 5, 10, 25, 50, 100, 250))
LATENCY_TRAINING_SAMPLES = Counter(
    "inference_extension_latency_predictor_training_samples_total",
    "Online latency-model training samples ingested",
    ("kind",), registry=REGISTRY)  # kind: ttft | tpot
SLO_VIOLATION_TOTAL = Counter(
    "inference_extension_slo_violation_total",
    "Completed requests whose observed latency violated the request SLO",
    ("kind",), registry=REGISTRY)
# Metrics-data-source scrape health: per-endpoint failure counts and the
# scrape latency distribution (label cardinality bounded by pool size).
SCRAPE_ERRORS_TOTAL = Counter(
    "inference_extension_metrics_scrape_errors_total",
    "Failed engine /metrics scrapes", ("target",), registry=REGISTRY)
SCRAPE_DURATION_SECONDS = Histogram(
    "inference_extension_metrics_scrape_duration_seconds",
    "Engine /metrics scrape latency", registry=REGISTRY,
    buckets=(.001, .005, .01, .025, .05, .1, .25, .5, 1, 2))
# Resilient data plane (router/resilience.py): retry/failover, passive
# endpoint circuit breaking, end-to-end deadlines, stream-abort handling.
RETRIES_TOTAL = Counter(
    "router_retries_total",
    "Gateway retry/failover attempts after a pre-stream upstream failure",
    ("kind",), registry=REGISTRY)  # kind: connect | read | status
RETRY_BUDGET_EXHAUSTED_TOTAL = Counter(
    "router_retry_budget_exhausted_total",
    "Retries suppressed because the token-bucket retry budget was empty",
    registry=REGISTRY)
BREAKER_STATE = Gauge(
    "router_endpoint_circuit_breaker_state",
    "Per-endpoint breaker state: 0 closed, 1 half-open, 2 open",
    ("endpoint",), registry=REGISTRY)  # cardinality bounded by pool size
BREAKER_TRANSITIONS_TOTAL = Counter(
    "router_circuit_breaker_transitions_total",
    "Breaker state transitions per endpoint",
    ("endpoint", "to_state"), registry=REGISTRY)
DEADLINE_EXCEEDED_TOTAL = Counter(
    "router_request_deadline_exceeded_total",
    "Requests rejected at the gateway with the end-to-end deadline exhausted",
    registry=REGISTRY)
UPSTREAM_STREAM_ABORTED_TOTAL = Counter(
    "router_upstream_stream_aborted_total",
    "Response streams cut mid-relay by an upstream disconnect (closed "
    "cleanly toward the client instead of raising)", registry=REGISTRY)
# Decision flight recorder aggregates (router/decisions.py): the histogram/
# counter shadows of the per-request records, so score distributions, filter
# pressure, and pick decisiveness are graphable without reading records.
# Label cardinality is bounded by the configured plugin set.
SCORER_SCORE = Histogram(
    "router_scorer_score",
    "Per-endpoint raw scorer outputs observed at scheduling time",
    ("scorer",), registry=REGISTRY,
    buckets=(0.0, .1, .2, .3, .4, .5, .6, .7, .8, .9, 1.0))
FILTER_DROPPED_TOTAL = Counter(
    "router_filter_dropped_endpoints_total",
    "Candidate endpoints removed per scheduling filter",
    ("filter",), registry=REGISTRY)
PICKER_WIN_MARGIN = Histogram(
    "router_picker_win_margin",
    "Weighted-score margin between the picked endpoint and the runner-up "
    "(0 = coin flip; large = decisive pick)",
    ("picker",), registry=REGISTRY,
    buckets=(0.0, .01, .025, .05, .1, .25, .5, 1.0, 2.0, 4.0))
# Concurrent scheduling engine (router/schedpool.py + router/snapshot.py):
# off-loop scheduler workers over copy-on-write pool snapshots, batched
# flow-control dispatch.
SCHED_OFFLOAD_QUEUE_SECONDS = Histogram(
    "router_sched_offload_queue_seconds",
    "Time a scheduling cycle waited between submission to the worker pool "
    "and a worker picking it up",
    registry=REGISTRY,
    buckets=(.00001, .0001, .00025, .0005, .001, .0025, .005, .01, .05, .1))
SCHED_BATCH_SIZE = Histogram(
    "router_sched_batch_size",
    "Flow-control items dispatched per shard wake (co-dispatched batches "
    "share one pool-snapshot epoch)",
    registry=REGISTRY, buckets=(1, 2, 4, 8, 16, 32, 64))
LOOP_LAG_SECONDS = Histogram(
    "router_loop_lag_seconds",
    "Event-loop scheduling stall sampled by the gateway's heartbeat "
    "(sleep-overshoot of a 100ms timer; the stall token relays experience)",
    registry=REGISTRY, buckets=LOOP_LAG_BUCKETS)
STREAM_GAP_MAX_SECONDS = Histogram(
    "router_stream_gap_max_seconds",
    "Longest gap between two token-bearing chunks of one relayed stream, "
    "observed once when the request closes (the inter-token-latency tail at "
    "the front door)",
    registry=REGISTRY, buckets=PERIOD_BUCKETS)
# SLO & goodput ledger (router/slo.py): per-request serving outcomes,
# predictor calibration, goodput vs raw token rate. The per-request detail
# (predicted vs actual vs SLO, miss reason, transfer row) lives in the
# DecisionRecord outcome block; these are the graphable aggregates.
SLO_ATTAINMENT = Gauge(
    "router_slo_attainment",
    "Running SLO attainment ratio (slo_met terminal requests / all terminal "
    "requests) per endpoint", ("endpoint",),
    registry=REGISTRY)  # children evicted with SloLedger.MAX_ENDPOINTS LRU
SLO_REQUESTS_TOTAL = Counter(
    "router_slo_requests_total",
    "Terminal serving outcomes by verdict (met / missed / error)",
    ("verdict",), registry=REGISTRY)
GOODPUT_TOKENS_TOTAL = Counter(
    "router_goodput_tokens_total",
    "Completion tokens delivered inside the request SLO (goodput; "
    "P/D-Serve's fleet objective)", ("model",), registry=REGISTRY)
OUTPUT_TOKENS_TOTAL = Counter(
    "router_output_tokens_total",
    "All completion tokens delivered (raw token rate — divergence from "
    "router_goodput_tokens_total is wasted work)",
    ("model",), registry=REGISTRY)
PREDICTOR_ERROR_MS = Histogram(
    "router_predictor_error_ms",
    "Absolute error of the predicted-latency ridge vs the observed value "
    "(kind: ttft | tpot; role: served endpoint's pool role). Signed "
    "error/bias is in the /debug/slo rollup.",
    ("kind", "role"), registry=REGISTRY,
    buckets=(1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 2500))
KV_TRANSFER_MS = Histogram(
    "router_kv_transfer_ms",
    "Per-request KV pull duration measured by the decode engine and relayed "
    "through the sidecar (per-pair EWMA table at /debug/transfers)",
    registry=REGISTRY,
    buckets=(1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 2500))
KV_TRANSFER_EXPOSED_MS = Histogram(
    "router_kv_transfer_exposed_ms",
    "Per-request KV pull time NOT hidden behind prefill compute on pipelined "
    "P/D requests (raw pull minus overlap; the cost pair scorers/rebalancer "
    "read). Absent on serial 2-phase pulls, where exposed == raw.",
    registry=REGISTRY,
    buckets=(1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 2500))
# Goodput-max overload control (router/overload.py): predictive SLO
# admission, degrade ladder, Retry-After shedding, and predicted-unmeetable
# queue eviction. Reason/action label sets are fixed small enums.
ADMISSION_SHED_TOTAL = Counter(
    "router_admission_shed_total",
    "Requests shed by the overload controller before capacity was spent "
    "(reason: predicted_ttft_miss | predicted_tpot_miss | queue_unmeetable)",
    ("reason",), registry=REGISTRY)
DEGRADED_REQUESTS_TOTAL = Counter(
    "router_degraded_requests_total",
    "Requests admitted via the degrade ladder instead of being shed "
    "(action: clamp_max_tokens | model_rewrite)",
    ("action",), registry=REGISTRY)
RETRY_AFTER_SECONDS = Histogram(
    "router_retry_after_seconds",
    "Computed Retry-After handed to shed requests (derived from the queue "
    "drain rate; always finite)",
    registry=REGISTRY, buckets=(1, 2, 5, 10, 15, 30, 60))
QUEUE_DRAIN_RATE = Gauge(
    "router_queue_drain_rate",
    "Measured flow-control dispatch rate (requests/second, EWMA) feeding "
    "the overload controller's queue-wait and Retry-After estimates",
    registry=REGISTRY)
# KV-cache & prefix-reuse observability (router/kvobs.py): the
# predicted-vs-confirmed hit ledger behind /debug/kv. Per-request detail
# (per-candidate predictions, the engine-confirmed actual, signed error)
# lives in the DecisionRecord cache block; these are the graphable
# aggregates.
KV_PREDICTED_HIT_BLOCKS = Histogram(
    "router_kv_predicted_hit_blocks",
    "Schedule-time predicted prefix-hit depth (blocks) for the chosen "
    "endpoint (approx producer / precise scorer prediction)",
    registry=REGISTRY, buckets=(0, 1, 2, 4, 8, 16, 32, 64, 128))
KV_HIT_PREDICTION_ERROR = Histogram(
    "router_kv_hit_prediction_error",
    "Absolute error (blocks) between the predicted hit depth and the "
    "engine-confirmed actual (x-kv-hit-blocks); signed bias is in the "
    "/debug/kv rollup",
    registry=REGISTRY, buckets=(0, 1, 2, 4, 8, 16, 32, 64))
KV_ACTUAL_HIT_RATIO = Histogram(
    "router_kv_actual_hit_ratio",
    "Engine-confirmed prefix-hit ratio (hit tokens / prompt tokens) per "
    "completed request",
    registry=REGISTRY, buckets=(0.0, .1, .25, .5, .75, .9, 1.0))
# Session-aware prefill classifier (router/plugins/disagg.py): the
# ledger-driven placement stage that routes high-confidence cache-hit
# prefills straight to the decode pod (skip the P/D hop). Verdicts are a
# fixed small enum; the per-request detail (predicted depth, trust
# discount, threshold, post-hoc judgement) is the DecisionRecord
# classifier block, and per-pod precision/recall is on /debug/kv.
PD_CLASSIFIER_DECISIONS_TOTAL = Counter(
    "router_pd_classifier_decisions_total",
    "Prefill-classifier verdicts per evaluation (verdict: skip = route "
    "straight to the decode pod, keep = run the P/D decider as usual, "
    "low_confidence = not enough measured trust to act on the prediction)",
    ("verdict",), registry=REGISTRY)
PD_HOP_SKIPPED_TOTAL = Counter(
    "router_pd_hop_skipped_total",
    "Requests routed straight to the decode pod by the prefill classifier "
    "(no prefill leg, no KV pull — the P/D hop skipped)",
    registry=REGISTRY)
# Fleet flight recorder (router/timeline.py): the /debug/timeline sampler,
# the multi-window SLO burn-rate monitor, and the /debug/incidents ring.
# The per-tick detail lives in the timeline samples; these are the
# graphable aggregates (and the liveness signal that the sampler ticks).
TIMELINE_TICKS = Counter(
    "router_timeline_ticks_total",
    "Timeline sampler ticks recorded (liveness of the flight recorder; "
    "absent/frozen under the timeline kill-switch)", registry=REGISTRY)
SLO_BURN_RATE = Gauge(
    "router_slo_burn_rate",
    "Multi-window SLO error-budget burn rate ((1 - met/arrivals) / "
    "(1 - target); arrivals include sheds — the arrival-relative goodput "
    "view, deliberately stricter than /debug/slo's served-relative "
    "attainment)", ("window",), registry=REGISTRY)  # window: fast | slow
INCIDENTS_TOTAL = Counter(
    "router_incidents_total",
    "Triggered incident snapshots captured into the /debug/incidents ring "
    "(rule: burn_rate | shed_rate | drain_collapse | divergence); "
    "dedup/cooldown means a sustained episode counts once",
    ("rule",), registry=REGISTRY)
# Process self-telemetry feeding the timeline: before these the only
# process-health signal was router_loop_lag_seconds.
PROCESS_RSS_BYTES = Gauge(
    "router_process_rss_bytes",
    "Resident set size of the router process (/proc/self/statm, sampled "
    "per timeline tick)", registry=REGISTRY)
PROCESS_OPEN_FDS = Gauge(
    "router_process_open_fds",
    "Open file descriptors of the router process (sockets, pipes, files; "
    "sampled per timeline tick)", registry=REGISTRY)
GC_PAUSE_SECONDS = Counter(
    "router_gc_pause_seconds_total",
    "Cumulative stop-the-world garbage-collection pause time "
    "(gc.callbacks; every pause stalls the event loop and all scheduler "
    "workers)", registry=REGISTRY)
# Effective-config identity (/debug/config): the hash label changes only
# with the loaded config, so cardinality is one series per process — the
# fleet fan-in compares hashes across shards to catch config skew.
CONFIG_INFO = Gauge(
    "router_config_info",
    "Constant 1, labeled with the xxh64 hash of the effective loaded "
    "config — scrape-joinable config-skew detection (redacted snapshot at "
    "/debug/config)", ("hash",), registry=REGISTRY)
# Shadow policy evaluation (router/shadow.py): counterfactual scheduling
# verdicts and the signed estimated-regret distribution per registered
# policy. Policy/verdict label sets are bounded by the configured policy
# list and the fixed verdict enum; per-request detail is the DecisionRecord
# shadow block, the per-policy rollup is GET /debug/shadow.
SHADOW_DECISIONS_TOTAL = Counter(
    "router_shadow_decisions_total",
    "Shadow-policy counterfactual verdicts per evaluated scheduling cycle "
    "(verdict: agree = shadow pick matches the live pick, diverge = the "
    "policy would have picked differently, no_signal = the policy's "
    "measured feed has no data yet)",
    ("policy", "verdict"), registry=REGISTRY)
SHADOW_REGRET_MS = Histogram(
    "router_shadow_regret_ms",
    "Signed estimated regret of the LIVE policy per judged divergent pick "
    "(live measured cost minus the shadow arm's estimate from the measured "
    "feeds; positive = the shadow policy would have been cheaper). Only "
    "judged divergences observe — agreements credit both arms at "
    "/debug/shadow instead",
    ("policy",), registry=REGISTRY,
    buckets=(-250, -100, -50, -25, -10, -5, -1, 0,
             1, 5, 10, 25, 50, 100, 250))
# Self-balancing pool (router/rebalance.py): dynamic P/D role rebalancing
# with drain-cycle role flips and predictive scaling advice. Role/direction
# label sets are fixed small enums; the per-flip detail (full controller
# inputs) is served at /debug/rebalance.
REBALANCE_HEADROOM = Gauge(
    "router_rebalance_headroom",
    "Per-role goodput headroom computed by the rebalance controller each "
    "tick (0 = saturated, 1 = idle; 1 - max(engine queue pressure, "
    "workload SLO miss rate) — full inputs at /debug/rebalance)",
    ("role",), registry=REGISTRY)
ROLE_FLIPS_TOTAL = Counter(
    "router_role_flips",
    "Completed drain-cycle pod role flips (llm-d.ai/role republished "
    "after in-flight work cleared); every flip's full inputs are at "
    "/debug/rebalance",
    ("from", "to"), registry=REGISTRY)
POOL_ADVICE = Gauge(
    "router_pool_advice",
    "Predictive scaling advice per role (1 = advised): direction=up when "
    "a role starves and no role flip can help, direction=down when a role "
    "idles against a healthy peer (for prefill, a sustained hop-skip rate "
    "is extra evidence) — the autoscaler hook a k8s InferencePool "
    "reconciler would consume",
    ("role", "direction"), registry=REGISTRY)
POOL_ADVICE_CHANGES = Counter(
    "router_pool_advice_changes_total",
    "Scaling-advice state TRANSITIONS per role (incremented only when the "
    "advised direction changes, labeled with the direction entered: "
    "up | down | hold) — rate() this for advice churn; the point-in-time "
    "verdict stays on router_pool_advice",
    ("role", "direction"), registry=REGISTRY)
# Traffic forecaster & capacity observatory (router/forecast.py): judged
# multi-horizon prediction over the timeline grid. Series/horizon label
# sets are bounded (the engine caps tracked series; horizons come from
# config); the full ledger is GET /debug/forecast.
FORECAST_MAE = Gauge(
    "router_forecast_mae",
    "Windowed mean absolute forecast error per judged (series, horizon) "
    "cell, in the series' native unit (req/s, tokens/s, requests, "
    "headroom) — every elapsed forecast joins against the actual "
    "timeline sample, never assumed (/debug/forecast)",
    ("series", "horizon"), registry=REGISTRY)
FORECAST_SKILL = Gauge(
    "router_forecast_skill",
    "Forecast skill vs the naive last-value persistence baseline per "
    "(series, horizon): 1 - MAE/MAE_persistence over the judged window. "
    "<= 0 means the model cannot beat copying the current value forward "
    "— visibly worthless, by design", ("series", "horizon"),
    registry=REGISTRY)
FORECAST_COVERAGE = Gauge(
    "router_forecast_interval_coverage",
    "Fraction of judged forecasts whose actual landed inside the stamped "
    "prediction interval, per (series, horizon) — held against the "
    "configured forecast.intervals target", ("series", "horizon"),
    registry=REGISTRY)
FORECAST_STAMPS = Counter(
    "router_forecast_stamps_total",
    "Forecasts stamped (one per series per horizon per timeline tick "
    "after warmup; zero under the forecast kill-switch)",
    registry=REGISTRY)
FORECAST_JOINS = Counter(
    "router_forecast_joins_total",
    "Elapsed-horizon forecasts judged against their actual timeline "
    "sample (joins/(joins+gap_skips) is the join-coverage rate)",
    registry=REGISTRY)
FORECAST_GAP_SKIPS = Counter(
    "router_forecast_gap_skips_total",
    "Forecasts dropped unjudged because their target bucket was a gap "
    "(sampler stall/restart, or the series absent from the sample) — "
    "gaps are skipped, never interpolated", registry=REGISTRY)
TIME_TO_SATURATION = Gauge(
    "router_time_to_saturation_seconds",
    "Capacity observatory: projected seconds until the role's forecasted "
    "headroom crosses zero (level+trend zero-crossing of the rebalancer's "
    "per-role headroom series; +Inf when no saturation is projected) — "
    "the scale-ahead lead the pool advice carries as lead_s",
    ("role",), registry=REGISTRY)
# Confirmed-index replication (router/fleet.py): a follower that detects a
# sequence gap in the leader's KV delta stream stops applying deltas and
# waits for the next full-index checkpoint frame to resync. Worker-side —
# the fleet /metrics merge sums it across shards.
KV_INDEX_RESYNCS = Counter(
    "router_kv_index_resyncs_total",
    "Confirmed KV-index delta-stream resyncs in this worker: a sequence "
    "gap (dropped frame, leader change, reconnect) was detected and the "
    "replica waited for the next full-index checkpoint instead of "
    "applying deltas onto an uncertain base", registry=REGISTRY)
# Guarded elastic-fleet actuator (router/autoscale.py): every guarded
# action's terminal verdict, the rollback-freeze latch, and the live fleet
# census the actuator is steering.
AUTOSCALE_ACTIONS = Counter(
    "router_autoscale_actions",
    "Guarded actuator actions by terminal outcome (completed / refused / "
    "aborted / rolled_back) per kind (spawn_pod / retire_pod / "
    "spawn_worker / retire_worker) — refusals are deduplicated per "
    "sustained reason episode in the /debug/autoscale ledger but counted "
    "here per tick", ("kind", "outcome"), registry=REGISTRY)
AUTOSCALE_FROZEN = Gauge(
    "router_autoscale_frozen",
    "1 while the actuator is frozen by rollback-on-incident (a burn-rate "
    "trip or attainment collapse inside a post-action observation window "
    "reversed the last action and latched this until operator reset)",
    registry=REGISTRY)
FLEET_SIZE = Gauge(
    "router_fleet_size",
    "Live fleet census per role as the actuator sees it: engine pods per "
    "routing role (prefill / decode, draining included) plus the active "
    "gateway worker count under role=\"worker\" when worker scaling is "
    "wired", ("role",), registry=REGISTRY)
# Tail-latency attribution observatory (router/tails.py, ISSUE 18): the
# per-request critical-path waterfall decomposed into stage histograms, and
# the online dominant-stage verdict for requests classified into a cohort's
# tail at close time. Exemplar request-ids live in the /debug/tails JSON
# payload, never on labels (FORBIDDEN_LABELS).
STAGE_MS = Histogram(
    "router_stage_ms",
    "Per-request critical-path stage time (ms) from the closed waterfall: "
    "queue (flow-control admission wait), sched (scheduling cycle + "
    "offload dispatch), attempts (time burned in failed failover "
    "attempts), engine_queue (engine admission-to-first-step wait), "
    "prefill (x-prefill-duration-ms), kv_transfer (x-kv-transfer-ms), "
    "decode (residual TTFT), stream (first-to-last token relay)",
    ("stage",),
    buckets=(1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000,
             10000),
    registry=REGISTRY)
TAIL_DOMINANT_STAGE_TOTAL = Counter(
    "router_tail_dominant_stage",
    "Requests classified into their cohort's tail at close time (TTFT "
    "above the rolling tailQuantile threshold), by the stage with the "
    "largest excess over the cohort's body mean — the online twin of the "
    "/debug/tails attribution", ("cohort", "stage"), registry=REGISTRY)
# Multi-process sharded gateway (router/fleet.py): each worker exposes the
# pool-snapshot epoch it last built (leader) or applied from the IPC stream
# (follower) — the supervisor re-labels it per shard, making snapshot-IPC
# staleness graphable fleet-wide.
SNAPSHOT_EPOCH = Gauge(
    "router_snapshot_epoch",
    "Pool-snapshot epoch this process last built (datalayer leader / "
    "single-process router) or applied from the fleet leader's IPC stream "
    "(follower worker)", registry=REGISTRY)

# Binary snapshot frames (router/snapwire.py) that failed validation and
# were skipped by a follower. Skipped, not fatal: the outer length prefix
# keeps the stream aligned, so one bad frame costs one epoch of staleness.
# reason: truncated | checksum | version | malformed.
SNAPSHOT_FRAME_ERRORS = Counter(
    "router_snapshot_frame_errors",
    "Binary snapshot-IPC frames a follower rejected and skipped (bad "
    "magic/shape=malformed, payload digest mismatch=checksum, length "
    "short of the header's claim=truncated, unsupported format "
    "version=version)",
    ("reason",), registry=REGISTRY)

# Fleet-supervisor registry (router/fleet.py): families that exist only in
# the supervisor process — worker liveness, per-shard request/epoch views
# derived from the admin-plane scrapes, and the hash balancer's connection
# counts. A SEPARATE registry: the supervisor must not re-emit the router
# families above with zero values next to the workers' merged real ones.
FLEET_REGISTRY = CollectorRegistry()

FLEET_WORKERS = Gauge(
    "router_fleet_workers",
    "Configured gateway worker processes in the fleet",
    registry=FLEET_REGISTRY)
SHARD_UP = Gauge(
    "router_shard_up",
    "Per-shard worker liveness as seen by the fleet supervisor (1 = the "
    "worker process is alive and its admin plane answers)",
    ("shard",), registry=FLEET_REGISTRY)
SHARD_STATE = Gauge(
    "router_shard_state",
    "Per-shard lifecycle state companion to router_shard_up, so a worker "
    "retired ON PURPOSE by the scale-in path is distinguishable from a "
    "crashed one (0 = down/crashed, 1 = up, 2 = retiring — draining its "
    "flows before exit, 3 = retired — deliberately scaled in)",
    ("shard",), registry=FLEET_REGISTRY)
SHARD_SNAPSHOT_EPOCH = Gauge(
    "router_shard_snapshot_epoch",
    "router_snapshot_epoch per worker, re-labeled by shard at merge time — "
    "a follower lagging the leader's epoch is visible as a gap",
    ("shard",), registry=FLEET_REGISTRY)
SHARD_REQUESTS = Counter(
    "router_shard_requests",
    "Requests handled per shard (derived from each worker's "
    "inference_extension_request_total at merge time)",
    ("shard",), registry=FLEET_REGISTRY)
FLEET_BALANCER_CONNECTIONS = Counter(
    "router_fleet_balancer_connections",
    "Connections routed per shard by the hash-by-flow-id front balancer "
    "(fleet.balancer: hash; absent under SO_REUSEPORT kernel balancing)",
    ("shard",), registry=FLEET_REGISTRY)
FLEET_LEADER = Gauge(
    "router_fleet_leader",
    "Datalayer-leader role per shard (1 = this worker runs the scrape + "
    "kv-event pipeline and publishes snapshot/KV-delta frames; moves on "
    "leader re-election when the leader process dies)",
    ("shard",), registry=FLEET_REGISTRY)
LEADER_ELECTIONS = Counter(
    "router_leader_elections",
    "Datalayer-leader re-elections performed by the fleet supervisor (a "
    "dead leader was replaced by promoting the lowest-index live "
    "follower)", registry=FLEET_REGISTRY)
KV_INDEX_DIVERGENCE = Gauge(
    "router_kv_index_divergence",
    "Per-shard KV-index divergence derived at /debug/kv fan-in time: the "
    "fraction of the leader's engine-confirmed KvBlockIndex blocks a "
    "follower's (speculative-only) view cannot account for — 0 on the "
    "leader, 1 on a follower with no overlapping stamps. Measures the "
    "ROADMAP item-1 follower-fidelity caveat (run balancer: hash when it "
    "matters)", ("shard",), registry=FLEET_REGISTRY)
