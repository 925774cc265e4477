"""Standalone EPP gateway: the router's HTTP data plane.

Plays the role of Envoy+EPP fused into one process (the reference's
standalone mode, chart at config/charts/standalone/ — SURVEY §L0/L1): parses
OpenAI requests, runs the Director (admission → producers → scheduling),
proxies to the picked engine, streams the response back, and feeds the
response hooks. The ext-proc gRPC server for a real Envoy data plane layers
on the same Director.

Wire behavior kept from the reference:
- x-gateway-destination-endpoint set from the scheduling result
  (handlers/request.go), echoed back as x-gateway-destination-endpoint-served
- unparseable bodies fall back to a random endpoint (server.go:335-342)
- 429/503 rejections carry x-removal-reason (pkg/common/error)
- response bodies rewrite "model" back to the client-facing name when a
  rewrite was applied (server.go:471-485)
"""

from __future__ import annotations

import asyncio
import json
import logging
import sys
import time
import uuid
from typing import Any

import aiohttp
import httpx
from aiohttp import web
from prometheus_client import generate_latest

from .config.loader import Handle, RouterConfig, load_config
from .datalayer.datastore import Datastore
from .datalayer.runtime import DataLayerRuntime
from .decisions import SCHEMA_VERSION, DecisionConfig, DecisionRecorder
from .framework.scheduling import InferenceRequest
from .handlers.parsers import make_parser
from .metrics import (
    DEADLINE_EXCEEDED_TOTAL,
    KV_TRANSFER_EXPOSED_MS,
    KV_TRANSFER_MS,
    LOOP_LAG_SECONDS,
    POOL_AVG_KV_CACHE,
    POOL_AVG_QUEUE,
    POOL_READY_ENDPOINTS,
    REGISTRY,
    REQUEST_DURATION,
    RETRIES_TOTAL,
    RETRY_BUDGET_EXHAUSTED_TOTAL,
    TTFT_SECONDS,
    INPUT_TOKENS,
    OUTPUT_TOKENS,
    UPSTREAM_STREAM_ABORTED_TOTAL,
)
from .requestcontrol.admission import AdmissionError, X_REMOVAL_REASON
from .resilience import (
    DEADLINE_EXCEEDED_REASON,
    Deadline,
    H_REQUEST_TIMEOUT,
    RETRY_BUDGET_REASON,
    ResilienceConfig,
    RetryBudget,
    UpstreamFailure,
)
from .requestcontrol.director import (
    Director,
    H_DESTINATION,
    H_DESTINATION_SERVED,
    H_REQUEST_ID,
    RequestError,
)
from .kvobs import H_KV_HIT_BLOCKS, H_KV_HIT_TOKENS, CacheLedger, KvObsConfig
from .overload import DrainRateEstimator, OverloadConfig, OverloadController
from .autoscale import ActuatorController, AutoscaleConfig
from .forecast import ForecastConfig, ForecastEngine
from .rebalance import RebalanceConfig, RebalanceController
from .schedpool import LoopLagMonitor, SchedulerPool, SchedulingConfig
from .shadow import ShadowConfig, ShadowEvaluator
from .slo import SloConfig, SloLedger, finite_float_or_none
from .tails import TailsConfig, TailsObservatory
from .timeline import (
    TimelineConfig,
    TimelineSampler,
    config_hash,
    redact_config,
)
from .datalayer.data_graph import validate_and_order_producers

log = logging.getLogger("router.gateway")

FORWARD_HEADERS = ("x-prefiller-host-port", "x-encoder-hosts-ports",
                   "x-data-parallel-host-port", "x-request-id", "content-type")
ROUTER_OWNED_HEADERS = ("x-prefiller-host-port", "x-encoder-hosts-ports",
                        "x-data-parallel-host-port",
                        "x-gateway-destination-endpoint")

# Decision flight recorder opt-in: a request carrying
# `x-debug-decision: summary` gets the compact one-line verdict echoed in
# the response's x-decision-summary header (curl-level debugging; the full
# record stays on /debug/decisions/<request-id>).
H_DEBUG_DECISION = "x-debug-decision"
H_DECISION_SUMMARY = "x-decision-summary"

# Fleet shard identity echoed on proxied responses (router/fleet.py): which
# worker process served this request — the per-request twin of the
# supervisor's router_shard_* families.
H_ROUTER_SHARD = "x-router-shard"

# Engine queue-wait stamp (engine/server.py, sim parity in engine/sim.py;
# the sidecar relays it on the disagg path): per-request admission-to-
# first-step wait, separating engine queueing from compute in the
# waterfall's decode residual (router/tails.py). Non-streaming responses
# only — a streamed response's headers leave before admission completes.
H_ENGINE_QUEUE = "x-engine-queue-ms"

# Request bodies at or above this size have their JSON parse routed through
# the scheduler pool's workers instead of the event loop (json.loads of a
# multi-megabyte long-context body is a multi-millisecond loop stall —
# larger than the scheduling cycle the pool exists to offload). Small
# bodies parse inline: the executor hop costs more than the parse.
LARGE_BODY_PARSE_BYTES = 16 << 10


class Gateway:
    def __init__(self, cfg: RouterConfig, datastore: Datastore,
                 dl_runtime: DataLayerRuntime, *, host: str = "127.0.0.1",
                 port: int = 8081, grpc_health_port: int | None = None,
                 grpc_ext_proc_port: int | None = None,
                 lease_path: str | None = None,
                 config_watch_path: str | None = None,
                 kube_binding=None, kube_elector=None,
                 secure_serving: bool = False,
                 cert_path: str | None = None,
                 enable_cert_reload: bool = False,
                 fleet=None):
        self.cfg = cfg
        # Fleet worker identity (router/fleet.py FleetWorkerSpec): when set,
        # this gateway is one shard of a multi-process fleet — it may share
        # the listen port via SO_REUSEPORT, serve a private admin listener
        # for the supervisor's fan-in plane, and (as a follower) replicate
        # the leader's pool snapshots instead of scraping. None (the
        # default, and fleet.workers: 1) is the single-process router,
        # bit-identical to the pre-fleet gateway.
        self.fleet = fleet
        # Secure serving (reference runserver.go:136-171): one identity for
        # the HTTP listener and the ext-proc gRPC port; self-signed fallback
        # when no cert dir is mounted.
        self.tls = None
        if secure_serving:
            from .tlsutil import TlsServing

            self.tls = TlsServing(cert_path, enable_cert_reload)
        self.datastore = datastore
        self.dl_runtime = dl_runtime
        self.host, self.port = host, port
        self.parser = make_parser(cfg.parser_spec)

        # Resilience: retry/failover policy, token-bucket retry budget, and
        # the datastore-shared breaker registry (router/resilience.py).
        self.resilience = ResilienceConfig.from_spec(cfg.resilience)
        self.retry_budget = RetryBudget(
            ratio=self.resilience.retry_budget_ratio,
            min_per_sec=self.resilience.retry_budget_min_per_sec,
            burst=self.resilience.retry_budget_burst)
        datastore.breakers.configure(self.resilience)

        # Decision flight recorder (router/decisions.py): default-on bounded
        # ring; `decisions: {enabled: false}` is the kill-switch that
        # restores the zero-overhead baseline.
        self.decision_recorder = DecisionRecorder(
            DecisionConfig.from_spec(cfg.decisions))

        # SLO & goodput ledger (router/slo.py): per-request serving outcomes
        # closing the predict→observe loop. `slo: {enabled: false}` removes
        # the per-chunk hook from the streaming path entirely.
        self.slo_ledger = SloLedger(SloConfig.from_spec(cfg.slo))

        # Tail-latency attribution observatory (router/tails.py): the
        # per-request critical-path waterfall + body-vs-tail cohort ledger
        # behind /debug/tails. Default-on (the kvCache precedent); `tails:
        # {enabled: false}` means no waterfall object ever rides a request.
        self.tails_obs = TailsObservatory(TailsConfig.from_spec(cfg.tails))

        # KV-cache & prefix-reuse observability (router/kvobs.py): the
        # predicted-vs-confirmed hit ledger behind /debug/kv. `kvCache:
        # {enabled: false}` is the kill-switch; the per-pod EWMA table
        # lives on the datastore (plugins can read measured reuse).
        self.kv_ledger = CacheLedger(KvObsConfig.from_spec(cfg.kv_cache),
                                     datastore=datastore)
        self.kv_ledger.attach_plugins(cfg.plugins_by_name.values())

        # Shadow policy evaluation (router/shadow.py): the counterfactual
        # scheduling ledger behind /debug/shadow. Default-on but inert
        # until `shadow: {policies: [...]}` lists a policy; the live path
        # pays only an enqueue onto the shadow worker.
        self.shadow_eval = ShadowEvaluator(ShadowConfig.from_spec(cfg.shadow),
                                           datastore=datastore)

        # Goodput-max overload controller (router/overload.py): predictive
        # SLO admission, degrade ladder, Retry-After shedding. Disabled by
        # default (`overload: {enabled: true}` opts in); the predictor is
        # the predicted-latency producer when one is configured.
        producers = validate_and_order_producers(cfg.producers)
        self.overload = OverloadController(
            OverloadConfig.from_spec(cfg.overload),
            ledger=self.slo_ledger,
            predictor=next((p for p in producers
                            if hasattr(p, "admission_estimate")), None))
        if self.overload.enabled:
            # Little's-law backlog: the in-flight counter sees the queue a
            # new arrival actually stands behind (flow queue + scheduled +
            # streaming), before engine scrapes or saturation ever move.
            self.overload.inflight_fn = lambda: self._inflight

        # Outbound TLS verification policy for router-side client legs
        # (upstream proxy, /debug/traces + /v1/models fan-out). Default:
        # skip-verify (in-cluster pod-local certs); `tlsClient.caCertPath`
        # turns real verification on.
        from .tlsutil import client_verify

        tc = cfg.tls_client or {}
        self._client_tls_verify = client_verify(
            insecure_skip_verify=bool(tc.get("insecureSkipVerify", True)),
            ca_cert_path=tc.get("caCertPath") or None)
        # aiohttp form of the same policy: None = stock verification,
        # SSLContext = CA bundle or permissive skip-verify context.
        self._upstream_ssl = (None if self._client_tls_verify is True
                              else self._client_tls_verify)

        # saturation detector: explicit spec or default utilization-detector
        from .framework.plugin import global_registry
        det_spec = cfg.saturation_detector_spec or {"type": "utilization-detector"}
        self.detector = global_registry.instantiate(
            det_spec.get("type", "utilization-detector"),
            det_spec.get("name", "saturation-detector"),
            det_spec.get("parameters") or {}, None)

        from .flowcontrol.eviction import RequestEvictor

        self.evictor = RequestEvictor()
        self.flow_controller = None
        if cfg.feature_gates.get("flowControl"):
            from .flowcontrol import (
                FlowControlAdmissionController,
                FlowControlConfig,
                FlowController,
            )

            fc_cfg = FlowControlConfig.from_spec(cfg.flow_control or {})
            self.flow_controller = FlowController(
                fc_cfg,
                saturation_fn=lambda: self.detector.saturation(
                    self.datastore.endpoint_list()))
            admission = FlowControlAdmissionController(
                self.flow_controller, evictor=self.evictor,
                overload=self.overload if self.overload.enabled else None,
                shard=fleet.index if fleet is not None else None)
            if self.overload.enabled:
                # Queue depth + measured drain rate feed the feasibility
                # estimate; the queues gain unmeetable eviction + priority
                # decay (all gated on the same kill-switch).
                self.overload.attach_flow(self.flow_controller)
        else:
            from .requestcontrol.admission import LegacyAdmissionController

            admission = LegacyAdmissionController(self.detector)

        # Concurrent scheduling engine (router/schedpool.py): worker threads
        # run scheduling cycles over copy-on-write pool snapshots when
        # `scheduling: {workers: N>0}`; workers: 0 (default) = inline path.
        # The pool's executor doubles as the CPU-offload pool for scrape
        # parsing (data layer) and large-body request parsing (below).
        self.sched_pool = SchedulerPool(
            cfg.scheduler, SchedulingConfig.from_spec(cfg.scheduling))
        dl_runtime.offload = self.sched_pool.executor
        if self.flow_controller is not None and self.sched_pool.offloaded:
            # Batched flow-control dispatch: one shard wake hands up to
            # maxBatch co-dispatched requests to the pool; they share one
            # snapshot epoch and one scrape-state view.
            self.flow_controller.cfg.dispatch_batch = max(
                self.flow_controller.cfg.dispatch_batch,
                self.sched_pool.cfg.max_batch)
        self.loop_lag = LoopLagMonitor(LOOP_LAG_SECONDS)

        self.director = Director(
            datastore, cfg.scheduler, admission=admission,
            producers=producers,
            admit_plugins=cfg.admit_plugins,
            pre_request_plugins=cfg.pre_request_plugins,
            response_received=cfg.response_received,
            response_streaming=cfg.response_streaming,
            response_complete=cfg.response_complete,
            recorder=self.decision_recorder,
            sched_pool=self.sched_pool,
            overload=self.overload if self.overload.enabled else None,
            shadow=self.shadow_eval if self.shadow_eval.active else None)

        # Fleet flight recorder (router/timeline.py): the /debug/timeline
        # history + burn-rate monitor + /debug/incidents ring. Default-on
        # (the kvCache precedent); `timeline: {enabled: false}` removes the
        # sampler task entirely — the disabled sampler object only exists
        # so /debug/timeline still answers JSON.
        tl_cfg = TimelineConfig.from_spec(cfg.timeline)
        rb_cfg = RebalanceConfig.from_spec(cfg.rebalance)
        drain_fn = None
        if (tl_cfg.enabled or rb_cfg.enabled) \
                and self.flow_controller is not None:
            if self.overload.enabled:
                # The overload controller already measures drain; reuse it.
                drain_fn = self.overload.drain.rate
            else:
                # Overload off: the timeline/rebalancer keep one shared
                # estimator on the dispatch observer (single slot, nothing
                # else owns it when overload is disabled).
                est = DrainRateEstimator()
                self.flow_controller.dispatch_observer = est.note
                drain_fn = est.rate

        # Self-balancing pool (router/rebalance.py): dynamic P/D role
        # rebalancing through drain-cycle flips + scaling advice. Disabled
        # by default (`rebalance: {enabled: true}` opts in); in fleet mode
        # only the datalayer-owning worker acts — a follower's flip would
        # be overwritten by the next leader snapshot (promote() arms it on
        # leader re-election).
        disagg_handlers = [p for p in cfg.plugins_by_name.values()
                           if hasattr(p, "hop_skips")]
        self.rebalancer = RebalanceController(
            rb_cfg,
            datastore=datastore,
            slo_ledger=self.slo_ledger,
            flow=self.flow_controller,
            drain_rate_fn=drain_fn,
            hop_skips_fn=((lambda: sum(p.hop_skips
                                       for p in disagg_handlers))
                          if disagg_handlers else None),
            acting=(fleet is None or fleet.runs_datalayer))

        # Traffic forecaster (router/forecast.py): judged multi-horizon
        # prediction over the flight recorder. No task of its own — it
        # rides the sampler's tick (so `forecast.enabled: false` OR
        # `timeline.enabled: false` means zero stamps), and qualifies
        # the rebalancer's advice with time-to-saturation leads.
        fc_cfg = ForecastConfig.from_spec(cfg.forecast)
        self.forecaster = ForecastEngine(fc_cfg, tick_s=tl_cfg.tick_s)
        fc_live = fc_cfg.enabled and tl_cfg.enabled

        # Guarded elastic-fleet actuator (router/autoscale.py): consumes
        # the rebalancer's sustained, lead-qualified advice and
        # spawns/retires pods (and workers, when a scaler is wired)
        # through the preflight/budget/watchdog/rollback pipeline.
        # Default-OFF kill-switch; the pod launcher is injected by the
        # embedding harness (bench, tests, a k8s reconciler) — without
        # one the actuator runs dry (refusals only). In fleet mode only
        # the datalayer-owning worker acts (promote() arms it).
        as_cfg = AutoscaleConfig.from_spec(cfg.autoscale)
        # Worker dimension in fleet mode: the acting worker drives the
        # supervisor's POST /fleet/scale (token shared via the worker
        # spec). Single-process or podsPerWorker:0 -> pods only.
        worker_scaler = None
        if (as_cfg.enabled and as_cfg.pods_per_worker > 0
                and fleet is not None
                and getattr(fleet, "sup_admin_port", 0)):
            from .autoscale import HttpWorkerScaler

            worker_scaler = HttpWorkerScaler(
                "127.0.0.1", fleet.sup_admin_port, fleet.control_token)
        self.autoscaler = ActuatorController(
            as_cfg,
            datastore=datastore,
            advice_fn=self.rebalancer.advice,
            worker_scaler=worker_scaler,
            burn_fn=self._burn_tripped,
            attainment_fn=self._last_attainment,
            acting=(fleet is None or fleet.runs_datalayer))

        self.timeline = TimelineSampler(
            tl_cfg,
            slo_ledger=self.slo_ledger,
            kv_ledger=self.kv_ledger,
            datastore=datastore,
            flow=self.flow_controller,
            inflight_fn=lambda: self._inflight,
            drain_rate_fn=drain_fn,
            degraded_fn=(lambda: self.overload.degraded_total)
            if self.overload.enabled else None,
            decisions_fn=self._recent_bad_decisions,
            shadow=self.shadow_eval if self.shadow_eval.active else None,
            rebalance=self.rebalancer if self.rebalancer.enabled else None,
            forecast=self.forecaster if fc_live else None,
            autoscale=self.autoscaler if self.autoscaler.enabled else None,
            tails=self.tails_obs if self.tails_obs.enabled else None)
        if fc_live and self.rebalancer.enabled:
            self.rebalancer.forecast = self.forecaster

        # Effective-config identity: the hash covers the UNREDACTED loaded
        # doc (config skew across fleet shards must show even when only
        # secrets differ); /debug/config serves the redacted snapshot.
        self.config_hash = config_hash(cfg.raw_doc)
        from .metrics import CONFIG_INFO

        CONFIG_INFO.labels(self.config_hash).set(1)

        self.app = web.Application()
        self.app.add_routes([
            web.post("/v1/completions", self.handle_inference),
            web.post("/v1/chat/completions", self.handle_inference),
            web.post("/v1/responses", self.handle_inference),
            web.post("/v1/embeddings", self.handle_inference),
            web.get("/metrics", self.metrics),
            web.get("/health", self.health),
            web.get("/v1/models", self.models),
            web.get("/debug/traces", self.traces),
            web.get("/debug/profile", self.profile),
            web.get("/debug/decisions", self.decisions),
            web.get("/debug/decisions/{request_id}", self.decision_detail),
            web.get("/debug/slo", self.slo),
            web.get("/debug/tails", self.tails_view),
            web.get("/debug/transfers", self.transfers),
            web.get("/debug/kv", self.kv),
            web.get("/debug/shadow", self.shadow_view),
            web.get("/debug/timeline", self.timeline_view),
            web.get("/debug/incidents", self.incidents_view),
            web.get("/debug/rebalance", self.rebalance_view),
            web.get("/debug/forecast", self.forecast_view),
            web.get("/debug/autoscale", self.autoscale_view),
            web.get("/debug/config", self.config_view),
            # Fleet control plane (router/fleet.py, loopback-guarded): the
            # supervisor's leader-election notices — promote this follower
            # to datalayer leader / re-aim the snapshot subscriber at a
            # freshly-elected leader's socket.
            web.post("/fleet/promote", self.fleet_promote),
            web.post("/fleet/retarget", self.fleet_retarget),
        ])
        self._runner: web.AppRunner | None = None
        # Fleet snapshot IPC endpoints (router/fleet.py): the datalayer
        # leader publishes PoolSnapshot epochs, followers apply them.
        self._snapshot_pub = None
        self._snapshot_sub = None
        self._client: httpx.AsyncClient | None = None
        self.draining = False   # SIGTERM drain: readiness flips not-ready
        self._inflight = 0      # live proxied requests (drain gate)
        self._models_fallback_cache: tuple[float, list] = (0.0, [])
        self._flusher: asyncio.Task | None = None
        self._profile_lock = asyncio.Lock()
        self.grpc_health = None
        if grpc_health_port is not None:
            from .health_grpc import HealthServer

            self.grpc_health = HealthServer(
                ready_fn=self._ready, host=host, port=grpc_health_port,
                tls=self.tls)
        # HA leader election + config reconciliation (controlplane.py —
        # reference runner.go:306-316 lease election with readiness coupling,
        # pkg/epp/controller reconcilers).
        self.elector = None
        if kube_elector is not None:
            # coordination.k8s.io/v1 Lease election (reference
            # controller_manager.go:84-91) — no shared volume required.
            self.elector = kube_elector
        elif lease_path is not None:
            from .controlplane import LeaseConfig, LeaseElector

            self.elector = LeaseElector(LeaseConfig(path=lease_path))
        self.reconciler = None
        if config_watch_path is not None:
            from .controlplane import ConfigReconciler

            self.reconciler = ConfigReconciler(config_watch_path, datastore)
        # k8s list+watch binding (router/kube.py) — replaces the static
        # pool / file reconciler when the gateway runs against an API server.
        self.kube_binding = kube_binding
        self.grpc_ext_proc = None
        if grpc_ext_proc_port is not None:
            from .handlers.extproc_grpc import ExtProcServer

            self.grpc_ext_proc = ExtProcServer(
                self.director, self.parser, evictor=self.evictor,
                host=host, port=grpc_ext_proc_port, tls=self.tls)

    # ---- lifecycle ------------------------------------------------------

    async def start(self):
        for meta in self.cfg.static_endpoints:
            self.datastore.endpoint_add_or_update(meta)
        self.datastore.pool_set(self.cfg.pool)
        for obj in self.cfg.objectives:
            self.datastore.objective_set(obj)
        for rw in self.cfg.model_rewrites:
            self.datastore.rewrite_set(rw)
        if self.fleet is None or self.fleet.runs_datalayer:
            await self.dl_runtime.start()
            if self.fleet is not None and self.fleet.ipc_path is not None:
                # Datalayer leader: the ONLY process scraping the engines;
                # every snapshot epoch broadcasts to the follower workers.
                await self._start_snapshot_publisher(self.fleet.ipc_path)
        else:
            # Fleet follower: pool state (membership + scrape metrics +
            # producer attributes) arrives as leader-published PoolSnapshot
            # epochs over IPC — no collectors, no per-worker SSE
            # subscriptions, so N workers impose 1x load on every engine.
            # With fleet.replication the same stream carries the leader's
            # engine-confirmed KvBlockIndex deltas + checkpoints, applied
            # into this worker's own index so precise-prefix scoring (and
            # everything built on it) behaves identically in every shard.
            from .fleet import SnapshotSubscriber

            self._snapshot_sub = SnapshotSubscriber(
                self.datastore, self.fleet.ipc_path,
                kv_index=(self._precise_index()
                          if self.fleet.replication else None))
            self._snapshot_sub.start()
        if self.flow_controller is not None:
            await self.flow_controller.start()
        # Verification policy from tlsClient config (default skip-verify:
        # pod-local certs — configured, not hardcoded).
        self._client = httpx.AsyncClient(timeout=httpx.Timeout(300.0, connect=5.0),
                                         verify=self._client_tls_verify)
        # The proxy hop uses aiohttp's client: its C http parser costs a
        # fraction of httpx/h11 per chunk, and iter_any() coalesces SSE
        # events under load — together worth >30% through-router throughput
        # at 128 concurrent streams (VERDICT r4 weak #4; measured with
        # scripts/profile_router_sse.py).
        import aiohttp as _aiohttp

        self._upstream = _aiohttp.ClientSession(
            timeout=_aiohttp.ClientTimeout(total=300.0, sock_connect=5.0))
        # Bounded handler shutdown: stop() must not sit out aiohttp's 60 s
        # default waiting on SSE proxy handlers after a drain timeout.
        self._runner = web.AppRunner(self.app, shutdown_timeout=5.0)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port,
                           reuse_port=(True if self.fleet is not None
                                       and self.fleet.reuse_port else None),
                           ssl_context=self.tls.ssl_context
                           if self.tls else None)
        await site.start()
        if self.fleet is not None and self.fleet.admin_port is not None:
            # Private per-worker admin listener: under SO_REUSEPORT the
            # supervisor cannot address one worker through the shared data
            # port, so the fan-in plane (merged /metrics, /debug lookups)
            # reaches each shard here. Same app — every route, loopback
            # only.
            admin_site = web.TCPSite(self._runner, self.fleet.admin_host,
                                     self.fleet.admin_port)
            await admin_site.start()
        self._flusher = asyncio.get_running_loop().create_task(self._flush_pool_gauges())
        # Loop-lag heartbeat: the stall token relays experience, live on
        # /metrics (router_loop_lag_seconds) — the number the scheduler
        # offload exists to shrink.
        self.loop_lag.start()
        # Fleet flight recorder: grid-aligned sampler ticks (no-op under
        # the timeline kill-switch).
        self.timeline.start()
        # Self-balancing pool controller (no-op when disabled or when this
        # worker is a fleet follower — promote() arms it on re-election).
        self.rebalancer.start()
        # Guarded elastic-fleet actuator (kill-switch: no task at all).
        self.autoscaler.start()
        if self.grpc_health is not None:
            await self.grpc_health.start()
        if self.grpc_ext_proc is not None:
            await self.grpc_ext_proc.start()
        if self.elector is not None:
            await self.elector.start()
        if self.reconciler is not None:
            await self.reconciler.start()
        if self.kube_binding is not None:
            await self.kube_binding.start()
        log.info("gateway listening on %s:%s (%d endpoints)",
                 self.host, self.port, len(self.datastore.endpoint_list()))

    async def stop(self):
        self.loop_lag.stop()
        await self.timeline.stop()
        await self.rebalancer.stop()
        await self.autoscaler.stop()
        if self._flusher:
            self._flusher.cancel()
        if self.grpc_health is not None:
            await self.grpc_health.stop()
        if self.grpc_ext_proc is not None:
            await self.grpc_ext_proc.stop()
        if self.kube_binding is not None:
            await self.kube_binding.stop()
        if self.reconciler is not None:
            await self.reconciler.stop()
        if self.elector is not None:
            await self.elector.stop()
        if self.flow_controller is not None:
            await self.flow_controller.stop()
        if self._snapshot_pub is not None:
            await self._snapshot_pub.stop()
        if self._snapshot_sub is not None:
            await self._snapshot_sub.stop()
        if self._runner:
            await self._runner.cleanup()
        if self._client:
            await self._client.aclose()
        if getattr(self, "_upstream", None) is not None:
            await self._upstream.close()
        await self.dl_runtime.stop()
        self.shadow_eval.stop()
        self.sched_pool.shutdown()
        if self.tls is not None:
            self.tls.close()

    async def _flush_pool_gauges(self):
        # reference: periodic pool-gauge flusher (datalayer/logger.go:38-124)
        try:
            while True:
                eps = self.datastore.endpoint_list()
                POOL_READY_ENDPOINTS.set(len(eps))
                if eps:
                    POOL_AVG_KV_CACHE.set(
                        sum(e.metrics.kv_cache_usage_percent for e in eps) / len(eps))
                    POOL_AVG_QUEUE.set(
                        sum(e.metrics.waiting_queue_size for e in eps) / len(eps))
                await asyncio.sleep(1.0)
        except asyncio.CancelledError:
            pass

    # ---- handlers ---------------------------------------------------------

    async def traces(self, request: web.Request) -> web.Response:
        """Finished-span ring buffer. With ?merge=1, fan out to every pool
        endpoint's /debug/traces and merge (dedup by span_id), so one call
        assembles cross-process gateway→sidecar→engine trace trees — the
        parent links survive because every hop propagates traceparent."""
        from .tracing import tracer

        spans = list(tracer.snapshot())
        if request.query.get("merge") not in (None, "", "0"):
            seen = {s["span_id"] for s in spans}

            async def fetch(ep):
                try:
                    r = await self._client.get(
                        ep.metadata.url + "/debug/traces", timeout=2.0)
                    return (r.json().get("spans") or []) if r.status_code == 200 else []
                except Exception:
                    return []

            for remote in await asyncio.gather(
                    *[fetch(ep) for ep in self.datastore.endpoint_list()]):
                for s in remote:
                    if isinstance(s, dict) and s.get("span_id") not in seen:
                        seen.add(s.get("span_id"))
                        spans.append(s)
        return web.json_response({"spans": spans})

    async def decisions(self, request: web.Request) -> web.Response:
        """Recent decision records (compact). ?n=N bounds the page (default
        50); each entry carries the one-line summary plus admission/final
        sections — the full record lives at /debug/decisions/<request-id>.
        Operator filters (decisions.record_matches): ?verdict=met|missed|
        error|shed (the SLO ledger's serving verdict), ?endpoint=<ip:port>
        (the destination that served), ?outcome=miss|shed (convenience
        aliases), ?profile=prefill|decode|skip-hop (the disaggregation
        shape the request took — skip-hop isolates the prefill
        classifier's skipped P/D hops), ?stage=<dominant-stage> (tail
        attribution: records whose waterfall landed in the cohort tail
        with that dominant stage, router/tails.py) — so records are
        findable without client-side scans."""
        from .decisions import record_matches

        try:
            n = int(request.query.get("n", "50"))
        except ValueError:
            n = 50
        n = max(1, n)
        verdict = request.query.get("verdict") or None
        endpoint = request.query.get("endpoint") or None
        outcome = request.query.get("outcome") or None
        profile = request.query.get("profile") or None
        stage = request.query.get("stage") or None
        # ?divergent=1 — shadow-policy counterfactual filter: only records
        # where a registered shadow policy would have picked differently
        # (?divergent=0 inverts; any other value matches nothing,
        # loudly-by-empty — the sibling filters' convention).
        # router/shadow.py, docs/shadow.md.
        div_q = request.query.get("divergent")
        divergent: Any = (None if div_q in (None, "")
                          else True if div_q in ("1", "true")
                          else False if div_q in ("0", "false")
                          else "invalid")
        filtered = verdict is not None or endpoint is not None \
            or outcome is not None or profile is not None \
            or divergent is not None or stage is not None
        # Filtering scans the WHOLE ring (the n newest matches, not the
        # matches within the n newest); the unfiltered path keeps the
        # cheap bounded snapshot.
        recs = self.decision_recorder.snapshot(None if filtered else n)
        docs = []
        for r in recs:
            doc = r.to_dict(compact=True)
            if filtered:
                # The endpoint filter also matches the attempt trail and
                # the profile filter the per-round profile sections — both
                # omitted from the compact form. Graft the raw lists onto
                # the probe (zero-copy; record_matches only reads
                # a["endpoint"] / each round's profile outcome) so
                # failed-over pods and P/D shapes are findable too.
                probe = doc
                if endpoint is not None or profile is not None:
                    probe = dict(doc)
                    if endpoint is not None:
                        probe["attempts"] = r.attempts
                    if profile is not None:
                        probe["rounds"] = r.rounds
                if not record_matches(probe, verdict=verdict,
                                      endpoint=endpoint, outcome=outcome,
                                      profile=profile, divergent=divergent,
                                      stage=stage):
                    continue
            docs.append(doc)
            if len(docs) >= n:
                break
        return web.json_response({
            "schema_version": SCHEMA_VERSION,
            "enabled": self.decision_recorder.enabled,
            "count": len(self.decision_recorder),
            "decisions": docs,
        })

    def _recent_bad_decisions(self, k: int) -> list[dict[str, Any]]:
        """The last K missed/shed DecisionRecords (compact), newest first —
        the incident recorder embeds them in each snapshot so "what broke"
        comes with "which requests it broke"."""
        out: list[dict[str, Any]] = []
        for rec in self.decision_recorder.snapshot(None):
            outcome = rec.outcome or {}
            verdict = outcome.get("verdict")
            if verdict in ("missed", "shed", "error"):
                out.append(rec.to_dict(compact=True))
                if len(out) >= k:
                    break
        return out

    def _burn_tripped(self) -> bool:
        """The actuator's rollback trigger: is the PR 12 multi-window
        burn-rate monitor tripped right now? (False under the timeline
        kill-switch — no monitor, no trigger.)"""
        if not self.timeline.enabled:
            return False
        burn = self.timeline.burn
        return burn.tripped(*burn.rates())

    def _last_attainment(self) -> float | None:
        """The most recent timeline tick's SLO attainment (None when the
        tick had no served arrivals, or under the timeline kill-switch)."""
        if not self.timeline.enabled or not self.timeline.ring:
            return None
        return self.timeline.ring[-1].get("attainment")

    async def timeline_view(self, request: web.Request) -> web.Response:
        """Fleet flight recorder history (router/timeline.py): raw ticks
        plus windowed aggregates; ?window_s=N bounds the returned window
        (default: the whole retained ring), ?series=a,b keeps only the
        named top-level keys, ?step_s=N downsamples ticks into coarser
        mean buckets (gap-aware: empty buckets stay absent)."""
        window_s = finite_float_or_none(request.query.get("window_s"))
        series_q = request.query.get("series")
        series = ([s for s in (p.strip() for p in series_q.split(","))
                   if s] if series_q else None)
        step_s = finite_float_or_none(request.query.get("step_s"))
        return web.json_response(self.timeline.snapshot(
            window_s=window_s if window_s and window_s > 0 else None,
            series=series or None,
            step_s=step_s if step_s and step_s > 0 else None))

    async def incidents_view(self, request: web.Request) -> web.Response:
        """Triggered incident snapshots (router/timeline.py): timeline
        window ±N ticks, the last K missed/shed DecisionRecords, and the
        /debug/slo + /debug/kv rollups captured at trigger time."""
        return web.json_response({
            "enabled": self.timeline.enabled,
            **self.timeline.incidents.snapshot(),
        })

    async def rebalance_view(self, request: web.Request) -> web.Response:
        """Self-balancing pool controller (router/rebalance.py): per-role
        headroom series, flip history with full DecisionRecord-style
        inputs, active drain cycles, and the current scaling advice."""
        return web.json_response(self.rebalancer.snapshot())

    async def forecast_view(self, request: web.Request) -> web.Response:
        """Traffic forecaster (router/forecast.py): per-series model
        state, the latest stamped forecast per horizon, the judged error
        ledger (MAE/MAPE/bias/coverage + skill vs persistence), and the
        capacity observatory's per-role saturation projections.
        ?joins=N inlines the N most recent judged rows per cell."""
        joins_q = request.query.get("joins")
        try:
            joins_n = max(0, min(int(joins_q), 1000)) if joins_q else None
        except ValueError:
            joins_n = None
        return web.json_response(self.forecaster.snapshot(
            joins_n=joins_n or None))

    async def autoscale_view(self, request: web.Request) -> web.Response:
        """Guarded elastic-fleet actuator (router/autoscale.py): the
        judged action ledger — every action, refusal, timeout, and
        rollback with its preflight inputs (advice, lead_s, headroom,
        budgets) and post-hoc outcome — plus the live budget window,
        breaker states, and the rollback-freeze latch."""
        return web.json_response(self.autoscaler.snapshot())

    async def config_view(self, request: web.Request) -> web.Response:
        """Redacted effective-config snapshot: what config THIS worker
        actually loaded (secrets masked, paths reduced to basenames), plus
        the hash router_config_info carries — the fleet fan-in compares it
        across shards."""
        return web.json_response({
            "hash": self.config_hash,
            "shard": self.fleet.index if self.fleet is not None else None,
            "config": redact_config(self.cfg.raw_doc),
        })

    # ---- fleet control plane (router/fleet.py leader election) ---------

    def _precise_index(self):
        """The precise-prefix scorer's engine-confirmed KvBlockIndex, when
        one is configured — the replication unit of fleet.replication
        (same discovery contract as CacheLedger.attach_plugins)."""
        found = [p for p in self.cfg.plugins_by_name.values()
                 if hasattr(p, "index_counts") and hasattr(p, "index")]
        if len(found) > 1:
            log.warning("fleet.replication: %d precise-prefix scorers "
                        "configured; replicating only %r",
                        len(found), found[0].name)
        return found[0].index if found else None

    async def _start_snapshot_publisher(self, path: str) -> None:
        from .fleet import KvReplicationSource, SnapshotPublisher

        kv_source = None
        if self.fleet.replication:
            index = self._precise_index()
            if index is not None:
                kv_source = KvReplicationSource(index)
        self._snapshot_pub = SnapshotPublisher(
            self.datastore, path, kv_source=kv_source,
            kv_checkpoint_s=self.fleet.kv_checkpoint_s,
            wire=self.fleet.wire)
        await self._snapshot_pub.start()

    def _fleet_request_allowed(self, request: web.Request) -> str | None:
        """Guard for the supervisor-only control routes: fleet mode with
        snapshot IPC, loopback peers, AND the per-fleet-run shared token —
        the loopback check alone is spoofable through the hash balancer's
        splice (the worker sees the balancer's loopback address, not the
        client's), and the same app serves the public data port."""
        if self.fleet is None or self.fleet.ipc_path is None:
            return "not a fleet worker (no snapshot IPC)"
        peer = (request.transport.get_extra_info("peername")
                if request.transport is not None else None)
        if (isinstance(peer, (tuple, list)) and peer
                and peer[0] not in ("127.0.0.1", "::1", "localhost")):
            return f"fleet control refused for non-loopback peer {peer[0]}"
        token = getattr(self.fleet, "control_token", None)
        if token and request.headers.get("x-fleet-token") != token:
            return "fleet control refused: bad or missing x-fleet-token"
        return None

    async def fleet_promote(self, request: web.Request) -> web.Response:
        """Supervisor promotion notice (leader re-election): this follower
        becomes the datalayer leader — start the scrape collectors +
        kv-event SSE lifecycle, resume local snapshot-epoch minting
        (continuing the dead leader's numbering), and publish on the fresh
        socket the supervisor advertises. Idempotent: a re-delivered
        promotion for the path already served returns 200."""
        err = self._fleet_request_allowed(request)
        if err is not None:
            return web.json_response({"error": err}, status=403)
        try:
            path = str((await request.json())["ipcPath"])
        except Exception:
            return web.json_response({"error": "ipcPath required"},
                                     status=400)
        if self.fleet.role == "leader" and self._snapshot_pub is not None:
            if self._snapshot_pub.path != path:
                # Re-promotion onto a fresh socket (e.g. a supervisor
                # retry that lost the first ack): move the publisher.
                await self._snapshot_pub.stop()
                self._snapshot_pub = None
                await self._start_snapshot_publisher(path)
            self.fleet.ipc_path = path
            return web.json_response({"role": "leader", "ipcPath": path})
        log.warning("promoted to datalayer leader (publishing on %s)", path)
        if self._snapshot_sub is not None:
            await self._snapshot_sub.stop()
            self._snapshot_sub = None
        self.datastore.resume_local_snapshots()
        # The lifecycle plugins build_gateway skipped for followers (per-pod
        # kv-event subscribers, LRU teardown) register now — and their
        # endpoint_added hooks fire for the pool that already exists, since
        # the datastore events that normally drive them are long past.
        for plugin in self.cfg.plugins_by_name.values():
            if (hasattr(plugin, "endpoint_added")
                    or hasattr(plugin, "endpoint_removed")):
                # Guard against a supervisor promote retry that lost the
                # first ack mid-setup: registration must stay idempotent.
                if plugin in self.dl_runtime.lifecycle_plugins:
                    continue
                self.dl_runtime.register_lifecycle(plugin)
                added = getattr(plugin, "endpoint_added", None)
                if added is not None:
                    for ep in self.datastore.endpoint_list():
                        try:
                            added(ep)
                        except Exception:
                            log.exception("lifecycle plugin failure "
                                          "(promotion add)")
        await self.dl_runtime.start()
        self.fleet.role = "leader"
        self.fleet.ipc_path = path
        await self._start_snapshot_publisher(path)
        # The promoted worker now owns the datalayer, so the rebalance
        # controller and the elastic-fleet actuator (if configured) may
        # act on pool metadata.
        self.rebalancer.promote()
        self.autoscaler.promote()
        return web.json_response({"role": "leader", "ipcPath": path})

    async def fleet_retarget(self, request: web.Request) -> web.Response:
        """Supervisor re-target notice: a new leader was elected on a
        fresh snapshot socket; aim the subscriber there NOW (event-driven —
        not after an exponential backoff against the dead socket)."""
        err = self._fleet_request_allowed(request)
        if err is not None:
            return web.json_response({"error": err}, status=403)
        try:
            path = str((await request.json())["ipcPath"])
        except Exception:
            return web.json_response({"error": "ipcPath required"},
                                     status=400)
        self.fleet.ipc_path = path
        if self._snapshot_sub is not None:
            self._snapshot_sub.retarget(path)
        return web.json_response({"role": self.fleet.role, "ipcPath": path})

    async def shadow_view(self, request: web.Request) -> web.Response:
        """Shadow-policy counterfactual ledger rollup (router/shadow.py):
        per-policy agreement rate, coverage, signed estimated-regret ms,
        and the recent-divergence ring — every registered policy's regret
        curve, measured in shadow before a config activates it live."""
        return web.json_response(self.shadow_eval.snapshot())

    async def kv(self, request: web.Request) -> web.Response:
        """KV-cache & prefix-reuse observability rollup (router/kvobs.py):
        per-pod measured hit-rate and signed-prediction-error EWMAs, index
        occupancy (approx LRU blocks, precise confirmed/speculative stamp
        counts), scraped engine hit counters, and the prediction MAE over
        all predicted→confirmed joins."""
        return web.json_response(self.kv_ledger.snapshot())

    async def slo(self, request: web.Request) -> web.Response:
        """Fleet SLO/goodput rollup (router/slo.py): per-endpoint and
        per-band attainment, predictor signed error + MAE, goodput vs raw
        token counts, bounded miss-reason tallies."""
        return web.json_response(self.slo_ledger.snapshot())

    async def tails_view(self, request: web.Request) -> web.Response:
        """Tail-latency attribution observatory (router/tails.py): per-
        (model, band, shape) body-vs-tail cohort split with per-stage
        p50/p95/p99, dominant-stage attribution of the tail cohort's
        excess time with culprit drill-down (endpoint / transfer pair /
        shed rung), and bounded exemplar request-ids linking into
        /debug/decisions/<id>."""
        return web.json_response(self.tails_obs.snapshot())

    async def transfers(self, request: web.Request) -> web.Response:
        """Per-(prefill, decode)-pair KV-transfer EWMA table
        (datalayer/transfers.py): pull duration, bytes, derived wire speed,
        and prefill-leg duration per pair."""
        return web.json_response(self.datastore.transfers.snapshot())

    async def decision_detail(self, request: web.Request) -> web.Response:
        """Full schema-versioned DecisionRecord for one request id:
        admission → flow control → per-profile filter drops + scorer tables +
        picker pick → retry/failover attempt trail."""
        rid = request.match_info["request_id"]
        rec = self.decision_recorder.get(rid)
        if rec is None:
            return web.json_response(
                {"error": f"no decision record for request id {rid!r}",
                 "enabled": self.decision_recorder.enabled}, status=404)
        return web.json_response(rec.to_dict())

    async def profile(self, request: web.Request) -> web.Response:
        """CPU profile of the router process for ?seconds=N (pprof analogue;
        reference mounts pprof handlers behind --enable-pprof, SURVEY §5).
        ``?format=json`` returns the top-N cumulative rows as structured
        data instead of the pstats text dump (machine-readable for CI and
        the verify-debug probe, which drives this route's REAL path)."""
        import cProfile
        import io
        import pstats

        import math

        try:
            seconds = min(float(request.query.get("seconds", "5")), 60.0)
        except ValueError:
            seconds = float("nan")
        if not math.isfinite(seconds) or seconds <= 0:
            return web.json_response(
                {"error": "seconds must be a positive finite number"}, status=400)
        if self._profile_lock.locked():
            return web.json_response(
                {"error": "a profile is already running"}, status=409)
        async with self._profile_lock:
            prof = cProfile.Profile()
            prof.enable()
            try:
                await asyncio.sleep(seconds)
            finally:
                # Cancellation/shutdown must not leave the C profile hook
                # installed on the event-loop thread.
                prof.disable()
        try:
            top_n = max(1, min(int(request.query.get("n", "40")), 500))
        except ValueError:
            top_n = 40
        if request.query.get("format") == "json":
            stats = pstats.Stats(prof)
            rows = []
            for (fname, line, func), (cc, nc, tt, ct, _callers) in \
                    stats.stats.items():  # type: ignore[attr-defined]
                rows.append({
                    "function": f"{fname}:{line}({func})",
                    "ncalls": nc,
                    "primitive_calls": cc,
                    "tottime_s": round(tt, 6),
                    "cumtime_s": round(ct, 6),
                })
            rows.sort(key=lambda r: r["cumtime_s"], reverse=True)
            return web.json_response({
                "seconds": seconds,
                "functions_profiled": len(rows),
                "rows": rows[:top_n],
            })
        buf = io.StringIO()
        pstats.Stats(prof, stream=buf).sort_stats("cumulative").print_stats(top_n)
        return web.Response(text=buf.getvalue(), content_type="text/plain")

    async def handle_inference(self, request: web.Request) -> web.StreamResponse:
        from .tracing import tracer

        self._inflight += 1
        try:
            # Joins the client's W3C trace context when a traceparent header
            # arrives; otherwise roots a fresh trace (sampling applies).
            with tracer.span_from_headers("gateway.request", request.headers,
                                          path=request.path) as span:
                resp = await self._handle_inference(request, span)
                span.set_attribute("status", resp.status)
                return resp
        finally:
            self._inflight -= 1

    async def _handle_inference(self, request: web.Request,
                                span=None) -> web.StreamResponse:
        t_start = time.monotonic()
        raw = await request.read()
        headers = {k.lower(): v for k, v in request.headers.items()}
        # Router-owned routing headers must never be client-controlled: only
        # scheduling plugins (e.g. DisaggProfileHandler.pre_request) may set
        # them, else a client could SSRF the sidecar into arbitrary targets.
        for h in ROUTER_OWNED_HEADERS:
            headers.pop(h, None)
        headers.setdefault(H_REQUEST_ID, f"req-{uuid.uuid4().hex[:12]}")

        # End-to-end deadline: client x-request-timeout (float seconds) or
        # the configured default; decremented across hops from here on.
        deadline = Deadline.from_headers(
            headers, default_s=self.resilience.default_timeout_s,
            max_s=self.resilience.max_timeout_s)
        if deadline is not None and deadline.expired:
            DEADLINE_EXCEEDED_TOTAL.inc()
            return web.json_response(
                {"error": "deadline exceeded"}, status=504,
                headers={X_REMOVAL_REASON: DEADLINE_EXCEEDED_REASON})

        # Large bodies parse off-loop (the parsers are stateless): a
        # multi-megabyte long-context JSON body is pure CPU that would
        # otherwise stall every live SSE relay for milliseconds.
        if (len(raw) >= LARGE_BODY_PARSE_BYTES
                and self.sched_pool.executor is not None):
            import functools

            parse = await asyncio.get_running_loop().run_in_executor(
                self.sched_pool.executor,
                functools.partial(self.parser.parse, raw, headers,
                                  path=request.path))
        else:
            parse = self.parser.parse(raw, headers, path=request.path)
        if parse.error:
            return web.json_response({"error": parse.error}, status=400)

        if parse.skip:
            ep = self.director.get_random_endpoint()
            if ep is None:
                return web.json_response({"error": "no endpoints"}, status=503)
            return await self._proxy_with_failover(
                request, None, [ep], raw, headers, t_start,
                original_model="", deadline=deadline)

        ireq = InferenceRequest(
            request_id=headers[H_REQUEST_ID],
            target_model=parse.model,
            body=parse.body,
            headers=headers,
            request_size_bytes=len(raw))
        original_model = parse.model
        # SLO ledger: opened BEFORE orchestration so the flow-control
        # admission hook can stamp queue time and the predicted-latency
        # PreRequest hook can stamp this request's prediction.
        self.slo_ledger.start(ireq, t_start)
        # Waterfall (router/tails.py): opened beside the SLO observation so
        # every layer hook past this point can stamp its stage.
        self.tails_obs.start(ireq, t_start)

        try:
            result = await self.director.handle_request(None, ireq)
        except RequestError as e:
            # Director error finalization (no endpoints, admission shed,
            # admit-plugin reject, scheduling failure): the ledger records
            # slo_met=false with the reason — an absent field would
            # overcount attainment. Overload sheds are the distinct ledger
            # verdict and carry a finite computed Retry-After header.
            shed = getattr(e, "shed", False)
            retry_after = getattr(e, "retry_after_s", None)
            self.slo_ledger.complete(ireq, status=e.code, reason=e.reason,
                                     shed=shed)
            self.tails_obs.complete(ireq, status=e.code, reason=e.reason,
                                    shed=shed)
            body: dict[str, Any] = {"error": e.reason}
            headers = {X_REMOVAL_REASON: e.reason,
                       **self._decision_headers(ireq)}
            if retry_after is not None:
                # HTTP delta-seconds is an integer; never hand out 0.
                headers["Retry-After"] = str(max(int(round(retry_after)), 1))
                body["retry_after_s"] = retry_after
            return web.json_response(body, status=e.code, headers=headers)

        # Cache ledger (router/kvobs.py): stamp the per-candidate predicted
        # hit depth the scorers just routed on; the engine-confirmed actual
        # joins it on completion.
        self.kv_ledger.record_scheduled(ireq, result)

        # Repackage through the parser (director.go:289-306) only when the
        # bytes must change: model rewrite, or a translating (non-OpenAI)
        # parser; otherwise forward the raw body untouched (hot path).
        body_out = raw
        payload = ireq.body.payload
        needs_repackage = (payload is not None
                           and (ireq.target_model != original_model
                                # Degrade ladder (router/overload.py): the
                                # controller mutated the payload (e.g.
                                # max_tokens clamp) — the raw client bytes
                                # no longer match what must be served.
                                or getattr(ireq, "degraded", False)
                                or self.parser.typed_name().type
                                not in ("openai-parser", "passthrough-parser")))
        if needs_repackage:
            if ireq.target_model != original_model:
                payload["model"] = ireq.target_model
            body_out = self.parser.serialize(ireq.body)

        # Register for mid-flight eviction: sheddable in-flight requests can be
        # cancelled to admit higher-priority work (reference eviction channel →
        # ImmediateResponse(429), handlers/server.go:266-284).
        task = asyncio.current_task()
        evict_key = self.evictor.register(ireq.request_id,
                                          ireq.objectives.priority, task.cancel)
        stream_state = {"started": False}
        try:
            return await self._proxy_with_failover(
                request, ireq, list(result.primary().target_endpoints),
                body_out, ireq.headers, t_start,
                original_model=original_model, stream_state=stream_state,
                deadline=deadline)
        except asyncio.CancelledError:
            if self.evictor.was_evicted(evict_key) and not stream_state["started"]:
                from .flowcontrol.eviction import EVICTED_REASON

                if ireq.decision is not None:
                    ireq.decision.record_event("evicted_inflight")
                    ireq.decision.finalize(429, reason=EVICTED_REASON)
                self.slo_ledger.complete(ireq, status=429,
                                         reason=EVICTED_REASON)
                self.tails_obs.complete(ireq, status=429,
                                        reason=EVICTED_REASON)
                self.shadow_eval.observe_response(ireq, transfer=None,
                                                  status=429)
                return web.json_response(
                    {"error": EVICTED_REASON}, status=429,
                    headers={X_REMOVAL_REASON: EVICTED_REASON,
                             **self._decision_headers(ireq)})
            # Mid-stream eviction (or external cancel): the 200 status line is
            # already on the wire — the only clean signal is the dropped
            # connection, so propagate (the ledger still closes: an aborted
            # stream is slo_met=false, not an absent row).
            self.slo_ledger.complete(ireq, status=499,
                                     reason="cancelled-mid-stream")
            self.tails_obs.complete(ireq, status=499,
                                    reason="cancelled-mid-stream")
            self.shadow_eval.observe_response(ireq, transfer=None,
                                              status=499)
            raise
        finally:
            self.evictor.deregister(evict_key)

    @staticmethod
    def _decision_headers(ireq: InferenceRequest | None) -> dict[str, str]:
        """The x-decision-summary echo, present only when the client opted
        in with `x-debug-decision: summary` and a record exists."""
        if (ireq is not None and ireq.decision is not None
                and ireq.headers.get(H_DEBUG_DECISION, "").lower() == "summary"):
            return {H_DECISION_SUMMARY: ireq.decision.summary_line()}
        return {}

    def _dp_override(self, ireq: InferenceRequest, target) -> str | None:
        """DP rank routing: when a profile handler picked a rank, route to
        the pod's rank-specific listener (what Envoy does with the
        reference's x-data-parallel-host-port) after validating it belongs
        to the target pod."""
        from .plugins.disagg import DataParallelProfileHandler
        from .requestcontrol.director import H_DATA_PARALLEL

        dp_target = ireq.headers.get(H_DATA_PARALLEL)
        if not dp_target:
            return None
        try:
            host, _, port = dp_target.rpartition(":")
            port = int(port)
            dp_size = int(target.metadata.labels.get(
                DataParallelProfileHandler.DP_SIZE_LABEL, "1"))
        except ValueError:
            host, port, dp_size = "", -1, 1
        if (host == target.metadata.address
                and target.metadata.port <= port < target.metadata.port + dp_size):
            # Consumed for routing; the rank listener itself encodes the
            # rank, so don't forward the header downstream.
            ireq.headers.pop(H_DATA_PARALLEL, None)
            return f"http://{host}:{port}"
        return None

    async def _proxy_with_failover(self, request: web.Request,
                                   ireq: InferenceRequest | None,
                                   candidates: list, body: bytes,
                                   headers: dict[str, str], t_start: float,
                                   *, original_model: str,
                                   stream_state: dict | None = None,
                                   deadline: Deadline | None = None
                                   ) -> web.StreamResponse:
        """Dispatch with retry + failover: walk the scheduling result's
        ranked candidates on pre-stream failures (connect errors, retryable
        502/503 such as ``x-removal-reason: sidecar-draining``), then
        re-schedule ONCE with the failed endpoints excluded. Bounded by the
        per-request attempt cap and the token-bucket retry budget so retries
        cannot amplify an outage; a response whose stream has started is
        never retried (the status line is on the wire). Endpoint outcomes
        feed the passive circuit breakers."""
        res = self.resilience
        breakers = self.datastore.breakers
        self.retry_budget.deposit()
        rec = ireq.decision if ireq is not None else None
        # Waterfall attempts stage (router/tails.py): time burned in FAILED
        # dispatch attempts — the serving attempt's own time lands in the
        # downstream stages, so only the walk's dead ends are charged here.
        wf = getattr(ireq, "waterfall", None) if ireq is not None else None
        attempted: set[str] = set()
        rescheduled = ireq is None  # only scheduled requests can re-schedule
        failure: UpstreamFailure | None = None
        budget_exhausted = False
        blocked: set[str] = set()  # breaker-denied this request
        last_target = None
        attempt = 0
        while attempt < res.max_attempts:
            if deadline is not None and deadline.expired:
                failure = UpstreamFailure(
                    "deadline", 504, DEADLINE_EXCEEDED_REASON)
                if rec is not None:
                    rec.record_event("deadline_exceeded")
                break
            target = None
            for ep in candidates:
                k = ep.metadata.address_port
                if k in attempted or k in blocked:
                    continue
                if not breakers.allow(k):
                    blocked.add(k)
                    if rec is not None:
                        rec.record_event("breaker_denied", endpoint=k)
                    continue
                target = ep
                break
            if target is None and not rescheduled:
                rescheduled = True
                # Breaker-denied endpoints join the exclusion set: without
                # them the scheduler can re-pick the same open endpoint
                # (it looks idle) and the request dies with healthy pods
                # available.
                result = self.director.reschedule(None, ireq,
                                                  exclude=attempted | blocked)
                if result is not None:
                    # Fresh candidates merge into the cache block: the
                    # actual may be confirmed by a pod the first scheduling
                    # pass never ranked.
                    self.kv_ledger.record_scheduled(ireq, result)
                    candidates = list(result.primary().target_endpoints)
                    continue
            if target is None:
                break
            key = target.metadata.address_port
            if attempt > 0:
                if not self.retry_budget.try_spend():
                    RETRY_BUDGET_EXHAUSTED_TOTAL.inc()
                    budget_exhausted = True
                    # allow() above may have claimed the half-open probe
                    # slot; this attempt never dispatches, so free it.
                    breakers.release_probe(key)
                    if rec is not None:
                        rec.record_event("retry_budget_exhausted",
                                         endpoint=key)
                    break
                RETRIES_TOTAL.labels(failure.kind if failure
                                     else "other").inc()
            attempt += 1
            last_target = target
            override = (self._dp_override(ireq, target)
                        if ireq is not None else None)
            attempt_t0 = time.monotonic() if wf is not None else 0.0
            try:
                resp = await self._proxy(
                    request, ireq, target, body, headers, t_start,
                    original_model=original_model,
                    stream_state=stream_state, url_override=override,
                    deadline=deadline)
            except UpstreamFailure as f:
                if wf is not None:
                    wf.attempts_ms += (time.monotonic() - attempt_t0) * 1e3
                failure = f
                attempted.add(key)
                breakers.record_failure(key)
                if rec is not None:
                    rec.record_attempt(key, f.kind,
                                       status=f.status or None,
                                       reason=f.reason)
                log.warning("upstream %s failed pre-stream (%s: %s); %s",
                            key, f.kind, f.detail or f.reason,
                            "retrying" if attempt < res.max_attempts
                            else "attempt cap reached")
                continue
            except asyncio.CancelledError:
                # Eviction / client cancel mid-attempt: no outcome to
                # record, but the probe slot must not leak.
                breakers.release_probe(key)
                raise
            # Relayed responses feed the breaker: sub-500 is endpoint
            # health; a relayed 500 is endpoint brokenness. Other relayed
            # 5xx (an engine-side deadline 504, a 501 unimplemented
            # surface) reflect the REQUEST, not the pod — recording them as
            # failures would let short-deadline traffic eject healthy
            # endpoints fleet-wide, so they only release the probe slot.
            if resp.status < 500:
                breakers.record_success(key)
            elif resp.status == 500:
                breakers.record_failure(key)
            else:
                breakers.release_probe(key)
            return resp
        # Out of options: close the request-control bracket exactly once
        # (handle_request incremented the running counter) and surface the
        # last failure with the canonical x-removal-reason contract.
        if ireq is not None:
            self.director.handle_response_complete(None, ireq, last_target, {})
            # Shadow judge on the FAILED terminal too: a sampled
            # divergence on a request that then timed out must not stay
            # unjudged forever — that would bias the regret curve toward
            # successful requests. No transfer row; the judge's EWMA
            # fallback exists for exactly this.
            self.shadow_eval.observe_response(
                ireq, transfer=None,
                status=failure.status if failure is not None else 503)
        dec_headers = self._decision_headers(ireq)
        if failure is not None and failure.kind == "deadline":
            DEADLINE_EXCEEDED_TOTAL.inc()
            if rec is not None:
                rec.finalize(504, reason=DEADLINE_EXCEEDED_REASON)
            if ireq is not None:
                self.slo_ledger.complete(ireq, status=504,
                                         reason=DEADLINE_EXCEEDED_REASON)
                self.tails_obs.complete(ireq, status=504,
                                        reason=DEADLINE_EXCEEDED_REASON)
            return web.json_response(
                {"error": "deadline exceeded"}, status=504,
                headers={X_REMOVAL_REASON: DEADLINE_EXCEEDED_REASON,
                         **dec_headers})
        # Budget-suppressed fast-fails are marked in the body so operators
        # (and tests) can tell them from ordinary upstream errors; the
        # x-removal-reason header keeps the upstream's own cause.
        extra = {"retry": RETRY_BUDGET_REASON} if budget_exhausted else {}
        if failure is not None and failure.kind in ("connect", "read"):
            if rec is not None:
                rec.finalize(502, reason=failure.reason)
            if ireq is not None:  # retry-exhausted terminal
                self.slo_ledger.complete(ireq, status=502,
                                         reason=failure.reason)
                self.tails_obs.complete(ireq, status=502,
                                        reason=failure.reason)
            return web.json_response(
                {"error": f"upstream {failure.kind} failed: {failure.detail}",
                 **extra},
                status=502, headers={X_REMOVAL_REASON: failure.reason,
                                     **dec_headers})
        if failure is not None:  # retryable status, relayed as-is
            if rec is not None:
                rec.finalize(failure.status, reason=failure.reason)
            if ireq is not None:
                self.slo_ledger.complete(ireq, status=failure.status,
                                         reason=failure.reason)
                self.tails_obs.complete(ireq, status=failure.status,
                                        reason=failure.reason)
            return web.json_response(
                {"error": failure.reason, **extra}, status=failure.status,
                headers={X_REMOVAL_REASON: failure.reason, **dec_headers})
        if rec is not None:
            rec.finalize(503, reason="no-upstream-available")
        if ireq is not None:
            self.slo_ledger.complete(ireq, status=503,
                                     reason="no-upstream-available")
            self.tails_obs.complete(ireq, status=503,
                                    reason="no-upstream-available")
        return web.json_response(
            {"error": "no upstream endpoint available"}, status=503,
            headers={X_REMOVAL_REASON: "no-upstream-available", **dec_headers})

    async def _proxy(self, request: web.Request, ireq: InferenceRequest | None,
                     endpoint, body: bytes, headers: dict[str, str],
                     t_start: float, original_model: str,
                     stream_state: dict | None = None,
                     url_override: str | None = None,
                     deadline: Deadline | None = None) -> web.StreamResponse:
        url = (url_override or endpoint.metadata.url) + request.path
        fwd = {k: v for k, v in headers.items() if k in FORWARD_HEADERS}
        fwd["content-type"] = "application/json"
        # Propagate the trace context downstream (sidecar/engine join it):
        # the gateway.request span is current here, so it becomes the parent
        # of the next hop's server span.
        from .tracing import tracer

        tracer.inject_headers(fwd)
        model_label = (ireq.target_model if ireq else "") or "unknown"

        kwargs = {}
        if deadline is not None:
            # The downstream leg inherits the REMAINING budget: stamped on
            # the wire for the next hop, and enforced locally as the
            # attempt's total timeout (covers connect + full body relay).
            remaining = max(deadline.remaining_s, 0.001)
            fwd[H_REQUEST_TIMEOUT] = deadline.header_value()
            kwargs["timeout"] = aiohttp.ClientTimeout(
                total=remaining, sock_connect=min(5.0, remaining))
        try:
            # TLS legs follow the tlsClient verification policy (default: a
            # skip-verify context for pod-local certs — engines started with
            # --secure-serving; a configured CA bundle verifies for real).
            resp = await self._upstream.post(
                url, data=body, headers=fwd,
                ssl=self._upstream_ssl if url.startswith("https") else None,
                **kwargs)
        except Exception as e:
            raise UpstreamFailure("connect", 0, "upstream-connect-error",
                                  str(e)) from e

        # Pre-stream retryable failures: nothing has been relayed to the
        # client yet, so a 502/503 (e.g. x-removal-reason: sidecar-draining
        # from PR 1's drain path) walks to the next candidate instead of
        # becoming client-visible.
        if resp.status in (502, 503):
            reason = (resp.headers.get(X_REMOVAL_REASON)
                      or f"upstream-{resp.status}")
            resp.release()
            raise UpstreamFailure("status", resp.status, reason)

        streaming_body = "text/event-stream" in resp.headers.get("content-type", "")
        data = None
        if not streaming_body:
            # The full body read is still pre-stream from the client's view
            # (headers go out only with the assembled web.Response below), so
            # an upstream dying mid-body stays retryable too.
            try:
                data = await resp.read()
            except Exception as e:
                resp.release()
                raise UpstreamFailure("read", 0, "upstream-read-error",
                                      str(e)) from e

        # Non-streaming responses hold their full body (and so the usage
        # record) before any header goes out: parse it once here — the
        # cache-ledger join below and the token metrics in `finally` both
        # reuse it.
        usage: dict[str, int] = {}
        if not streaming_body and data is not None:
            usage = _usage_from_json(data) or {}
        if ireq is not None:
            self.director.handle_response_received(None, ireq, endpoint, resp.status)
            if not streaming_body:
                # Join the engine-confirmed hit NOW, with the exact
                # prompt_tokens from the parsed usage, so the actual ratio
                # is token-exact and the x-decision-summary echo built
                # below shows predicted vs actual in one line. Streamed
                # responses join once in the terminal accounting instead
                # (their usage arrives with the final SSE event, and the
                # relayed hit headers are still in hand there).
                self.kv_ledger.observe_response(ireq, endpoint, resp.headers,
                                                usage)
            if ireq.decision is not None:
                # The relayed attempt is recorded BEFORE the response headers
                # are built so the x-decision-summary echo below agrees with
                # the /debug/decisions record (same attempt count/outcome).
                ireq.decision.record_attempt(
                    endpoint.metadata.address_port, "ok", status=resp.status)
                ireq.decision.finalize(
                    resp.status, destination=endpoint.metadata.address_port)

        out_headers = {
            H_DESTINATION_SERVED: endpoint.metadata.address_port,
            "content-type": resp.headers.get("content-type", "application/json"),
        }
        # Relay the engine-confirmed prefix-hit depth to the client beside
        # the served-endpoint echo (curl-level cache debugging; the full
        # predicted-vs-actual join is on /debug/decisions/<id>).
        for h in (H_KV_HIT_BLOCKS, H_KV_HIT_TOKENS):
            v = resp.headers.get(h)
            if v is not None:
                out_headers[h] = v
        if self.fleet is not None:
            out_headers[H_ROUTER_SHARD] = str(self.fleet.index)
        out_headers.update(self._decision_headers(ireq))  # x-debug-decision echo
        if ireq is not None and "x-session-token" in ireq.headers:
            # Session stickiness: return the (scheduling-stamped) encoded
            # token to the client (reference session_affinity.go ResponseBody).
            out_headers["x-session-token"] = ireq.headers["x-session-token"]
        first_byte_at: float | None = None
        # SLO-ledger observation: None when the kill-switch is off, so the
        # per-chunk hook below costs exactly one `is None` check.
        obs = ireq.outcome if ireq is not None else None

        # Per-pair KV-transfer landing at HEADER time — for streams too:
        # the pair row's headers travel with the status line, so waiting
        # for the terminal usage chunk (the pre-PR-18 behavior) left a
        # mid-incident stream's transfer invisible in /debug/transfers
        # until it finished — the gap PR 10's header-time-join hardening
        # noted. The `finally` below reuses this row; calling
        # _record_transfer there again would double-count the EWMA table.
        transfer: dict[str, Any] | None = None
        wf = getattr(ireq, "waterfall", None) if ireq is not None else None
        if ireq is not None:
            transfer = self._record_transfer(ireq, endpoint, resp.headers)
            if wf is not None:
                # Waterfall stage stamps (router/tails.py): every stage the
                # engine/sidecar measured rides the response headers, in
                # hand before any byte is relayed.
                v = finite_float_or_none(resp.headers.get(H_ENGINE_QUEUE))
                if v is not None and v > 0:
                    wf.engine_queue_ms = v
                v = finite_float_or_none(
                    resp.headers.get("x-prefill-duration-ms"))
                if v is not None and v > 0:
                    wf.prefill_ms = v
                v = finite_float_or_none(
                    resp.headers.get("x-kv-transfer-ms"))
                if v is not None and v > 0:
                    wf.kv_transfer_ms = v
                    # Pipelined P/D pulls stamp exposed (non-overlapped)
                    # time separately: the waterfall's kv_transfer stage
                    # holds ONLY the exposed cost so stage sums reconcile
                    # against TTFT, with the hidden remainder in
                    # overlap_ms (excluded from accounted_ms()).
                    ve = finite_float_or_none(
                        resp.headers.get("x-kv-transfer-exposed-ms"))
                    if ve is not None and 0 <= ve <= v:
                        wf.kv_transfer_ms = ve
                        wf.overlap_ms = v - ve
                v = finite_float_or_none(
                    resp.headers.get("x-kv-transfer-bytes"))
                if v is not None:
                    wf.kv_bytes = int(v)
                if transfer is not None:
                    wf.pair = f"{transfer['prefill']}→{transfer['decode']}"

        try:
            if streaming_body:
                ws = web.StreamResponse(status=resp.status, headers=out_headers)
                if stream_state is not None:
                    stream_state["started"] = True
                await ws.prepare(request)
                sse_carry = b""
                sse_tail = b""
                stream_hook = (self.director.handle_response_streaming
                               if ireq is not None
                               and self.cfg.response_streaming else None)
                # Upstream reads and client writes fail differently: an
                # upstream disconnect mid-stream is counted (and closed
                # cleanly — the 200 status line is already on the wire, so
                # no retry is possible and a traceback'd 500 would corrupt
                # the stream), while a client hanging up is routine and
                # must not pollute the upstream-abort metric or blame the
                # (healthy) endpoint in logs.
                upstream_iter = resp.content.iter_any()
                while True:
                    try:
                        chunk = await upstream_iter.__anext__()
                    except StopAsyncIteration:
                        break
                    except (aiohttp.ClientError, ConnectionResetError,
                            asyncio.TimeoutError) as e:
                        UPSTREAM_STREAM_ABORTED_TOTAL.inc()
                        if obs is not None:
                            obs.abort_reason = "upstream-stream-aborted"
                        log.warning("upstream stream aborted mid-relay from "
                                    "%s: %s",
                                    endpoint.metadata.address_port, e)
                        break
                    # TTFT counts the first *token-bearing* event: a
                    # role-only chat delta (no content) would otherwise
                    # flatter the metric. Events split across transport
                    # chunks are reassembled via the carry; unparseable
                    # events count (fail-open).
                    if first_byte_at is None:
                        found, sse_carry = _sse_scan_for_token(sse_carry, chunk)
                        if found:
                            first_byte_at = time.monotonic()
                            TTFT_SECONDS.labels(model_label).observe(first_byte_at - t_start)
                            if obs is not None:
                                # Reuses the monotonic read TTFT just paid.
                                obs.first_token(first_byte_at)
                    elif obs is not None and _token_bearing(chunk):
                        # Per-token inter-arrival capture: one clock read +
                        # a few adds per transport chunk (<1% of the 5ms
                        # token cadence; benchmarks/SLO_OBS.json). Framing
                        # chunks are not token arrivals — counting them
                        # would stretch last_token_at past the real last
                        # token and inflate actual TPOT into a false SLO
                        # miss.
                        obs.on_chunk()
                    if stream_hook is not None:
                        stream_hook(None, ireq, endpoint, chunk)
                    # Usage rides the FINAL SSE event: keep a bounded tail
                    # of COMPLETE events and scan once at stream end.
                    # Trimming on event boundaries (not a fixed byte
                    # window) means a large terminal usage-bearing event
                    # survives intact instead of being silently truncated
                    # to {}.
                    sse_tail = _sse_tail_append(sse_tail, chunk)
                    try:
                        await ws.write(chunk)
                    except (ConnectionResetError, ConnectionError) as e:
                        if obs is not None:
                            obs.abort_reason = "client-disconnect"
                        log.debug("client closed stream mid-relay: %s", e)
                        break
                usage = _usage_from_sse(sse_tail) or {}
                try:
                    await ws.write_eof()
                except (ConnectionResetError, ConnectionError):
                    pass  # client already gone
                return ws
            else:
                first_byte_at = time.monotonic()
                TTFT_SECONDS.labels(model_label).observe(first_byte_at - t_start)
                data = _rewrite_model_name(data, ireq, original_model)
                return web.Response(body=data, status=resp.status,
                                    headers=out_headers)
        finally:
            # Fully-consumed bodies return the connection to the keep-alive
            # pool; an abandoned stream closes it.
            resp.release()
            if ireq is not None:
                self.director.handle_response_complete(None, ireq, endpoint, usage)
                if self.flow_controller is not None:
                    # Backend capacity freed: wake saturated dispatch shards
                    # immediately instead of waiting out their backoff poll.
                    self.flow_controller.notify_capacity()
                # An exception unwinding through this finally (eviction /
                # client-disconnect CancelledError from the relay loop —
                # not in any caught tuple above) is an aborted stream: the
                # ledger must not stamp it as a met 200. The outer 499
                # complete() can't fix it later — complete is first-wins.
                if (obs is not None and obs.abort_reason is None
                        and sys.exc_info()[0] is not None):
                    obs.abort_reason = "cancelled-mid-stream"
                # Terminal ledger accounting: the per-pair KV-transfer row
                # landed at header time above (streams included), then the
                # SLO verdict (met/missed, or error for relayed 4xx/5xx
                # and aborts) and the waterfall close ride the same spot.
                # Streamed responses confirm the hit via the terminal usage
                # record (prompt_tokens_details.cached_tokens); the early
                # header-time join above already marked non-streamed ones
                # done, so this is one attribute check for them.
                self.kv_ledger.observe_response(ireq, endpoint, resp.headers,
                                                usage)
                self.slo_ledger.complete(ireq, status=resp.status,
                                         endpoint=endpoint, usage=usage,
                                         transfer=transfer)
                self.tails_obs.complete(ireq, status=resp.status,
                                        endpoint=endpoint, usage=usage)
                # Shadow judge (router/shadow.py): hand the measured
                # outcome to the counterfactual ledger — one attribute
                # check for unsampled requests, an enqueue otherwise.
                self.shadow_eval.observe_response(ireq, transfer=transfer,
                                                  status=resp.status)
                if (self.overload.enabled and resp.status < 400
                        and (obs is None or obs.abort_reason is None)):
                    # Served-outcome feedback for the overload controller:
                    # the healthy-e2e Little's-law anchor plus the
                    # observed-vs-predicted TTFT bias corrector. Aborted /
                    # evicted streams are excluded — their truncated e2e
                    # would drag the healthy anchor down and make the
                    # controller shed MORE exactly when eviction pressure
                    # is highest (a self-reinforcing loop).
                    self.overload.note_served(
                        ireq, (time.monotonic() - t_start) * 1e3)
                REQUEST_DURATION.labels(model_label).observe(time.monotonic() - t_start)
                if usage.get("prompt_tokens"):
                    INPUT_TOKENS.labels(model_label).observe(usage["prompt_tokens"])
                if usage.get("completion_tokens"):
                    OUTPUT_TOKENS.labels(model_label).observe(usage["completion_tokens"])

    def _record_transfer(self, ireq: InferenceRequest, endpoint,
                         resp_headers) -> dict[str, Any] | None:
        """Land the sidecar-relayed per-pair KV-transfer stats
        (``x-kv-transfer-ms``/``-bytes`` from the decode engine's measured
        pull, ``x-kv-prefiller`` for the pair identity, and the existing
        ``x-prefill-duration-ms``) into the datastore's EWMA table. Returns
        the row for the DecisionRecord outcome block, or None when the
        response carries no disagg telemetry."""
        pull = resp_headers.get("x-kv-transfer-ms")
        prefill = resp_headers.get("x-prefill-duration-ms")
        if not pull and not prefill:
            return None
        # Pair identity comes ONLY from the sidecar's served-prefiller stamp:
        # on fallback-to-decode the sidecar sends x-prefill-duration-ms (the
        # wasted walk time) with no x-kv-prefiller, and attributing that to
        # a routing-header candidate that never served would poison the
        # per-pair EWMAs the transfer-cost scorer will read.
        prefiller = resp_headers.get("x-kv-prefiller")
        if not prefiller:
            return None
        pull_ms = finite_float_or_none(pull)
        prefill_ms = finite_float_or_none(prefill)
        # Exposed (non-overlapped) pull cost from pipelined P/D pulls.
        # Clamped into [0, pull_ms] — both stamps ride the same engine
        # clock, so anything outside that range is a malformed relay, and
        # landing it would poison the exposed EWMA pair scorers read.
        exposed_ms = finite_float_or_none(
            resp_headers.get("x-kv-transfer-exposed-ms"))
        if exposed_ms is not None and (
                pull_ms is None or not 0 <= exposed_ms <= pull_ms):
            exposed_ms = None
        nbytes = finite_float_or_none(resp_headers.get("x-kv-transfer-bytes"))
        nbytes = int(nbytes) if nbytes is not None else None
        decode = endpoint.metadata.address_port
        self.datastore.transfers.record(prefiller, decode, pull_ms=pull_ms,
                                        nbytes=nbytes, prefill_ms=prefill_ms,
                                        exposed_ms=exposed_ms)
        if pull_ms is not None:
            KV_TRANSFER_MS.observe(pull_ms)
        if exposed_ms is not None:
            KV_TRANSFER_EXPOSED_MS.observe(exposed_ms)
        row: dict[str, Any] = {"prefill": prefiller, "decode": decode}
        if pull_ms is not None:
            row["pull_ms"] = pull_ms
        if exposed_ms is not None:
            row["exposed_ms"] = exposed_ms
        if nbytes is not None:
            row["bytes"] = nbytes
        if prefill_ms is not None:
            row["prefill_ms"] = prefill_ms
        return row

    async def metrics(self, request: web.Request) -> web.Response:
        return web.Response(body=generate_latest(REGISTRY),
                            content_type="text/plain", charset="utf-8")

    def _ready(self) -> bool:
        """Readiness couples to leadership (reference health.go:52-104): a
        follower replica reports not-ready so the LB routes to the leader;
        a draining replica reports not-ready so traffic moves off before
        SIGTERM teardown."""
        if self.draining:
            return False
        if self.elector is not None and not self.elector.is_leader:
            return False
        return self.datastore.pool_ready and bool(self.datastore.endpoint_list())

    async def health(self, request: web.Request) -> web.Response:
        ready = self._ready()
        follower = self.elector is not None and not self.elector.is_leader
        return web.json_response(
            {"status": "ok" if ready else ("follower" if follower else "not-ready"),
             "endpoints": len(self.datastore.endpoint_list())},
            status=200 if ready else 503)

    async def models(self, request: web.Request) -> web.Response:
        """Union of served models across the pool. Prefer the datastore's
        models-data-source attribute (heterogeneous pools serve different
        models — reading one endpoint under-reports); fall back to live
        fetches from every endpoint when the source isn't configured."""
        from .datalayer.models_source import endpoint_models

        eps = self.datastore.endpoint_list()
        merged: dict[str, dict] = {}
        unpolled = []
        for ep in eps:
            models = endpoint_models(ep)
            if models is None:
                unpolled.append(ep)
                continue
            for m in models:
                merged.setdefault(m["id"], {"id": m["id"], "object": "model",
                                            **({"parent": m["parent"]}
                                               if m.get("parent") else {})})
        if unpolled:
            # Live-fetch fallback (models-data-source not configured). The
            # fan-out is pool-wide, so cache it briefly: a client polling
            # /v1/models must not multiply into N upstream requests/s.
            now = time.monotonic()
            expiry, cached = self._models_fallback_cache
            if now >= expiry:
                import asyncio as _aio

                async def fetch(ep):
                    try:
                        r = await self._client.get(ep.metadata.url + "/v1/models")
                        return (r.json().get("data") or []) if r.status_code == 200 else []
                    except Exception:
                        return []

                cached = [m for data in
                          await _aio.gather(*[fetch(ep) for ep in unpolled])
                          for m in data if isinstance(m, dict) and m.get("id")]
                self._models_fallback_cache = (now + 5.0, cached)
            for m in cached:
                merged.setdefault(str(m["id"]), m)
        return web.json_response({"object": "list",
                                  "data": sorted(merged.values(),
                                                 key=lambda m: m["id"])})


def _rewrite_model_name(data: bytes, ireq: InferenceRequest | None,
                        original_model: str) -> bytes:
    """Rewrite "model" in responses back to the client-facing name
    (reference server.go:471-485)."""
    if ireq is None or not original_model or ireq.target_model == original_model:
        return data
    try:
        doc = json.loads(data)
        if isinstance(doc, dict) and "model" in doc:
            doc["model"] = original_model
            return json.dumps(doc).encode()
    except Exception:
        pass
    return data


def _token_bearing(chunk: bytes) -> bool:
    """Cheap streaming-relay classification: count the transport chunk as a
    token arrival unless it is pure framing — keep-alive comment, blank
    heartbeat, or the [DONE] sentinel. iter_any() chunks can split an SSE
    event mid-separator, so leading CR/LF is stripped before classifying:
    a token event arriving as '\\ndata: …' must still advance the TPOT
    clock. (A usage-only terminal event still counts: telling it apart
    needs a JSON parse the per-chunk budget can't afford, and engines emit
    it back-to-back with the final token.)"""
    if chunk[:1] in (b"\n", b"\r"):
        chunk = chunk.lstrip(b"\r\n")
    b0 = chunk[:1]
    return bool(b0) and b0 != b":" and not chunk.startswith(b"data: [DONE]")


def _usage_from_json(data: bytes) -> dict[str, int] | None:
    try:
        doc = json.loads(data)
        u = doc.get("usage")
        return u if isinstance(u, dict) else None
    except Exception:
        return None


def _sse_scan_for_token(carry: bytes, chunk: bytes) -> tuple[bool, bytes]:
    """Scan complete SSE lines in ``carry + chunk`` for generated output
    (completion text or a chat delta with content) — role-only/handshake
    deltas don't count toward TTFT. Returns (saw_token, new_carry) where
    new_carry is the trailing partial line, so events split across transport
    chunks are reassembled instead of misclassified. Complete-but-unparseable
    data lines count, so unknown engines keep the old first-byte semantics."""
    data = carry + chunk
    lines = data.split(b"\n")
    carry = lines.pop()  # trailing partial line ('' when chunk ends on \n)
    if len(carry) > 1 << 20:
        # A megabyte with no newline is not an SSE event stream; fail open
        # rather than buffer unboundedly.
        return True, b""
    for line in lines:
        line = line.rstrip(b"\r")
        if not line.startswith(b"data: ") or line == b"data: [DONE]":
            continue
        try:
            doc = json.loads(line[6:])
        except Exception:
            return True, carry
        for choice in doc.get("choices") or []:
            if choice.get("text"):
                return True, carry
            delta = choice.get("delta") or {}
            if delta.get("content") or delta.get("tool_calls"):
                return True, carry
        if "choices" not in doc:
            return True, carry  # not an OpenAI chunk shape — fail open
    return False, carry


# Rolling-tail target for end-of-stream usage extraction: the terminal usage
# event plus the [DONE] line are a few hundred bytes; 4 KiB leaves wide
# margin without per-chunk memory growth. Trimming respects event boundaries,
# so one oversized trailing event may exceed the target (bounded by the hard
# cap — a tail that big with no event boundary is not a sane SSE stream).
_USAGE_TAIL = 4096
_USAGE_TAIL_HARD = 1 << 20


def _sse_tail_append(tail: bytes, chunk: bytes) -> bytes:
    """Append a transport chunk to the rolling SSE tail, trimming whole
    events from the front. The tail always starts at an event boundary (or
    the stream start), so the final usage-bearing event is never cut mid-
    event no matter how large it is, up to the 1 MiB fail-safe."""
    tail += chunk
    if len(tail) <= _USAGE_TAIL:
        return tail
    # Resume at the start of the event CONTAINING the window edge: whole
    # events ahead of it drop, but an event straddling (or overflowing) the
    # window is kept from its own start — never cut mid-event. SSE permits
    # LF or CRLF event terminators; honor both.
    edge = len(tail) - _USAGE_TAIL
    lf = tail.rfind(b"\n\n", 0, edge)
    crlf = tail.rfind(b"\r\n\r\n", 0, edge)
    start = max(lf + 2 if lf != -1 else 0,
                crlf + 4 if crlf != -1 else 0)
    if start:
        tail = tail[start:]
    if len(tail) > _USAGE_TAIL_HARD:
        tail = tail[-_USAGE_TAIL_HARD:]
    return tail


def _usage_from_sse(tail: bytes) -> dict[str, int] | None:
    """Extract the usage record from the final bytes of an SSE stream. The
    caller hands the end-of-stream tail, so events split across transport
    chunks arrive reassembled here (a truncated leading line simply fails
    the JSON parse and is skipped)."""
    if b'"usage"' not in tail:
        return None
    usage = None
    for line in tail.split(b"\n"):
        line = line.rstrip(b"\r")
        if line.startswith(b"data: ") and line != b"data: [DONE]":
            try:
                doc = json.loads(line[6:])
                u = doc.get("usage")
                if isinstance(u, dict):
                    usage = u  # last one wins (the terminal event's record)
            except Exception:
                continue
    return usage


def build_gateway(config_text: str | None, *, host: str = "127.0.0.1",
                  port: int = 8081, poll_interval: float = 0.05,
                  grpc_health_port: int | None = None,
                  grpc_ext_proc_port: int | None = None,
                  lease_path: str | None = None,
                  config_watch_path: str | None = None,
                  kube: dict | None = None,
                  secure_serving: bool = False,
                  cert_path: str | None = None,
                  enable_cert_reload: bool = False,
                  fleet=None) -> Gateway:
    datastore = Datastore()
    dl_runtime = DataLayerRuntime(datastore, poll_interval=poll_interval)
    handle = Handle(datastore=datastore, dl_runtime=dl_runtime)
    import llm_d_inference_scheduler_tpu.router.plugins  # noqa: F401 (register)
    import llm_d_inference_scheduler_tpu.router.plugins.saturation  # noqa: F401
    import llm_d_inference_scheduler_tpu.router.requestcontrol.producers  # noqa: F401
    cfg = load_config(config_text, handle)
    # Endpoint lifecycle plugins (per-pod subscribers, LRU teardown — the
    # reference's EndpointExtractors, runtime.go:361) ride datastore events.
    # Fleet followers skip them: a per-pod SSE subscription in every worker
    # would put the N x engine load back that the snapshot IPC removes.
    # Engine-CONFIRMED kv-event state (the precise scorer's KvBlockIndex)
    # reaches followers anyway: with `fleet.replication` (default on) the
    # leader appends confirmed-index deltas + periodic checkpoints to the
    # snapshot stream and the follower's SnapshotSubscriber applies them
    # into its own index (docs/performance.md §Scale-out). A promoted
    # follower registers these plugins at /fleet/promote time instead
    # (leader re-election, docs/resilience.md §Fleet failover).
    if fleet is None or fleet.runs_datalayer:
        for plugin in cfg.plugins_by_name.values():
            if hasattr(plugin, "endpoint_added") or hasattr(plugin, "endpoint_removed"):
                dl_runtime.register_lifecycle(plugin)
    kube_binding = None
    # Endpoint discovery needs a pool to scope the pod selector; a kube dict
    # without one is lease-only (HA election against the API server while
    # endpoints still come from the config file). The CLI rejects an
    # api-url with neither pool nor lease, so nothing silently no-ops.
    if kube and kube.get("pool_name"):
        from .kube import KubeApiClient, KubeBinding

        if config_watch_path is not None:
            # Two writers calling datastore.resync() would flap the endpoint
            # set between the file pool and the k8s pool on every event.
            log.warning("--watch-config ignored: the k8s binding owns the "
                        "endpoint set when --kube-pool-name is given")
            config_watch_path = None
        client = KubeApiClient(kube["api_url"],
                               token_path=kube.get("token_path"))
        kube_binding = KubeBinding(datastore, client,
                                   kube.get("namespace", "default"),
                                   pool_name=kube.get("pool_name"))
    kube_elector = None
    if kube and kube.get("lease_name"):
        from .kube import KubeApiClient, KubeLeaseElector

        if lease_path is not None:
            log.warning("--ha-lease-path ignored: Lease-object election "
                        "active (--kube-lease-name)")
            lease_path = None
        # Separate client: the elector must keep renewing even when the
        # informers' connection pool is saturated mid-relist.
        kube_elector = KubeLeaseElector(
            KubeApiClient(kube["api_url"], token_path=kube.get("token_path")),
            kube.get("namespace", "default"), kube["lease_name"])
    return Gateway(cfg, datastore, dl_runtime, host=host, port=port,
                   grpc_health_port=grpc_health_port,
                   grpc_ext_proc_port=grpc_ext_proc_port,
                   kube_binding=kube_binding,
                   lease_path=lease_path,
                   kube_elector=kube_elector,
                   config_watch_path=config_watch_path,
                   secure_serving=secure_serving,
                   cert_path=cert_path,
                   enable_cert_reload=enable_cert_reload,
                   fleet=fleet)


async def run_gateway(gw: Gateway, drain_timeout_s: float = 30.0):
    """Serve until SIGTERM/SIGINT, then drain: readiness flips not-ready
    (LB + ext-proc health pull this replica; stopping the elector releases
    leadership so a standby takes over fast), in-flight proxied requests
    finish bounded by ``drain_timeout_s``, then the gateway stops."""
    import signal

    await gw.start()
    stop_ev = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, stop_ev.set)
        except (NotImplementedError, RuntimeError):
            pass
    try:
        await stop_ev.wait()
        gw.draining = True
        if gw.elector is not None:
            await gw.elector.stop()
            gw.elector = None
        log.info("SIGTERM: draining %d in-flight requests", gw._inflight)
        deadline = loop.time() + drain_timeout_s
        while loop.time() < deadline and gw._inflight > 0:
            await asyncio.sleep(0.25)
        if gw._inflight:
            log.warning("drain timeout with %d requests still in flight; "
                        "closing", gw._inflight)
    except asyncio.CancelledError:
        pass
    await gw.stop()


def main(argv: list[str] | None = None):
    import argparse

    p = argparse.ArgumentParser(description="TPU inference router gateway (standalone EPP)")
    p.add_argument("--config-file", default=None)
    p.add_argument("--config-text", default=None)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8081)
    p.add_argument("--endpoints", default=None,
                   help="comma-separated host:port[:role] static pool "
                        "(overrides config pool)")
    p.add_argument("--grpc-ext-proc-port", type=int, default=None,
                   help="serve the Envoy ext-proc FULL_DUPLEX_STREAMED gRPC "
                        "service on this port (the EPP wire surface)")
    p.add_argument("--grpc-health-port", type=int, default=None,
                   help="serve grpc.health.v1.Health on this port")
    p.add_argument("--ha-lease-path", default=None,
                   help="enable leader election via this shared lease file; "
                        "followers report not-ready until they take over")
    p.add_argument("--watch-config", action="store_true",
                   help="reconcile pool/objectives/rewrites live when "
                        "--config-file changes on disk")
    p.add_argument("--kube-api-url", default=None,
                   help="k8s API server base URL; combine with "
                        "--kube-pool-name for the list+watch endpoint "
                        "binding and/or --kube-lease-name for Lease-object "
                        "HA election")
    p.add_argument("--kube-namespace", default="default")
    p.add_argument("--kube-pool-name", default=None,
                   help="InferencePool name to watch for selector/ports")
    p.add_argument("--kube-token-path", default=None,
                   help="bearer token file (defaults to the in-cluster "
                        "service-account path when unset)")
    p.add_argument("--kube-lease-name", default=None,
                   help="coordination.k8s.io/v1 Lease name for HA leader "
                        "election (reference id shape: "
                        "epp-<ns>-<pool>.llm-d.ai); requires --kube-api-url "
                        "and supersedes --ha-lease-path")
    p.add_argument("--secure-serving", action="store_true",
                   help="serve HTTP and ext-proc gRPC over TLS; without "
                        "--cert-path a self-signed certificate is minted "
                        "(runserver.go:136-171)")
    p.add_argument("--cert-path", default=None,
                   help="directory holding tls.crt + tls.key (the "
                        "kubernetes.io/tls Secret mount layout)")
    p.add_argument("--enable-cert-reload", action="store_true",
                   help="re-read --cert-path on change so cert-manager "
                        "rotations apply without a restart (certs.go)")
    p.add_argument("--drain-timeout", type=float, default=30.0,
                   help="seconds to let in-flight proxied requests finish "
                        "after SIGTERM (readiness flips not-ready and the "
                        "lease is released immediately)")
    p.add_argument("--fleet-workers", type=int, default=None,
                   help="override fleet.workers: >1 runs the multi-process "
                        "sharded fleet (router/fleet.py) instead of a "
                        "single gateway process")
    args = p.parse_args(argv)

    text = args.config_text
    if args.config_file:
        with open(args.config_file) as f:
            text = f.read()

    # Multi-process fleet delegation (router/fleet.py): `fleet.workers > 1`
    # (or --fleet-workers) spawns N full gateway workers behind one port.
    # workers: 1 — the default — continues below, bit-identical to the
    # pre-fleet router.
    from .config.loader import load_raw_config
    from .fleet import FleetConfig

    fleet_spec = dict(load_raw_config(text).fleet)
    if args.fleet_workers is not None:
        fleet_spec["workers"] = args.fleet_workers
    fleet_cfg = FleetConfig.from_spec(fleet_spec)
    if fleet_cfg.workers > 1:
        unsupported = {
            "--grpc-ext-proc-port": args.grpc_ext_proc_port,
            "--grpc-health-port": args.grpc_health_port,
            "--kube-api-url": args.kube_api_url,
            "--ha-lease-path": args.ha_lease_path,
            "--secure-serving": args.secure_serving or None,
            "--watch-config": args.watch_config or None,
            "--endpoints": args.endpoints,
        }
        bad = [flag for flag, v in unsupported.items() if v]
        if bad:
            p.error(f"fleet mode (workers={fleet_cfg.workers}) does not "
                    f"support {', '.join(bad)} yet; run workers: 1 or drop "
                    "the flag(s)")
        from .fleet import run_fleet

        logging.basicConfig(level=logging.INFO)
        run_fleet(text, host=args.host, port=args.port, fleet=fleet_cfg,
                  drain_timeout_s=args.drain_timeout)
        return

    from .kube import DEFAULT_TOKEN_PATH

    kube = None
    if args.kube_api_url:
        if not (args.kube_pool_name or args.kube_lease_name):
            p.error("--kube-api-url needs --kube-pool-name (endpoint "
                    "discovery) and/or --kube-lease-name (HA election)")
        kube = {"api_url": args.kube_api_url,
                "namespace": args.kube_namespace,
                "pool_name": args.kube_pool_name,
                "lease_name": args.kube_lease_name,
                "token_path": args.kube_token_path or DEFAULT_TOKEN_PATH}
    elif args.kube_lease_name:
        p.error("--kube-lease-name requires --kube-api-url")
    gw = build_gateway(text, host=args.host, port=args.port,
                       grpc_health_port=args.grpc_health_port,
                       grpc_ext_proc_port=args.grpc_ext_proc_port,
                       lease_path=args.ha_lease_path,
                       config_watch_path=(args.config_file
                                          if args.watch_config else None),
                       kube=kube,
                       secure_serving=args.secure_serving,
                       cert_path=args.cert_path,
                       enable_cert_reload=args.enable_cert_reload)
    if args.endpoints:
        from .framework.datalayer import EndpointMetadata
        metas = []
        for spec in args.endpoints.split(","):
            parts = spec.strip().split(":")
            labels = {"llm-d.ai/role": parts[2]} if len(parts) > 2 else {}
            metas.append(EndpointMetadata(name=spec, address=parts[0],
                                          port=int(parts[1]), labels=labels))
        gw.cfg.static_endpoints = metas

    logging.basicConfig(level=logging.INFO)

    asyncio.run(run_gateway(gw, drain_timeout_s=args.drain_timeout))


if __name__ == "__main__":
    main()
