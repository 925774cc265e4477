"""P/D disaggregation sidecar: the decode-pod data plane.

Mirrors /root/reference/pkg/sidecar/proxy (SURVEY §2.10): an HTTP reverse
proxy colocated with each decode engine that executes the multi-stage
Prefill→Decode lifecycle. It reads and strips the router's
x-prefiller-host-port header, runs the configured KV connector protocol
against the remote prefill worker, then dispatches decode locally. No sidecar
runs on prefill nodes (docs/disaggregation.md:168-177).

Connectors:
- tpu-dcn (default; the NIXL-v2 analogue, connector_nixlv2.go:35-300):
  2-phase — (1) prefill request with kv_transfer_params{do_remote_decode},
  stream=false, max_tokens=1; (2) decode request carrying the prefiller's
  returned kv_transfer_params so the decode engine pulls KV over the
  host-staged DCN path (engine /kv fetch). Falls back to plain decode when
  prefill fails.
- passthrough: ignore disagg headers, always decode locally.

SSRF protection: with an allowlist configured, only listed prefill targets
are honored (reference allowlist.go).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import logging
import time
from typing import Any

import httpx
from aiohttp import web

from ..requestcontrol.director import H_DATA_PARALLEL, H_ENCODERS, H_PREFILLER
from ..resilience import DEADLINE_EXCEEDED_REASON, Deadline, H_REQUEST_TIMEOUT
from ..slo import finite_float_or_none

log = logging.getLogger("router.sidecar")

GEN_PATHS = ("/v1/completions", "/v1/chat/completions", "/v1/responses")


@dataclasses.dataclass
class SidecarConfig:
    port: int = 8000
    host: str = "127.0.0.1"
    decoder_url: str = "http://127.0.0.1:8200"
    # "tpu-dcn" | "shared-storage" | "sglang" | "passthrough"
    connector: str = "tpu-dcn"
    cache_hit_threshold: float = 0.8   # shared-storage decode-first probe
    # sglang connector: engine-side KV bootstrap rendezvous port
    # (reference connector_sglang.go init: SGLANG_BOOTSTRAP_PORT, default 8998).
    bootstrap_port: int = 8998
    ssrf_allowlist: list[str] | None = None  # None disables SSRF protection
    prefill_timeout_s: float = 120.0
    decode_timeout_s: float = 300.0
    # Chunked decode (reference decode.go:62-444): split decode into
    # max_tokens=N slices, re-appending generated text. 0 disables.
    decode_chunk_size: int = 0
    # Data parallelism (reference data_parallel.go:19-88): one extra listener
    # per DP rank; rank i listens on port+i and dispatches to decoderPort+i.
    data_parallel_size: int = 1
    # Prefiller sampling (reference chat_completions.go:79-95): when the
    # router supplies MULTIPLE prefill candidates (repeated header values or
    # one comma-separated value), pick one uniformly at random instead of
    # always the first — spreads prefill load when the scheduler returns a
    # candidate set rather than a single pick.
    enable_prefiller_sampling: bool = False
    # Secure serving + per-leg TLS (reference proxy.go:153-170): the sidecar
    # itself can serve HTTPS (cert dir or self-signed fallback), and each
    # outbound leg independently chooses TLS + verification — in-cluster
    # engines usually present pod-local certs, so skip-verify is per-leg.
    secure_serving: bool = False
    cert_path: str | None = None
    enable_cert_reload: bool = False
    use_tls_for_prefiller: bool = False
    use_tls_for_decoder: bool = False
    use_tls_for_encoder: bool = False
    insecure_skip_verify_prefiller: bool = False
    insecure_skip_verify_decoder: bool = False
    insecure_skip_verify_encoder: bool = False
    # Pipelined P/D (the ``pipeline: {enabled: ...}`` mode): pre-assign the
    # prefill request id, fire the prefill leg concurrently, and dispatch
    # the decode leg — with a chunk-streaming KV pull — as soon as the
    # prefill engine acks first-chunk staging, so the transfer overlaps the
    # remainder of prefill (docs/disaggregation.md §Pipelined KV streaming).
    # Default OFF: the serial 2-phase path stays bit-identical (the
    # vectorized/rebalance kill-switch precedent). Any pre-dispatch failure
    # falls back to the serial candidate walk.
    pipeline_enabled: bool = False


class Sidecar:
    def __init__(self, cfg: SidecarConfig, *, dp_rank: int = 0):
        import random

        from prometheus_client import (
            CollectorRegistry,
            Counter,
            Gauge,
            Histogram,
        )

        self.cfg = cfg
        self.dp_rank = dp_rank
        # Injectable for tests (reference prefillSamplerFn).
        self._prefill_sampler = random.randrange
        self.app = web.Application()
        self.app.add_routes([web.post(p, self.handle_generate) for p in GEN_PATHS])
        self.app.add_routes([
            # Embeddings carry no KV state → no disagg protocol; straight
            # passthrough to the local engine (the reference proxies
            # non-generate OpenAI surfaces the same way).
            web.post("/v1/embeddings", self._proxy_post),
            web.get("/metrics", self._metrics),
            web.get("/health", self._health),
            web.get("/debug/traces", self._traces),
            web.get("/v1/models", self._proxy_get),
            # Streaming: the precise-prefix scorer's SSE subscriber must work
            # against sidecar-fronted decode endpoints too.
            web.get("/kv_events", self._proxy_get_stream),
        ])
        self._runner: web.AppRunner | None = None
        self._client: httpx.AsyncClient | None = None       # decode leg
        self._prefill_client: httpx.AsyncClient | None = None
        self._encode_client: httpx.AsyncClient | None = None
        self._tls = None          # TlsServing; rank 0 owns, children borrow
        self._tls_owned = False
        self._inflight = 0        # live generate requests (SIGTERM drain)
        self.draining = False     # SIGTERM: health 503s, new work refused
        self._dp_children: list["Sidecar"] = []
        self._bg_tasks: set = set()  # strong refs for fire-and-forget legs
        # Sidecar-local metric families, appended to the proxied engine
        # scrape so the drain (and relay load) is observable per pod.
        self.metrics_registry = CollectorRegistry()
        self._g_draining = Gauge(
            "sidecar_draining",
            "1 while this sidecar is draining after SIGTERM",
            registry=self.metrics_registry)
        self._g_inflight = Gauge(
            "sidecar_inflight_requests",
            "Generate requests currently relayed by this sidecar",
            registry=self.metrics_registry)
        self._c_prefill_failover = Counter(
            "sidecar_prefill_failovers_total",
            "Prefill attempts that failed over to the next header candidate",
            registry=self.metrics_registry)
        self._c_stream_aborted = Counter(
            "sidecar_upstream_stream_aborted_total",
            "Decode streams cut mid-relay by an upstream disconnect "
            "(closed cleanly toward the client)",
            registry=self.metrics_registry)
        self._c_deadline = Counter(
            "sidecar_deadline_exceeded_total",
            "Requests rejected because the end-to-end deadline was exhausted",
            registry=self.metrics_registry)
        self._h_kv_transfer = Histogram(
            "sidecar_kv_transfer_ms",
            "KV pull duration measured by the decode engine and relayed "
            "through this sidecar (x-kv-pull-ms -> x-kv-transfer-ms)",
            registry=self.metrics_registry,
            buckets=(1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 2500))
        self._h_kv_overlap = Histogram(
            "sidecar_kv_overlap_ms",
            "Per-request KV pull time hidden behind the prefill engine's "
            "remaining compute on pipelined P/D requests (pull wall-time "
            "minus exposed time; 0 on serial requests)",
            registry=self.metrics_registry,
            buckets=(1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 2500))
        self._c_pipeline_fallback = Counter(
            "sidecar_pipeline_fallbacks_total",
            "Pipelined P/D attempts that fell back to the serial 2-phase "
            "candidate walk (prefill leg failed or never acked a chunk)",
            registry=self.metrics_registry)

    # ---- per-leg TLS (reference proxy.go:153-166) -----------------------

    def _prefill_base(self, prefiller: str) -> str:
        scheme = "https" if self.cfg.use_tls_for_prefiller else "http"
        return f"{scheme}://{prefiller}"

    def _encode_base(self, host: str) -> str:
        scheme = "https" if self.cfg.use_tls_for_encoder else "http"
        return f"{scheme}://{host}"

    def _dp_header_url(self, request: web.Request) -> str | None:
        """Legacy x-data-parallel-host-port dispatch (data_parallel.go:19-88):
        honored only when it names one of THIS decoder's rank ports."""
        hp = request.headers.get(H_DATA_PARALLEL)
        if not hp:
            return None
        from urllib.parse import urlsplit

        parts = urlsplit(self.cfg.decoder_url)
        try:
            host, _, port = hp.rpartition(":")
            port = int(port)
        except ValueError:
            return None
        if (host == parts.hostname and parts.port is not None
                and parts.port <= port < parts.port + max(self.cfg.data_parallel_size, 1)):
            scheme = ("https" if self.cfg.use_tls_for_decoder
                      else parts.scheme)
            return f"{scheme}://{host}:{port}{parts.path.rstrip('/')}"
        log.warning("ignoring out-of-range %s: %s", H_DATA_PARALLEL, hp)
        return None

    def _rank_url(self) -> str:
        """decoder URL shifted by this listener's DP rank (data_parallel.go:39-88);
        use_tls_for_decoder upgrades the scheme (proxy.go:155). Any path
        prefix on the decoder URL is preserved."""
        from urllib.parse import urlsplit

        parts = urlsplit(self.cfg.decoder_url)
        scheme = "https" if self.cfg.use_tls_for_decoder else parts.scheme
        path = parts.path.rstrip("/")
        if self.dp_rank == 0:
            return f"{scheme}://{parts.netloc}{path}"
        if parts.port is None:
            raise ValueError(
                f"decoder URL {self.cfg.decoder_url!r} needs an explicit port "
                f"for data-parallel rank dispatch")
        return (f"{scheme}://{parts.hostname}:{parts.port + self.dp_rank}"
                f"{path}")

    async def start(self):
        from ..tlsutil import client_verify

        self._client = httpx.AsyncClient(
            timeout=httpx.Timeout(self.cfg.decode_timeout_s, connect=5.0),
            verify=client_verify(self.cfg.insecure_skip_verify_decoder))
        self._prefill_client = httpx.AsyncClient(
            timeout=httpx.Timeout(self.cfg.prefill_timeout_s, connect=5.0),
            verify=client_verify(self.cfg.insecure_skip_verify_prefiller))
        self._encode_client = httpx.AsyncClient(
            timeout=httpx.Timeout(self.cfg.prefill_timeout_s, connect=5.0),
            verify=client_verify(self.cfg.insecure_skip_verify_encoder))
        if self.cfg.secure_serving and self._tls is None:
            from ..tlsutil import TlsServing

            self._tls = TlsServing(self.cfg.cert_path,
                                   self.cfg.enable_cert_reload)
            self._tls_owned = True
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.cfg.host,
                           self.cfg.port + self.dp_rank,
                           ssl_context=self._tls.ssl_context
                           if self._tls else None)
        await site.start()
        log.info("sidecar on %s:%s -> decoder %s (connector=%s, dp_rank=%d)",
                 self.cfg.host, self.cfg.port + self.dp_rank, self._rank_url(),
                 self.cfg.connector, self.dp_rank)
        if self.dp_rank == 0 and self.cfg.data_parallel_size > 1:
            for rank in range(1, self.cfg.data_parallel_size):
                child = Sidecar(self.cfg, dp_rank=rank)
                child._tls = self._tls  # one serving identity per pod
                child._rank_url()  # fail fast on port-less decoder URLs
                await child.start()
                self._dp_children.append(child)

    async def begin_drain(self):
        """SIGTERM step 1: stop ACCEPTING WORK before waiting out in-flight
        requests — readiness flips 503 (the LB/router pulls this replica)
        and new generate arrivals get an immediate retryable 503 instead of
        being reset at the end of the grace window. The listener itself
        stays up through the window so /health and /metrics (including the
        sidecar_draining gauge) stay observable from fresh connections;
        stop() closes it after the drain."""
        self.draining = True
        self._g_draining.set(1)
        for child in self._dp_children:
            await child.begin_drain()

    async def stop(self):
        for child in self._dp_children:
            await child.stop()
        self._dp_children.clear()
        if self._runner:
            await self._runner.cleanup()
        for c in (self._client, self._prefill_client, self._encode_client):
            if c is not None:
                await c.aclose()
        if self._tls is not None and self._tls_owned:
            self._tls.close()

    @staticmethod
    def _trace_headers(extra: dict[str, str] | None = None) -> dict[str, str]:
        """Outbound headers carrying the current span's W3C trace context
        (empty when no span is live — tracing off or sampled out)."""
        from ..tracing import tracer

        h = dict(extra or {})
        tracer.inject_headers(h)
        return h

    # ---- request handling ------------------------------------------------

    async def handle_generate(self, request: web.Request) -> web.StreamResponse:
        from ..tracing import tracer

        if self.draining:
            # Clean retryable rejection: the router resubmits elsewhere; a
            # request accepted now could be cut off mid-stream at teardown.
            return web.json_response(
                {"error": "sidecar draining"}, status=503,
                headers={"x-removal-reason": "sidecar-draining"})
        self._inflight += 1
        self._g_inflight.set(self._inflight)
        try:
            # Joins the gateway's trace via the propagated traceparent; the
            # connector-protocol spans nest under this server span, and the
            # decode/prefill legs re-propagate the context to the engines.
            with tracer.span_from_headers("sidecar.request", request.headers,
                                          path=request.path,
                                          connector=self.cfg.connector,
                                          dp_rank=self.dp_rank) as span:
                resp = await self._handle_generate(request)
                span.set_attribute("status", resp.status)
                return resp
        finally:
            self._inflight -= 1
            self._g_inflight.set(self._inflight)

    async def _handle_generate(self, request: web.Request) -> web.StreamResponse:
        raw = await request.read()
        try:
            body = json.loads(raw)
        except Exception:
            return web.json_response({"error": "invalid JSON"}, status=400)

        # End-to-end deadline: the gateway stamps the REMAINING budget on
        # x-request-timeout; every leg below inherits what's left.
        deadline = Deadline.from_headers(request.headers)
        if deadline is not None and deadline.expired:
            self._c_deadline.inc()
            return web.json_response(
                {"error": "deadline exceeded"}, status=504,
                headers={"x-removal-reason": DEADLINE_EXCEEDED_REASON})

        # Disagg headers are consumed here and never forwarded downstream
        # (upstream dispatch builds its own header set).
        prefillers = self._prefiller_candidates(request)
        encoders = request.headers.get(H_ENCODERS)

        if encoders and self.cfg.connector != "passthrough":
            hosts = [h.strip() for h in encoders.split(",") if h.strip()]
            if self.cfg.ssrf_allowlist is not None:
                bad = [h for h in hosts if h not in self.cfg.ssrf_allowlist]
                if bad:
                    return web.json_response(
                        {"error": f"encoders {bad} not in allowlist"}, status=403)
            err = await self._run_encode_primers(request, body, hosts)
            if err is not None:
                log.warning("encode primer failed (%s); continuing without", err)

        if prefillers and self.cfg.connector != "passthrough":
            if self.cfg.ssrf_allowlist is not None:
                allowed = [h for h in prefillers
                           if h in self.cfg.ssrf_allowlist]
                if not allowed:
                    return web.json_response(
                        {"error": f"prefillers {prefillers} not in allowlist"},
                        status=403)
                if len(allowed) < len(prefillers):
                    log.warning("dropping non-allowlisted prefill candidates "
                                "%s", [h for h in prefillers
                                       if h not in allowed])
                prefillers = allowed
            if self.cfg.connector == "shared-storage":
                return await self._run_shared_storage_protocol(
                    request, body, prefillers, deadline)
            if self.cfg.connector == "sglang":
                return await self._run_sglang_protocol(request, body,
                                                       prefillers, deadline)
            return await self._run_pd_protocol(request, body, prefillers,
                                               deadline)
        return await self._dispatch_decode(request, body, deadline=deadline)

    def _prefiller_candidates(self, request: web.Request) -> list[str]:
        """Resolve the FULL ordered prefill candidate list from the routing
        header (chat_completions.go:79-95): the router may send repeated
        header values or one comma-separated value. The P/D and SGLang
        protocols walk this list on prefiller failure before falling back to
        local decode. With sampling enabled, the list is rotated to a
        uniformly random starting candidate (the sampling knob became a
        shuffle of the failover order, spreading prefill load while keeping
        every candidate reachable)."""
        values = request.headers.getall(H_PREFILLER, [])
        if len(values) == 1:
            values = values[0].split(",")
        hosts = [v.strip() for v in values if v.strip()]
        if len(hosts) > 1 and self.cfg.enable_prefiller_sampling:
            start = self._prefill_sampler(len(hosts))
            hosts = hosts[start:] + hosts[:start]
        return hosts

    def _pick_prefiller(self, request: web.Request) -> str | None:
        """First candidate of the ordered list (kept for callers that need
        exactly one target)."""
        hosts = self._prefiller_candidates(request)
        return hosts[0] if hosts else None

    async def _run_sglang_protocol(self, request: web.Request,
                                   body: dict[str, Any],
                                   prefillers: list[str],
                                   deadline: Deadline | None = None
                                   ) -> web.StreamResponse:
        """SGLang-style connector (reference connector_sglang.go:43-231):
        inject bootstrap {host, port, room-id} into BOTH legs, fire the
        prefill request asynchronously, and dispatch decode CONCURRENTLY —
        the engines rendezvous on the bootstrap channel for the KV transfer
        (no kv_transfer_params relay, no prefill-completion wait). The
        async prefill leg walks the candidate list on failure; the decode
        leg keeps the first candidate's bootstrap fields because the
        rendezvous target is fixed the moment decode is dispatched. With
        real sglang engines a failed-over prefill therefore warms the new
        candidate's cache but cannot complete THIS request's KV transfer —
        the decode engine times out its bootstrap wait and computes
        locally, exactly as it would with no failover at all (no-worse);
        deferring decode until a prefiller answers would forfeit the
        connector's defining concurrency."""
        import asyncio
        import random
        import time as _time

        from ..tracing import tracer

        boot = dict(body)
        boot["bootstrap_host"] = (prefillers[0].rpartition(":")[0]
                                  or prefillers[0])
        boot["bootstrap_port"] = self.cfg.bootstrap_port
        boot["bootstrap_room"] = _time.time_ns() + random.randint(0, 999)

        with tracer.span("sidecar.sglang_protocol", prefiller=prefillers[0],
                         room=boot["bootstrap_room"]) as span:
            # Snapshot the trace context NOW: the leg may outlive this span.
            leg_headers = self._trace_headers()

            async def prefill_leg():
                # Fire-and-forget with its own lifetime: the decode response
                # finishing first must not cancel the prefill leg
                # (connector_sglang.go uses context.WithoutCancel).
                for i, prefiller in enumerate(prefillers):
                    if deadline is not None and deadline.expired:
                        return
                    if i:
                        self._c_prefill_failover.inc()
                    leg_boot = dict(boot)
                    leg_boot["bootstrap_host"] = (
                        prefiller.rpartition(":")[0] or prefiller)
                    hdrs = dict(leg_headers)
                    timeout = self.cfg.prefill_timeout_s
                    if deadline is not None:
                        # Re-stamped per attempt: a later candidate must see
                        # what is left NOW, not the walk-start snapshot.
                        timeout = max(min(timeout, deadline.remaining_s), 0.001)
                        hdrs[H_REQUEST_TIMEOUT] = deadline.header_value()
                    try:
                        r = await self._prefill_client.post(
                            self._prefill_base(prefiller) + request.path,
                            json=leg_boot, headers=hdrs,
                            timeout=timeout)
                        if r.status_code < 300:
                            return
                        log.warning("sglang prefill at %s returned %d",
                                    prefiller, r.status_code)
                    except Exception as e:
                        log.warning("sglang prefill at %s failed: %s",
                                    prefiller, e)

            task = asyncio.get_running_loop().create_task(prefill_leg())
            self._bg_tasks.add(task)
            task.add_done_callback(self._bg_tasks.discard)
            t0 = time.monotonic()
            try:
                return await self._dispatch_decode(request, boot,
                                                   deadline=deadline)
            finally:
                span.set_attribute("decode_duration_ms",
                                   round((time.monotonic() - t0) * 1e3, 1))

    async def _run_shared_storage_protocol(self, request: web.Request,
                                           body: dict[str, Any],
                                           prefillers: list[str],
                                           deadline: Deadline | None = None
                                           ) -> web.StreamResponse:
        """Shared-storage connector (reference connector_shared_storage.go:
        30-271): try decode FIRST with a cache_hit_threshold probe; only if the
        decode engine reports finish_reason=cache_threshold (cache too cold),
        run the remote prefill leg, then retry decode. Here the 'shared
        storage' is the prefill engine's staged KV export pulled over DCN."""
        from ..tracing import tracer

        with tracer.span("sidecar.shared_storage_protocol",
                         prefiller=prefillers[0]) as span:
            # Cheap probe: max_tokens=1 so a warm hit never generates the
            # completion twice; the real generation always goes through
            # _dispatch_decode (which also honors decode_chunk_size/stream).
            probe_body = dict(body)
            probe_body["cache_hit_threshold"] = self.cfg.cache_hit_threshold
            probe_body["stream"] = False
            probe_body[self._max_tokens_field(request.path)] = 1
            warm = False
            try:
                r = await self._client.post(self._rank_url() + request.path,
                                            json=probe_body,
                                            headers=self._trace_headers())
                if r.status_code == 200:
                    doc = r.json()
                    if doc.get("object") == "response":
                        # Responses bodies carry truncation cause in
                        # incomplete_details, not choices[].finish_reason.
                        finish = (doc.get("incomplete_details")
                                  or {}).get("reason")
                    else:
                        finish = (doc.get("choices")
                                  or [{}])[0].get("finish_reason")
                    warm = finish != "cache_threshold"
            except Exception as e:
                log.warning("shared-storage probe failed (%s); running P/D", e)
            span.set_attribute("cache_hit", warm)
            if warm:
                return await self._dispatch_decode(request, body,
                                                   deadline=deadline)
            return await self._run_pd_protocol(request, body, prefillers,
                                               deadline)

    @staticmethod
    def _multimodal_items(body: dict[str, Any]) -> list[dict[str, Any]]:
        """Extract image/video/audio content blocks from a chat body
        (reference multimodal_helpers.go)."""
        items = []
        for m in body.get("messages") or []:
            content = m.get("content")
            if isinstance(content, list):
                for block in content:
                    if isinstance(block, dict) and block.get("type") in (
                            "image_url", "video_url", "input_audio"):
                        items.append(block)
        return items

    async def _run_encode_primers(self, request: web.Request,
                                  body: dict[str, Any],
                                  hosts: list[str]) -> str | None:
        """E/PD stage: fan multimodal items out across the encode workers
        (reference connector_epd_shared_storage.go:38-211). Items are split
        round-robin; every worker is primed with its share before P/D runs."""
        items = self._multimodal_items(body)
        if not items or not hosts:
            return None
        rid = (body.get("request_id")
               or request.headers.get("x-request-id")
               or f"epd-{id(body):x}")
        shares: list[list[dict[str, Any]]] = [[] for _ in hosts]
        share_indices: list[list[int]] = [[] for _ in hosts]
        for i, item in enumerate(items):
            shares[i % len(hosts)].append(item)
            share_indices[i % len(hosts)].append(i)
        try:
            import asyncio as _aio

            primed = [(h, share, idxs) for h, share, idxs
                      in zip(hosts, shares, share_indices) if share]
            trace_headers = self._trace_headers()
            results = await _aio.gather(*[
                self._encode_client.post(self._encode_base(h) + "/v1/encode",
                                         json={"request_id": rid,
                                               "items": share,
                                               "item_indices": idxs},
                                         headers=trace_headers)
                for h, share, idxs in primed])
            for r in results:
                if r.status_code != 200:
                    return f"encoder returned {r.status_code}"
        except Exception as e:
            return str(e)
        # Tell the downstream engines where to pull the staged embeddings
        # (the EC-connector config of reference engines, here per-request).
        # Scheme-qualified when the encoder leg is TLS so the decode
        # engine's /ec pull dials the right protocol.
        body["request_id"] = rid
        body["ec_sources"] = [self._encode_base(h)
                              if self.cfg.use_tls_for_encoder else h
                              for h, _, _ in primed]
        return None

    async def _run_pd_protocol(self, request: web.Request, body: dict[str, Any],
                               prefillers: list[str],
                               deadline: Deadline | None = None
                               ) -> web.StreamResponse:
        """2-phase tpu-dcn protocol (NIXL-v2 analogue). Span attributes mirror
        the reference's sidecar spans (true_ttft_ms/prefill_duration_ms,
        connector_nixlv2.go:276-299)."""
        from ..tracing import tracer

        with tracer.span("sidecar.pd_protocol",
                         prefiller=prefillers[0]) as span:
            return await self._run_pd_protocol_inner(request, body, prefillers,
                                                     span, deadline)

    @staticmethod
    def _max_tokens_field(path: str) -> str:
        """The Responses API bounds output with ``max_output_tokens``
        (reference proxy.go:48); the other OpenAI surfaces use
        ``max_tokens``."""
        return ("max_output_tokens" if path.endswith("/responses")
                else "max_tokens")

    async def _run_pd_protocol_inner(self, request, body, prefillers, span,
                                     deadline=None):
        if self.cfg.pipeline_enabled:
            resp = await self._run_pd_pipelined(request, body, prefillers,
                                                span, deadline)
            if resp is not None:
                return resp
            # Pipelined attempt failed BEFORE the decode leg was dispatched
            # (prefill error / no ack): fall through to the serial
            # candidate walk below — the client sees no error, and the
            # fallback is visible via sidecar_pipeline_fallbacks_total and
            # the span's pipeline_fallback attribute.
        t0 = time.monotonic()
        prefill_body = dict(body)
        prefill_body["kv_transfer_params"] = {"do_remote_decode": True}
        prefill_body["stream"] = False
        # connector_nixlv2.go:109-131: prefill generates exactly one token;
        # the decode leg keeps the caller's original limit (or absence).
        prefill_body[self._max_tokens_field(request.path)] = 1

        # Failover across the router's ranked candidates (P/D-Serve's fast
        # inter-instance failover): each attempt inherits the REMAINING
        # deadline budget; when every candidate fails (or the budget runs
        # out) the request falls back to aggregated local decode.
        ktp = None
        served_prefiller = None
        hit_headers: dict[str, str] = {}
        attempts = 0
        for i, prefiller in enumerate(prefillers):
            if deadline is not None and deadline.expired:
                log.warning("prefill deadline exhausted after %d attempt(s); "
                            "falling back to decode", attempts)
                break
            if i:
                self._c_prefill_failover.inc()
            attempts += 1
            timeout = self.cfg.prefill_timeout_s
            headers = self._trace_headers()
            if deadline is not None:
                timeout = max(min(timeout, deadline.remaining_s), 0.001)
                headers[H_REQUEST_TIMEOUT] = deadline.header_value()
            try:
                r = await self._prefill_client.post(
                    self._prefill_base(prefiller) + request.path,
                    json=prefill_body, headers=headers, timeout=timeout)
                if r.status_code == 200:
                    ktp = r.json().get("kv_transfer_params")
                    served_prefiller = prefiller
                    # The PREFILL leg is where the prefix-cache hit actually
                    # happened on a P/D split — relay its engine-confirmed
                    # depth (engine server _kv_hit_headers) so the router's
                    # cache ledger joins it against the prediction. The
                    # decode leg's own headers (absent for KV imports) must
                    # not shadow these.
                    for h in ("x-kv-hit-blocks", "x-kv-hit-tokens"):
                        v = r.headers.get(h)
                        if v is not None:
                            hit_headers[h] = v
                    span.set_attribute("prefill_endpoint", prefiller)
                    break
                log.warning("prefill at %s returned %d; %s", prefiller,
                            r.status_code,
                            "trying next candidate"
                            if i + 1 < len(prefillers)
                            else "falling back to decode")
            except Exception as e:
                log.warning("prefill at %s failed (%s); %s", prefiller, e,
                            "trying next candidate"
                            if i + 1 < len(prefillers)
                            else "falling back to decode")

        decode_body = dict(body)
        if ktp is not None:
            decode_body["kv_transfer_params"] = ktp
        prefill_ms = (time.monotonic() - t0) * 1e3
        span.set_attribute("prefill_duration_ms", round(prefill_ms, 1))
        span.set_attribute("prefill_attempts", attempts)
        span.set_attribute("fallback_to_decode", ktp is None)
        extra = {"x-prefill-duration-ms": f"{prefill_ms:.1f}", **hit_headers}
        if served_prefiller is not None:
            # Pair identity for the router's /debug/transfers table: the
            # prefill candidate that actually served (post-failover), not
            # whatever the routing header listed first.
            extra["x-kv-prefiller"] = served_prefiller
        return await self._dispatch_decode(request, decode_body,
                                           extra_headers=extra,
                                           deadline=deadline)

    async def _run_pd_pipelined(self, request, body, prefillers, span,
                                deadline=None):
        """Pipelined P/D handoff (``pipeline_enabled``): pre-assign the
        prefill request id so the export record is addressable before the
        prefill response exists, fire the prefill leg concurrently, long-poll
        the prefill engine's ``/kv/{rid}?ack=1`` surface for first-chunk
        staging, and dispatch the decode leg — whose engine pulls KV chunk k
        while the prefill engine computes chunk k+1 — the moment the ack
        lands. Returns the client response, or None to fall back to the
        serial candidate walk (nothing was dispatched decode-side yet, so
        the fallback is invisible to the client). A prefill engine that dies
        AFTER decode dispatch is the decode engine's problem: its chunk poll
        404s and it degrades to local prefill (zero client-visible errors —
        the chaos drill's contract)."""
        import uuid as _uuid

        t0 = time.monotonic()
        prefiller = prefillers[0]
        rid = str(body.get("request_id")
                  or f"pd-{_uuid.uuid4().hex[:12]}")
        prefill_body = dict(body)
        prefill_body["request_id"] = rid
        prefill_body["kv_transfer_params"] = {"do_remote_decode": True,
                                              "stream_chunks": True}
        prefill_body["stream"] = False
        prefill_body[self._max_tokens_field(request.path)] = 1
        timeout = self.cfg.prefill_timeout_s
        headers = self._trace_headers()
        if deadline is not None:
            timeout = max(min(timeout, deadline.remaining_s), 0.001)
            headers[H_REQUEST_TIMEOUT] = deadline.header_value()

        async def _prefill_leg():
            r = await self._prefill_client.post(
                self._prefill_base(prefiller) + request.path,
                json=prefill_body, headers=headers, timeout=timeout)
            return r, (time.monotonic() - t0) * 1e3

        task = asyncio.get_running_loop().create_task(_prefill_leg())
        self._bg_tasks.add(task)
        task.add_done_callback(self._bg_tasks.discard)

        if not await self._await_first_chunk(prefiller, rid, task, deadline):
            self._c_pipeline_fallback.inc()
            span.set_attribute("pipeline_fallback", True)
            self._reap_pipelined_prefill(prefiller, rid, task)
            return None

        span.set_attribute("prefill_endpoint", prefiller)
        span.set_attribute("pipelined", True)
        host, _, port = prefiller.rpartition(":")
        decode_body = dict(body)
        decode_body["kv_transfer_params"] = {
            "remote_host": host, "remote_port": int(port),
            "remote_request_id": rid, "stream_chunks": True,
            "remote_scheme": ("https" if self.cfg.use_tls_for_prefiller
                              else "http"),
        }
        resp = await self._dispatch_decode(
            request, decode_body,
            extra_headers={"x-kv-prefiller": prefiller}, deadline=deadline)

        # The prefill leg necessarily finished before the decode engine's
        # final chunk pull, so stamping its timing/hit headers here adds no
        # wall-clock — but a prepared stream's headers are already on the
        # wire (same loss as the serial path's pull headers on streams).
        try:
            r, prefill_ms = await asyncio.wait_for(asyncio.shield(task),
                                                   timeout=10.0)
        except Exception:
            return resp
        if not resp.prepared:
            resp.headers["x-prefill-duration-ms"] = f"{prefill_ms:.1f}"
            if r.status_code == 200:
                for h in ("x-kv-hit-blocks", "x-kv-hit-tokens"):
                    v = r.headers.get(h)
                    if v is not None:
                        resp.headers[h] = v
        span.set_attribute("prefill_duration_ms", round(prefill_ms, 1))
        return resp

    async def _await_first_chunk(self, prefiller: str, rid: str, task,
                                 deadline=None) -> bool:
        """Bounded long-poll for first-chunk staging on the prefill engine.
        True once any chunk is staged (or the whole prefill completed —
        engines that never chunk still ack at completion); False when the
        prefill leg failed or the budget ran out (caller falls back)."""
        bound = self.cfg.prefill_timeout_s
        if deadline is not None:
            bound = max(min(bound, deadline.remaining_s), 0.001)
        t_end = time.monotonic() + bound
        url = self._prefill_base(prefiller) + f"/kv/{rid}"
        while time.monotonic() < t_end:
            if task.done():
                try:
                    r, _ = task.result()
                except Exception:
                    return False
                return r.status_code == 200
            try:
                r = await self._prefill_client.get(
                    url, params={"ack": "1", "wait_ms": 500}, timeout=5.0)
                if r.status_code == 200:
                    return True
            except Exception:
                pass  # engine booting / mid-restart: keep polling in budget
            await asyncio.sleep(0.01)
        return False

    def _reap_pipelined_prefill(self, prefiller: str, rid: str, task) -> None:
        """Fallback cleanup: let the stray prefill leg drain in the
        background, then release whatever export it staged (best-effort —
        the engine's TTL sweep is the backstop)."""

        async def _reap():
            try:
                await task
            except Exception:
                pass
            try:
                await self._prefill_client.delete(
                    self._prefill_base(prefiller) + f"/kv/{rid}",
                    timeout=5.0)
            except Exception:
                pass

        t = asyncio.get_running_loop().create_task(_reap())
        self._bg_tasks.add(t)
        t.add_done_callback(self._bg_tasks.discard)

    async def _dispatch_decode(self, request: web.Request, body: dict[str, Any],
                               extra_headers: dict[str, str] | None = None,
                               deadline: Deadline | None = None
                               ) -> web.StreamResponse:
        if deadline is not None and deadline.expired:
            # The prefill walk (or queueing) consumed the whole budget:
            # honor the deadline contract instead of dispatching a decode
            # doomed to a 1 ms timeout and surfacing as a retryable 502.
            self._c_deadline.inc()
            return web.json_response(
                {"error": "deadline exceeded"}, status=504,
                headers={**(extra_headers or {}),
                         "x-removal-reason": DEADLINE_EXCEEDED_REASON})
        chunkable = (self.cfg.decode_chunk_size > 0 and not body.get("stream")
                     and "kv_transfer_params" not in body
                     and int(body.get("max_tokens") or 16) > 0
                     and ("messages" in body or isinstance(body.get("prompt"), str)))
        base_url = self._dp_header_url(request) or self._rank_url()
        if chunkable:
            return await self._chunked_decode(request, body, extra_headers,
                                              base_url, deadline)
        url = base_url + request.path
        leg_headers = self._trace_headers({"content-type": "application/json"})
        timeout = self.cfg.decode_timeout_s
        if deadline is not None:
            # The decode leg inherits the remaining end-to-end budget.
            timeout = max(min(timeout, deadline.remaining_s), 0.001)
            leg_headers[H_REQUEST_TIMEOUT] = deadline.header_value()
        try:
            upstream = self._client.build_request(
                "POST", url, json=body, headers=leg_headers, timeout=timeout)
            resp = await self._client.send(upstream, stream=True)
        except Exception as e:
            return web.json_response({"error": f"decode dispatch failed: {e}"},
                                     status=502,
                                     headers=dict(extra_headers or {}))
        out_headers = {"content-type": resp.headers.get("content-type",
                                                        "application/json")}
        out_headers.update(extra_headers or {})
        # Relay the decode engine's measured KV pull cost (non-streaming
        # responses only — streamed headers leave before the pull resolves)
        # so the router can land the (prefill, decode) pair observation.
        pull_ms = resp.headers.get("x-kv-pull-ms")
        if pull_ms:
            out_headers["x-kv-transfer-ms"] = pull_ms
            pull_bytes = resp.headers.get("x-kv-pull-bytes")
            if pull_bytes:
                out_headers["x-kv-transfer-bytes"] = pull_bytes
            v = finite_float_or_none(pull_ms)
            if v is not None:
                self._h_kv_transfer.observe(v)
            # Pipelined pulls also report the NON-overlapped tail: relay it
            # (x-kv-transfer-exposed-ms → the router's exposed pair EWMAs)
            # and observe how much transfer time the overlap hid.
            exposed_ms = resp.headers.get("x-kv-pull-exposed-ms")
            if exposed_ms:
                out_headers["x-kv-transfer-exposed-ms"] = exposed_ms
                ve = finite_float_or_none(exposed_ms)
                if v is not None and ve is not None:
                    self._h_kv_overlap.observe(max(v - ve, 0.0))
        # Relay the decode engine's measured admission wait (same
        # non-streaming caveat) so the router's tail waterfall can split
        # engine queueing out of the decode residual (router/tails.py).
        queue_ms = resp.headers.get("x-engine-queue-ms")
        if queue_ms:
            out_headers["x-engine-queue-ms"] = queue_ms
        # Local-decode fallback (and passthrough/monolithic fronting): the
        # decode engine's own prefix-hit headers relay unless a prefill
        # leg already supplied the authoritative pair (extra_headers).
        if "x-kv-hit-tokens" not in out_headers:
            for h in ("x-kv-hit-blocks", "x-kv-hit-tokens"):
                v = resp.headers.get(h)
                if v is not None:
                    out_headers[h] = v
        try:
            if "text/event-stream" in out_headers["content-type"]:
                ws = web.StreamResponse(status=resp.status_code, headers=out_headers)
                await ws.prepare(request)
                # Engine reads vs client writes fail differently: an engine
                # disconnect mid-stream is counted and the relay closed
                # cleanly (the status line is on the wire — the router's
                # stream-abort guard mirrors this on its own hop); a client
                # hangup is routine and must not count as an engine abort.
                engine_iter = resp.aiter_bytes()
                while True:
                    try:
                        chunk = await engine_iter.__anext__()
                    except StopAsyncIteration:
                        break
                    except (httpx.HTTPError, ConnectionResetError,
                            ConnectionError) as e:
                        self._c_stream_aborted.inc()
                        log.warning("decode stream aborted mid-relay: %s", e)
                        break
                    try:
                        await ws.write(chunk)
                    except (ConnectionResetError, ConnectionError) as e:
                        log.debug("client closed stream mid-relay: %s", e)
                        break
                try:
                    await ws.write_eof()
                except (ConnectionResetError, ConnectionError):
                    pass  # client already gone
                return ws
            try:
                data = await resp.aread()
            except (httpx.HTTPError, ConnectionResetError,
                    ConnectionError) as e:
                # Body read died before anything was relayed: still a clean
                # 502 toward the client, with the prefill timing headers
                # preserved for observability.
                self._c_stream_aborted.inc()
                return web.json_response(
                    {"error": f"decode read failed: {e}"}, status=502,
                    headers=dict(extra_headers or {}))
            return web.Response(body=data, status=resp.status_code,
                                headers=out_headers)
        finally:
            await resp.aclose()

    async def _chunked_decode(self, request: web.Request, body: dict[str, Any],
                              extra_headers: dict[str, str] | None,
                              base_url: str | None = None,
                              deadline: Deadline | None = None) -> web.StreamResponse:
        """Bounded decode slices (reference decode.go:62-444): issue decode in
        max_tokens=chunk steps, re-appending the generated text between steps
        (chat uses the continue-final-message pattern)."""
        chunk = self.cfg.decode_chunk_size
        total = int(body.get("max_tokens", 16))
        chat = "messages" in body
        acc_text = ""
        completion_tokens = 0
        doc: dict[str, Any] = {}
        remaining = total
        while remaining > 0:
            step_body = dict(body)
            step_body["max_tokens"] = min(chunk, remaining)
            if chat:
                msgs = list(body["messages"])
                if acc_text:
                    msgs.append({"role": "assistant", "content": acc_text})
                    step_body["continue_final_message"] = True
                step_body["messages"] = msgs
            else:
                step_body["prompt"] = body["prompt"] + acc_text
            step_headers = self._trace_headers()
            step_timeout = self.cfg.decode_timeout_s
            if deadline is not None:
                if deadline.expired:
                    # Mid-sequence deadline: return what was decoded so far
                    # rather than burning budget on further slices.
                    break
                step_timeout = max(min(step_timeout, deadline.remaining_s),
                                   0.001)
                step_headers[H_REQUEST_TIMEOUT] = deadline.header_value()
            r = await self._client.post(
                (base_url or self._rank_url()) + request.path, json=step_body,
                headers=step_headers, timeout=step_timeout)
            if r.status_code != 200:
                return web.Response(body=r.content, status=r.status_code,
                                    content_type="application/json")
            doc = r.json()
            choice = doc["choices"][0]
            piece = (choice.get("message", {}).get("content")
                     if chat else choice.get("text")) or ""
            acc_text += piece
            completion_tokens += doc.get("usage", {}).get("completion_tokens", 0)
            remaining -= step_body["max_tokens"]
            if choice.get("finish_reason") != "length":
                break

        if not doc:
            # Deadline expired before the first slice completed.
            self._c_deadline.inc()
            return web.json_response(
                {"error": "deadline exceeded"}, status=504,
                headers={**(extra_headers or {}),
                         "x-removal-reason": DEADLINE_EXCEEDED_REASON})
        if chat:
            doc["choices"][0]["message"]["content"] = acc_text
        else:
            doc["choices"][0]["text"] = acc_text
        if "usage" in doc:
            doc["usage"]["completion_tokens"] = completion_tokens
            doc["usage"]["total_tokens"] = (doc["usage"].get("prompt_tokens", 0)
                                            + completion_tokens)
        headers = {"content-type": "application/json"}
        headers.update(extra_headers or {})
        return web.Response(body=json.dumps(doc).encode(), headers=headers)

    async def _proxy_post(self, request: web.Request) -> web.Response:
        try:
            r = await self._client.post(
                self._rank_url() + request.path, content=await request.read(),
                headers={"content-type": "application/json"})
            return web.Response(body=r.content, status=r.status_code,
                                content_type=r.headers.get(
                                    "content-type",
                                    "application/json").split(";")[0])
        except Exception as e:
            return web.json_response({"error": str(e)}, status=502)

    async def _proxy_get(self, request: web.Request) -> web.Response:
        try:
            r = await self._client.get(self._rank_url() + request.path)
            return web.Response(body=r.content, status=r.status_code,
                                content_type=r.headers.get("content-type",
                                                           "text/plain").split(";")[0])
        except Exception as e:
            return web.json_response({"error": str(e)}, status=502)

    async def _health(self, request: web.Request) -> web.Response:
        """Readiness couples to the drain state: a draining sidecar reports
        503 immediately (the LB/router stops routing here) instead of
        relaying the engine's still-green health."""
        if self.draining:
            return web.json_response({"status": "draining"}, status=503)
        return await self._proxy_get(request)

    async def _traces(self, request: web.Request) -> web.Response:
        """Sidecar span ring buffer + the decode engine's, merged (dedup by
        span_id). The gateway's /debug/traces?merge=1 only sees POOL
        endpoints — in a P/D topology that's this sidecar, so it must relay
        the engine's spans or the engine leg of every trace is invisible."""
        from ..tracing import tracer

        spans = list(tracer.snapshot())
        seen = {s["span_id"] for s in spans}
        try:
            r = await self._client.get(self._rank_url() + "/debug/traces",
                                       timeout=2.0)
            remote = (r.json().get("spans") or []) if r.status_code == 200 else []
        except Exception:
            remote = []
        for s in remote:
            if isinstance(s, dict) and s.get("span_id") not in seen:
                seen.add(s.get("span_id"))
                spans.append(s)
        return web.json_response({"service": "sidecar", "spans": spans})

    async def _metrics(self, request: web.Request) -> web.Response:
        """Engine scrape relay + sidecar-local families (sidecar_draining,
        sidecar_inflight_requests) appended, so one scrape covers both. An
        unreachable engine still yields the sidecar families — the drain
        gauge must stay observable through teardown."""
        from prometheus_client import generate_latest

        own = generate_latest(self.metrics_registry)
        try:
            r = await self._client.get(self._rank_url() + "/metrics")
            if r.status_code == 200:
                body = r.content + own
            else:
                # A non-2xx relay would make Prometheus discard the whole
                # body, losing the sidecar families too — degrade to a
                # comment + own families instead.
                body = (f"# engine /metrics returned {r.status_code}\n"
                        .encode()) + own
        except Exception as e:
            body = (f"# engine scrape failed: {e}\n".encode()) + own
        return web.Response(body=body, status=200,
                            content_type="text/plain", charset="utf-8")

    async def _proxy_get_stream(self, request: web.Request) -> web.StreamResponse:
        """Long-lived streaming GET proxy (SSE /kv_events): bytes are relayed
        as they arrive, no buffering — the KV index must see events live."""
        url = self._rank_url() + request.path
        try:
            upstream = self._client.build_request(
                "GET", url, headers={"accept": "text/event-stream"})
            resp = await self._client.send(upstream, stream=True)
        except Exception as e:
            return web.json_response({"error": str(e)}, status=502)
        ws = web.StreamResponse(status=resp.status_code, headers={
            "content-type": resp.headers.get("content-type",
                                             "text/event-stream")})
        try:
            await ws.prepare(request)
            async for chunk in resp.aiter_bytes():
                await ws.write(chunk)
            await ws.write_eof()
        except (ConnectionResetError, ConnectionError, httpx.HTTPError) as e:
            # Routine subscriber teardown / engine restart mid-stream: not an
            # error worth a traceback; the subscriber reconnects.
            log.debug("kv_events relay ended: %s", e)
        finally:
            await resp.aclose()
        return ws


def main(argv: list[str] | None = None):
    import argparse
    import asyncio

    p = argparse.ArgumentParser(description="P/D disaggregation sidecar")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--decoder", default="http://127.0.0.1:8200")
    p.add_argument("--connector", default="tpu-dcn",
                   choices=["tpu-dcn", "shared-storage", "sglang", "passthrough"])
    p.add_argument("--cache-hit-threshold", type=float, default=0.8)
    p.add_argument("--bootstrap-port", type=int, default=8998,
                   help="sglang connector: engine KV bootstrap rendezvous port")
    p.add_argument("--allowlist", default=None,
                   help="comma-separated allowed prefill host:ports "
                        "(enables SSRF protection)")
    p.add_argument("--decode-chunk-size", type=int, default=0)
    p.add_argument("--data-parallel-size", type=int, default=1)
    p.add_argument("--enable-prefiller-sampling", action="store_true",
                   help="sample a random prefiller from the candidate list "
                        "instead of the first (chat_completions.go:89)")
    p.add_argument("--pipeline", action="store_true",
                   help="pipelined P/D: dispatch the decode leg on first-"
                        "chunk staging so the KV pull overlaps prefill "
                        "(docs/disaggregation.md); default serial 2-phase")
    p.add_argument("--secure-serving", action="store_true",
                   help="serve HTTPS; without --cert-path a self-signed "
                        "certificate is minted (proxy_helpers.go:55-100)")
    p.add_argument("--cert-path", default=None,
                   help="directory holding tls.crt + tls.key")
    p.add_argument("--enable-cert-reload", action="store_true",
                   help="re-read --cert-path when it changes")
    for leg in ("prefiller", "decoder", "encoder"):
        p.add_argument(f"--use-tls-for-{leg}", action="store_true",
                       help=f"send {leg} requests over https (proxy.go:155)")
        p.add_argument(f"--insecure-skip-verify-{leg}", action="store_true",
                       help=f"skip TLS verification on the {leg} leg")
    args = p.parse_args(argv)
    cfg = SidecarConfig(
        port=args.port, host=args.host, decoder_url=args.decoder,
        connector=args.connector,
        ssrf_allowlist=[s.strip() for s in args.allowlist.split(",") if s.strip()]
        if args.allowlist else None,
        decode_chunk_size=args.decode_chunk_size,
        data_parallel_size=args.data_parallel_size,
        cache_hit_threshold=args.cache_hit_threshold,
        bootstrap_port=args.bootstrap_port,
        enable_prefiller_sampling=args.enable_prefiller_sampling,
        pipeline_enabled=args.pipeline,
        secure_serving=args.secure_serving,
        cert_path=args.cert_path,
        enable_cert_reload=args.enable_cert_reload,
        use_tls_for_prefiller=args.use_tls_for_prefiller,
        use_tls_for_decoder=args.use_tls_for_decoder,
        use_tls_for_encoder=args.use_tls_for_encoder,
        insecure_skip_verify_prefiller=args.insecure_skip_verify_prefiller,
        insecure_skip_verify_decoder=args.insecure_skip_verify_decoder,
        insecure_skip_verify_encoder=args.insecure_skip_verify_encoder)
    logging.basicConfig(level=logging.INFO)

    async def run():
        import signal

        sc = Sidecar(cfg)
        await sc.start()
        stop_ev = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, stop_ev.set)
            except (NotImplementedError, RuntimeError):
                pass
        try:
            await stop_ev.wait()
            # Drain: flip readiness + reject new generate work FIRST (clean
            # retryable 503s instead of resets at teardown), then let
            # in-flight P/D protocols finish (each leg has its own timeout),
            # bounded. The sidecar_draining gauge marks the window; /health
            # and /metrics stay reachable until stop().
            await sc.begin_drain()
            deadline = loop.time() + 30.0
            inflight = lambda: sc._inflight + sum(  # noqa: E731
                ch._inflight for ch in sc._dp_children)
            log.info("SIGTERM: draining %d in-flight requests", inflight())
            while loop.time() < deadline and inflight() > 0:
                await asyncio.sleep(0.25)
        except asyncio.CancelledError:
            pass
        await sc.stop()

    asyncio.run(run())


if __name__ == "__main__":
    main()
