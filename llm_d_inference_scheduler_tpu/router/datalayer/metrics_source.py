"""Metrics data source: scrapes each endpoint's Prometheus /metrics.

Reference: framework/plugins/datalayer/source/metrics (HTTP scrape) feeding
core-metrics-extractor — SURVEY §2.5.
"""

from __future__ import annotations

import logging
import time
from typing import Any

import httpx

from ..framework.datalayer import Endpoint
from ..framework.plugin import PluginBase
from ..metrics import SCRAPE_DURATION_SECONDS, SCRAPE_ERRORS_TOTAL

log = logging.getLogger("router.datalayer.metrics")


class MetricsDataSource(PluginBase):
    TYPE = "metrics-data-source"

    def __init__(self, name: str | None = None, timeout_s: float = 2.0):
        super().__init__(name)
        self._extractors: list[Any] = []
        self._timeout = timeout_s
        self._client: httpx.AsyncClient | None = None
        # TLS verification for https scrape targets: default skip-verify
        # (pod-local certs, the reference scrape client's default), or a CA
        # bundle for real verification (tlsutil.client_verify).
        self._insecure_skip_verify = True
        self._ca_cert_path: str | None = None

    def configure(self, params: dict[str, Any], handle: Any) -> None:
        self._timeout = float(params.get("timeoutSeconds", self._timeout))
        self._insecure_skip_verify = bool(
            params.get("insecureSkipVerify", self._insecure_skip_verify))
        self._ca_cert_path = params.get("caCertPath") or None

    def add_extractor(self, ex: Any) -> None:
        self._extractors.append(ex)

    def extractors(self) -> list[Any]:
        return list(self._extractors)

    async def collect(self, endpoint: Endpoint) -> str | None:
        if self._client is None:
            from ..tlsutil import client_verify

            self._client = httpx.AsyncClient(
                timeout=self._timeout,
                verify=client_verify(self._insecure_skip_verify,
                                     self._ca_cert_path))
        t0 = time.monotonic()
        try:
            r = await self._client.get(endpoint.metadata.metrics_url)
            r.raise_for_status()
            SCRAPE_DURATION_SECONDS.observe(time.monotonic() - t0)
            return r.text
        except Exception as e:
            SCRAPE_ERRORS_TOTAL.labels(endpoint.metadata.address_port).inc()
            log.debug("scrape failed for %s: %s", endpoint.metadata.address_port, e)
            return None

    async def close(self):
        if self._client is not None:
            await self._client.aclose()
            self._client = None
