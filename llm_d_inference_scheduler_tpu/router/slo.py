"""SLO & goodput ledger: per-request serving outcomes closing the predict →
observe loop.

The router *predicts* TTFT/TPOT at scheduling time
(requestcontrol/predicted_latency.py) and *records* every scheduling decision
(router/decisions.py), but neither says whether the request actually met its
SLO, how wrong the predictor was, or what the fleet's goodput is. P/D-Serve
(arXiv:2408.08147) runs its gateway on exactly this feedback — goodput, not
throughput, is the fleet objective — and NetKV (arXiv:2606.03910) needs
measured per-pair transfer cost before transfer-aware pairing can exist.

One ``RequestObservation`` rides each InferenceRequest (``request.outcome``):

- opened by the gateway before orchestration (captures queue time via the
  flow-control admission hook and the predictor's per-request prediction via
  the predicted-latency PreRequest hook);
- fed per transport chunk on the streaming path (one monotonic read + a few
  adds — the <1% of the 5 ms token cadence contract ``bench.py --slo-ramp``
  measures; the ``slo: {enabled: false}`` kill-switch reduces the per-chunk
  hook to one ``is None`` check);
- closed exactly once on EVERY terminal path — success, admission shed,
  retry-exhausted, deadline, mid-stream abort — computing actual TTFT / TPOT
  / e2e / queue time and an ``slo_met`` verdict against ``x-slo-ttft-ms`` /
  ``x-slo-tpot-ms`` (or configured per-model defaults).

The verdict is stamped back into the request's DecisionRecord (so
``/debug/decisions/<id>`` shows predicted vs actual vs SLO side by side),
aggregated into the fleet rollup served at ``/debug/slo`` (per-endpoint /
per-band attainment, predictor signed error + MAE, goodput vs raw token
rate), and exported as metric families (``router_slo_attainment``,
``router_goodput_tokens_total`` vs ``router_output_tokens_total``,
``router_predictor_error_ms{kind,role}``). ``scripts/verify_slo.py`` asserts
every terminal path stamps the ledger.
"""

from __future__ import annotations

import dataclasses
import math
import time
from collections import OrderedDict
from typing import Any

from .framework.datalayer import ROLE_LABEL
from .metrics import (
    GOODPUT_TOKENS_TOTAL,
    OUTPUT_TOKENS_TOTAL,
    PREDICTOR_ERROR_MS,
    SLO_ATTAINMENT,
    SLO_REQUESTS_TOTAL,
    STREAM_GAP_MAX_SECONDS,
)

# SLO request headers (reference latencyslo/plugin.go:38-40); the
# predicted-latency producer consumes the same contract.
H_SLO_TTFT = "x-slo-ttft-ms"
H_SLO_TPOT = "x-slo-tpot-ms"

# Inter-arrival gap buckets (ms) for the streaming path: cheap fixed-size
# integer counters instead of a per-chunk Prometheus observe (~20x cheaper).
GAP_BUCKET_BOUNDS_MS = (2.5, 10.0, 50.0, 250.0)


@dataclasses.dataclass
class SloTargets:
    ttft_ms: float = 0.0
    tpot_ms: float = 0.0


@dataclasses.dataclass
class SloConfig:
    """The YAML ``slo:`` section. ``enabled: false`` is the kill-switch the
    overhead contract requires (per-chunk hook degrades to one ``is None``
    check). Per-model defaults apply when the request carries no SLO
    headers; 0 means "no SLO on that axis"."""

    enabled: bool = True
    default_ttft_ms: float = 0.0
    default_tpot_ms: float = 0.0
    per_model: dict[str, SloTargets] = dataclasses.field(default_factory=dict)

    @classmethod
    def from_spec(cls, spec: dict[str, Any] | None) -> "SloConfig":
        spec = spec or {}
        per_model = {}
        for model, t in (spec.get("perModel") or {}).items():
            per_model[model] = SloTargets(
                ttft_ms=float(t.get("ttftMs", 0.0)),
                tpot_ms=float(t.get("tpotMs", 0.0)))
        return cls(enabled=bool(spec.get("enabled", True)),
                   default_ttft_ms=float(spec.get("defaultTtftMs", 0.0)),
                   default_tpot_ms=float(spec.get("defaultTpotMs", 0.0)),
                   per_model=per_model)


class RequestObservation:
    """One request's serving observation. Mutated in place by the layer
    hooks; the ledger's ``complete()`` computes the verdict exactly once."""

    __slots__ = ("request_id", "model", "band", "t_start",
                 "slo_ttft_ms", "slo_tpot_ms",
                 "predicted_ttft_ms", "predicted_tpot_ms",
                 "endpoint", "role", "queue_ms",
                 "first_token_at", "last_token_at", "token_events",
                 "gap_sum_ms", "gap_max_ms", "gap_buckets",
                 "streamed", "abort_reason", "done")

    def __init__(self, request_id: str, model: str, band: int,
                 t_start: float, slo_ttft_ms: float, slo_tpot_ms: float):
        self.request_id = request_id
        self.model = model
        self.band = band
        self.t_start = t_start
        self.slo_ttft_ms = slo_ttft_ms
        self.slo_tpot_ms = slo_tpot_ms
        self.predicted_ttft_ms: float | None = None
        self.predicted_tpot_ms: float | None = None
        self.endpoint = ""
        self.role = ""
        self.queue_ms = 0.0
        self.first_token_at: float | None = None
        self.last_token_at: float | None = None
        self.token_events = 0
        self.gap_sum_ms = 0.0
        self.gap_max_ms = 0.0
        self.gap_buckets = [0, 0, 0, 0, 0]
        self.streamed = False
        self.abort_reason: str | None = None
        self.done = False

    # ---- streaming hot path --------------------------------------------
    #
    # first_token() reuses the monotonic read the gateway's TTFT observation
    # already paid for; on_chunk() is the only per-chunk cost the ledger
    # adds to the token relay — one clock read plus a handful of float ops
    # (microbenched in benchmarks/SLO_OBS.json against the 5 ms cadence).

    def first_token(self, now: float) -> None:
        self.first_token_at = now
        self.last_token_at = now
        self.token_events = 1
        self.streamed = True

    def on_chunk(self) -> None:
        now = time.monotonic()
        gap = (now - self.last_token_at) * 1e3
        self.last_token_at = now
        self.token_events += 1
        self.gap_sum_ms += gap
        if gap > self.gap_max_ms:
            self.gap_max_ms = gap
        b = self.gap_buckets
        if gap < GAP_BUCKET_BOUNDS_MS[0]:
            b[0] += 1
        elif gap < GAP_BUCKET_BOUNDS_MS[1]:
            b[1] += 1
        elif gap < GAP_BUCKET_BOUNDS_MS[2]:
            b[2] += 1
        elif gap < GAP_BUCKET_BOUNDS_MS[3]:
            b[3] += 1
        else:
            b[4] += 1


class _ErrAgg:
    """Signed-error accumulator for one (kind) of predictor error."""

    __slots__ = ("n", "sum_signed_ms", "sum_abs_ms")

    def __init__(self):
        self.n = 0
        self.sum_signed_ms = 0.0
        self.sum_abs_ms = 0.0

    def add(self, signed_ms: float) -> None:
        self.n += 1
        self.sum_signed_ms += signed_ms
        self.sum_abs_ms += abs(signed_ms)

    def render(self) -> dict[str, Any]:
        if not self.n:
            return {"n": 0}
        return {"n": self.n,
                "mae_ms": round(self.sum_abs_ms / self.n, 3),
                "mean_signed_ms": round(self.sum_signed_ms / self.n, 3)}


class _Agg:
    """Attainment + goodput accumulator (one per endpoint / band / total)."""

    __slots__ = ("requests", "slo_met", "shed", "output_tokens",
                 "goodput_tokens", "ttft_err", "tpot_err")

    def __init__(self):
        self.requests = 0
        self.slo_met = 0
        self.shed = 0
        self.output_tokens = 0
        self.goodput_tokens = 0
        self.ttft_err = _ErrAgg()
        self.tpot_err = _ErrAgg()

    def render(self, *, predictor: bool = True) -> dict[str, Any]:
        # Shed-at-admission is a DISTINCT verdict, not an SLO miss: a shed
        # request consumed no serving capacity and generated no tokens, so
        # attainment is judged over the requests the router actually tried
        # to serve. The shed count stays visible beside it.
        served = self.requests - self.shed
        doc: dict[str, Any] = {
            "requests": self.requests,
            "slo_met": self.slo_met,
            "shed": self.shed,
            "attainment": (round(self.slo_met / served, 4)
                           if served > 0 else None),
            "output_tokens": self.output_tokens,
            "goodput_tokens": self.goodput_tokens,
        }
        if predictor:
            doc["predictor"] = {"ttft": self.ttft_err.render(),
                                "tpot": self.tpot_err.render()}
        return doc


class SloLedger:
    """Fleet-level rollup of per-request serving outcomes.

    All writers run on the gateway's event loop (admission hook, PreRequest,
    the proxy's terminal paths), so the rollup needs no locking; the
    ``/debug/slo`` reader renders a point-in-time view."""

    # Endpoint-keyed state must survive pod churn without growing forever:
    # a rescheduled pod arrives under a fresh ip:port, so "endpoints ever
    # served" is unbounded even though the live pool is small. Same
    # rationale as TransferTable.MAX_PAIRS; eviction also drops the
    # router_slo_attainment gauge child so the series count stays bounded.
    MAX_ENDPOINTS = 256

    def __init__(self, cfg: SloConfig | None = None):
        self.cfg = cfg or SloConfig()
        self._totals = _Agg()
        self._by_endpoint: OrderedDict[str, _Agg] = OrderedDict()
        self._by_band: dict[int, _Agg] = {}
        self._miss_reasons: dict[str, int] = {}
        self._shed_reasons: dict[str, int] = {}
        self._start_unix = time.time()
        # Flat counters the timeline sampler (router/timeline.py) reads
        # every tick: prompt-token total and the per-role prompt/completion
        # token split — the prefill:decode mix is the P/D rebalancer's
        # controller input (ROADMAP item 5), and reading raw counters
        # keeps the tick path off the full snapshot() render.
        self.prompt_tokens_total = 0
        self.tokens_by_role: dict[str, tuple[int, int]] = {}
        # Per-WORKLOAD-CLASS aggregates ("prefill"-heavy vs "decode"-heavy
        # requests, classified by their own prompt:completion token split
        # at completion). Distinct from the per-serving-role split above:
        # a P/D request terminates on its decode pod, so serving-role
        # attainment can never say "prefill-shaped traffic is missing its
        # SLO" — which is exactly the starvation signal the rebalance
        # controller (router/rebalance.py) keys its per-role headroom on.
        # Public flat state, read per tick (the tokens_by_role precedent).
        self.by_workload: dict[str, _Agg] = {}

    @property
    def enabled(self) -> bool:
        return self.cfg.enabled

    @property
    def totals(self) -> _Agg:
        """The cumulative rollup accumulator (requests / slo_met / shed /
        output_tokens / goodput_tokens) — the timeline sampler's per-tick
        delta source."""
        return self._totals

    # ---- open -----------------------------------------------------------

    def resolve_targets(self, model: str,
                        headers: dict[str, str]) -> tuple[float, float]:
        """Request SLO targets: explicit headers win; per-model config, then
        global defaults fill the gaps. 0 = no SLO on that axis."""
        per_model = self.cfg.per_model.get(model)
        ttft = parse_slo_header_ms(headers, H_SLO_TTFT)
        if ttft <= 0:
            ttft = per_model.ttft_ms if per_model else self.cfg.default_ttft_ms
        tpot = parse_slo_header_ms(headers, H_SLO_TPOT)
        if tpot <= 0:
            tpot = per_model.tpot_ms if per_model else self.cfg.default_tpot_ms
        return ttft, tpot

    def start(self, request: Any, t_start: float) -> RequestObservation | None:
        """Open an observation (None when the kill-switch is off — every
        layer hook then degrades to a single ``is None`` check)."""
        if not self.cfg.enabled:
            return None
        ttft, tpot = self.resolve_targets(request.target_model,
                                          request.headers)
        obs = RequestObservation(request.request_id, request.target_model,
                                 request.objectives.priority, t_start,
                                 ttft, tpot)
        request.outcome = obs
        return obs

    # ---- close ----------------------------------------------------------

    def complete(self, request: Any, *, status: int,
                 endpoint: Any = None, usage: dict[str, int] | None = None,
                 reason: str | None = None,
                 transfer: dict[str, Any] | None = None,
                 shed: bool = False) -> None:
        """Terminal accounting: exactly once per request (first call wins —
        error paths may overlap with the proxy's finally)."""
        obs: RequestObservation | None = getattr(request, "outcome", None)
        if obs is None or obs.done:
            return
        obs.done = True
        now = time.monotonic()
        # Priority band re-read at completion: start() runs before the
        # director resolves the x-objective header onto the request, so the
        # open-time value would file all objective-classified traffic under
        # band 0.
        objectives = getattr(request, "objectives", None)
        if objectives is not None:
            obs.band = objectives.priority
        # Model re-read for the same reason: the director's weighted /
        # header rewrite lands after start(), and the token counters must
        # share label values with the serving-model families. Explicit
        # header targets survive re-resolution (headers win); only the
        # per-model defaults move to the serving name.
        model = getattr(request, "target_model", obs.model)
        if model != obs.model:
            obs.model = model
            obs.slo_ttft_ms, obs.slo_tpot_ms = self.resolve_targets(
                model, getattr(request, "headers", None) or {})
        if endpoint is not None:
            served = endpoint.metadata.address_port
            if obs.endpoint and obs.endpoint != served:
                # Pre-stream failover walks the ranked candidate list
                # WITHOUT re-running PreRequest (only a full reschedule
                # does), so the stamped prediction/role belong to the
                # rank-1 candidate. Charging them to the endpoint that
                # actually served would inflate its calibration MAE exactly
                # during failover incidents — drop them instead.
                obs.predicted_ttft_ms = None
                obs.predicted_tpot_ms = None
                obs.role = ""
            obs.endpoint = served
            # The predicted-latency producer may already have stamped the
            # role via its configurable endpointRoleLabel — don't clobber it
            # with the default-label lookup.
            if not obs.role:
                role = endpoint.metadata.labels.get(ROLE_LABEL)
                if role:
                    obs.role = role

        e2e_ms = (now - obs.t_start) * 1e3
        tokens = int((usage or {}).get("completion_tokens") or 0)
        actual_ttft_ms: float | None = None
        actual_tpot_ms: float | None = None
        if obs.first_token_at is not None:
            actual_ttft_ms = (obs.first_token_at - obs.t_start) * 1e3
            if tokens > 1 and obs.last_token_at is not None:
                actual_tpot_ms = ((obs.last_token_at - obs.first_token_at)
                                  * 1e3 / (tokens - 1))
        elif status < 400 and reason is None and obs.abort_reason is None:
            # Non-streaming completion: e2e IS the first (and only) byte —
            # record e2e-as-TTFT and a whole-response TPOT so the ledger
            # isn't stream-only.
            actual_ttft_ms = e2e_ms
            if tokens > 0:
                actual_tpot_ms = e2e_ms / tokens

        # Verdict: errors/aborts are slo_met=false with a reason — leaving
        # the field absent would overcount attainment ratios.
        slo_defined = obs.slo_ttft_ms > 0 or obs.slo_tpot_ms > 0
        if reason is None and obs.abort_reason is not None:
            reason = obs.abort_reason
        if reason is None and status >= 400:
            reason = f"http-{status}"
        if shed:
            # Overload shed (router/overload.py): the request was refused
            # BEFORE capacity was spent — a deliberate control action, not
            # an SLO miss and not a serving error. Distinct verdict so
            # attainment/goodput stay honest under admission control.
            met, verdict = False, "shed"
            reason = reason or "shed-at-admission"
        elif reason is not None:
            met, verdict = False, "error"
        else:
            met = True
            if obs.slo_ttft_ms > 0 and actual_ttft_ms is not None \
                    and actual_ttft_ms > obs.slo_ttft_ms:
                met = False
                reason = (f"ttft {actual_ttft_ms:.1f}ms > "
                          f"slo {obs.slo_ttft_ms:.0f}ms")
            if met and obs.slo_tpot_ms > 0 and actual_tpot_ms is not None \
                    and actual_tpot_ms > obs.slo_tpot_ms:
                met = False
                reason = (f"tpot {actual_tpot_ms:.2f}ms > "
                          f"slo {obs.slo_tpot_ms:.0f}ms")
            verdict = "met" if met else "missed"
        SLO_REQUESTS_TOTAL.labels(verdict).inc()
        if obs.streamed:
            # Once a request, here where it closes: the relay's per-chunk
            # path (on_chunk) pays nothing for it.
            STREAM_GAP_MAX_SECONDS.observe(obs.gap_max_ms / 1e3)
        if tokens:
            OUTPUT_TOKENS_TOTAL.labels(obs.model).inc(tokens)
            if met:
                GOODPUT_TOKENS_TOTAL.labels(obs.model).inc(tokens)
        # Token-mix counters for the timeline (prompt tokens ≈ prefill
        # work, completion tokens ≈ decode work; per serving role so a
        # disagg pool's P:D split is readable as counter deltas).
        prompt_tokens = int((usage or {}).get("prompt_tokens") or 0)
        if prompt_tokens or tokens:
            self.prompt_tokens_total += prompt_tokens
            role_key = obs.role or "default"
            p, c = self.tokens_by_role.get(role_key, (0, 0))
            self.tokens_by_role[role_key] = (p + prompt_tokens, c + tokens)

        # Predictor calibration: signed error feeds the rollup (bias), the
        # absolute error feeds the histogram family. Only meaningful when
        # the prediction targeted the endpoint that actually served (the
        # PreRequest hook re-stamps on failover reschedules), and only when
        # actual and predicted measure the same quantity:
        # - the TTFT ridge is dispatch-relative (predicted_latency's
        #   rc.start is set post-admission), so the flow-control queue wait
        #   inside the client-observed TTFT is subtracted — otherwise the
        #   MAE under load reports queue time, not model error;
        # - the TPOT ridge trains exclusively on streamed inter-token
        #   cadence, so the non-streamed whole-response average (which
        #   folds in prefill) must not feed kind=tpot.
        # The SLO verdict above deliberately stays client-observed.
        role_label = obs.role or "default"
        ttft_signed = tpot_signed = None
        if obs.predicted_ttft_ms is not None and actual_ttft_ms is not None:
            ttft_signed = ((actual_ttft_ms - obs.queue_ms)
                           - obs.predicted_ttft_ms)
            PREDICTOR_ERROR_MS.labels("ttft", role_label).observe(
                abs(ttft_signed))
        if obs.predicted_tpot_ms is not None and actual_tpot_ms is not None \
                and obs.streamed:
            tpot_signed = actual_tpot_ms - obs.predicted_tpot_ms
            PREDICTOR_ERROR_MS.labels("tpot", role_label).observe(
                abs(tpot_signed))

        # Workload class: which pool role's capacity this request mostly
        # consumed — prompt-dominant requests are prefill-pool work,
        # completion-dominant ones decode-pool work (the rebalance
        # controller's per-role attainment input; see by_workload above).
        # Requests with no token evidence (errors, sheds) file under
        # decode: they cannot claim prefill starvation.
        workload = "prefill" if prompt_tokens > tokens else "decode"

        # Rollup.
        for agg in (self._totals,
                    self._endpoint_agg(obs.endpoint or "(unrouted)"),
                    self._agg(self._by_band, obs.band),
                    self._agg(self.by_workload, workload)):
            agg.requests += 1
            if shed:
                agg.shed += 1
            if met:
                agg.slo_met += 1
            agg.output_tokens += tokens
            if met:
                agg.goodput_tokens += tokens
            if ttft_signed is not None:
                agg.ttft_err.add(ttft_signed)
            if tpot_signed is not None:
                agg.tpot_err.add(tpot_signed)
        if shed and reason:
            key = reason.split(" ")[0]  # bounded cardinality: drop numbers
            self._shed_reasons[key] = self._shed_reasons.get(key, 0) + 1
        elif not met and reason:
            key = reason.split(" ")[0]  # bounded cardinality: drop numbers
            self._miss_reasons[key] = self._miss_reasons.get(key, 0) + 1
        if obs.endpoint:
            ep_agg = self._by_endpoint[obs.endpoint]
            served = ep_agg.requests - ep_agg.shed
            if served > 0:
                SLO_ATTAINMENT.labels(obs.endpoint).set(
                    ep_agg.slo_met / served)

        # Stamp the outcome block into the decision record so
        # /debug/decisions/<id> shows predicted vs actual vs SLO.
        rec = getattr(request, "decision", None)
        if rec is not None and hasattr(rec, "record_outcome"):
            actual: dict[str, Any] = {
                "e2e_ms": round(e2e_ms, 3),
                "queue_ms": round(obs.queue_ms, 3),
                "tokens": tokens,
            }
            if actual_ttft_ms is not None:
                actual["ttft_ms"] = round(actual_ttft_ms, 3)
            if actual_tpot_ms is not None:
                actual["tpot_ms"] = round(actual_tpot_ms, 3)
            if obs.streamed:
                actual["gap_max_ms"] = round(obs.gap_max_ms, 3)
                if obs.token_events > 1:
                    actual["gap_mean_ms"] = round(
                        obs.gap_sum_ms / (obs.token_events - 1), 3)
                actual["gap_buckets_ms"] = dict(zip(
                    [f"<{b:g}" for b in GAP_BUCKET_BOUNDS_MS] + ["inf"],
                    obs.gap_buckets))
            block: dict[str, Any] = {
                "predicted": {
                    "ttft_ms": (round(obs.predicted_ttft_ms, 3)
                                if obs.predicted_ttft_ms is not None else None),
                    "tpot_ms": (round(obs.predicted_tpot_ms, 3)
                                if obs.predicted_tpot_ms is not None else None),
                },
                "actual": actual,
                "slo": {"ttft_ms": obs.slo_ttft_ms,
                        "tpot_ms": obs.slo_tpot_ms,
                        "defined": slo_defined},
                "slo_met": met,
                # The ledger's verdict enum (met | missed | error | shed),
                # spelled out so /debug/decisions list filters don't have
                # to re-derive it from slo_met/reason/shed.
                "verdict": verdict,
                "streamed": obs.streamed,
            }
            if shed:
                block["shed"] = True
            if reason:
                block["reason"] = reason
            if transfer:
                block["transfer"] = transfer
            rec.record_outcome(block)

    @staticmethod
    def _agg(table: dict, key) -> _Agg:
        agg = table.get(key)
        if agg is None:
            agg = table[key] = _Agg()
        return agg

    def _endpoint_agg(self, key: str) -> _Agg:
        table = self._by_endpoint
        agg = table.get(key)
        if agg is not None:
            table.move_to_end(key)
            return agg
        if len(table) >= self.MAX_ENDPOINTS:
            evicted, _ = table.popitem(last=False)
            try:
                SLO_ATTAINMENT.remove(evicted)
            except KeyError:
                pass
        agg = table[key] = _Agg()
        return agg

    # ---- render ---------------------------------------------------------

    def snapshot(self) -> dict[str, Any]:
        """The /debug/slo payload: cumulative attainment/goodput rollup with
        predictor calibration, per endpoint and per priority band."""
        t = self._totals
        doc: dict[str, Any] = {
            "enabled": self.cfg.enabled,
            "since_unix": self._start_unix,
            "window_s": round(time.time() - self._start_unix, 1),
            "totals": t.render(),
            "endpoints": {ep: a.render()
                          for ep, a in sorted(self._by_endpoint.items())},
            "bands": {str(b): a.render(predictor=False)
                      for b, a in sorted(self._by_band.items())},
            # Prefill-heavy vs decode-heavy attainment (the rebalance
            # controller's starvation signal — see by_workload).
            "workloads": {w: a.render(predictor=False)
                          for w, a in sorted(self.by_workload.items())},
            "miss_reasons": dict(sorted(self._miss_reasons.items())),
            "shed_reasons": dict(sorted(self._shed_reasons.items())),
        }
        if t.output_tokens:
            doc["totals"]["goodput_ratio"] = round(
                t.goodput_tokens / t.output_tokens, 4)
        return doc


def finite_float_or_none(v: str | None) -> float | None:
    """The one parser for float telemetry/SLO headers (gateway KV-transfer
    landing and the sidecar relay share it): None for absent, garbage, or
    non-finite input — 'nan' would dodge every <=0/>0 guard, propagate
    through EWMAs (0.8·NaN + 0.2·x stays NaN) and histogram sums forever,
    and serialize as literal NaN in the JSON debug payloads; 'inf' would
    mint an always-met SLO."""
    if not v:
        return None
    try:
        f = float(v)
    except ValueError:
        return None
    return f if math.isfinite(f) else None


def parse_slo_header_ms(headers: dict[str, str], name: str) -> float:
    """SLO header contract (shared with the predicted-latency producer and
    the latency-slo admitter): float ms, absent/blank/garbage/non-finite →
    0 = no SLO on that axis (configured defaults then apply)."""
    v = finite_float_or_none(headers.get(name))
    return v if v is not None else 0.0
