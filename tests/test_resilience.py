"""Resilient data plane: retry/failover, circuit breaking, deadlines, chaos.

Unit tier: the resilience primitives (token-bucket retry budget, breaker
state machine, deadline arithmetic, deterministic fault decisions). E2E
tier: hermetic gateway/sidecar/engine stacks with the engine-side chaos
shim injecting resets, 503s, and mid-stream stalls — every client-visible
guarantee (zero 502s under failover, bounded retry storms, breaker-open
visibility in /metrics, half-open recovery, drain-retry with zero errors)
is asserted over real HTTP. Chaos decisions are a stable hash of
(CHAOS_SEED, kind, request id), so `make test-chaos` reruns are
bit-identical.
"""

import asyncio
import os

import httpx
import pytest

from llm_d_inference_scheduler_tpu.engine import EngineConfig
from llm_d_inference_scheduler_tpu.engine.server import EngineServer
from llm_d_inference_scheduler_tpu.router.gateway import build_gateway
from llm_d_inference_scheduler_tpu.router.resilience import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    BreakerRegistry,
    CircuitBreaker,
    Deadline,
    FaultInjector,
    ResilienceConfig,
    RetryBudget,
)
from llm_d_inference_scheduler_tpu.router.sidecar import Sidecar, SidecarConfig

CHAOS_SEED = int(os.environ.get("CHAOS_SEED", "0"))


def run(coro):
    return asyncio.run(coro)


# ---- unit tier -----------------------------------------------------------


def test_retry_budget_token_bucket():
    clock = [0.0]
    b = RetryBudget(ratio=0.5, min_per_sec=1.0, burst=2.0,
                    clock=lambda: clock[0])
    # Starts full; retries drain it.
    assert b.try_spend()
    assert b.try_spend()
    assert not b.try_spend()
    # Deposits (one per admitted request) refill by ratio.
    b.deposit()
    b.deposit()
    assert b.try_spend()
    assert not b.try_spend()
    # Time trickle refills too, capped at burst.
    clock[0] += 10.0
    assert b.tokens == pytest.approx(2.0)
    assert b.try_spend() and b.try_spend() and not b.try_spend()


def test_circuit_breaker_state_machine():
    clock = [0.0]
    cb = CircuitBreaker(failure_threshold=2, open_s=5.0,
                        half_open_successes=2, clock=lambda: clock[0])
    assert cb.state == CLOSED and cb.allow()
    cb.record_failure()
    # A success resets the consecutive-failure count.
    cb.record_success()
    cb.record_failure()
    assert cb.state == CLOSED
    cb.record_failure()
    assert cb.state == OPEN and not cb.allow() and not cb.would_allow()
    # Open window elapses -> half-open admits exactly ONE in-flight probe.
    clock[0] += 5.0
    assert cb.allow()
    assert cb.state == HALF_OPEN
    assert not cb.allow()  # second concurrent probe rejected
    cb.record_success()
    assert cb.state == HALF_OPEN  # needs two successes to close
    assert cb.allow()
    cb.record_success()
    assert cb.state == CLOSED
    # Probe failure reopens immediately.
    cb.record_failure()
    cb.record_failure()
    clock[0] += 5.0
    assert cb.allow() and cb.state == HALF_OPEN
    cb.record_failure()
    assert cb.state == OPEN


def test_breaker_probe_slot_released_on_abandoned_attempt():
    """An allow()ed attempt that never reaches an outcome (retry-budget
    fast-fail, caller cancelled, non-retryable 5xx path) must release the
    half-open probe slot — otherwise the endpoint is unprobeable forever."""
    clock = [0.0]
    cb = CircuitBreaker(failure_threshold=1, open_s=1.0,
                        clock=lambda: clock[0])
    cb.record_failure()
    clock[0] += 1.0
    assert cb.allow()          # half-open: probe slot claimed
    assert not cb.allow()
    cb.release()               # attempt abandoned with no outcome
    assert cb.allow()          # slot free again
    cb.record_success()
    assert cb.state == CLOSED
    # release() outside half-open is a no-op.
    cb.release()
    assert cb.state == CLOSED and cb.allow()


def test_breaker_registry_gauge_and_removal():
    from prometheus_client import generate_latest

    from llm_d_inference_scheduler_tpu.router.metrics import REGISTRY

    clock = [0.0]
    reg = BreakerRegistry(failure_threshold=1, open_s=60.0,
                          clock=lambda: clock[0])
    key = "10.9.9.9:1234"  # unique: the router REGISTRY is process-global
    assert reg.allow(key)
    reg.record_failure(key)
    assert reg.state(key) == OPEN and not reg.allow(key)
    text = generate_latest(REGISTRY).decode()
    assert ('router_endpoint_circuit_breaker_state{endpoint="%s"} 2.0'
            % key) in text
    reg.remove(key)
    # The state gauge drops the departed endpoint's label (the transitions
    # counter keeps its history — counters are monotonic by contract).
    gauge_lines = [l for l in generate_latest(REGISTRY).decode().splitlines()
                   if l.startswith("router_endpoint_circuit_breaker_state{")]
    assert not any(key in l for l in gauge_lines)
    assert reg.state(key) == CLOSED  # unknown endpoints default closed


def test_deadline_parse_decrement_and_header():
    clock = [100.0]
    d = Deadline.from_headers({"x-request-timeout": "2.5"},
                              clock=lambda: clock[0])
    assert d is not None and not d.expired
    assert d.remaining_s == pytest.approx(2.5)
    clock[0] += 1.0
    assert d.header_value() == "1.500"
    clock[0] += 2.0
    assert d.expired and d.remaining_s == 0.0
    # Absent header + no default -> no deadline; default applies when set.
    assert Deadline.from_headers({}) is None
    d = Deadline.from_headers({}, default_s=3.0, clock=lambda: clock[0])
    assert d is not None and d.remaining_s == pytest.approx(3.0)
    # A forwarded zero budget is an already-expired deadline, not "none".
    d = Deadline.from_headers({"x-request-timeout": "0.000"},
                              clock=lambda: clock[0])
    assert d is not None and d.expired
    # Garbage header falls back to the default.
    assert Deadline.from_headers({"x-request-timeout": "soon"}) is None
    # Client asks are capped.
    d = Deadline.from_headers({"x-request-timeout": "9999"}, max_s=10.0,
                              clock=lambda: clock[0])
    assert d.remaining_s <= 10.0


def test_fault_injector_spec_and_determinism():
    inj = FaultInjector.from_spec("reset:50,delay:100:250", seed=CHAOS_SEED)
    assert [r.kind for r in inj.rules] == ["reset", "delay"]
    assert inj.rules[1].arg == 250.0
    # Same request id -> same decision, every time.
    decisions = {rid: (inj.decide(rid) or type("n", (), {"kind": None})).kind
                 for rid in (f"req-{i}" for i in range(64))}
    for rid, kind in decisions.items():
        got = inj.decide(rid)
        assert (got.kind if got else None) == kind
    # pct 50 + a 100% fallthrough rule: both kinds appear across 64 ids.
    assert set(decisions.values()) == {"reset", "delay"}
    # Gating: disabled injector never fires; empty spec means no injector.
    inj.enabled = False
    assert inj.decide("req-0") is None
    assert FaultInjector.from_spec("") is None
    assert FaultInjector.from_spec(None) is None
    with pytest.raises(ValueError):
        FaultInjector.from_spec("meteor:100")


# ---- e2e tier ------------------------------------------------------------


def _metric_value(text: str, needle: str) -> float:
    for line in text.splitlines():
        if line.startswith(needle + " ") or (
                line.startswith(needle) and line[len(needle)] in "{ "):
            return float(line.rsplit(" ", 1)[-1])
    return 0.0


async def _sim(port, **kw):
    kw.setdefault("backend", "sim")
    kw.setdefault("model", "tiny")
    kw.setdefault("max_batch", 8)
    kw.setdefault("sim_decode_ms_per_token", 1.0)
    s = EngineServer(EngineConfig(port=port, **kw))
    await s.start()
    return s


def test_gateway_retries_draining_sidecar_zero_client_errors():
    """Drain lifecycle end-to-end (PR 1's retryable 503s finally have a
    consumer): a draining sidecar's `x-removal-reason: sidecar-draining`
    503 is retried by the gateway onto the healthy endpoint with ZERO
    client-visible errors."""
    GW, SCA, SCB, EA, EB = 18740, 18741, 18742, 18743, 18744
    # Low breaker threshold: the draining sidecar's breaker OPENS mid-run,
    # which also regression-tests the reschedule exclusion set — an open
    # endpoint the scheduler re-picks must not strand the request while a
    # healthy endpoint exists.
    cfg = f"""
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {SCA}}}
    - {{address: 127.0.0.1, port: {SCB}}}
resilience:
  breakerFailureThreshold: 3
  breakerOpenS: 60
"""

    async def body():
        ea, eb = await _sim(EA), await _sim(EB)
        sca = Sidecar(SidecarConfig(port=SCA, decoder_url=f"http://127.0.0.1:{EA}"))
        scb = Sidecar(SidecarConfig(port=SCB, decoder_url=f"http://127.0.0.1:{EB}"))
        await sca.start()
        await scb.start()
        gw = build_gateway(cfg, port=GW, poll_interval=0.02)
        await gw.start()
        try:
            await sca.begin_drain()  # A now 503s every generate request
            async with httpx.AsyncClient(timeout=30) as c:
                served = []
                for i in range(16):
                    r = await c.post(f"http://127.0.0.1:{GW}/v1/completions",
                                     json={"model": "tiny", "prompt": "hi",
                                           "max_tokens": 2})
                    assert r.status_code == 200, (i, r.status_code, r.text)
                    served.append(
                        r.headers["x-gateway-destination-endpoint-served"])
                # Every request landed on the healthy sidecar.
                assert set(served) == {f"127.0.0.1:{SCB}"}
                m = (await c.get(f"http://127.0.0.1:{GW}/metrics")).text
                assert _metric_value(
                    m, 'router_retries_total{kind="status"}') > 0
        finally:
            await gw.stop()
            await sca.stop()
            await scb.stop()
            await ea.stop()
            await eb.stop()

    run(body())


def test_chaos_failover_breaker_opens_and_recovers():
    """The acceptance scenario: chaos kills one decode endpoint mid-run
    (connection reset on every request). All traffic still completes via
    failover (zero client-visible 502s), the ejected endpoint shows
    breaker-open state in /metrics, and after the open window a half-open
    probe recovers it. The open window passes on a clock the test moves
    (the breakers' injectable one): on the wall clock twenty requests beside
    five compiling workers outlast any window short enough to sleep through,
    and the breaker is then found half-open where the test reads open."""
    GW, EA, EB = 18750, 18751, 18752
    OPEN_S = 0.5
    cfg = f"""
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {EA}}}
    - {{address: 127.0.0.1, port: {EB}}}
plugins:
  - {{type: circuit-breaker-filter}}
  - {{type: queue-scorer}}
schedulingProfiles:
  - name: default
    plugins:
      - {{pluginRef: circuit-breaker-filter}}
      - {{pluginRef: queue-scorer}}
resilience:
  maxAttempts: 3
  breakerFailureThreshold: 2
  breakerOpenS: {OPEN_S}
"""

    async def body():
        ea = await _sim(EA, chaos="reset:100", chaos_seed=CHAOS_SEED)
        eb = await _sim(EB)
        gw = build_gateway(cfg, port=GW, poll_interval=0.02)
        now = [1000.0]
        gw.datastore.breakers._clock = lambda: now[0]   # (none exists yet)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=30) as c:
                statuses = []
                for i in range(20):
                    r = await c.post(f"http://127.0.0.1:{GW}/v1/completions",
                                     json={"model": "tiny",
                                           "prompt": f"p{i}", "max_tokens": 2},
                                     headers={"x-request-id": f"chaos-{i}"})
                    statuses.append(r.status_code)
                # >= 99% success; with failover available there are ZERO
                # client-visible 502s.
                assert statuses.count(200) == len(statuses), statuses
                m = (await c.get(f"http://127.0.0.1:{GW}/metrics")).text
                assert _metric_value(
                    m, 'router_endpoint_circuit_breaker_state'
                       '{endpoint="127.0.0.1:%d"}' % EA) == 2.0  # open
                assert _metric_value(
                    m, 'router_retries_total{kind="connect"}') > 0

                # Inside the window the breaker never left open: one
                # opening, nothing else.
                transitions = 'router_circuit_breaker_transitions_total' \
                    '{endpoint="127.0.0.1:%d",to_state="%%s"}' % EA
                assert [_metric_value(m, transitions % to) for to in
                        ("open", "half-open", "closed")] == [1, 0, 0]

                # Heal the endpoint; after the open window a half-open probe
                # closes the breaker and traffic returns to A.
                ea.chaos.enabled = False
                now[0] += OPEN_S
                served = set()
                for i in range(30):
                    r = await c.post(f"http://127.0.0.1:{GW}/v1/completions",
                                     json={"model": "tiny",
                                           "prompt": f"r{i}", "max_tokens": 1})
                    assert r.status_code == 200
                    served.add(
                        r.headers["x-gateway-destination-endpoint-served"])
                    if f"127.0.0.1:{EA}" in served:
                        break
                assert f"127.0.0.1:{EA}" in served
                m = (await c.get(f"http://127.0.0.1:{GW}/metrics")).text
                assert _metric_value(
                    m, 'router_endpoint_circuit_breaker_state'
                       '{endpoint="127.0.0.1:%d"}' % EA) == 0.0  # closed
                assert [_metric_value(m, transitions % to) for to in
                        ("open", "half-open", "closed")] == [1, 1, 1]
        finally:
            await gw.stop()
            await ea.stop()
            await eb.stop()

    run(body())


def test_chaos_retry_budget_bounds_storm():
    """With every endpoint failing and the budget drained, excess failures
    return immediately with x-removal-reason instead of amplifying load:
    total upstream attempts == requests + burst, exactly. (A failed
    endpoint joins the exclusion set, so retries are failovers — two
    chaotic endpoints give each request one retry opportunity.)"""
    GW, EA, EB = 18760, 18761, 18762
    cfg = f"""
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {EA}}}
    - {{address: 127.0.0.1, port: {EB}}}
resilience:
  maxAttempts: 4
  retryBudgetRatio: 0
  retryBudgetMinPerSec: 0
  retryBudgetBurst: 2
  breakerFailureThreshold: 1000
"""

    async def body():
        ea = await _sim(EA, chaos="http503:100", chaos_seed=CHAOS_SEED)
        eb = await _sim(EB, chaos="http503:100", chaos_seed=CHAOS_SEED)
        gw = build_gateway(cfg, port=GW, poll_interval=0.02)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=30) as c:
                budget_marked = 0
                for i in range(6):
                    r = await c.post(f"http://127.0.0.1:{GW}/v1/completions",
                                     json={"model": "tiny", "prompt": "x",
                                           "max_tokens": 1})
                    assert r.status_code == 503
                    assert r.headers["x-removal-reason"] == "chaos-injected"
                    budget_marked += (r.json().get("retry")
                                      == "retry-budget-exhausted")
                # Once the bucket drains, fast-fails are marked as such.
                assert budget_marked >= 4
                # 6 first attempts + exactly `burst` (2) failover retries
                # hit the engines; the rest failed fast on the empty bucket.
                triggered = (ea.chaos.triggered["http503"]
                             + eb.chaos.triggered["http503"])
                assert triggered == 8, triggered
                m = (await c.get(f"http://127.0.0.1:{GW}/metrics")).text
                assert _metric_value(
                    m, "router_retry_budget_exhausted_total") >= 4
        finally:
            await gw.stop()
            await ea.stop()
            await eb.stop()

    run(body())


def test_chaos_sustained_overload_sheds_at_admission_only():
    """Sustained-overload invariant (router/overload.py): engine delay chaos
    plus >1x offered load, overload control on — requests that were admitted
    and began streaming are NEVER killed by shedding. Sheds happen at
    admission or in-queue only: every non-200 is a 429 carrying a finite
    Retry-After (the overload contract), every 200 stream runs to [DONE].
    Deterministic under the fixed CHAOS_SEED `make test-chaos` pins."""
    GW, EA = 18830, 18831
    cfg = f"""
featureGates: {{flowControl: true}}
overload: {{enabled: true}}
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {EA}}}
plugins:
  - {{type: predicted-latency-producer}}
  - {{type: queue-scorer}}
schedulingProfiles:
  - name: default
    plugins:
      - {{pluginRef: queue-scorer}}
"""

    async def body():
        # Every request eats a 40ms injected delay on a 2-slot engine: the
        # pool saturates as soon as more than a handful arrive together.
        ea = await _sim(EA, chaos="delay:100:40", chaos_seed=CHAOS_SEED,
                        max_batch=2, sim_decode_ms_per_token=2.0)
        gw = build_gateway(cfg, port=GW, poll_interval=0.02)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=60) as c:
                url = f"http://127.0.0.1:{GW}/v1/completions"

                # Train the ridge with concurrency variation so the
                # in-flight feature carries signal into the burst.
                for wave in range(3):
                    rs = await asyncio.gather(*[
                        c.post(url, json={"model": "tiny",
                                          "prompt": f"w{wave}-{i}",
                                          "max_tokens": 2})
                        for i in range(4)])
                    assert all(r.status_code == 200 for r in rs)

                async def one(i: int) -> tuple[int, bool, bool]:
                    """(status, stream_completed, aborted_mid_stream)."""
                    try:
                        async with c.stream(
                                "POST", url,
                                json={"model": "tiny", "prompt": f"o{i}",
                                      "max_tokens": 16, "stream": True},
                                headers={"x-request-id": f"ovl-{i}",
                                         "x-slo-ttft-ms": "250"}) as r:
                            if r.status_code != 200:
                                # Shed path: 429 + finite Retry-After.
                                assert r.status_code == 429, r.status_code
                                ra = r.headers.get("retry-after")
                                assert ra is not None and int(ra) >= 1
                                return r.status_code, False, False
                            saw_done = False
                            async for line in r.aiter_lines():
                                if line.startswith("data: [DONE]"):
                                    saw_done = True
                            return 200, saw_done, not saw_done
                    except (httpx.HTTPError, ConnectionError):
                        return -1, False, True

                # >1x offered load: 48 concurrent streams against 2 slots.
                results = await asyncio.gather(*[one(i) for i in range(48)])
                served = [r for r in results if r[0] == 200]
                shed = [r for r in results if r[0] == 429]
                aborted = [r for r in results if r[2]]
                # THE invariant: nothing admitted-and-streaming was killed.
                assert not aborted, aborted
                assert all(done for _, done, _ in served)
                assert len(served) + len(shed) == len(results)
                # The overload ramp actually engaged both mechanisms' range:
                # some traffic served, some shed at admission/in-queue.
                assert served, results
                assert shed, results
                slo = (await c.get(f"http://127.0.0.1:{GW}/debug/slo")).json()
                assert slo["totals"]["shed"] == len(shed)
                # Every shed is explained: pick one and check the block.
                recs = (await c.get(f"http://127.0.0.1:{GW}/debug/decisions"
                                    "?n=100")).json()["decisions"]
                blocks = [r["shed"] for r in recs if r.get("shed")]
                assert blocks and all("slo_ttft_ms" in b for b in blocks)
        finally:
            await gw.stop()
            await ea.stop()

    run(body())


def test_chaos_pd_prefiller_failover():
    """Chaos kills one prefiller: the sidecar walks the router's ranked
    candidate list (multi-candidate x-prefiller-host-port) to the healthy
    prefiller; the client sees 200, and the failover is counted."""
    GW, SC, DEC, PA, PB = 18770, 18771, 18772, 18773, 18774
    cfg = f"""
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {SC}, labels: {{llm-d.ai/role: decode}}}}
    - {{address: 127.0.0.1, port: {PA}, labels: {{llm-d.ai/role: prefill}}}}
    - {{address: 127.0.0.1, port: {PB}, labels: {{llm-d.ai/role: prefill}}}}
plugins:
  - {{type: decode-filter}}
  - {{type: prefill-filter}}
  - {{type: queue-scorer}}
  - type: max-score-picker
    parameters: {{maxNumOfEndpoints: 2}}
  - type: disagg-profile-handler
    parameters:
      pdDecider: always-disagg-pd-decider
schedulingProfiles:
  - name: decode
    plugins:
      - {{pluginRef: decode-filter}}
      - {{pluginRef: queue-scorer}}
  - name: prefill
    plugins:
      - {{pluginRef: prefill-filter}}
      - {{pluginRef: queue-scorer}}
      - {{pluginRef: max-score-picker}}
"""

    async def body():
        dec = await _sim(DEC)
        pa = await _sim(PA, chaos="reset:100", chaos_seed=CHAOS_SEED)
        pb = await _sim(PB)
        sc = Sidecar(SidecarConfig(port=SC,
                                   decoder_url=f"http://127.0.0.1:{DEC}",
                                   prefill_timeout_s=5.0))
        await sc.start()
        gw = build_gateway(cfg, port=GW, poll_interval=0.02)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=60) as c:
                ok = 0
                for i in range(6):
                    r = await c.post(f"http://127.0.0.1:{GW}/v1/completions",
                                     json={"model": "tiny",
                                           "prompt": "failover " * 8,
                                           "max_tokens": 2})
                    ok += r.status_code == 200
                assert ok == 6
                # The healthy prefiller really prefilled (pb counters grew)
                # whenever chaos reset the first candidate.
                mb = (await c.get(f"http://127.0.0.1:{PB}/metrics")).text
                assert _metric_value(mb, "jetstream:prompt_tokens_total") > 0
                ms = (await c.get(f"http://127.0.0.1:{SC}/metrics")).text
                assert _metric_value(
                    ms, "sidecar_prefill_failovers_total") > 0
        finally:
            await gw.stop()
            await sc.stop()
            await pa.stop()
            await pb.stop()
            await dec.stop()

    run(body())


def test_chaos_midstream_stall_counted_not_500():
    """Satellite 1: a mid-stream upstream disconnect after headers are on
    the wire is closed cleanly toward the client (truncated SSE, no 500/
    traceback) and counted in router_upstream_stream_aborted_total."""
    GW, EA = 18780, 18781
    cfg = f"""
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {EA}}}
"""

    async def body():
        ea = await _sim(EA, chaos="stall:100", chaos_seed=CHAOS_SEED)
        gw = build_gateway(cfg, port=GW, poll_interval=0.02)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=30) as c:
                chunks = []
                async with c.stream(
                        "POST", f"http://127.0.0.1:{GW}/v1/completions",
                        json={"model": "tiny", "prompt": "x", "stream": True,
                              "max_tokens": 5}) as r:
                    assert r.status_code == 200  # stream started
                    try:
                        async for chunk in r.aiter_bytes():
                            chunks.append(chunk)
                    except httpx.HTTPError:
                        pass  # truncated transfer is acceptable client-side
                assert b"chaos" in b"".join(chunks)
                m = (await c.get(f"http://127.0.0.1:{GW}/metrics")).text
                assert _metric_value(
                    m, "router_upstream_stream_aborted_total") >= 1
        finally:
            await gw.stop()
            await ea.stop()

    run(body())


def test_chaos_sidecar_stream_abort_guard():
    """Satellite 2: the sidecar's decode relay survives a mid-stream engine
    stall — clean truncation plus sidecar_upstream_stream_aborted_total."""
    SC, EA = 18790, 18791

    async def body():
        ea = await _sim(EA, chaos="stall:100", chaos_seed=CHAOS_SEED)
        sc = Sidecar(SidecarConfig(port=SC,
                                   decoder_url=f"http://127.0.0.1:{EA}"))
        await sc.start()
        try:
            async with httpx.AsyncClient(timeout=30) as c:
                async with c.stream(
                        "POST", f"http://127.0.0.1:{SC}/v1/completions",
                        json={"prompt": "x", "stream": True,
                              "max_tokens": 5}) as r:
                    assert r.status_code == 200
                    try:
                        async for _ in r.aiter_bytes():
                            pass
                    except httpx.HTTPError:
                        pass
                m = (await c.get(f"http://127.0.0.1:{SC}/metrics")).text
                assert _metric_value(
                    m, "sidecar_upstream_stream_aborted_total") >= 1
        finally:
            await sc.stop()
            await ea.stop()

    run(body())


def test_deadline_end_to_end():
    """x-request-timeout bounds the whole pipeline: an expired budget 504s
    at the gateway without dispatching; a budget that expires mid-serve is
    enforced engine-side (504 relayed, wall-clock bounded)."""
    import time as _time

    GW, EA = 18800, 18801
    cfg = f"""
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {EA}}}
"""

    async def body():
        # 200 ms/token * 100 tokens >> the 1 s budget.
        ea = await _sim(EA, sim_decode_ms_per_token=200.0)
        gw = build_gateway(cfg, port=GW, poll_interval=0.02)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=30) as c:
                r = await c.post(f"http://127.0.0.1:{GW}/v1/completions",
                                 json={"model": "tiny", "prompt": "x",
                                       "max_tokens": 1},
                                 headers={"x-request-timeout": "0"})
                assert r.status_code == 504
                assert r.headers["x-removal-reason"] == "deadline-exceeded"

                t0 = _time.monotonic()
                r = await c.post(f"http://127.0.0.1:{GW}/v1/completions",
                                 json={"model": "tiny", "prompt": "x",
                                       "max_tokens": 100},
                                 headers={"x-request-timeout": "1.0"})
                assert r.status_code == 504
                assert _time.monotonic() - t0 < 5.0
                m = (await c.get(f"http://127.0.0.1:{GW}/metrics")).text
                assert _metric_value(
                    m, "router_request_deadline_exceeded_total") >= 1
        finally:
            await gw.stop()
            await ea.stop()

    run(body())


def test_sidecar_deadline_inherited_by_prefill_leg():
    """The sidecar prefill leg inherits the REMAINING budget: with a dead
    prefiller and a short deadline, fallback-to-decode happens within the
    budget instead of sitting out the full prefill timeout."""
    import time as _time

    SC, DEC = 18810, 18811

    async def body():
        dec = await _sim(DEC)
        # Prefill timeout configured long (60 s); the deadline must win.
        sc = Sidecar(SidecarConfig(port=SC,
                                   decoder_url=f"http://127.0.0.1:{DEC}",
                                   prefill_timeout_s=60.0))
        await sc.start()
        try:
            async with httpx.AsyncClient(timeout=30) as c:
                t0 = _time.monotonic()
                # 127.0.0.1:9 is closed -> fast refusal is typical, but the
                # per-leg timeout is also clamped to the 2 s budget.
                r = await c.post(
                    f"http://127.0.0.1:{SC}/v1/completions",
                    json={"prompt": "x", "max_tokens": 2},
                    headers={"x-prefiller-host-port": "127.0.0.1:9",
                             "x-request-timeout": "2.0"})
                assert r.status_code == 200  # fell back to local decode
                assert _time.monotonic() - t0 < 5.0
                # An exhausted budget is rejected outright.
                r = await c.post(
                    f"http://127.0.0.1:{SC}/v1/completions",
                    json={"prompt": "x", "max_tokens": 2},
                    headers={"x-request-timeout": "0"})
                assert r.status_code == 504
                m = (await c.get(f"http://127.0.0.1:{SC}/metrics")).text
                assert _metric_value(
                    m, "sidecar_deadline_exceeded_total") >= 1
        finally:
            await sc.stop()
            await dec.stop()

    run(body())


def test_prefiller_candidates_full_list_and_rotation():
    """Satellite 3: the sidecar resolves the FULL ordered candidate list;
    the sampling knob rotates the starting point instead of discarding the
    tail, so failover keeps every candidate reachable."""
    from multidict import CIMultiDict

    class _Req:
        def __init__(self, items):
            self.headers = CIMultiDict(items)

    plain = Sidecar(SidecarConfig())
    r = _Req([("x-prefiller-host-port", "a:1,b:2,c:3")])
    assert plain._prefiller_candidates(r) == ["a:1", "b:2", "c:3"]

    sampling = Sidecar(SidecarConfig(enable_prefiller_sampling=True))
    sampling._prefill_sampler = lambda n: 1
    assert sampling._prefiller_candidates(r) == ["b:2", "c:3", "a:1"]
    assert sampling._pick_prefiller(r) == "b:2"


def test_chaos_pipelined_prefill_503_serial_fallback_zero_errors():
    """Chaos drill (ISSUE 20): every prefill answers 503, the sidecar runs
    in pipelined mode. The pipelined handoff aborts BEFORE the decode leg
    dispatches (first-chunk ack never lands), falls back to the serial
    candidate walk — which also finds the prefiller dead and degrades to
    local decode. The client sees 200 every time; the fallback is counted
    on sidecar_pipeline_fallbacks_total and the request's DecisionRecord
    still carries the full attempt trail."""
    GW, SC, DEC, PRE = 18918, 18919, 18920, 18921
    cfg = f"""
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {SC}, labels: {{llm-d.ai/role: decode}}}}
    - {{address: 127.0.0.1, port: {PRE}, labels: {{llm-d.ai/role: prefill}}}}
plugins:
  - {{type: decode-filter}}
  - {{type: prefill-filter}}
  - {{type: queue-scorer}}
  - type: disagg-profile-handler
    parameters:
      pdDecider: always-disagg-pd-decider
schedulingProfiles:
  - name: decode
    plugins:
      - {{pluginRef: decode-filter}}
      - {{pluginRef: queue-scorer}}
  - name: prefill
    plugins:
      - {{pluginRef: prefill-filter}}
      - {{pluginRef: queue-scorer}}
"""

    async def body():
        dec = await _sim(DEC)
        pre = await _sim(PRE, chaos="http503:100", chaos_seed=CHAOS_SEED)
        sc = Sidecar(SidecarConfig(port=SC,
                                   decoder_url=f"http://127.0.0.1:{DEC}",
                                   prefill_timeout_s=5.0,
                                   pipeline_enabled=True))
        await sc.start()
        gw = build_gateway(cfg, port=GW, poll_interval=0.02)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=60) as c:
                for i in range(4):
                    r = await c.post(
                        f"http://127.0.0.1:{GW}/v1/completions",
                        json={"model": "tiny", "prompt": "drill " * 8,
                              "max_tokens": 2},
                        headers={"x-request-id": f"chaos-pipe-{i}"})
                    assert r.status_code == 200, r.text
                m = (await c.get(f"http://127.0.0.1:{SC}/metrics")).text
                assert _metric_value(
                    m, "sidecar_pipeline_fallbacks_total") >= 4
                # The attempt trail survives: the router's DecisionRecord
                # for a drilled request shows the disagg round that picked
                # the (doomed) prefiller — the fallback is explainable.
                r = await c.get(
                    f"http://127.0.0.1:{GW}/debug/decisions/chaos-pipe-0")
                assert r.status_code == 200
                rec = r.json()
                prof = rec["rounds"][0]["profiles"]
                assert prof["prefill"]["outcome"] == "picked"
                assert prof["decode"]["outcome"] == "picked"
        finally:
            await gw.stop()
            await sc.stop()
            await pre.stop()
            await dec.stop()

    run(body())


def test_chaos_prefiller_killed_mid_chunk_stream_zero_errors():
    """Chaos drill (ISSUE 20): the prefill engine DIES mid-chunk-stream,
    after the decode leg already dispatched against its partial export.
    The decode engine's chunk poll hits connection errors, abandons the
    import, and degrades to local prefill — the client still sees a 200
    with the full completion (zero client-visible errors)."""
    SC, DEC, PRE = 18922, 18923, 18924

    async def body():
        dec = await _sim(DEC)
        # Slow, chunked prefill: 64 tokens at 20 ms/token over 8-token
        # windows -> first chunk staged ~160 ms in, export complete only
        # at ~1.3 s. Killing the server at ~450 ms lands mid-stream.
        pre = await _sim(PRE, role="prefill", prefill_chunk=8,
                         sim_prefill_ms_per_token=20.0)
        sc = Sidecar(SidecarConfig(port=SC,
                                   decoder_url=f"http://127.0.0.1:{DEC}",
                                   prefill_timeout_s=10.0,
                                   pipeline_enabled=True))
        await sc.start()
        try:
            async with httpx.AsyncClient(timeout=60) as c:
                req = asyncio.create_task(c.post(
                    f"http://127.0.0.1:{SC}/v1/completions",
                    json={"prompt": list(range(3, 67)), "max_tokens": 2},
                    headers={"x-prefiller-host-port":
                             f"127.0.0.1:{PRE}"}))
                await asyncio.sleep(0.45)
                await pre.stop()  # mid-stream kill
                r = await req
                assert r.status_code == 200, r.text
                out = r.json()
                assert out["usage"]["completion_tokens"] == 2
                assert out["usage"]["prompt_tokens"] == 64
        finally:
            await sc.stop()
            await dec.stop()

    run(body())
