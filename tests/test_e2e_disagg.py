"""Full P/D disaggregation path: gateway → sidecar → prefill/decode engines.

A prefill pool and a decode pool at CPU-test scale: the disagg profile handler gates a
remote prefill on the decode pod's prefix state, the sidecar runs the 2-phase
tpu-dcn connector, and the decode engine imports the prefilled KV.
"""

import asyncio

import httpx

from llm_d_inference_scheduler_tpu.engine import EngineConfig
from llm_d_inference_scheduler_tpu.engine.server import EngineServer
from llm_d_inference_scheduler_tpu.router.gateway import build_gateway
from llm_d_inference_scheduler_tpu.router.sidecar import Sidecar, SidecarConfig

GW, SC, DEC, PRE = 18360, 18361, 18362, 18363

CFG = f"""
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {SC}, labels: {{llm-d.ai/role: decode}}}}
    - {{address: 127.0.0.1, port: {PRE}, labels: {{llm-d.ai/role: prefill}}}}
plugins:
  - {{type: decode-filter}}
  - {{type: prefill-filter}}
  - {{type: queue-scorer}}
  - {{type: approx-prefix-cache-producer}}
  - {{type: prefix-cache-scorer}}
  - type: disagg-profile-handler
    parameters:
      pdDecider:
        type: prefix-based-pd-decider
        parameters: {{thresholdTokens: 16}}
schedulingProfiles:
  - name: decode
    plugins:
      - {{pluginRef: decode-filter}}
      - {{pluginRef: prefix-cache-scorer, weight: 3}}
      - {{pluginRef: queue-scorer, weight: 2}}
  - name: prefill
    plugins:
      - {{pluginRef: prefill-filter}}
      - {{pluginRef: queue-scorer}}
"""

LONG_PROMPT = "please summarise the following very important document: " * 4
SHORT_PROMPT = "hi"


def _engine(port, role):
    return EngineServer(EngineConfig(backend="tpu", model="tiny", port=port,
                                     max_batch=4, max_model_len=256, role=role))


def test_disagg_path_end_to_end():
    async def body():
        dec = _engine(DEC, "decode")
        pre = _engine(PRE, "prefill")
        await dec.start()
        await pre.start()
        sc = Sidecar(SidecarConfig(port=SC, decoder_url=f"http://127.0.0.1:{DEC}",
                                   ssrf_allowlist=[f"127.0.0.1:{PRE}"]))
        await sc.start()
        gw = build_gateway(CFG, port=GW, poll_interval=0.02)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=120) as c:
                # Monolithic reference answer straight from the decode engine.
                r = await c.post(f"http://127.0.0.1:{DEC}/v1/completions",
                                 json={"prompt": LONG_PROMPT, "max_tokens": 6,
                                       "temperature": 0})
                mono_text = r.json()["choices"][0]["text"]

                pre_prompt_tokens_before = _counter_value(
                    pre, "jetstream:prompt_tokens_total")

                # Through the router: long prompt → P/D split. SLO headers
                # opt the request into a defined-SLO ledger verdict.
                r = await c.post(f"http://127.0.0.1:{GW}/v1/completions",
                                 json={"model": "tiny", "prompt": LONG_PROMPT,
                                       "max_tokens": 6, "temperature": 0},
                                 headers={"x-request-id": "disagg-slo-1",
                                          "x-slo-ttft-ms": "60000"})
                assert r.status_code == 200
                assert r.headers["x-gateway-destination-endpoint-served"] == \
                    f"127.0.0.1:{SC}"
                assert r.json()["choices"][0]["text"] == mono_text

                # The prefill engine really prefilled.
                assert _counter_value(pre, "jetstream:prompt_tokens_total") > \
                    pre_prompt_tokens_before

                # Short prompt below threshold → decode-only (no prefill growth).
                pre_after = _counter_value(pre, "jetstream:prompt_tokens_total")
                r = await c.post(f"http://127.0.0.1:{GW}/v1/completions",
                                 json={"model": "tiny", "prompt": SHORT_PROMPT,
                                       "max_tokens": 2})
                assert r.status_code == 200
                assert _counter_value(pre, "jetstream:prompt_tokens_total") == pre_after

                # Router counted both decision types.
                m = await c.get(f"http://127.0.0.1:{GW}/metrics")
                assert 'disagg_decision_total{decision_type="prefill-decode"}' in m.text
                assert 'disagg_decision_total{decision_type="decode"}' in m.text

                # SLO-ledger outcome block on the decision record: predicted
                # vs actual vs SLO plus the per-pair transfer row (the P/D
                # request's KV pull was measured by the decode engine and
                # relayed sidecar → gateway).
                r = await c.get(
                    f"http://127.0.0.1:{GW}/debug/decisions/disagg-slo-1")
                out = r.json()["outcome"]
                assert out["slo_met"] is True
                assert out["slo"] == {"ttft_ms": 60000.0, "tpot_ms": 0.0,
                                      "defined": True}
                assert out["actual"]["ttft_ms"] > 0
                assert out["actual"]["tokens"] == 6
                tr = out["transfer"]
                assert tr["prefill"] == f"127.0.0.1:{PRE}"
                assert tr["decode"] == f"127.0.0.1:{SC}"
                assert tr["pull_ms"] > 0 and tr["bytes"] > 0
                assert tr["prefill_ms"] > 0

                # Fleet rollups are non-empty: /debug/slo attainment + the
                # /debug/transfers per-pair EWMA row.
                slo = (await c.get(f"http://127.0.0.1:{GW}/debug/slo")).json()
                assert slo["totals"]["requests"] >= 2
                assert slo["totals"]["slo_met"] >= 2
                assert f"127.0.0.1:{SC}" in slo["endpoints"]
                transfers = (await c.get(
                    f"http://127.0.0.1:{GW}/debug/transfers")).json()
                pair = next(p for p in transfers["pairs"]
                            if p["prefill"] == f"127.0.0.1:{PRE}"
                            and p["decode"] == f"127.0.0.1:{SC}")
                assert pair["pulls"] >= 1
                assert pair["ewma_pull_ms"] > 0
                assert pair["bytes_total"] > 0
                assert pair["ewma_prefill_ms"] > 0

                # And the router metric families observed the same pull.
                m = await c.get(f"http://127.0.0.1:{GW}/metrics")
                assert "router_kv_transfer_ms_count" in m.text
                assert 'router_goodput_tokens_total{model="tiny"}' in m.text

                # Golden cache block, P/D split (router/kvobs.py): the
                # first long-prompt request ran the 2-phase protocol, so
                # the sidecar relayed the PREFILL leg's engine-confirmed
                # hit headers (beside x-prefill-duration-ms, with
                # x-kv-prefiller naming the pod) and the DecisionRecord
                # joined them against the schedule-time per-candidate
                # prediction — decode pick AND prefill candidate.
                d = (await c.get(
                    f"http://127.0.0.1:{GW}/debug/decisions/disagg-slo-1")
                    ).json()
                cache = d["cache"]
                assert f"127.0.0.1:{SC}" in cache["predicted"]
                assert f"127.0.0.1:{PRE}" in cache["predicted"]
                assert cache["chosen"] == f"127.0.0.1:{SC}"
                actual = cache["actual"]
                assert actual["pod"] == f"127.0.0.1:{PRE}"  # x-kv-prefiller
                assert actual["source"] == "headers"
                assert actual["tokens"] == 0  # cold prefill engine

                # Warm repeat: the approx index now knows the decode pod
                # holds the blocks, so the PD decider keeps it local — the
                # sidecar's local-decode fallback relays the DECODE
                # engine's hit headers instead, and the join attributes
                # the (real, >0) hit to the decode pod.
                r = await c.post(f"http://127.0.0.1:{GW}/v1/completions",
                                 json={"model": "tiny", "prompt": LONG_PROMPT,
                                       "max_tokens": 6, "temperature": 0},
                                 headers={"x-request-id": "disagg-kv-2"})
                assert r.status_code == 200
                assert int(r.headers["x-kv-hit-tokens"]) > 0  # relayed
                d = (await c.get(
                    f"http://127.0.0.1:{GW}/debug/decisions/disagg-kv-2")
                    ).json()
                actual = d["cache"]["actual"]
                assert actual["pod"] == f"127.0.0.1:{SC}"
                assert actual["source"] == "headers"
                assert actual["tokens"] > 0 and actual["ratio"] > 0
                kv = (await c.get(f"http://127.0.0.1:{GW}/debug/kv")).json()
                assert kv["confirmed_joins"] >= 2
                assert f"127.0.0.1:{PRE}" in kv["pods"]
                assert f"127.0.0.1:{SC}" in kv["pods"]
        finally:
            await gw.stop()
            await sc.stop()
            await pre.stop()
            await dec.stop()

    asyncio.run(body())


def test_disagg_fallback_when_prefill_dead():
    async def body():
        dec = _engine(DEC, "decode")
        await dec.start()
        sc = Sidecar(SidecarConfig(port=SC, decoder_url=f"http://127.0.0.1:{DEC}",
                                   prefill_timeout_s=2.0))
        await sc.start()
        gw = build_gateway(CFG, port=GW, poll_interval=0.02)  # PRE never started
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=120) as c:
                r = await c.post(f"http://127.0.0.1:{GW}/v1/completions",
                                 json={"model": "tiny", "prompt": LONG_PROMPT,
                                       "max_tokens": 4})
                # Prefill target is dead: sidecar must fall back to local decode.
                assert r.status_code == 200
                assert len(r.json()["choices"][0]["text"]) > 0
        finally:
            await gw.stop()
            await sc.stop()
            await dec.stop()

    asyncio.run(body())


def test_sidecar_ssrf_allowlist():
    async def body():
        dec = _engine(DEC, "decode")
        await dec.start()
        sc = Sidecar(SidecarConfig(port=SC, decoder_url=f"http://127.0.0.1:{DEC}",
                                   ssrf_allowlist=["10.0.0.1:9999"]))
        await sc.start()
        try:
            async with httpx.AsyncClient(timeout=60) as c:
                r = await c.post(f"http://127.0.0.1:{SC}/v1/completions",
                                 json={"prompt": "x", "max_tokens": 1},
                                 headers={"x-prefiller-host-port": "evil:1"})
                assert r.status_code == 403
        finally:
            await sc.stop()
            await dec.stop()

    asyncio.run(body())


def _counter_value(server: EngineServer, metric: str) -> float:
    text = server.engine.telemetry.render().decode()
    for line in text.splitlines():
        if line.startswith(metric + " ") or line.startswith(metric + "_total "):
            return float(line.split()[-1])
    return 0.0


def test_gateway_strips_client_injected_disagg_headers():
    """A client must not be able to steer the sidecar via x-prefiller-host-port
    (SSRF/decider bypass): the gateway strips router-owned headers."""
    async def body():
        dec = _engine(DEC, "decode")
        await dec.start()
        sc = Sidecar(SidecarConfig(port=SC, decoder_url=f"http://127.0.0.1:{DEC}"))
        await sc.start()
        gw = build_gateway(CFG, port=GW, poll_interval=0.02)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=120) as c:
                # Short prompt (decode-only decision) + injected prefiller
                # header pointing at an attacker target.
                r = await c.post(
                    f"http://127.0.0.1:{GW}/v1/completions",
                    json={"model": "tiny", "prompt": SHORT_PROMPT,
                          "max_tokens": 2},
                    headers={"x-prefiller-host-port": "127.0.0.1:1"})
                # Served normally (no prefill attempt against the bogus host;
                # a forwarded header would stall the sidecar on connect).
                assert r.status_code == 200
                assert len(r.json()["choices"][0]["text"]) > 0
        finally:
            await gw.stop()
            await sc.stop()
            await dec.stop()

    asyncio.run(body())


def test_sidecar_chunked_decode_and_dp_ranks():
    """Chunked decode reassembles full text across max_tokens slices; DP rank
    listeners dispatch to per-rank decoder ports."""
    SC2, DEC2 = 18390, 18394  # SC2+rank must not collide with engine ports

    async def body():
        # two sim "DP rank" engines on consecutive ports
        e0 = EngineServer(EngineConfig(backend="sim", model="tiny", port=DEC2))
        e1 = EngineServer(EngineConfig(backend="sim", model="tiny", port=DEC2 + 1))
        await e0.start()
        await e1.start()
        sc = Sidecar(SidecarConfig(port=SC2, decoder_url=f"http://127.0.0.1:{DEC2}",
                                   decode_chunk_size=3, data_parallel_size=2))
        await sc.start()
        try:
            async with httpx.AsyncClient(timeout=60) as c:
                # chunked: 8 tokens in chunks of 3 -> "lorem ip" reassembled
                r = await c.post(f"http://127.0.0.1:{SC2}/v1/completions",
                                 json={"prompt": "x", "max_tokens": 8})
                assert r.status_code == 200
                doc = r.json()
                assert doc["usage"]["completion_tokens"] == 8
                assert len(doc["choices"][0]["text"]) == 8

                # DP rank 1 listener dispatches to engine on DEC2+1
                r = await c.post(f"http://127.0.0.1:{SC2 + 1}/v1/completions",
                                 json={"prompt": "x", "max_tokens": 2})
                assert r.status_code == 200
        finally:
            await sc.stop()
            await e1.stop()
            await e0.stop()

    asyncio.run(body())


def test_shared_storage_connector():
    """Decode-first probe: cold cache -> cache_threshold -> remote prefill ->
    retry; warm cache -> served locally without touching the prefiller."""
    SC3, DEC3, PRE3 = 18396, 18397, 18398

    async def body():
        dec = EngineServer(EngineConfig(backend="tpu", model="tiny", port=DEC3,
                                        max_batch=4, max_model_len=256))
        pre = EngineServer(EngineConfig(backend="tpu", model="tiny", port=PRE3,
                                        max_batch=4, max_model_len=256,
                                        role="prefill"))
        await dec.start()
        await pre.start()
        sc = Sidecar(SidecarConfig(port=SC3, decoder_url=f"http://127.0.0.1:{DEC3}",
                                   connector="shared-storage",
                                   cache_hit_threshold=0.5))
        await sc.start()
        try:
            prompt = [1] + list(range(50, 98))  # 49 tokens, 3 full blocks
            async with httpx.AsyncClient(timeout=120) as c:
                pre_before = _counter_value(pre, "jetstream:prompt_tokens_total")
                r = await c.post(f"http://127.0.0.1:{SC3}/v1/completions",
                                 json={"prompt": prompt, "max_tokens": 4,
                                       "ignore_eos": True},
                                 headers={"x-prefiller-host-port":
                                          f"127.0.0.1:{PRE3}"})
                assert r.status_code == 200
                text1 = r.json()["choices"][0]["text"]
                # Cold cache -> the prefill leg ran remotely.
                assert _counter_value(pre, "jetstream:prompt_tokens_total") > pre_before

                # Second identical request: decode-side cache is warm (KV was
                # imported), so it's served locally without another prefill.
                # (Token equality across the imported-KV vs prefix-recompute
                # numeric paths is NOT asserted: with random weights, near-tie
                # argmaxes can flip between the two bitwise-different but
                # equally-valid computations.)
                pre_mid = _counter_value(pre, "jetstream:prompt_tokens_total")
                r = await c.post(f"http://127.0.0.1:{SC3}/v1/completions",
                                 json={"prompt": prompt, "max_tokens": 4,
                                       "ignore_eos": True},
                                 headers={"x-prefiller-host-port":
                                          f"127.0.0.1:{PRE3}"})
                assert r.status_code == 200
                assert len(r.json()["choices"][0]["text"]) > 0 and text1
                assert _counter_value(pre, "jetstream:prompt_tokens_total") == pre_mid
        finally:
            await sc.stop()
            await pre.stop()
            await dec.stop()

    asyncio.run(body())


def test_data_parallel_profile_handler():
    """DP handler writes x-data-parallel-host-port from the dp-size label; the
    sidecar dispatches to that rank's engine; out-of-range targets ignored."""
    GW4, SC4, E0, E1 = 18440, 18441, 18445, 18446

    cfg = f"""
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {SC4},
       labels: {{llm-d.ai/role: decode, llm-d.ai/dp-size: "2"}}}}
plugins:
  - {{type: queue-scorer}}
  - {{type: data-parallel-profile-handler}}
schedulingProfiles:
  - name: default
    plugins:
      - {{pluginRef: queue-scorer}}
"""

    async def body():
        engines = [EngineServer(EngineConfig(backend="sim", model="tiny", port=p))
                   for p in (E0, E1)]
        for e in engines:
            await e.start()
        sc = Sidecar(SidecarConfig(port=SC4, decoder_url=f"http://127.0.0.1:{E0}",
                                   data_parallel_size=2))
        await sc.start()
        gw = build_gateway(cfg, port=GW4, poll_interval=0.02)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=60) as c:
                served_ranks = set()
                for _ in range(4):
                    r = await c.post(f"http://127.0.0.1:{GW4}/v1/completions",
                                     json={"model": "tiny", "prompt": "x",
                                           "max_tokens": 2})
                    assert r.status_code == 200
                # round-robin must have touched both rank engines
                m0 = (await c.get(f"http://127.0.0.1:{E0}/metrics")).text
                m1 = (await c.get(f"http://127.0.0.1:{E1}/metrics")).text
                for m in (m0, m1):
                    for line in m.splitlines():
                        if line.startswith("jetstream:generation_tokens_total "):
                            served_ranks.add(float(line.split()[-1]) > 0)
                assert served_ranks == {True}

                # out-of-range header at the sidecar -> ignored, still served
                r = await c.post(f"http://127.0.0.1:{SC4}/v1/completions",
                                 json={"prompt": "x", "max_tokens": 1},
                                 headers={"x-data-parallel-host-port":
                                          "127.0.0.1:9"})
                assert r.status_code == 200
        finally:
            await gw.stop()
            await sc.stop()
            for e in engines:
                await e.stop()

    asyncio.run(body())

def test_sidecar_proxies_kv_events_stream():
    """The precise-prefix SSE subscriber must work against sidecar-fronted
    decode endpoints: GET /kv_events is stream-proxied."""
    DEC6, SC6 = 18375, 18376

    async def body():
        dec = _engine(DEC6, "decode")
        await dec.start()
        sc = Sidecar(SidecarConfig(port=SC6, decoder_url=f"http://127.0.0.1:{DEC6}"))
        await sc.start()
        try:
            async with httpx.AsyncClient(timeout=30) as c:
                # Generate so the engine publishes stored block hashes.
                r = await c.post(f"http://127.0.0.1:{DEC6}/v1/completions",
                                 json={"prompt": "hello " * 20, "max_tokens": 2})
                assert r.status_code == 200

                got_stored = False
                async with c.stream(
                        "GET", f"http://127.0.0.1:{SC6}/kv_events") as resp:
                    assert resp.status_code == 200
                    assert "text/event-stream" in resp.headers["content-type"]
                    async for line in resp.aiter_lines():
                        if line.startswith("data: ") and '"stored"' in line:
                            got_stored = True
                            break
                assert got_stored
        finally:
            await sc.stop()
            await dec.stop()

    asyncio.run(body())


def test_golden_decision_record_disagg_with_chaos_failover():
    """Golden DecisionRecord through the disagg path: the full record for one
    request must show admission (flow control: queue time + band), the
    prefill profile's filter drops, the decode profile's per-endpoint scorer
    table and picker pick, and a chaos-induced failover attempt trail —
    first attempt against a chaos-reset decode endpoint, reschedule, then
    success via the healthy sidecar-fronted decode pod."""
    GW7, EA7, SC7, DEC7, PRE7 = 18960, 18961, 18962, 18963, 18964

    cfg = f"""
featureGates: {{flowControl: true}}
decisions: {{topK: 4}}
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {EA7}, labels: {{llm-d.ai/role: decode}}}}
    - {{address: 127.0.0.1, port: {SC7}, labels: {{llm-d.ai/role: decode}}}}
    - {{address: 127.0.0.1, port: {PRE7}, labels: {{llm-d.ai/role: prefill}}}}
plugins:
  - {{type: header-based-testing-filter}}
  - {{type: decode-filter}}
  - {{type: prefill-filter}}
  - {{type: queue-scorer}}
  - type: disagg-profile-handler
    parameters:
      pdDecider:
        type: prefix-based-pd-decider
        parameters: {{thresholdTokens: 16}}
schedulingProfiles:
  - name: decode
    plugins:
      - {{pluginRef: decode-filter}}
      - {{pluginRef: header-based-testing-filter}}
      - {{pluginRef: queue-scorer, weight: 2}}
  - name: prefill
    plugins:
      - {{pluginRef: prefill-filter}}
      - {{pluginRef: queue-scorer}}
"""

    async def body():
        # Chaos decode endpoint: resets every connection (deterministic shim).
        ea = EngineServer(EngineConfig(backend="sim", model="tiny", port=EA7,
                                       chaos="reset:100"))
        dec = _engine(DEC7, "decode")
        pre = _engine(PRE7, "prefill")
        await ea.start()
        await dec.start()
        await pre.start()
        sc = Sidecar(SidecarConfig(port=SC7,
                                   decoder_url=f"http://127.0.0.1:{DEC7}",
                                   ssrf_allowlist=[f"127.0.0.1:{PRE7}"]))
        await sc.start()
        gw = build_gateway(cfg, port=GW7, poll_interval=0.02)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=120) as c:
                r = await c.post(
                    f"http://127.0.0.1:{GW7}/v1/completions",
                    json={"model": "tiny", "prompt": LONG_PROMPT,
                          "max_tokens": 4, "temperature": 0},
                    headers={"x-request-id": "golden-disagg-1",
                             "x-debug-decision": "summary",
                             "test-epp-endpoint-selection":
                                 f"127.0.0.1:{EA7}"})
                assert r.status_code == 200
                # Failover landed on the healthy sidecar-fronted pod.
                assert r.headers["x-gateway-destination-endpoint-served"] == \
                    f"127.0.0.1:{SC7}"
                assert ea.chaos.triggered["reset"] > 0
                assert f"winner=127.0.0.1:" in r.headers["x-decision-summary"]

                r = await c.get(f"http://127.0.0.1:{GW7}"
                                "/debug/decisions/golden-disagg-1")
                assert r.status_code == 200
                rec = r.json()
                assert rec["schema_version"] == 1

                # Admission: flow-control verdict with queue time + band.
                adm = rec["admission"]
                assert adm["mechanism"] == "flow-control"
                assert adm["outcome"] == "dispatched"
                assert adm["priority_band"] == 0
                assert adm["queue_ms"] >= 0

                # Round 1 (schedule): decode profile — filter drops recorded
                # per filter, per-endpoint weighted scorer table, picker pick
                # of the (chaos) endpoint the test header forced.
                assert [rd["reason"] for rd in rec["rounds"]] == \
                    ["schedule", "reschedule"]
                d1 = rec["rounds"][0]["profiles"]["decode"]
                by_plugin = {f["plugin"].split("/")[0]: f
                             for f in d1["filters"]}
                assert f"127.0.0.1:{PRE7}" in \
                    by_plugin["decode-filter"]["dropped"]
                assert f"127.0.0.1:{SC7}" in \
                    by_plugin["header-based-testing-filter"]["dropped"]
                qs = d1["scorers"]["queue-scorer/queue-scorer"]
                assert qs["weight"] == 2.0
                assert f"127.0.0.1:{EA7}" in qs["scores"]
                assert set(qs["scores"][f"127.0.0.1:{EA7}"]) == \
                    {"raw", "weighted"}
                assert d1["picker"]["picked"] == [f"127.0.0.1:{EA7}"]

                # Round 1: prefill profile — role filter drops both decode
                # endpoints, prefill pod picked.
                p1 = rec["rounds"][0]["profiles"]["prefill"]
                pf = next(f for f in p1["filters"]
                          if f["plugin"].startswith("prefill-filter"))
                assert set(pf["dropped"]) == {f"127.0.0.1:{EA7}",
                                              f"127.0.0.1:{SC7}"}
                assert p1["picker"]["picked"] == [f"127.0.0.1:{PRE7}"]

                # Round 2 (failover reschedule): the healthy pod wins.
                d2 = rec["rounds"][1]["profiles"]["decode"]
                assert d2["picker"]["picked"] == [f"127.0.0.1:{SC7}"]

                # Attempt trail: chaos connect failure → reschedule event
                # (excluding the broken pod) → success on the sidecar.
                attempts = rec["attempts"]
                assert attempts[0]["endpoint"] == f"127.0.0.1:{EA7}"
                assert attempts[0]["outcome"] == "connect"
                resched = next(a for a in attempts if a.get("event") ==
                               "reschedule")
                assert f"127.0.0.1:{EA7}" in resched["excluded"]
                ok = attempts[-1]
                assert ok["endpoint"] == f"127.0.0.1:{SC7}"
                assert ok["outcome"] == "ok" and ok["status"] == 200

                assert rec["final"]["status"] == 200
                assert rec["final"]["destination"] == f"127.0.0.1:{SC7}"

                # Outcome block closes the loop even on the failover path:
                # no SLO headers → vacuously met, e2e/TTFT still measured.
                out = rec["outcome"]
                assert out["slo_met"] is True
                assert out["slo"]["defined"] is False
                assert out["actual"]["e2e_ms"] > 0
        finally:
            await gw.stop()
            await sc.stop()
            await pre.stop()
            await dec.stop()
            await ea.stop()

    asyncio.run(body())


def test_golden_disagg_waterfall_and_stream_header_time_join():
    """Golden tail waterfall through the full disagg path (router/tails.py):
    the decision record's waterfall block must decompose the request into
    queue (flow-control wait) + sched + prefill + kv_transfer + decode
    residual — every stage > 0, stages summing back to the TTFT — and the
    /debug/tails cohort ledger must have absorbed it. Second half: the
    per-pair TransferTable row must land at HEADER time for STREAMED
    responses too (the PR 10 gap), observable while the stream is open."""
    GW8, SC8, DEC8, PRE8 = 18990, 18991, 18992, 18993

    cfg = f"""
featureGates: {{flowControl: true}}
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {SC8}, labels: {{llm-d.ai/role: decode}}}}
    - {{address: 127.0.0.1, port: {PRE8}, labels: {{llm-d.ai/role: prefill}}}}
plugins:
  - {{type: decode-filter}}
  - {{type: prefill-filter}}
  - {{type: queue-scorer}}
  - type: disagg-profile-handler
    parameters:
      pdDecider:
        type: prefix-based-pd-decider
        parameters: {{thresholdTokens: 16}}
schedulingProfiles:
  - name: decode
    plugins:
      - {{pluginRef: decode-filter}}
      - {{pluginRef: queue-scorer, weight: 2}}
  - name: prefill
    plugins:
      - {{pluginRef: prefill-filter}}
      - {{pluginRef: queue-scorer}}
"""

    async def body():
        dec = _engine(DEC8, "decode")
        pre = _engine(PRE8, "prefill")
        await dec.start()
        await pre.start()
        sc = Sidecar(SidecarConfig(port=SC8,
                                   decoder_url=f"http://127.0.0.1:{DEC8}",
                                   ssrf_allowlist=[f"127.0.0.1:{PRE8}"]))
        await sc.start()
        gw = build_gateway(cfg, port=GW8, poll_interval=0.02)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=120) as c:
                r = await c.post(f"http://127.0.0.1:{GW8}/v1/completions",
                                 json={"model": "tiny", "prompt": LONG_PROMPT,
                                       "max_tokens": 4, "temperature": 0},
                                 headers={"x-request-id": "wf-gold-1",
                                          "x-debug-decision": "summary"})
                assert r.status_code == 200
                # The echo header leaves before the waterfall closes, so
                # it carries the pre-close summary; the post-close list
                # view's summary (below) gains the TTFT note.
                assert "winner=" in r.headers["x-decision-summary"]

                lst = (await c.get(f"http://127.0.0.1:{GW8}"
                                   "/debug/decisions?n=5")).json()
                row = next(d for d in lst["decisions"]
                           if d["request_id"] == "wf-gold-1")
                assert "ttft=" in row["summary"]

                rec = (await c.get(f"http://127.0.0.1:{GW8}"
                                   "/debug/decisions/wf-gold-1")).json()
                wf = rec["waterfall"]
                assert wf["verdict"] == "ok"
                assert wf["cohort"] == "tiny|b0|unary"
                st = wf["stages"]
                # Every critical-path stage measured and positive: the
                # flow-control queue wait, the scheduling cycle, the
                # prefill leg, the measured KV pull, and the decode
                # residual that absorbs the rest of the TTFT.
                for stage in ("queue", "sched", "prefill", "kv_transfer",
                              "decode"):
                    assert st.get(stage, 0) > 0, f"stage {stage} missing"
                # Non-streamed: TTFT == e2e, and the stages (decode being
                # the residual) reassemble it to rounding tolerance.
                assert wf["ttft_ms"] > 0
                assert abs(wf["e2e_ms"] - wf["ttft_ms"]) < 5.0
                assert abs(sum(st.values()) - wf["ttft_ms"]) < 5.0
                assert wf["pair"] == \
                    f"127.0.0.1:{PRE8}→127.0.0.1:{SC8}"

                # The tail observatory absorbed the served request.
                tails = (await c.get(
                    f"http://127.0.0.1:{GW8}/debug/tails")).json()
                assert tails["enabled"] is True
                cohort = tails["cohorts"]["tiny|b0|unary"]
                assert cohort["closed"] >= 1
                assert cohort["digests"]["kv_transfer"]["n"] >= 1

                # And the stage histogram family saw the same close.
                m = await c.get(f"http://127.0.0.1:{GW8}/metrics")
                assert 'router_stage_ms_count{stage="kv_transfer"}' in m.text

                # ---- streamed header-time pair landing (PR 10 gap) ----
                tr = (await c.get(
                    f"http://127.0.0.1:{GW8}/debug/transfers")).json()
                row = next(p for p in tr["pairs"]
                           if p["prefill"] == f"127.0.0.1:{PRE8}")
                stamp_before = row["last_unix"]

                # A DIFFERENT long prompt (cold for the approx index, so
                # the PD decider splits again), streamed this time.
                stream_prompt = ("stream this other important document: "
                                 * 4)
                async with c.stream(
                        "POST", f"http://127.0.0.1:{GW8}/v1/completions",
                        json={"model": "tiny", "prompt": stream_prompt,
                              "max_tokens": 64, "stream": True},
                        headers={"x-request-id": "wf-stream-1"}) as sr:
                    assert sr.status_code == 200
                    # Response headers are on the wire but the token
                    # stream is NOT consumed yet: the pair row must have
                    # landed already (header-time join — pre-PR-18 it
                    # waited for the terminal usage chunk).
                    tr = (await c.get(
                        f"http://127.0.0.1:{GW8}/debug/transfers")).json()
                    row = next(p for p in tr["pairs"]
                               if p["prefill"] == f"127.0.0.1:{PRE8}")
                    assert row["last_unix"] > stamp_before
                    assert row["ewma_prefill_ms"] > 0
                    async for _ in sr.aiter_bytes():
                        pass
        finally:
            await gw.stop()
            await sc.stop()
            await pre.stop()
            await dec.stop()

    asyncio.run(body())


def test_pd_pipeline_token_parity_exposed_cost_and_waterfall():
    """Pipelined P/D (ISSUE 20): with `pipeline_enabled` the sidecar
    dispatches the decode leg on first-chunk ack and the decode engine
    chunk-streams the KV while prefill computes. Gates: token parity with
    the serial 2-phase arm; the serial arm's response headers bit-identical
    to the pre-PR protocol (no exposed stamp — kill-switch contract); the
    pipelined response carries x-kv-transfer-exposed-ms <= x-kv-transfer-ms;
    the waterfall's kv_transfer stage holds the EXPOSED cost so stage sums
    still reconcile vs TTFT; /debug/transfers lands the exposed EWMA."""
    GW9, SC9, SC9P, DEC9, PRE9 = 18860, 18861, 18862, 18863, 18864

    cfg = f"""
featureGates: {{flowControl: true}}
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {SC9P}, labels: {{llm-d.ai/role: decode}}}}
    - {{address: 127.0.0.1, port: {PRE9}, labels: {{llm-d.ai/role: prefill}}}}
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
        dec = _engine(DEC9, "decode")
        pre = _engine(PRE9, "prefill")
        await dec.start()
        await pre.start()
        sc_serial = Sidecar(SidecarConfig(
            port=SC9, decoder_url=f"http://127.0.0.1:{DEC9}",
            ssrf_allowlist=[f"127.0.0.1:{PRE9}"]))
        sc_pipe = Sidecar(SidecarConfig(
            port=SC9P, decoder_url=f"http://127.0.0.1:{DEC9}",
            ssrf_allowlist=[f"127.0.0.1:{PRE9}"],
            pipeline_enabled=True))
        await sc_serial.start()
        await sc_pipe.start()
        gw = build_gateway(cfg, port=GW9, poll_interval=0.02)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=120) as c:
                # Pipelined arm through the gateway, cold caches: the
                # decode leg MUST chunk-stream the KV (a warm decode-side
                # prefix would skip the pull and hide the transfer).
                r = await c.post(
                    f"http://127.0.0.1:{GW9}/v1/completions",
                    json={"model": "tiny", "prompt": LONG_PROMPT,
                          "max_tokens": 6, "temperature": 0},
                    headers={"x-request-id": "pipe-gold-1"})
                assert r.status_code == 200
                pipe_text = r.json()["choices"][0]["text"]

                # Token parity: the serial 2-phase arm over the same
                # prompt (prefixes now warm — that changes timing, never
                # greedy logits) produces the identical continuation.
                r = await c.post(
                    f"http://127.0.0.1:{SC9}/v1/completions",
                    json={"prompt": LONG_PROMPT, "max_tokens": 6,
                          "temperature": 0},
                    headers={"x-prefiller-host-port": f"127.0.0.1:{PRE9}"})
                assert r.status_code == 200, r.text
                assert r.json()["choices"][0]["text"] == pipe_text

                # Kill-switch contract on a cold prompt: the serial
                # sidecar's headers stay bit-identical to the pre-pipeline
                # protocol — raw pull stamped, NO exposed stamp.
                r = await c.post(
                    f"http://127.0.0.1:{SC9}/v1/completions",
                    json={"prompt": "a different saga about container "
                          "fleets sailing the high seas " * 4,
                          "max_tokens": 6, "temperature": 0},
                    headers={"x-prefiller-host-port": f"127.0.0.1:{PRE9}"})
                assert r.status_code == 200
                assert float(r.headers["x-kv-transfer-ms"]) > 0
                assert "x-kv-transfer-exposed-ms" not in r.headers

                # Waterfall: the gateway consumed the transfer headers
                # (they are not relayed to clients) — kv_transfer carries
                # the EXPOSED cost, overlap_ms rides beside it excluded
                # from the accounted sum, and stage sums still reconcile
                # vs TTFT (no double-counted transfer time). overlap_ms
                # present at all proves the chunk-streamed pull ran: the
                # serial 2-phase path never stamps an exposed split.
                rec = (await c.get(f"http://127.0.0.1:{GW9}"
                                   "/debug/decisions/pipe-gold-1")).json()
                wf = rec["waterfall"]
                assert wf["verdict"] == "ok"
                st = wf["stages"]
                exposed = st.get("kv_transfer", 0.0)
                overlap = wf["overlap_ms"]
                assert exposed >= 0 and overlap > 0
                assert abs(sum(st.values()) - wf["ttft_ms"]) < 10.0
                assert wf["pair"] == f"127.0.0.1:{PRE9}→127.0.0.1:{SC9P}"

                # The pair EWMA table landed the exposed cost beside the
                # raw pull EWMA.
                tr = (await c.get(
                    f"http://127.0.0.1:{GW9}/debug/transfers")).json()
                pair = next(p for p in tr["pairs"]
                            if p["prefill"] == f"127.0.0.1:{PRE9}"
                            and p["decode"] == f"127.0.0.1:{SC9P}")
                assert pair["pulls"] >= 1
                assert pair["ewma_pull_ms"] > 0
                assert pair["exposed_ms"] <= pair["ewma_pull_ms"]

                # And the new histogram families observed the request.
                m = (await c.get(f"http://127.0.0.1:{GW9}/metrics")).text
                v = next(ln.split()[-1] for ln in m.splitlines()
                         if ln.startswith("router_kv_transfer_exposed_ms_count"))
                assert float(v) >= 1
                ms = (await c.get(
                    f"http://127.0.0.1:{SC9P}/metrics")).text
                v = next(ln.split()[-1] for ln in ms.splitlines()
                         if ln.startswith("sidecar_kv_overlap_ms_count"))
                assert float(v) >= 1
        finally:
            await gw.stop()
            await sc_pipe.stop()
            await sc_serial.stop()
            await pre.stop()
            await dec.stop()

    asyncio.run(body())


def test_kv_chunk_longpoll_timeout_and_gap_edges():
    """The /kv chunk surface's protocol edges (ISSUE 20): a bounded
    long-poll for a not-yet-staged chunk expires 202 (not a hang, not an
    error); a chunk index past the end of a COMPLETE export answers 204
    with the final metadata; an unknown rid 404s even with a wait; the ack
    probe releases as soon as the first chunk stages."""
    E10 = 18865

    async def body():
        # Slow streamed prefill: 64 tokens at 10 ms/token over 16-token
        # windows -> 4 chunks ~160 ms apart, plenty to observe mid-stream.
        srv = EngineServer(EngineConfig(
            backend="sim", model="tiny", port=E10, max_batch=4,
            prefill_chunk=16, sim_prefill_ms_per_token=10.0))
        await srv.start()
        try:
            async with httpx.AsyncClient(timeout=30) as c:
                gen = asyncio.create_task(c.post(
                    f"http://127.0.0.1:{E10}/v1/completions",
                    json={"prompt": list(range(3, 67)), "max_tokens": 1,
                          "request_id": "lp-1",
                          "kv_transfer_params": {"do_remote_decode": True,
                                                 "stream_chunks": True}}))
                base = f"http://127.0.0.1:{E10}/kv/lp-1"
                # Unknown rid (export not created yet is indistinguishable
                # from never-existed): bounded wait, then 404.
                r = await c.get(f"http://127.0.0.1:{E10}/kv/nope",
                                params={"chunk": 0, "wait_ms": 30})
                assert r.status_code == 404

                # Ack long-poll: 200 the moment the first chunk stages.
                t0 = asyncio.get_event_loop().time()
                while True:
                    r = await c.get(base, params={"ack": "1",
                                                  "wait_ms": 1000})
                    if r.status_code == 200:
                        break
                    assert r.status_code in (202, 404)
                    assert asyncio.get_event_loop().time() - t0 < 20
                assert int(r.headers["x-kv-chunks-staged"]) >= 1

                # A far-future chunk with a short wait: 202 (mid-stream,
                # chunk not staged yet), carrying the staging progress.
                r = await c.get(base, params={"chunk": 30, "wait_ms": 40})
                if r.headers.get("x-kv-complete") != "1":
                    assert r.status_code == 202
                    assert int(r.headers["x-kv-chunks-staged"]) < 30

                # Chunk 0 is staged: served immediately (sim: headers only).
                r = await c.get(base, params={"chunk": 0, "wait_ms": 100})
                assert r.status_code == 200
                assert r.headers["x-kv-chunk"] == "0"
                assert int(r.headers["x-kv-chunk-blocks"]) >= 1

                resp = await gen
                assert resp.status_code == 200

                # Complete export: a past-the-end chunk answers 204 with
                # the terminal metadata (the puller's stop signal).
                # Long-poll until the completion flag lands.
                t0 = asyncio.get_event_loop().time()
                while True:
                    r = await c.get(base, params={"chunk": 99,
                                                  "wait_ms": 500})
                    if r.status_code == 204:
                        break
                    assert asyncio.get_event_loop().time() - t0 < 20
                assert r.headers["x-kv-complete"] == "1"
                staged = int(r.headers["x-kv-chunks-staged"])
                assert staged >= 2
                assert int(r.headers["x-kv-blocks-staged"]) >= 4

                # Every staged chunk is individually addressable.
                blocks = 0
                for i in range(staged):
                    r = await c.get(base, params={"chunk": i})
                    assert r.status_code == 200
                    blocks += int(r.headers["x-kv-chunk-blocks"])
                assert blocks == int(r.headers["x-kv-blocks-staged"])

                r = await c.delete(base)
                assert r.status_code == 200
                r = await c.get(base, params={"chunk": 0, "wait_ms": 10})
                assert r.status_code == 404
        finally:
            await srv.stop()

    asyncio.run(body())
