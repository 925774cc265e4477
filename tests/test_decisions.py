"""Decision flight recorder: ring-buffer bounds, kill-switch, scheduler
recording, the gateway debug surface, and the coverage lint.

Unit tier drives DecisionRecorder/Scheduler directly; the e2e tier runs a
hermetic gateway over sim engines and reads /debug/decisions + the
x-debug-decision header echo. The golden disagg-path record (prefill filter
drops + decode scorer table + chaos failover trail) lives in
tests/test_e2e_disagg.py beside the rest of the P/D coverage.
"""

import asyncio
import pathlib
import sys

import httpx

from llm_d_inference_scheduler_tpu.engine import EngineConfig
from llm_d_inference_scheduler_tpu.engine.server import EngineServer
from llm_d_inference_scheduler_tpu.router.decisions import (
    SCHEMA_VERSION,
    DecisionConfig,
    DecisionRecord,
    DecisionRecorder,
)
from llm_d_inference_scheduler_tpu.router.framework.datalayer import (
    Endpoint,
    EndpointMetadata,
)
from llm_d_inference_scheduler_tpu.router.framework.scheduling import (
    InferenceRequest,
    InferenceRequestBody,
)
from llm_d_inference_scheduler_tpu.router.gateway import build_gateway


def run(coro):
    return asyncio.run(coro)


# ---- unit tier -----------------------------------------------------------


def test_ring_buffer_bounds_and_index():
    rec = DecisionRecorder(DecisionConfig(capacity=4))
    for i in range(10):
        rec.start(f"r{i}", "m")
    assert len(rec) == 4
    # Oldest evicted, newest retrievable; index follows the ring.
    assert rec.get("r0") is None and rec.get("r5") is None
    assert rec.get("r9") is not None
    assert [r.request_id for r in rec.snapshot()] == ["r9", "r8", "r7", "r6"]
    assert [r.request_id for r in rec.snapshot(2)] == ["r9", "r8"]


def test_ring_does_not_recycle_referenced_records():
    """A record still attached to an in-flight request must not be recycled
    into another request's trail when the ring evicts it."""
    rec = DecisionRecorder(DecisionConfig(capacity=2))
    held = rec.start("held", "m")
    held.record_admission("flow-control", "dispatched")
    for i in range(8):
        rec.start(f"f{i}", "m")
    # The held record keeps its identity and content.
    assert held.request_id == "held"
    assert held.admission["outcome"] == "dispatched"


def test_kill_switch_and_duplicate_ids():
    off = DecisionRecorder(DecisionConfig(enabled=False))
    assert off.start("x", "m") is None
    assert len(off) == 0 and not off.enabled

    on = DecisionRecorder(DecisionConfig(capacity=8))
    first = on.start("dup", "m")
    second = on.start("dup", "m")
    assert on.get("dup") is second is not first  # latest wins the index


def test_record_render_and_summary():
    rec = DecisionRecord("req-1", "tiny", top_k=2)
    rec.record_admission("flow-control", "dispatched", flow_id="f1",
                         priority_band=0, queue_ms=1.23456)
    sec = rec.begin_profile("decode", 3)
    rec.profile_filter(sec, "decode-filter/decode-filter", 3,
                       ["a:1", "b:1"], ["c:1"])
    rec.profile_scorer(sec, "queue-scorer/queue-scorer", 2.0,
                       {"a:1": 0.25, "b:1": 0.75})
    rec.profile_picker(sec, "max-score-picker/max-score-picker",
                       ["b:1"], {"a:1": 0.5, "b:1": 1.5})
    rec.record_attempt("b:1", "connect", reason="upstream-connect-error")
    rec.record_attempt("a:1", "ok", status=200)
    rec.finalize(200, destination="a:1")

    doc = rec.to_dict()
    assert doc["schema_version"] == SCHEMA_VERSION
    assert doc["admission"]["queue_ms"] == 1.235  # rendered rounding
    prof = doc["rounds"][0]["profiles"]["decode"]
    assert prof["filters"][0]["dropped"] == ["c:1"]
    scores = prof["scorers"]["queue-scorer/queue-scorer"]["scores"]
    assert scores["b:1"] == {"raw": 0.75, "weighted": 1.5}
    assert prof["picker"]["picked"] == ["b:1"]
    assert prof["picker"]["margin"] == 1.0
    assert [a.get("outcome") for a in doc["attempts"]] == ["connect", "ok"]
    assert doc["final"]["status"] == 200

    s = rec.summary_line()
    assert "winner=b:1" in s and "runner_up=a:1" in s and "margin=" in s
    assert "decode/decode-filter/decode-filter:1" in s
    assert "attempts=2" in s

    # top-K trimming: K=2 keeps both here; K=1 would trim.
    rec.top_k = 1
    scores = rec.to_dict()["rounds"][0]["profiles"]["decode"][
        "scorers"]["queue-scorer/queue-scorer"]
    assert list(scores["scores"]) == ["b:1"] and scores["candidates"] == 2


def test_render_snapshots_live_dicts():
    """Off-loop scheduling (scheduler-pool workers) can mutate a record
    while GET /debug/decisions renders it on the event loop — the render
    side must snapshot live dicts via an atomic ``dict()`` copy before
    iterating (a retry loop would livelock against a busy writer; see
    ``DecisionRecord._live_items``), never iterate them raw."""

    assert DecisionRecord._live_items({"k": 1}) == [("k", 1)]

    # End-to-end: a worker thread hammers round/profile/scorer inserts
    # while the loop side renders — no RuntimeError, every render a
    # consistent point-in-time document.
    import threading

    rec = DecisionRecord("req-race", "tiny")
    rec.begin_round("schedule", 2)

    def writer():
        # Bounded: renders walk every round, so an unbounded writer makes
        # each render slower than the last and the test quadratic.
        for i in range(2000):
            sec = rec.begin_profile(f"p{i}", 2)
            rec.profile_scorer(sec, f"s{i}", 1.0, {"a:1": 0.5})
            rec.profile_picker(sec, "picker", ["a:1"], {"a:1": 0.5})

    t = threading.Thread(target=writer)
    t.start()
    try:
        while t.is_alive():
            doc = rec.to_dict()
            assert doc["request_id"] == "req-race"
            rec.summary_line()
    finally:
        t.join()
    assert len(rec.to_dict()["rounds"][0]["profiles"]) == 2000


def test_scheduler_records_rounds_and_kill_switch_skips():
    from llm_d_inference_scheduler_tpu.router.plugins.filters import DecodeFilter
    from llm_d_inference_scheduler_tpu.router.plugins.pickers import MaxScorePicker
    from llm_d_inference_scheduler_tpu.router.plugins.scorers import QueueScorer
    from llm_d_inference_scheduler_tpu.router.plugins.profile_handlers import (
        SingleProfileHandler,
    )
    from llm_d_inference_scheduler_tpu.router.scheduling.scheduler import (
        Scheduler,
        SchedulerProfile,
        WeightedScorer,
    )

    eps = []
    for i, role in enumerate(["decode", "prefill", "decode"]):
        ep = Endpoint(EndpointMetadata(name=f"e{i}", address=f"10.1.0.{i}",
                                       port=9000,
                                       labels={"llm-d.ai/role": role}))
        ep.metrics.waiting_queue_size = i
        eps.append(ep)
    profile = SchedulerProfile("decode", [DecodeFilter("decode-filter")],
                               [WeightedScorer(QueueScorer("queue-scorer"), 2.0)],
                               MaxScorePicker("max-score-picker"))
    sched = Scheduler({"decode": profile}, SingleProfileHandler())

    recorder = DecisionRecorder(DecisionConfig())
    req = InferenceRequest(request_id="sched-1", target_model="tiny",
                           body=InferenceRequestBody(completions={"prompt": "x"}))
    req.decision = recorder.start(req.request_id, req.target_model)
    result = sched.schedule(None, req, eps)
    # Second schedule on the same request (the failover reschedule shape).
    sched.schedule(None, req, eps[:1])

    doc = req.decision.to_dict()
    assert [r["reason"] for r in doc["rounds"]] == ["schedule", "reschedule"]
    prof = doc["rounds"][0]["profiles"]["decode"]
    assert prof["candidates_in"] == 3
    # prefill endpoint dropped by the decode filter
    assert prof["filters"][0]["dropped"] == ["10.1.0.1:9000"]
    # per-endpoint weighted scores for both survivors; queue 0 beats queue 2
    qs = prof["scorers"]["queue-scorer/queue-scorer"]
    assert qs["weight"] == 2.0 and len(qs["scores"]) == 2
    assert prof["picker"]["picked"] == ["10.1.0.0:9000"]
    assert prof["picker"]["margin"] > 0
    assert result.primary().target_endpoints[0].metadata.address_port == \
        "10.1.0.0:9000"

    # Kill switch: same cycle records nothing and schedules identically.
    req2 = InferenceRequest(request_id="sched-2", target_model="tiny",
                            body=InferenceRequestBody(completions={"prompt": "x"}))
    req2.decision = DecisionRecorder(
        DecisionConfig(enabled=False)).start("sched-2", "tiny")
    assert req2.decision is None
    result2 = sched.schedule(None, req2, eps)
    assert result2.primary().target_endpoints[0].metadata.address_port == \
        "10.1.0.0:9000"


def test_verify_decisions_lint_clean():
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "scripts"))
    import verify_decisions

    assert verify_decisions.check() == []


# ---- e2e tier ------------------------------------------------------------

GW, EA, EB = 18953, 18954, 18955

CFG = f"""
featureGates: {{flowControl: true}}
pool:
  endpoints:
    - {{address: 127.0.0.1, port: {EA}}}
    - {{address: 127.0.0.1, port: {EB}}}
plugins:
  - {{type: queue-scorer}}
  - {{type: kv-cache-utilization-scorer}}
schedulingProfiles:
  - name: default
    plugins:
      - {{pluginRef: queue-scorer, weight: 2}}
      - {{pluginRef: kv-cache-utilization-scorer, weight: 2}}
"""


async def _sim(port, **kw):
    kw.setdefault("backend", "sim")
    kw.setdefault("model", "tiny")
    s = EngineServer(EngineConfig(port=port, **kw))
    await s.start()
    return s


def test_gateway_debug_decisions_and_header():
    async def body():
        ea, eb = await _sim(EA), await _sim(EB)
        gw = build_gateway(CFG, port=GW, poll_interval=0.02)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=30) as c:
                r = await c.post(
                    f"http://127.0.0.1:{GW}/v1/completions",
                    json={"model": "tiny", "prompt": "hello", "max_tokens": 2},
                    headers={"x-request-id": "dec-e2e-1",
                             "x-debug-decision": "summary"})
                assert r.status_code == 200
                # Header echo: compact one-line verdict.
                summary = r.headers["x-decision-summary"]
                assert "winner=127.0.0.1:" in summary
                assert "admission=dispatched" in summary

                # Recent-decisions page.
                r = await c.get(f"http://127.0.0.1:{GW}/debug/decisions")
                doc = r.json()
                assert doc["schema_version"] == SCHEMA_VERSION and doc["enabled"]
                assert any(d["request_id"] == "dec-e2e-1"
                           for d in doc["decisions"])

                # Full record: admission (flow control: band + queue time) →
                # profile (scorer table + picker) → attempt trail → final.
                r = await c.get(
                    f"http://127.0.0.1:{GW}/debug/decisions/dec-e2e-1")
                assert r.status_code == 200
                rec = r.json()
                adm = rec["admission"]
                assert adm["mechanism"] == "flow-control"
                assert adm["outcome"] == "dispatched"
                assert adm["priority_band"] == 0 and adm["queue_ms"] >= 0
                prof = rec["rounds"][0]["profiles"]["default"]
                assert len(prof["scorers"]) == 2
                for s in prof["scorers"].values():
                    assert s["scores"]  # per-endpoint table present
                assert prof["picker"]["picked"][0].startswith("127.0.0.1:")
                assert rec["attempts"][-1]["outcome"] == "ok"
                assert rec["final"]["status"] == 200
                assert rec["final"]["destination"].startswith("127.0.0.1:")

                # 404 contract for unknown ids.
                r = await c.get(
                    f"http://127.0.0.1:{GW}/debug/decisions/nope")
                assert r.status_code == 404
        finally:
            await gw.stop()
            await ea.stop()
            await eb.stop()

    run(body())


def test_gateway_kill_switch_disables_recording():
    cfg = CFG + "\ndecisions: {enabled: false}\n"

    async def body():
        ea, eb = await _sim(EA), await _sim(EB)
        gw = build_gateway(cfg, port=GW, poll_interval=0.02)
        await gw.start()
        try:
            async with httpx.AsyncClient(timeout=30) as c:
                r = await c.post(
                    f"http://127.0.0.1:{GW}/v1/completions",
                    json={"model": "tiny", "prompt": "hello", "max_tokens": 2},
                    headers={"x-request-id": "dec-off-1",
                             "x-debug-decision": "summary"})
                assert r.status_code == 200
                assert "x-decision-summary" not in r.headers
                r = await c.get(f"http://127.0.0.1:{GW}/debug/decisions")
                doc = r.json()
                assert doc["enabled"] is False and doc["decisions"] == []
                r = await c.get(
                    f"http://127.0.0.1:{GW}/debug/decisions/dec-off-1")
                assert r.status_code == 404
        finally:
            await gw.stop()
            await ea.stop()
            await eb.stop()

    run(body())
