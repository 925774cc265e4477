"""Router core: scheduler loop, scorers/filters/pickers, config loader, extractor."""

import pytest

from llm_d_inference_scheduler_tpu.router import plugins  # noqa: F401 (registers)
from llm_d_inference_scheduler_tpu.router.config.loader import Handle, load_config
from llm_d_inference_scheduler_tpu.router.datalayer.data_graph import (
    DataDependencyError,
    validate_and_order_producers,
)
from llm_d_inference_scheduler_tpu.router.datalayer.datastore import Datastore
from llm_d_inference_scheduler_tpu.router.datalayer.extractor import CoreMetricsExtractor
from llm_d_inference_scheduler_tpu.router.framework.datalayer import (
    Endpoint,
    EndpointMetadata,
)
from llm_d_inference_scheduler_tpu.router.framework.plugin import TypedName
from llm_d_inference_scheduler_tpu.router.framework.scheduling import (
    InferenceRequest,
    InferenceRequestBody,
)
from llm_d_inference_scheduler_tpu.router.plugins.attributes import (
    PREFIX_ATTRIBUTE_KEY,
    PrefixCacheMatchInfo,
)


def ep(addr, port=8200, role=None, waiting=0, kv=0.0, running=0, fresh=True):
    labels = {"llm-d.ai/role": role} if role else {}
    e = Endpoint(EndpointMetadata(name=addr, address=addr, port=port, labels=labels))
    e.metrics.waiting_queue_size = waiting
    e.metrics.kv_cache_usage_percent = kv
    e.metrics.running_requests_size = running
    if fresh:
        import time
        e.metrics.update_time = time.monotonic()
    return e


def req(model="m", prompt="hello", headers=None):
    return InferenceRequest(
        request_id="r1", target_model=model,
        body=InferenceRequestBody(completions={"model": model, "prompt": prompt}),
        headers=headers or {})


def test_default_config_schedules_least_loaded():
    handle = Handle(datastore=Datastore())
    cfg = load_config(None, handle)
    eps = [ep("10.0.0.1", waiting=10, kv=0.9),
           ep("10.0.0.2", waiting=0, kv=0.1),
           ep("10.0.0.3", waiting=5, kv=0.5)]
    result = cfg.scheduler.schedule(None, req(), eps)
    picked = result.primary().target_endpoints
    assert len(picked) == 1
    assert picked[0].metadata.address == "10.0.0.2"


def test_prefix_scorer_dominates_when_weighted():
    handle = Handle(datastore=Datastore())
    cfg = load_config(None, handle)  # prefix weight 3 vs queue/kv 2 each
    hot = ep("10.0.0.1", waiting=3, kv=0.5)
    hot.attributes.put(PREFIX_ATTRIBUTE_KEY, PrefixCacheMatchInfo(9, 10, 16))
    cold = ep("10.0.0.2", waiting=2, kv=0.4)
    cold.attributes.put(PREFIX_ATTRIBUTE_KEY, PrefixCacheMatchInfo(0, 10, 16))
    result = cfg.scheduler.schedule(None, req(), [hot, cold])
    # hot: queue 0*2 + kv 0.5*2 + prefix 0.9*3 = 3.7 ; cold: 2 + 1.2 + 0 = 3.2
    assert result.primary().target_endpoints[0].metadata.address == "10.0.0.1"


def test_role_filters():
    from llm_d_inference_scheduler_tpu.router.plugins.filters import (
        DecodeFilter, EncodeFilter, PrefillFilter)

    eps = [ep("1", role="prefill"), ep("2", role="decode"), ep("3"),
           ep("4", role="both"), ep("5", role="encode")]
    d = DecodeFilter("d").filter(None, None, req(), eps)
    assert {e.metadata.address for e in d} == {"2", "3", "4"}
    p = PrefillFilter("p").filter(None, None, req(), eps)
    assert {e.metadata.address for e in p} == {"1", "4"}
    enc = EncodeFilter("e").filter(None, None, req(), eps)
    assert {e.metadata.address for e in enc} == {"5"}


def test_custom_config_yaml():
    yaml_text = """
featureGates: {flowControl: false}
pool:
  endpoints:
    - address: 127.0.0.1
      port: 9001
      labels: {llm-d.ai/role: decode}
plugins:
  - type: load-aware-scorer
    parameters: {queueDepthThreshold: 10}
  - type: weighted-random-picker
    parameters: {maxNumOfEndpoints: 2}
schedulingProfiles:
  - name: default
    plugins:
      - pluginRef: load-aware-scorer
        weight: 1
      - pluginRef: weighted-random-picker
"""
    handle = Handle(datastore=Datastore())
    cfg = load_config(yaml_text, handle)
    assert cfg.static_endpoints[0].port == 9001
    eps = [ep("a", waiting=0), ep("b", waiting=0), ep("c", waiting=100)]
    result = cfg.scheduler.schedule(None, req(), eps)
    picked = result.primary().target_endpoints
    assert len(picked) == 2  # maxNumOfEndpoints honored
    assert {e.metadata.address for e in picked} <= {"a", "b", "c"}


def test_session_affinity_roundtrip():
    handle = Handle(datastore=Datastore())
    cfg = load_config("""
plugins:
  - type: session-affinity-scorer
  - type: queue-scorer
schedulingProfiles:
  - name: default
    plugins:
      - pluginRef: session-affinity-scorer
        weight: 10
      - pluginRef: queue-scorer
""", handle)
    eps = [ep("a", waiting=0), ep("b", waiting=5)]
    r1 = req()
    result = cfg.scheduler.schedule(None, r1, eps)
    chosen = result.primary().target_endpoints[0].metadata.address_port
    for p in cfg.pre_request_plugins:
        p.pre_request(None, r1, result)
    import base64

    # The stamped token is OPAQUE (base64 endpoint identity, reference
    # session_affinity.go), not a raw address echo.
    token = r1.headers["x-session-token"]
    assert token != chosen
    assert base64.standard_b64decode(token).decode() == chosen
    # A follow-up presenting the token sticks even if the other endpoint is
    # less loaded.
    r2 = req(headers={"x-session-token":
                      base64.standard_b64encode(b"b:8200").decode()})
    result2 = cfg.scheduler.schedule(None, r2, eps)
    assert result2.primary().target_endpoints[0].metadata.address_port == "b:8200"
    # Garbage tokens degrade to fresh placement, not errors.
    r3 = req(headers={"x-session-token": "!!not-base64!!"})
    result3 = cfg.scheduler.schedule(None, r3, eps)
    assert result3.primary().target_endpoints[0].metadata.address_port == "a:8200"


def test_extractor_parses_jetstream_and_vllm():
    text = """# HELP jetstream:num_requests_waiting w
# TYPE jetstream:num_requests_waiting gauge
jetstream:num_requests_waiting 7.0
jetstream:num_requests_running 3.0
jetstream:kv_cache_usage_perc 0.42
jetstream:lora_requests_info{max_lora="4",running_lora_adapters="a,b",waiting_lora_adapters="c"} 1.0
jetstream:cache_config_info{block_size="16",num_gpu_blocks="1000"} 1.0
"""
    e = ep("x", fresh=False)
    CoreMetricsExtractor("core").extract(text, e)
    m = e.metrics
    assert m.waiting_queue_size == 7 and m.running_requests_size == 3
    assert abs(m.kv_cache_usage_percent - 0.42) < 1e-9
    assert m.active_models == {"a": 1, "b": 1} and m.waiting_models == {"c": 1}
    assert m.max_active_models == 4
    assert m.kv_cache_max_token_capacity == 16000
    assert m.fresh

    vllm_text = "vllm:num_requests_waiting 9\nvllm:num_requests_running 1\nvllm:kv_cache_usage_perc 0.5\n"
    e2 = ep("y", fresh=False)
    e2.metadata.labels["llm-d.ai/engine-type"] = "vllm"
    CoreMetricsExtractor("core").extract(vllm_text, e2)
    assert e2.metrics.waiting_queue_size == 9


def test_data_graph_ordering_and_cycles():
    class P:
        def __init__(self, name, produces, consumes):
            self._n, self._p, self._c = name, produces, consumes

        def typed_name(self):
            return TypedName("producer", self._n)

        def produces(self):
            return self._p

        def consumes(self):
            return self._c

    a = P("a", ["k1"], [])
    b = P("b", ["k2"], ["k1"])
    c = P("c", [], ["k2"])
    order = validate_and_order_producers([c, b, a])
    assert order.index(a) < order.index(b) < order.index(c)

    x = P("x", ["k3"], ["k4"])
    y = P("y", ["k4"], ["k3"])
    with pytest.raises(DataDependencyError):
        validate_and_order_producers([x, y])


def test_model_rewrite_weighted():
    from llm_d_inference_scheduler_tpu.router.datalayer.datastore import (
        InferenceModelRewrite, ModelRewriteTarget)
    import random

    rw = InferenceModelRewrite("rw", "base", [
        ModelRewriteTarget("a", 3), ModelRewriteTarget("b", 1)])
    rng = random.Random(7)
    picks = [rw.pick_target(rng) for _ in range(400)]
    assert 0.6 < picks.count("a") / 400 < 0.9


def test_no_hit_lru_scorer_spreads_cold_traffic():
    from llm_d_inference_scheduler_tpu.router.plugins.scorers import NoHitLruScorer
    from llm_d_inference_scheduler_tpu.router.framework.scheduling import (
        ProfileRunResult, SchedulingResult)

    s = NoHitLruScorer("lru")
    eps = [ep("a"), ep("b"), ep("c")]
    for e in eps:
        e.attributes.put(PREFIX_ATTRIBUTE_KEY, PrefixCacheMatchInfo(0, 10, 16))

    # All cold, no history: never-cold endpoints rank by candidate order
    # (reference no_hit_lru.go:197-206: 1 - i/(N-1)).
    r1 = req()
    scores = s.score(None, None, r1, eps)
    assert scores["a:8200"] == 1.0
    assert scores["b:8200"] == 0.5
    assert scores["c:8200"] == 0.0

    # Record a cold route to "a" (same request whose score marked it cold):
    # "a" becomes most-recently-cold → lowest score; b/c (never used) lead.
    res = SchedulingResult({"default": ProfileRunResult([eps[0]])}, "default")
    s.pre_request(None, r1, res)
    scores = s.score(None, None, req(), eps)
    assert scores["b:8200"] == 1.0
    assert scores["c:8200"] == 0.5
    assert scores["a:8200"] == 0.0

    # Cold-route "b" too: LRU order now a (older) then b → a outranks b.
    r2 = req()
    s.score(None, None, r2, eps)
    s.pre_request(None, r2, SchedulingResult(
        {"default": ProfileRunResult([eps[1]])}, "default"))
    scores = s.score(None, None, req(), eps)
    assert scores["c:8200"] == 1.0          # never cold-routed
    assert scores["a:8200"] == 0.5          # oldest cold route
    assert scores["b:8200"] == 0.0          # most recent cold route

    # A "prefill" profile pick also counts as cache growth (P/D split).
    r3 = req()
    s.score(None, None, r3, eps)
    s.pre_request(None, r3, SchedulingResult(
        {"default": ProfileRunResult([eps[1]]),
         "prefill": ProfileRunResult([eps[2]])}, "default"))
    scores = s.score(None, None, req(), eps)
    assert scores["a:8200"] == 1.0          # now the least-recently cold
    assert scores["b:8200"] == 0.5
    assert scores["c:8200"] == 0.0

    # With a prefix hit somewhere, the scorer goes neutral.
    eps[1].attributes.put(PREFIX_ATTRIBUTE_KEY, PrefixCacheMatchInfo(5, 10, 16))
    scores = s.score(None, None, req(), eps)
    assert set(scores.values()) == {0.5}


def test_no_hit_lru_cold_flag_not_erased_across_profiles():
    """A warm pass in one profile must not wipe a cold decision recorded by
    another profile's pass (one scorer instance shared via pluginRef), and
    the primary profile's decision wins when it scored."""
    from llm_d_inference_scheduler_tpu.router.framework.scheduling import (
        CycleState, ProfileRunResult, SchedulingResult)
    from llm_d_inference_scheduler_tpu.router.plugins.scorers import NoHitLruScorer

    cold_eps = [ep("a"), ep("b")]
    for e in cold_eps:
        e.attributes.put(PREFIX_ATTRIBUTE_KEY, PrefixCacheMatchInfo(0, 10, 16))
    warm_eps = [ep("c")]
    warm_eps[0].attributes.put(PREFIX_ATTRIBUTE_KEY,
                               PrefixCacheMatchInfo(4, 10, 16))

    def run(primary_warm: bool, order):
        s = NoHitLruScorer("lru")
        r = req()
        state = CycleState()
        name = str(s.typed_name())
        raw = {}
        for profile in order:
            state.write("current_profile", profile)
            eps_for = warm_eps if (profile == "default") == primary_warm \
                else cold_eps
            raw[profile] = s.score(None, state, r, eps_for)
        res = SchedulingResult(
            {"default": ProfileRunResult([cold_eps[0]],
                                         raw_scores={name: raw["default"]}),
             "prefill": ProfileRunResult([cold_eps[1]],
                                         raw_scores={name: raw["prefill"]})},
            "default")
        s.pre_request(None, r, res)
        return list(s._lru)

    # Primary warm (hit), prefill cold — primary decision wins: no touch,
    # regardless of which profile scored last.
    assert run(primary_warm=True, order=["prefill", "default"]) == []
    assert run(primary_warm=True, order=["default", "prefill"]) == []
    # Primary cold, prefill warm — cold decision survives a later warm pass.
    assert run(primary_warm=False, order=["default", "prefill"]) \
        == ["a:8200", "b:8200"]


def test_vertexai_parser():
    from llm_d_inference_scheduler_tpu.router.handlers.parsers import VertexAIParser
    import json

    p = VertexAIParser("v")
    res = p.parse(json.dumps({
        "model": "m", "instances": [{"prompt": "hello"}],
        "parameters": {"maxOutputTokens": 7, "temperature": 0.5}}).encode(), {})
    assert res.error is None and not res.skip
    assert res.body.completions["prompt"] == "hello"
    assert res.body.completions["max_tokens"] == 7

    res = p.parse(json.dumps({
        "model": "m",
        "instances": [{"messages": [{"role": "user", "content": "hi"}]}]}).encode(), {})
    assert res.body.chat_completions is not None

    res = p.parse(b'{"no": "instances"}', {})
    assert res.error


def test_header_based_testing_filter_and_served_verifier():
    from llm_d_inference_scheduler_tpu.router.plugins.testing import (
        DestinationEndpointServedVerifier, HeaderBasedTestingFilter)

    eps = [ep("a"), ep("b"), ep("c")]
    f = HeaderBasedTestingFilter("t")
    out = f.filter(None, None, req(headers={"test-epp-endpoint-selection": "b:8200"}), eps)
    assert [e.metadata.address_port for e in out] == ["b:8200"]
    assert f.filter(None, None, req(), eps) == eps  # no header: pass-through
    # unknown endpoint named: fail open
    out = f.filter(None, None, req(headers={"test-epp-endpoint-selection": "zz:1"}), eps)
    assert out == eps

    v = DestinationEndpointServedVerifier("v")
    r1 = req(headers={"x-gateway-destination-endpoint": "a:8200,b:8200"})
    v.response_received(None, r1, eps[0], 200)   # served a -> ok
    assert v.mismatches == 0
    v.response_received(None, r1, eps[2], 200)   # served c -> mismatch
    assert v.mismatches == 1


def test_example_configs_load():
    """Every shipped examples/*.yaml must instantiate cleanly."""
    import pathlib

    ex_dir = pathlib.Path(__file__).resolve().parent.parent / "examples"
    assert ex_dir.is_dir()
    loaded = 0
    for path in sorted(ex_dir.glob("*.yaml")):
        cfg = load_config(path.read_text(), Handle())
        assert cfg.scheduler is not None, path.name
        loaded += 1
    assert loaded >= 3  # monolithic, disagg, slo_aware


def test_response_streaming_plugins_run_async_but_ordered():
    """Streaming plugins run off the hot path on a per-request worker
    (reference director.go:92-134), and completion runs strictly AFTER all
    queued chunks."""
    import asyncio

    from llm_d_inference_scheduler_tpu.router.requestcontrol.director import (
        Director,
    )

    events = []

    class SlowStreamPlugin:
        def typed_name(self):
            return ("t", "slow")

        def response_streaming(self, ctx, request, endpoint, chunk):
            events.append(("chunk", chunk))

        def response_complete(self, ctx, request, endpoint, usage):
            events.append(("complete", usage.get("n")))

    async def body():
        plugin = SlowStreamPlugin()
        d = Director(Datastore(), None, admission=None,
                     response_streaming=[plugin], response_complete=[plugin])
        r = req()
        t0 = __import__("time").monotonic()
        for i in range(5):
            d.handle_response_streaming(None, r, None, f"c{i}".encode())
        # Enqueue is non-blocking regardless of plugin cost.
        assert __import__("time").monotonic() - t0 < 0.05
        d.handle_response_complete(None, r, None, {"n": 7})
        await asyncio.sleep(0.1)  # worker drains
        assert events == [("chunk", b"c0"), ("chunk", b"c1"), ("chunk", b"c2"),
                          ("chunk", b"c3"), ("chunk", b"c4"), ("complete", 7)]

    asyncio.run(body())


def test_decode_batch_bucket():
    from llm_d_inference_scheduler_tpu.engine.config import EngineConfig
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    eng = TpuEngine(EngineConfig(model="tiny", max_batch=8, kv_events_port=0))
    # Never one lane: a one-row page scatter becomes a dynamic-update-slice
    # that re-lays-out both page buffers (see TpuEngine._batch_bucket).
    assert eng._batch_bucket(1) == 2
    assert eng._batch_bucket(2) == 2
    assert eng._batch_bucket(3) == 4
    assert eng._batch_bucket(5) == 8
    assert eng._batch_bucket(8) == 8


def test_disagg_headers_handler_prerequest_wiring():
    """Deprecated header-only PreRequest variant (reference
    disagg_headers_handler.go): writes/clears the disagg routing headers from
    named profile results without orchestrating the profiles itself."""
    from llm_d_inference_scheduler_tpu.router.framework.plugin import global_registry
    from llm_d_inference_scheduler_tpu.router.framework.scheduling import (
        ProfileRunResult,
        SchedulingResult,
    )
    from llm_d_inference_scheduler_tpu.router.requestcontrol.director import (
        H_ENCODERS,
        H_PREFILLER,
    )

    h = global_registry.instantiate(
        "disagg-headers-handler", "h", {"prefillProfile": "pf"}, Handle())
    r = req(headers={H_PREFILLER: "stale:1", H_ENCODERS: "stale:2"})
    res = SchedulingResult(
        profile_results={
            "decode": ProfileRunResult(target_endpoints=[ep("d")]),
            "pf": ProfileRunResult(target_endpoints=[ep("p")]),
            "encode": ProfileRunResult(target_endpoints=[ep("e1"), ep("e2")]),
        },
        primary_profile_name="decode")
    h.pre_request(None, r, res)
    assert r.headers[H_PREFILLER] == "p:8200"
    assert r.headers[H_ENCODERS] == "e1:8200,e2:8200"

    # No prefill/encode results: stale headers are cleared, not preserved.
    r2 = req(headers={H_PREFILLER: "stale:1", H_ENCODERS: "stale:2"})
    h.pre_request(None, r2, SchedulingResult(
        profile_results={"decode": ProfileRunResult(target_endpoints=[ep("d")])},
        primary_profile_name="decode"))
    assert H_PREFILLER not in r2.headers
    assert H_ENCODERS not in r2.headers

    # prefill-header-handler is a registered alias.
    alias = global_registry.instantiate("prefill-header-handler", "a", {}, Handle())
    assert alias is not None


def test_sse_has_token_classifier():
    """Gateway TTFT must ignore token-free chunks (role-only chat deltas)."""
    from llm_d_inference_scheduler_tpu.router.gateway import _sse_scan_for_token

    def has_token(chunk):
        found, _ = _sse_scan_for_token(b"", chunk)
        return found

    role_only = (b'data: {"choices": [{"delta": {"role": "assistant"}}]}\n\n')
    content = (b'data: {"choices": [{"delta": {"content": "hi"}}]}\n\n')
    completion = b'data: {"choices": [{"text": "hi"}]}\n\n'
    done = b"data: [DONE]\n\n"
    unparseable = b"data: not-json\n\n"
    assert not has_token(role_only)
    assert not has_token(done)
    assert has_token(content)
    assert has_token(completion)
    assert has_token(unparseable)  # fail open
    assert has_token(role_only + content)  # mixed chunk counts

    # Events split across transport chunks reassemble via the carry instead
    # of misclassifying (truncated role-only must NOT fail open mid-event).

    first, second = role_only[:20], role_only[20:]
    found, carry = _sse_scan_for_token(b"", first)
    assert not found and carry  # partial line buffered, not counted
    found, carry = _sse_scan_for_token(carry, second)
    assert not found  # reassembled role-only delta still token-free
    found, carry = _sse_scan_for_token(carry, content[:15])
    assert not found
    found, _ = _sse_scan_for_token(carry, content[15:])
    assert found  # reassembled content delta counts
