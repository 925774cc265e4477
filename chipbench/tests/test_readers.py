"""Each reader kind on recorded /metrics text (cut from an engine's and a
gateway's exposition) and on hand-made client records."""

import pytest

import layer
import prom
from client import Record

BEFORE = """\
# HELP jetstream:prompt_tokens_total Prefilled tokens
# TYPE jetstream:prompt_tokens_total counter
jetstream:prompt_tokens_total 1000.0
jetstream:prefill_tokens_total 1000.0
jetstream:prefix_hit_tokens_total 100.0
jetstream:decode_step_duration_seconds_bucket{le="0.25"} 10.0
jetstream:decode_step_duration_seconds_count 10.0
jetstream:decode_step_duration_seconds_sum 1.5
jetstream:time_to_first_token_seconds_count 4.0
jetstream:time_to_first_token_seconds_sum 0.4
jetstream:compile_events_total{bucket="1x128",op="prefill"} 1.0
jetstream:batch_fill_ratio 0.5
jetstream:engine_loop_seconds_total{phase="admit"} 1.0
jetstream:engine_loop_seconds_total{phase="decode_book"} 2.0
jetstream:engine_loop_seconds_total{phase="decode_wait"} 50.0
jetstream:engine_loop_seconds_total{phase="idle_wait"} 400.0
jetstream:queue_wait_seconds_count 4.0
jetstream:queue_wait_seconds_sum 0.1
jetstream:xla_builds_total{kind="compiled"} 12.0
jetstream:xla_builds_total{kind="cache_loaded"} 30.0
"""
AFTER = """\
jetstream:prompt_tokens_total 1500.0
jetstream:prefill_tokens_total 3000.0
jetstream:prefix_hit_tokens_total 1600.0
jetstream:decode_step_duration_seconds_bucket{le="0.25"} 30.0
jetstream:decode_step_duration_seconds_count 30.0
jetstream:decode_step_duration_seconds_sum 5.5
jetstream:time_to_first_token_seconds_count 14.0
jetstream:time_to_first_token_seconds_sum 1.4
jetstream:compile_events_total{bucket="1x128",op="prefill"} 1.0
jetstream:compile_events_total{bucket="1x256",op="prefill"} 1.0
jetstream:batch_fill_ratio 1.0
jetstream:engine_loop_seconds_total{phase="admit"} 1.5
jetstream:engine_loop_seconds_total{phase="decode_book"} 3.0
jetstream:engine_loop_seconds_total{phase="decode_wait"} 58.5
jetstream:engine_loop_seconds_total{phase="idle_wait"} 900.0
jetstream:engine_loop_seconds_total{phase="housekeeping"} 0.5
jetstream:queue_wait_seconds_count 14.0
jetstream:queue_wait_seconds_sum 1.6
jetstream:xla_builds_total{kind="compiled"} 13.0
jetstream:xla_builds_total{kind="cache_loaded"} 30.0
"""
GATEWAY_BEFORE = """\
router_stage_ms_count{stage="queue"} 0.0
router_stage_ms_sum{stage="queue"} 0.0
router_stage_ms_count{stage="sched"} 10.0
router_stage_ms_sum{stage="sched"} 20.0
"""
GATEWAY_AFTER = """\
router_stage_ms_count{stage="queue"} 2.0
router_stage_ms_sum{stage="queue"} 30.0
router_stage_ms_count{stage="sched"} 30.0
router_stage_ms_sum{stage="sched"} 80.0
"""


def rec(session, turn, served, due=1.0, ttft=0.15):
    r = Record(f"s{session}t{turn}", session, turn, due, due, 10, 4)
    r.status, r.prompt_tokens, r.completion_tokens = 200, 10, 4
    r.first_s, r.last_s, r.done_s = due + ttft, due + ttft + 0.3, due + ttft + 0.3
    r.pieces, r.served_by = [(r.first_s, 1), (r.last_s, 3)], served
    return r


@pytest.fixture
def ctx():
    b, a = prom.parse(BEFORE), prom.parse(AFTER)
    records = [rec(0, 0, "A"), rec(0, 1, "A"), rec(0, 2, "B"),
               rec(1, 0, "B", due=-1.0), rec(1, 1, "B"), rec(-1, 0, "A")]
    return layer.Context(
        records=records, seconds=10.0, chips=1,
        engine_scrapes=[(b, a), (b, a)],
        gateway_scrape=(prom.parse(GATEWAY_BEFORE), prom.parse(GATEWAY_AFTER)),
        gauge_samples=[(0.1, [b, b]), (0.3, [a, a])], traces=[],
        trace_span=None, model={}, device_kind="cpu")


def test_parse_and_delta():
    b, a = prom.parse(BEFORE), prom.parse(AFTER)
    assert b[("jetstream:prompt_tokens_total", "")] == 1000.0
    assert prom.delta(b, a, "jetstream:compile_events_total") == 1.0  # new label set
    assert prom.delta(b, a, "absent") is None


@pytest.mark.parametrize("name, expected", [
    ("eng_cached_token_share", 75.0),      # hits (1600-100) / admitted (3000-1000)
    ("decode_chunk_ms", 200.0),            # (5.5-1.5)/(30-10) s
    ("compiles_in_window", 2.0),           # one new shape on each of two replicas
    ("eng_batch_fill", 75.0),              # mean of 0.5, 0.5, 1.0, 1.0
    ("gw_prefix_route_share", 100 * 2 / 3),  # s0t1 stays, s0t2 moves, s1t1 stays
    ("gw_ttft_added_ms", 150.0 - 100.0),   # client mean 150 ms, engines' 1.0 s / 10
    ("ttft_p95_ms", 150.0),                # every request's TTFT is 150 ms
    ("eng_queue_ms", 150.0),               # (1.6-0.1)/(14-4) s
    # host phases (0.5 + 1.0 + 0.5 new) over host + decode_wait (8.5), per
    # replica; idle_wait is on neither side
    ("eng_loop_host_pct", 100 * 2.0 / 10.5),
    ("xla_builds_in_window", 2.0),         # one build on each of two replicas
    ("xla_builds_in_window.batch", 2.0),
    ("gw_sched_ms", 3.0),                  # (80-20)/(30-10) ms
    ("gw_queue_ms", 1.5),                  # 30 ms of waiting over 20 requests scheduled
    ("eng_admit_to_first_token_ms", None),  # the histogram is not exposed: left out
    ("prefill_device_ms", None),           # no trace
    ("idle_in_book_pct", None),
    ("idle_unattributed_pct", None),
    ("prefill_step_ms", None),             # the histogram is not exposed: left out
    ("device_idle_share", None),           # no trace
    ("paged_attention_roofline", None),
])
def test_each_metric_file_through_its_reader(ctx, name, expected):
    got = layer.read_metric(name, ctx)
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected)


def test_roofline_reader_on_a_reduced_trace(ctx):
    ctx.traces = [{"devices": [{"window_s": 2.0, "busy_s": 1.5, "ops": {
        "custom-call.7": {"count": 100, "seconds": 0.01,
                          "detail": "jit(f)/paged_decode_attention_pallas/pallas_call"},
        "fusion.1": {"count": 100, "seconds": 1.0, "detail": "dot"}},
        "idle_by_next_program": {}}]}]
    ctx.trace_span = (1.0, 1.6)
    ctx.device_kind = "TPU v5 lite"
    ctx.model = {"num_attention_heads": 32, "num_key_value_heads": 8,
                 "head_dim": 128, "hidden_size": 2560}
    # Requests decoding during the slice: the five with due 1.0 (first token
    # at 1.15, last at 1.45); each has prompt 10 and 1 token by then.
    share = layer.read_metric("paged_attention_roofline", ctx)
    note = ctx.notes["paged_attention_decode"]
    assert note["calls"] == 100 and note["bound"] == "memory"
    assert note["mean_lanes"] == pytest.approx(5 * 0.5, rel=0.02)
    assert share == pytest.approx(100 * 100 * note["least_seconds_per_call"] / 0.01)
    assert layer.read_metric("device_idle_share", ctx) == pytest.approx(25.0)


def test_label_ratio_sides_and_what_is_absent(ctx):
    read = layer.reader("prom_label_ratio")
    loop = "jetstream:engine_loop_seconds_total"
    spec = {"numerator": {"name": loop, "label": "phase", "values": ["decode_wait"]},
            "denominator": {"name": loop, "label": "phase",
                            "values": ["decode_wait", "idle_wait"]}}
    assert read(spec, ctx) == pytest.approx(8.5 / 508.5)
    assert read(dict(spec, scale=100.0), ctx) == pytest.approx(100 * 8.5 / 508.5)
    absent = {"name": loop, "label": "phase", "values": ["no_such_phase"]}
    assert read(dict(spec, numerator=absent), ctx) is None
    assert read(dict(spec, denominator=absent), ctx) is None
    # A side that did not move is no denominator.
    still = {"name": "jetstream:xla_builds_total", "label": "kind",
             "values": ["cache_loaded"]}
    assert read(dict(spec, denominator=still), ctx) is None


def test_module_mean_on_a_reduced_trace(ctx):
    ctx.traces = [{"devices": [{"modules": {
        "jit_prefill_b256(123)": {"count": 3, "seconds": 0.030},
        "jit_prefix_prefill_s64_p16(9)": {"count": 1, "seconds": 0.010},
        "jit__decode_chunk_impl(7)": {"count": 5, "seconds": 1.4}}}]},
        {"devices": [{"modules": {
            "jit_prefill_b512(5)": {"count": 1, "seconds": 0.040}}}]}]
    assert layer.read_metric("prefill_device_ms", ctx) == pytest.approx(1e3 * 0.080 / 5)
    note = ctx.notes["modules matching ^jit_(prefix_)?prefill_"]
    assert note == {"executions": 5, "device_seconds": pytest.approx(0.080)}
    ctx.traces = [{"devices": [{"modules": {
        "jit__decode_chunk_impl(7)": {"count": 5, "seconds": 1.4}}}]}]
    assert layer.read_metric("prefill_device_ms", ctx) is None   # none ran
