"""What PR 35 adds to the benchmark, on the CPU: the counts behind
`ssm_decode_roofline` against numbers worked by hand, its reader on a canned
trace, and the counter ratio `ssm_kernel_update_share`; each reads nothing,
and raises nothing, from a program without the kernel or the counter."""

import json
import os

import pytest

import kernels
import kernels_ssm
import layer
import prom
from client import Record

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "nemotron-3-super-cut.reason-batch"


def test_state_update_cost_by_hand():
    # Nemotron-3-Super's state layer: 128 heads of 64, a state of 128, 8
    # groups, float32. Sixty-four lanes.
    cost = kernels_ssm.ssm_state_update(64, 128, 64, 128, 8)
    values = 64 * 128 * 64 * 128                      # 67,108,864 a call
    assert cost["flops"] == 6 * values
    # The state in and out (4 B a value each way), and a lane's keep (128),
    # dt x and y (8,192 each), B and C (1,024 each), 4 B each.
    assert cost["bytes"] == 8 * values + 64 * 4 * (128 + 2 * 8192 + 2 * 1024)
    least, bound = kernels.roofline_seconds(cost, "TPU v5 lite")
    assert bound == "memory"                  # 0.75 FLOPs a byte against 240
    assert least == pytest.approx(541_622_272 / 819e9)
    # A padding lane is not counted: half the lanes, half the cost.
    half = kernels_ssm.ssm_state_update(32, 128, 64, 128, 8)
    assert half["bytes"] * 2 == cost["bytes"]


def _rec(first_s, last_s):
    r = Record(f"r{first_s}", -1, 0, first_s, first_s, 300, 200)
    r.status, r.prompt_tokens, r.completion_tokens = 200, 300, 200
    r.first_s, r.last_s, r.done_s = first_s, last_s, last_s
    r.pieces = [(r.first_s, 1), (r.last_s, 199)]
    return r


@pytest.fixture
def ctx():
    before = prom.parse(
        'jetstream:ssm_state_updates_total{form="kernel"} 1000.0\n'
        'jetstream:ssm_state_updates_total{form="gathered"} 500.0\n')
    after = prom.parse(
        'jetstream:ssm_state_updates_total{form="kernel"} 4000.0\n'
        'jetstream:ssm_state_updates_total{form="gathered"} 1500.0\n')
    with open(os.path.join(BENCH, "configs", "nemotron-3-super-cut.json")) as f:
        model = json.load(f)
    return layer.Context(
        records=[_rec(0.0, 9.0), _rec(0.5, 9.0), _rec(9.5, 9.9)],
        seconds=10.0, chips=1, engine_scrapes=[(before, after)],
        gateway_scrape=({}, {}), gauge_samples=[], traces=[],
        trace_span=None, model=model, device_kind="TPU v5 lite")


def test_the_kernels_share_of_the_updates(ctx):
    assert layer.read_metric("ssm_kernel_update_share", ctx) == pytest.approx(75.0)
    # A program without the counter (the parent): nothing to read, no error.
    ctx.engine_scrapes = [({}, {})]
    assert layer.read_metric("ssm_kernel_update_share", ctx) is None


def test_roofline_reader_on_a_canned_trace(ctx):
    assert layer.read_metric("ssm_decode_roofline", ctx) is None   # no trace
    ctx.traces = [{"devices": [{"window_s": 1.5, "busy_s": 1.4, "ops": {
        "%ssm_state_update.65": {"count": 40, "seconds": 0.002,
                                 "detail": "custom-call (f32[5,65,128,64,128])"},
        "custom-call.7": {"count": 40, "seconds": 0.002,
                          "detail": "jit(f)/ssm_state_update/pallas_call"},
        "fusion.9": {"count": 40, "seconds": 1.0, "detail": "dot"}},
        "idle_by_next_program": {}}]}]
    ctx.trace_span = (1.0, 2.5)
    share = layer.read_metric("ssm_decode_roofline", ctx)
    note = ctx.notes["ssm_state_update"]
    # Two requests decode through the slice; both spellings of the op count.
    assert note["calls"] == 80 and note["bound"] == "memory"
    assert note["mean_lanes"] == pytest.approx(2.0)
    least = (2 * 128 * 64 * 128 * 8
             + 2 * 4 * (128 + 2 * 8192 + 2 * 1024)) / 819e9
    assert note["least_seconds_per_call"] == pytest.approx(least)
    assert share == pytest.approx(100 * 80 * least / 0.004) and share < 100
    # The kernel is not in the trace (the parent, or the gathered form):
    # nothing to read.
    for name in ("%ssm_state_update.65", "custom-call.7"):
        ctx.traces[0]["devices"][0]["ops"].pop(name)
    assert layer.read_metric("ssm_decode_roofline", ctx) is None
    # A configuration without state-space layers: nothing, whatever the trace.
    ctx.model = {"num_attention_heads": 32, "num_key_value_heads": 8}
    assert layer.read_metric("ssm_decode_roofline", ctx) is None


def test_the_cell_reports_both_and_no_other_cell_does():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    added = {m["name"]: m for m in bench["per_layer"][-2:]}
    assert list(added) == ["ssm_kernel_update_share", "ssm_decode_roofline"]
    assert all(m["workloads"] == [CELL] for m in added.values())
    assert added["ssm_kernel_update_share"]["moves"] == "out_tokens_per_s"
    assert added["ssm_decode_roofline"]["moves"] == "tpot_p95_ms"
    assert added["ssm_decode_roofline"]["source"] == "device_trace"
