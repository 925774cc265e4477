"""The readers of ISSUE 36 on hand-made scrapes and records: the highest
bucket of a histogram that filled in the window (`+Inf`, an unexposed
histogram and an unmoved one among them), one labelled counter's delta, and
the longest gap of one stream at the client; then each new metric file
through its reader."""

import json
import os

import pytest

import layer
import prom
from client import Record

CELLS = ["qwen3-4b.chat-steady", "mixtral-8x7b-cut.batch-full",
         "kimi-vl-a3b-cut.longdoc-batch", "nemotron-3-super-cut.reason-batch"]
NEW = ["eng_longest_chunk_ms", "eng_stall_device_wait_s", "eng_stall_host_s",
       "eng_event_loop_lag_ms", "eng_event_loop_lag_max_ms",
       "gw_event_loop_lag_max_ms", "gw_stream_gap_max_ms",
       "client_stream_gap_max_ms", "kv_pool_usage_pct", "kv_pool_usage_pct.chat"]


def hist(name, cumulative, total=0.0):
    """Exposition text of one histogram from {le: cumulative count}."""
    lines = [f'{name}_bucket{{le="{le}"}} {n}' for le, n in cumulative.items()]
    count = list(cumulative.values())[-1]
    return "\n".join(lines + [f"{name}_count {count}", f"{name}_sum {total}"]) + "\n"


CHUNK = "jetstream:decode_step_duration_seconds"
LAG = "jetstream:event_loop_lag_seconds"
ENGINE_BEFORE = (
    hist(CHUNK, {"0.1": 5.0, "0.25": 50.0, "0.5": 51.0, "2.5": 51.0, "+Inf": 51.0})
    + hist(LAG, {"0.001": 100.0, "0.01": 100.0, "+Inf": 100.0}, 0.05)
    + 'jetstream:loop_stall_seconds_total{where="device_wait"} 0.0\n'
    + 'jetstream:loop_stall_seconds_total{where="host"} 0.25\n'
    + "jetstream:kv_cache_usage_perc 0.25\n")
# In the window: 200 chunks under 0.25 s, one between 0.5 and 2.5 s; the one
# beyond 0.25 s that was there before did not move.
ENGINE_AFTER = (
    hist(CHUNK, {"0.1": 5.0, "0.25": 250.0, "0.5": 251.0, "2.5": 252.0, "+Inf": 252.0})
    + hist(LAG, {"0.001": 500.0, "0.01": 600.0, "+Inf": 600.0}, 1.05)
    + 'jetstream:loop_stall_seconds_total{where="device_wait"} 1.75\n'
    + 'jetstream:loop_stall_seconds_total{where="host"} 0.25\n'
    + "jetstream:kv_cache_usage_perc 0.75\n")
GATEWAY_BEFORE = (
    hist("router_loop_lag_seconds", {"0.1": 7.0, "0.5": 7.0, "+Inf": 7.0})
    + hist("router_stream_gap_max_seconds", {"0.25": 0.0, "0.5": 0.0, "+Inf": 0.0}))
GATEWAY_AFTER = (
    hist("router_loop_lag_seconds", {"0.1": 9.0, "0.5": 9.0, "+Inf": 10.0})
    + hist("router_stream_gap_max_seconds", {"0.25": 0.0, "0.5": 0.0, "+Inf": 0.0}))


def rec(rid, due, arrivals):
    r = Record(rid, 0, 0, due, due + 0.002, 10, len(arrivals))
    r.status, r.prompt_tokens, r.completion_tokens = 200, 10, len(arrivals)
    r.pieces = [(t, 1) for t in arrivals]
    r.first_s, r.last_s, r.done_s = arrivals[0], arrivals[-1], arrivals[-1]
    return r


@pytest.fixture
def ctx():
    b, a = prom.parse(ENGINE_BEFORE), prom.parse(ENGINE_AFTER)
    records = [
        rec("ramp", -2.0, [-1.5, -0.25, 0.5]),        # 1.25 s, before the window
        rec("quiet", 1.0, [1.25, 1.5, 1.75]),
        rec("stopped", 2.0, [2.5, 2.75, 4.75, 5.0]),  # 2 s, inside it
        rec("late", 9.0, [9.5, 13.5])]                # 4 s, ends after it
    return layer.Context(
        records=records, seconds=10.0, chips=1, engine_scrapes=[(b, a)],
        gateway_scrape=(prom.parse(GATEWAY_BEFORE), prom.parse(GATEWAY_AFTER)),
        gauge_samples=[(0.1, [b]), (0.3, [a])], traces=[], trace_span=None,
        model={}, device_kind="cpu")


@pytest.mark.parametrize("name, expected", [
    ("eng_longest_chunk_ms", 2500.0),       # the 0.5-2.5 s bucket rose by one
    ("eng_stall_device_wait_s", 1.75),
    ("eng_stall_host_s", 0.0),              # exposed and unmoved: 0, not absent
    ("eng_event_loop_lag_ms", 2.0),         # (1.05 - 0.05) s / 500
    ("eng_event_loop_lag_max_ms", 10.0),    # 100 of the 500 in 1-10 ms
    ("gw_event_loop_lag_max_ms", 500.0),    # +Inf rose: the last finite bound
    ("gw_stream_gap_max_ms", 0.0),          # exposed, nothing observed
    ("client_stream_gap_max_ms", 2000.0),
    ("kv_pool_usage_pct", 50.0),
    ("kv_pool_usage_pct.chat", 50.0),
])
def test_each_new_metric_file_through_its_reader(ctx, name, expected):
    assert layer.read_metric(name, ctx) == pytest.approx(expected)


def test_a_program_without_the_series_leaves_the_metric_out(ctx):
    """The parent of ISSUE 36: the chunk's histogram and the gateway's lag
    are there (with other buckets), the rest is not. Nothing raises."""
    keep = ("jetstream:decode_step", "jetstream:kv_cache")
    ctx.engine_scrapes = [tuple({k: v for k, v in s.items()
                                 if k[0].startswith(keep)} for s in pair)
                          for pair in ctx.engine_scrapes]
    ctx.gateway_scrape = tuple({k: v for k, v in s.items()
                                if k[0].startswith("router_loop_lag")}
                               for s in ctx.gateway_scrape)
    got = {n: layer.read_metric(n, ctx) for n in NEW}
    assert [n for n, v in got.items() if v is None] == [
        "eng_stall_device_wait_s", "eng_stall_host_s", "eng_event_loop_lag_ms",
        "eng_event_loop_lag_max_ms", "gw_stream_gap_max_ms"]


def test_top_bucket_notes_an_observation_beyond_the_last_bound(ctx):
    layer.read_metric("gw_event_loop_lag_max_ms", ctx)
    assert "beyond the last finite bound 0.5" in \
        ctx.notes["top bucket of router_loop_lag_seconds in 10 s"]
    layer.read_metric("eng_longest_chunk_ms", ctx)
    assert list(ctx.notes) == ["top bucket of router_loop_lag_seconds in 10 s"]


def test_top_bucket_sums_replicas_before_it_looks(ctx):
    """A bucket that rose on one replica alone is still the top."""
    b, a = ctx.engine_scrapes[0]
    ctx.engine_scrapes = [(b, a), (b, b)]
    assert layer.read_metric("eng_longest_chunk_ms", ctx) == 2500.0
    ctx.engine_scrapes = [(b, b), (b, b)]
    assert layer.read_metric("eng_longest_chunk_ms", ctx) == 0.0


def test_client_gap_names_the_request_and_the_moment(ctx):
    layer.read_metric("client_stream_gap_max_ms", ctx)
    assert ctx.notes["longest stream gap at the client in 10 s"] == {
        "rid": "stopped", "from_s": 2.75, "to_s": 4.75,
        "late_max_ms": pytest.approx(2.0)}
    ctx.records = [r for r in ctx.records if r.rid == "late"]
    assert layer.read_metric("client_stream_gap_max_ms", ctx) is None


def test_benchmark_json_lists_the_new_metrics_for_their_cells():
    with open(os.path.join(os.path.dirname(layer.HERE), "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert set(NEW) <= set(per_layer)      # wherever a later PR's entries land
    for name in NEW[:-2]:
        assert per_layer[name]["workloads"] == CELLS
        assert per_layer[name]["moves"] == "tpot_p95_ms"
    # One quantity, split as `xla_builds_in_window` is: chat-steady reports
    # no out_tokens_per_s for it to move.
    assert per_layer["kv_pool_usage_pct"]["workloads"] == CELLS[1:]
    assert per_layer["kv_pool_usage_pct.chat"]["workloads"] == CELLS[:1]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    import rehearsal

    root = str(tmp_path_factory.mktemp("bench-stalls"))
    rehearsal.make_copy(root)
    return root


@pytest.mark.parametrize("cell, kv", [
    ("tiny.tiny-chat", "kv_pool_usage_pct.chat"),
    ("tiny.tiny-batch", "kv_pool_usage_pct")])
def test_a_cpu_rehearsal_prints_every_new_metric_as_a_number(copy, cell, kv):
    """None of the nine is left out of an ordinary run's line (a null on a
    cell's newest line would read as a metric done away with), the stall
    counters read 0.0 and not nothing, and the inside witnesses agree with
    the outside one to a bucket."""
    from test_run import no_leftovers, run_py

    rc, lines = run_py(copy, "--workload", cell, "--seed", str(2 ** 31 + 36),
                       "--seconds", "5", "--trace", "2", "--platform", "cpu",
                       timeout=400)
    assert rc == 0, lines[-3:]
    m = json.loads(lines[-1])["metrics"]
    names = NEW[:-2] + [kv]
    assert [n for n in names if not isinstance(m.get(n, {}).get("value"), float)] == []
    assert m["eng_stall_device_wait_s"]["value"] == 0.0
    assert m["eng_stall_host_s"]["value"] == 0.0
    assert 0 < m[kv]["value"] < 100
    assert m["eng_longest_chunk_ms"]["value"] > 0
    assert m["client_stream_gap_max_ms"]["value"] <= \
        m["gw_stream_gap_max_ms"]["value"] + 50
    assert no_leftovers()
