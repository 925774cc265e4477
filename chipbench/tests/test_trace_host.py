"""Device idle time by what the host was doing: trace_host.idle_by_span on a
hand-made trace whose answer is plain arithmetic, then on a small recorded
one - 30 ms cut from a v5e `--trace 2` run of qwen3-4b.chat-steady (PR 24):
the end of a decode chunk, its booking, an admission with a prefill, and the
start of the next chunk, in trace_reduce's plain form with the host plane
kept (`engine.*` events only)."""

import json
import os

import pytest

import layer
import trace_host
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def planes_of(ops, spans, extra_host=()):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [[f"%op.{i}", a, b - a, "fusion x"]
                                           for i, (a, b) in enumerate(ops)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [[n, a, b - a, " "] for n, a, b in spans]
             + list(extra_host)},
            {"name": "other-thread", "events": [["PjitFunction(f)", 0, 50, " "]]}]},
    ]


def test_a_gap_is_cut_along_the_spans_it_meets():
    # Device busy 0-100, 400-500, 520-1000 (ns): gaps 100-400 and 500-520.
    ops = [(0, 100), (400, 500), (520, 1000), (30, 60)]     # one nested op
    spans = [("engine.decode_wait", 0, 120),       # 20 of the first gap
             ("engine.decode_book", 130, 300),     # 170 of it
             ("engine.admit", 300, 350),           # 50; then 350-400 under none
             ("engine.decode_dispatch", 505, 600)]  # 15 of the second gap
    got = trace_host.idle_by_span(planes_of(
        ops, spans, extra_host=[["PjRtCompute", 100, 300, " "]]))
    dev = got["devices"][0]
    assert dev["window_s"] == pytest.approx(1000e-9)
    assert dev["busy_s"] == pytest.approx(680e-9)
    assert dev["idle_s"] == pytest.approx(320e-9)
    assert dev["idle_by_span_s"] == pytest.approx({
        "engine.decode_wait": 20e-9, "engine.decode_book": 170e-9,
        "engine.admit": 50e-9, "engine.decode_dispatch": 15e-9})
    # 120-130 and 350-400 of the first gap, 500-505 of the second.
    assert dev["idle_in_no_span_s"] == pytest.approx(65e-9)
    assert sum(dev["idle_by_span_s"].values()) + dev["idle_in_no_span_s"] == \
        pytest.approx(dev["idle_s"])
    host = got["host"]
    assert host["lines"] == ["python"] and host["overlap_s"] == 0
    assert host["spans"]["engine.decode_book"] == {
        "count": 1, "seconds": pytest.approx(170e-9)}
    assert "PjRtCompute" not in host["spans"]


def test_overlapping_spans_are_reported_and_no_device_plane_reduces_to_nothing():
    spans = [("engine.admit", 0, 100), ("engine.decode_prepare", 90, 200)]
    got = trace_host.idle_by_span(planes_of([(0, 10), (300, 400)], spans))
    assert got["host"]["overlap_s"] == pytest.approx(10e-9)
    cpu_only = trace_host.idle_by_span(planes_of([], spans)[1:])
    assert cpu_only["devices"] == [] and cpu_only["host"]["spans"]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_host_small.json")) as f:
        return json.load(f)


def test_recorded_sample_agrees_with_trace_reduce_and_adds_up(recorded):
    got = trace_host.idle_by_span(recorded)
    dev = got["devices"][0]
    plain = trace_reduce.reduce_planes(recorded)[0]
    assert dev["window_s"] == pytest.approx(plain["window_s"])
    assert dev["busy_s"] == pytest.approx(plain["busy_s"])
    assert sum(dev["idle_by_span_s"].values()) + dev["idle_in_no_span_s"] == \
        pytest.approx(dev["idle_s"], rel=1e-9)
    assert got["host"]["overlap_s"] == 0 and len(got["host"]["lines"]) == 1
    assert set(dev["idle_by_span_s"]) <= set(got["host"]["spans"])


def test_recorded_sample_has_a_gap_across_spans_and_one_under_none(recorded):
    """The one long gap, from the chunk's last operation to the prefill the
    next admission dispatches, straddles the booking, finalize_prefills,
    housekeeping and most of admit. Its first 1.2 ms lie under no span HERE:
    they are the readback's latency, inside a decode_wait span that began
    300 ms before the cut and so is not in the sample; between two spans
    the loop runs a few lines under none, a microsecond at a time."""
    got = trace_host.idle_by_span(recorded)
    dev, host = got["devices"][0], got["host"]["spans"]
    by = dev["idle_by_span_s"]
    assert "engine.decode_wait" not in host
    # Spans the gap covers whole count whole; admit only until its prefill runs.
    for whole in ("engine.decode_book", "engine.finalize_prefills",
                  "engine.housekeeping"):
        assert by[whole] == pytest.approx(host[whole]["seconds"])
    assert by["engine.decode_book"] == pytest.approx(0.0049266)
    assert 0.9 * host["engine.admit"]["seconds"] < by["engine.admit"] \
        < host["engine.admit"]["seconds"]
    assert by["engine.decode_dispatch"] < 1e-6      # the device is busy by then
    assert dev["idle_in_no_span_s"] == pytest.approx(0.001374089)
    assert dev["idle_s"] == pytest.approx(0.013465515)


def reader_ctx(rows):
    ctx = layer.Context(records=[], seconds=1.0, chips=1, engine_scrapes=[],
                        gateway_scrape=({}, {}), gauge_samples=[],
                        traces=[{"devices": [{}], "dir": "unused"}],
                        trace_span=(0.0, 1.0), model={}, device_kind="TPU v5 lite")
    ctx.notes["idle_by_host_span"] = rows      # as trace_host.py printed them
    return ctx


def test_the_three_shares_through_their_metric_files(recorded):
    row = trace_host.idle_by_span(recorded)
    dev = row["devices"][0]
    ctx = reader_ctx([row])
    book = layer.read_metric("idle_in_book_pct", ctx)
    prepare = layer.read_metric("idle_in_prepare_pct", ctx)
    none = layer.read_metric("idle_unattributed_pct", ctx)
    waits = sum(dev["idle_by_span_s"].get(f"engine.{p}", 0.0)
                for p in ("decode_wait", "idle_wait"))
    assert none == pytest.approx(100 * dev["idle_in_no_span_s"] / dev["window_s"])
    # With the busy share, the three are the slice less the gaps inside waits.
    assert book + prepare + none + 100 * dev["busy_s"] / dev["window_s"] == \
        pytest.approx(100 * (1 - waits / dev["window_s"]))
    # A program from before the spans were added: nothing to read, no error.
    old = reader_ctx([dict(row, host={"spans": {}, "lines": [], "overlap_s": 0})])
    assert layer.read_metric("idle_in_book_pct", old) is None
    assert layer.read_metric("idle_unattributed_pct", reader_ctx([])) is None
