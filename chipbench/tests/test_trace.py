"""The reduction from trace to numbers, on a small recorded trace: 30 ms cut
from a v5e run of qwen3-4b.chat-steady (PR 23) - the end of a decode chunk,
the gap after it, a prefill of bucket 256 and the start of the next program -
in trace_reduce's plain form. The expected values are recomputed here the
slow way, or were read off the recording."""

import json
import os

import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def planes():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        return json.load(f)


def test_only_device_planes_are_reduced(planes):
    assert [p["name"] for p in planes] == ["/device:TPU:0", "/host:CPU"]
    devices = trace_reduce.reduce_planes(planes)
    assert [d["plane"] for d in devices] == ["/device:TPU:0"]
    assert trace_reduce.reduce_planes([planes[1]]) == []     # a CPU run: nothing


def test_busy_is_the_union_of_op_intervals(planes):
    d = trace_reduce.reduce_planes(planes)[0]
    ops = [ln for ln in planes[0]["lines"] if ln["name"] == "XLA Ops"][0]["events"]
    first = min(e[1] for e in ops)
    last = max(e[1] + e[2] for e in ops)
    # The slow way: mark every microsecond in which some operation ran.
    ticks = set()
    for _, start, dur, _ in ops:
        ticks.update(range(int(start // 1000), int((start + dur) // 1000) + 1))
    assert d["window_s"] == pytest.approx((last - first) / 1e9)
    assert d["busy_s"] == pytest.approx(len(ticks) * 1e-6, rel=0.05)
    assert d["busy_s"] < d["window_s"]
    # Nested events (a while loop and its body) are not counted twice.
    assert d["busy_s"] < sum(e[2] for e in ops) / 1e9


def test_ops_are_keyed_by_instruction_and_loops_are_marked(planes):
    d = trace_reduce.reduce_planes(planes)[0]
    kernel = {k: v for k, v in d["ops"].items()
              if k.startswith("%paged_decode_attention_pallas")}
    assert sum(v["count"] for v in kernel.values()) == 4
    assert all(v["detail"].startswith("custom-call ") for v in kernel.values())
    loops = [k for k, v in d["ops"].items() if v["control_flow"]]
    assert {k.split(".")[0] for k in loops} == {"%while", "%conditional"}
    assert sum(v["count"] for v in d["ops"].values()) == 1726


def test_gaps_are_named_by_the_program_that_ran_next(planes):
    d = trace_reduce.reduce_planes(planes)[0]
    gaps = d["idle_by_next_program"]
    assert sum(gaps.values()) == pytest.approx(d["window_s"] - d["busy_s"], rel=1e-6)
    longest = max(gaps, key=gaps.get)
    # After the decode chunk the device waits for the host to read the chunk
    # back and dispatch; the first thing it then runs is the key split.
    assert longest.startswith("before jit__threefry_split")
    assert d["longest_gap_s"] == pytest.approx(0.005491336)


def test_opcode_of_instruction_text():
    assert trace_reduce.opcode(
        "(s32[]{:T(128)}, bf16[8,2560]{1,0:T(8,128)(2,1)S(1)}) while((s32[]) %t), "
        "condition=%c, body=%b") == "while"
    assert trace_reduce.opcode(
        'bf16[8,32,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call(s32[1024]{0} %b), '
        'custom_call_target="tpu_custom_call"') == "custom-call"
    assert trace_reduce.union_seconds([(0, 2), (1, 3), (5, 6)])[0] == 4
