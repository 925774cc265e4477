"""What the host was doing while the device was idle: the device trace's
gaps, attributed to the engine loop's own spans in the same trace.

The engine wraps each phase of its loop in a host span `engine.<phase>`
(llm_d_inference_scheduler_tpu/engine/core.py `_phase`); the profiler writes
them into the host plane of the trace that holds the device's operations, on
one clock. An idle gap is time inside the device's window (first operation's
start to last one's end) in which no "XLA Ops" event runs; each gap is cut
along the spans it meets, so one that straddles two spans counts under both,
in parts, and the rest under none.

Run as a program (pinned to the CPU, like trace_reduce.py) it prints one JSON
object per directory. The reduction, `idle_by_span`, is a pure function of
trace_reduce's plain plane structure with the host plane kept, and is tested
on a small recorded sample in that form (tests/data/trace_host_small.json).
"""

from __future__ import annotations

import glob
import json
import os
import sys

import trace_reduce

HOST_PLANE_PREFIX = "/host:"
SPAN_PREFIX = "engine."


def host_spans(planes: list[dict]) -> dict:
    """The `engine.*` events of the host planes: sorted (start, end, name),
    the lines (threads) they came from, seconds per name, and how much
    consecutive spans overlap (they never nest, so this should read 0)."""
    spans, lines = [], []
    for plane in planes:
        if not plane["name"].startswith(HOST_PLANE_PREFIX):
            continue
        for line in plane["lines"]:
            found = [(e[1], e[1] + e[2], e[0]) for e in line["events"]
                     if e[0].startswith(SPAN_PREFIX)]
            if found:
                lines.append(line["name"])
                spans += found
    spans.sort()
    by_name: dict[str, list] = {}
    for start, end, name in spans:
        row = by_name.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += (end - start) / 1e9
    overlap = sum(max(0, a[1] - b[0]) for a, b in zip(spans, spans[1:]))
    return {"intervals": spans, "lines": lines, "overlap_s": overlap / 1e9,
            "spans": {k: {"count": v[0], "seconds": v[1]}
                      for k, v in by_name.items()}}


def idle_by_span(planes: list[dict]) -> dict:
    host = host_spans(planes)
    spans = host.pop("intervals")
    devices = []
    for plane in planes:
        if not plane["name"].startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        ops = {ln["name"]: ln["events"] for ln in plane["lines"]}.get(
            trace_reduce.OPS_LINE)
        if not ops:
            continue
        busy_ns, merged = trace_reduce.union_seconds(
            [(e[1], e[1] + e[2]) for e in ops])
        by_span: dict[str, float] = {}
        under_none = 0.0
        i = 0
        for (_, gap_start), (gap_end, _) in zip(merged, merged[1:]):
            while i < len(spans) and spans[i][1] <= gap_start:
                i += 1
            covered, j = 0, i
            while j < len(spans) and spans[j][0] < gap_end:
                part = min(spans[j][1], gap_end) - max(spans[j][0], gap_start)
                if part > 0:
                    by_span[spans[j][2]] = by_span.get(spans[j][2], 0.0) + part / 1e9
                    covered += part
                j += 1
            under_none += (gap_end - gap_start - covered) / 1e9
        window_ns = merged[-1][1] - merged[0][0]
        devices.append({"plane": plane["name"], "window_s": window_ns / 1e9,
                        "busy_s": busy_ns / 1e9,
                        "idle_s": (window_ns - busy_ns) / 1e9,
                        "idle_by_span_s": by_span,
                        "idle_in_no_span_s": under_none})
    return {"host": host, "devices": devices}


def reduce_dir(trace_dir: str) -> dict:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {"dir": trace_dir, "host": {"spans": {}}, "devices": [],
                "error": "no xplane.pb"}
    planes = trace_reduce.load_planes(paths[-1], device_only=False)
    return dict(idle_by_span(planes), dir=trace_dir)


def main(argv: list[str]) -> int:
    for trace_dir in argv:
        print(json.dumps(reduce_dir(trace_dir)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
