"""From a profiler trace to numbers: device busy time, time per operation,
idle gaps. Kept with the benchmark so every PR computes them the same way.

Run as a program (after the servers are gone, pinned to the CPU so it can
never take a chip) it reads each `<dir>/plugins/profile/*/*.xplane.pb` with
`jax.profiler.ProfileData` and prints one JSON object per directory. The
reduction itself, `reduce_planes`, is a pure function of a plain structure
    [{"name": plane, "lines": [{"name": line, "events": [[name, start_ns,
      duration_ns, detail], ...]}]}]
so it is tested on a small recorded trace in that form
(`tests/data/trace_small.json`, cut from a v5e run).

A TPU trace has one plane per chip, "/device:TPU:<n>", whose line "XLA Ops"
holds one event per executed HLO instruction, named by the instruction's
whole text ("%fusion.3 = bf16[...] fusion(...), kind=kLoop, ..."), and "XLA
Modules" one per executed program ("jit__decode_chunk_impl(<id>)"). An
operation is keyed here by the instruction's name ("%fusion.3"); its detail
is its opcode and the start of that text ("fusion bf16[...] fusion(...").
A metric's `op_regex` is matched against both. Busy time is the union of the "XLA Ops" intervals. A trace
with no such plane (a CPU run) reduces to nothing: there is no device number
to report.
"""

from __future__ import annotations

import glob
import json
import os
import sys

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DETAIL_CHARS = 300


CONTROL_FLOW = ("while", "conditional", "call")


def opcode(text: str) -> str:
    """The opcode of an HLO instruction's text, "<shape> <opcode>(<operands>)
    ...", where the shape may be a tuple with nested parentheses."""
    i, depth = 0, 0
    while i < len(text):
        c = text[i]
        depth += c in "([{"
        depth -= c in ")]}"
        if c == " " and depth == 0:
            break
        i += 1
    rest = text[i + 1:]
    return rest.split("(", 1)[0].strip()


def load_planes(path: str, device_only: bool = True) -> list[dict]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if device_only and not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                name, _, text = ev.name.partition(" = ")
                events.append([name, ev.start_ns, ev.duration_ns,
                               f"{opcode(text)} {text[:DETAIL_CHARS]}"])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union_seconds(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """Total length of the union of [start, end) intervals, and the merged
    intervals, in the input's unit."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def reduce_plane(plane: dict) -> dict | None:
    lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
    ops = lines.get(OPS_LINE)
    if not ops:
        return None
    busy_ns, merged = union_seconds([(e[1], e[1] + e[2]) for e in ops])
    first, last = merged[0][0], merged[-1][1]
    by_name: dict[str, list] = {}
    for name, _, dur, detail in ops:
        row = by_name.setdefault(name, [0, 0.0, detail])
        row[0] += 1
        row[1] += dur / 1e9
    # Each idle gap is named by the program that ran next: what the device
    # was waiting for. What the host did meanwhile needs annotations inside
    # the program (PERF.md, Open questions).
    modules = sorted((e[1], e[0]) for e in lines.get(MODULES_LINE, []))
    gaps: dict[str, float] = {}
    longest = 0.0
    m = 0
    for (_, end), (start, _) in zip(merged, merged[1:]):
        while m < len(modules) and modules[m][0] < start - 1000:
            m += 1
        nxt = modules[m][1] if m < len(modules) else "unknown"
        gaps[f"before {nxt}"] = gaps.get(f"before {nxt}", 0.0) + (start - end) / 1e9
        longest = max(longest, (start - end) / 1e9)
    return {"plane": plane["name"], "window_s": (last - first) / 1e9,
            "busy_s": busy_ns / 1e9, "longest_gap_s": longest,
            # A while loop's event spans its body's: it counts for busy time
            # and is marked, so a sum over operations can leave it out.
            "ops": {k: {"count": v[0], "seconds": v[1], "detail": v[2],
                        "control_flow": v[2].split(" ", 1)[0] in CONTROL_FLOW}
                    for k, v in by_name.items()},
            "modules": _module_totals(lines.get(MODULES_LINE, [])),
            "idle_by_next_program": gaps}


def _module_totals(events: list) -> dict:
    out: dict[str, list] = {}
    for name, _, dur, _ in events:
        row = out.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += dur / 1e9
    return {k: {"count": v[0], "seconds": v[1]} for k, v in out.items()}


def reduce_planes(planes: list[dict]) -> list[dict]:
    return [r for r in (reduce_plane(p) for p in planes
                        if p["name"].startswith(DEVICE_PLANE_PREFIX))
            if r is not None]


def reduce_dir(trace_dir: str) -> dict:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {"dir": trace_dir, "devices": [], "error": "no xplane.pb"}
    out = {"dir": trace_dir, "bytes": os.path.getsize(paths[-1]),
           "devices": reduce_planes(load_planes(paths[-1]))}
    done = os.path.join(trace_dir, "done")
    if os.path.exists(done):
        with open(done) as f:
            out.update(json.load(f))
    return out


def main(argv: list[str]) -> int:
    for trace_dir in argv:
        print(json.dumps(reduce_dir(trace_dir)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
