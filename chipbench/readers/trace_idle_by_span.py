"""Share (%) of the traced slice in which no operation ran on the device AND
the engine's loop was inside one of `spans` (its `engine.<phase>` host spans
in the same trace) - or, with `"no_span": true`, inside none of them. Mean
over the traced chips, like device_idle_share, of which these are the parts.

The reduction is chipbench/trace_host.py, run once a run as a program pinned
to the CPU on the directories trace_reduce.py was given; its whole table is
left in the notes. Nothing without a device trace, or where the trace holds
no `engine.*` span (a program from before they were added)."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
NOTE = "idle_by_host_span"


def table(ctx) -> list:
    if NOTE not in ctx.notes:
        dirs = [t["dir"] for t in ctx.traces if t.get("devices") and t.get("dir")]
        rows = []
        if dirs:
            done = subprocess.run(
                [sys.executable, os.path.join(os.path.dirname(HERE), "trace_host.py"),
                 *dirs], env=dict(os.environ, JAX_PLATFORMS="cpu"),
                capture_output=True, text=True, timeout=240)
            if done.returncode != 0:
                raise RuntimeError(f"trace_host.py failed: {done.stderr[-1500:]}")
            rows = [json.loads(line) for line in done.stdout.splitlines()
                    if line.startswith("{")]
        ctx.notes[NOTE] = rows
    return ctx.notes[NOTE]


def read(spec, ctx):
    shares = []
    for row in table(ctx):
        if not row["host"]["spans"]:
            continue
        for dev in row["devices"]:
            if dev["window_s"] <= 0:
                continue
            idle = (dev["idle_in_no_span_s"] if spec.get("no_span") else
                    sum(dev["idle_by_span_s"].get(s, 0.0) for s in spec["spans"]))
            shares.append(idle / dev["window_s"])
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
