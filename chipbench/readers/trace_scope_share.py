"""Share (%) of device time that the ops of the programs `programs` ("decode",
"prefill", "other") spent under the block scopes `scopes`: a list of scope
names, each also a prefix ("ffn.experts" takes "ffn.experts.glue" along),
or "unscoped", or "all". Over the seconds of those programs' ops (`"of":
"programs"`) or of every program's (`"of": "busy"`), control flow left out
of both; summed over the traced chips.

The reduction is chipbench/trace_scopes.py, run once a run as a program on the
directories trace_reduce.py was given; its whole table (program kind x scope:
calls, seconds, XLA's flops and bytes, the longest unscoped ops) is left in
the notes. Nothing without a device trace, where none of the named programs
ran in the slice, and -- for a metric that names scopes -- where the trace
holds no `blk.` scope at all: a program from before the scopes, or executables
served from a compile cache that such a tree filled (JAX leaves metadata out
of the cache's key; the note then says so)."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
NOTE = "device_time_by_scope"
UNSCOPED = "unscoped"


def table(ctx) -> list:
    if NOTE not in ctx.notes:
        dirs = [t["dir"] for t in ctx.traces if t.get("devices") and t.get("dir")]
        rows = []
        if dirs:
            done = subprocess.run(
                [sys.executable, os.path.join(os.path.dirname(HERE),
                                              "trace_scopes.py"), *dirs],
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
                capture_output=True, text=True, timeout=240)
            if done.returncode != 0:
                raise RuntimeError(f"trace_scopes.py failed: {done.stderr[-1500:]}")
            rows = [json.loads(line) for line in done.stdout.splitlines()
                    if line.startswith("{")]
        ctx.notes[NOTE] = rows
    return ctx.notes[NOTE]


def _under(scope: str, wanted) -> bool:
    if wanted == "all":
        return True
    if wanted == UNSCOPED:
        return scope == UNSCOPED
    return any(scope == w or scope.startswith(w + ".") for w in wanted)


def read(spec, ctx):
    devices = [d for t in table(ctx) for d in t["devices"]]
    if not devices:
        return None
    if spec["scopes"] != "all" and not any(d["scoped"] for d in devices):
        ctx.notes["device_time_by_scope: no blk. scope in the trace"] = (
            "the programs named no block: a tree without models/scopes.py, or "
            "executables from a compile cache filled by one")
        return None
    part = whole = 0.0
    for dev in devices:
        for row in dev["rows"]:
            mine = row["program"] in spec["programs"]
            if mine and _under(row["scope"], spec["scopes"]):
                part += row["seconds"]
            if mine or spec["of"] == "busy":
                whole += row["seconds"]
    if not whole:
        return None
    return 100.0 * part / whole
