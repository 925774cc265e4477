"""Device time by program and block: where a step's time goes, booked by what
the program says of itself.

The models wrap their blocks in named scopes (`blk.attn.core`, ...:
llm_d_inference_scheduler_tpu/models/scopes.py). A scope lands in the `op_name`
of every HLO instruction traced under it, and the profiler keeps that path
beside each executed op: the op's EVENT METADATA holds the stats `tf_op`
("jit(_decode_chunk_impl)/while/body/closed_call/blk.ffn.experts/dot_general:"),
`program_id` (the id in the name of the "XLA Modules" event the op ran in,
"jit__decode_chunk_impl(<id>)"), `hlo_category`, `flops`, `bytes_accessed`
and `source`. `jax.profiler.ProfileData`, which trace_reduce.py reads with,
gives an event's OWN stats only, so this file reads the `.xplane.pb` itself:
`read_planes` walks the protobuf wire format for the handful of fields it
needs (XSpace -> planes -> event_metadata / stat_metadata / lines -> events)
and nothing else, with no dependency beyond the standard library.

Run as a program (after the servers are gone, like trace_reduce.py and
trace_host.py; it never touches JAX) on the directories trace_reduce.py was
given, or by an operator on any `--profile-dir`:

    python chipbench/trace_scopes.py DIR [DIR ...]      # one JSON object a DIR
    python chipbench/trace_scopes.py --text DIR          # a table to read

The reduction, `by_program_and_scope`, is a pure function of a plain structure
    [{"name": plane, "modules": [[name, start_ps, duration_ps], ...],
      "ops": [[metadata id, start_ps, duration_ps], ...],
      "op_metadata": {id: {"name": instruction text, "tf_op", "program_id",
                           "hlo_category", "flops", "bytes_accessed",
                           "source"}}}]
tested on a small recorded sample in that form
(tests/data/trace_scopes_small.json, cut from a v5e run). It books every
"XLA Ops" event that is not control flow (a `while` spans its body) to
(program kind, scope): kind from the op's `program_id` joined to the module
names (`decode`: the fused decode chunk; `prefill`: the prefill, prefix-prefill
and multimodal-prefill programs; `other`: everything else the engine runs),
scope the LAST `blk.<name>` component of `tf_op`, else `unscoped`. `flops` and
`bytes_accessed` are XLA's own cost model summed over calls: 0 for a Pallas
call, whose cost XLA cannot see (the benchmark's `*_roofline` metrics count
those from shapes).
"""

from __future__ import annotations

import glob
import json
import os
import re
import struct
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_reduce  # noqa: E402

SCOPE_PREFIX = "blk."
UNSCOPED = "unscoped"
KINDS = ("decode", "prefill", "other")
_KIND_OF = ((re.compile(r"^jit__decode_chunk_impl\b"), "decode"),
            (re.compile(r"^jit_(prefix_|mm_)?prefill_"), "prefill"))
# The stats of an op's event metadata that the table is made of.
_OP_STATS = ("tf_op", "program_id", "hlo_category", "flops", "bytes_accessed",
             "source")
LONGEST_UNSCOPED = 5


# ---- the wire format -----------------------------------------------------------

def _varint(buf, pos: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, pos
        shift += 7


def _fields(buf, pos: int, end: int):
    """(field number, wire type, value) of a message's fields: an int for a
    varint, 8 or 4 raw bytes for a fixed one, (start, end) for a
    length-delimited one."""
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
            yield key >> 3, 0, value
        elif wire == 2:
            size, pos = _varint(buf, pos)
            yield key >> 3, 2, (pos, pos + size)
            pos += size
        elif wire == 1:
            yield key >> 3, 1, buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            yield key >> 3, 5, buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an xplane")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, span, stat_names: dict):
    """An XStat as (its name, its value): a number, a string, or the string a
    `ref_value` names in the plane's stat metadata."""
    name, value = None, None
    for no, wire, v in _fields(buf, *span):
        if no == 1:
            name = stat_names.get(v)
        elif no == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif no == 3:
            value = v
        elif no == 4:
            value = _signed(v)
        elif no == 5:
            value = _text(buf, v)
        elif no == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_value(buf, span):
    """The value of a protobuf map entry (field 2), as a span."""
    for no, _, v in _fields(buf, *span):
        if no == 2:
            return v
    return None


def _plane(buf, span) -> dict | None:
    """One XPlane as the plain structure of the module's docstring; None for
    a plane that is no TPU's."""
    name, lines, event_md, stat_md = "", [], [], []
    for no, _, v in _fields(buf, *span):
        if no == 2:
            name = _text(buf, v)
        elif no == 3:
            lines.append(v)
        elif no == 4:
            event_md.append(v)
        elif no == 5:
            stat_md.append(v)
    if not name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
        return None
    stat_names = {}
    for entry in stat_md:
        sid, sname = 0, ""
        for no, _, v in _fields(buf, *_map_value(buf, entry)):
            if no == 1:
                sid = v
            elif no == 2:
                sname = _text(buf, v)
        stat_names[sid] = sname
    metadata = {}
    for entry in event_md:
        mid, row = 0, {}
        for no, _, v in _fields(buf, *_map_value(buf, entry)):
            if no == 1:
                mid = v
            elif no == 2:
                row["name"] = _text(buf, v)
            elif no == 5:
                sname, value = _stat(buf, v, stat_names)
                if sname in _OP_STATS:
                    row[sname] = value
        metadata[mid] = row
    out = {"name": name, "modules": [], "ops": [], "op_metadata": {}}
    for span in lines:
        line_name, t0_ns, events = "", 0, []
        for no, _, v in _fields(buf, *span):
            if no == 2:
                line_name = _text(buf, v)
            elif no == 3:
                t0_ns = _signed(v)
            elif no == 4:
                events.append(v)
        if line_name not in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
            continue
        for ev in events:
            mid = offset = duration = 0
            for no, wire, v in _fields(buf, *ev):
                if wire:
                    continue
                if no == 1:
                    mid = v
                elif no == 2:
                    offset = v
                elif no == 3:
                    duration = v
            start = t0_ns * 1000 + offset
            if line_name == trace_reduce.OPS_LINE:
                out["ops"].append([mid, start, duration])
                out["op_metadata"].setdefault(str(mid), metadata.get(mid, {}))
            else:
                out["modules"].append(
                    [metadata.get(mid, {}).get("name", ""), start, duration])
    return out


def read_planes(path: str) -> list[dict]:
    """The TPU planes of an `.xplane.pb`, each with its executed programs,
    its executed ops and the metadata of those ops."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for no, wire, v in _fields(buf, 0, len(buf)):
        if no == 1 and wire == 2:
            plane = _plane(buf, v)
            if plane is not None:
                planes.append(plane)
    return planes


# ---- the reduction -------------------------------------------------------------

def program_kind(module_name: str) -> str:
    """`decode`, `prefill` or `other` of a program's name as the "XLA
    Modules" line has it ("jit_prefill_b256(<id>)") or as `tf_op` starts
    ("jit(prefill_b256)")."""
    name = re.sub(r"^jit\(([^)]*)\)$", r"jit_\1", module_name)
    for pattern, kind in _KIND_OF:
        if pattern.search(name):
            return kind
    return "other"


def scope_of(tf_op: str) -> str:
    """The innermost `blk.` component of an op's framework path, without the
    prefix; `unscoped` where there is none."""
    found = [part for part in tf_op.rstrip(":").split("/")
             if part.startswith(SCOPE_PREFIX)]
    return found[-1][len(SCOPE_PREFIX):] if found else UNSCOPED


def _is_control_flow(instruction: str) -> bool:
    _, _, text = instruction.partition(" = ")
    return trace_reduce.opcode(text) in trace_reduce.CONTROL_FLOW


def reduce_plane(plane: dict) -> dict:
    programs = {}
    for name, _, _ in plane["modules"]:
        found = re.search(r"\((\d+)\)$", name)
        if found:
            programs[int(found.group(1))] = name[:found.start()]
    # Per op metadata: (kind, scope, control flow), worked out once.
    booked = {}
    for mid, md in plane["op_metadata"].items():
        tf_op = md.get("tf_op") or ""
        program = programs.get(md.get("program_id")) or tf_op.split("/")[0]
        booked[mid] = (program_kind(program), scope_of(tf_op),
                       _is_control_flow(md.get("name", "")), program)
    rows: dict[tuple, list] = {}
    by_program: dict[str, list] = {}
    unscoped: dict[tuple, list] = {}
    control_s = 0.0
    for mid, _, duration in plane["ops"]:
        kind, scope, control, program = booked[str(mid)]
        seconds = duration / 1e12
        if control:
            control_s += seconds
            continue
        md = plane["op_metadata"][str(mid)]
        row = rows.setdefault((kind, scope), [0, 0.0, 0, 0])
        row[0] += 1
        row[1] += seconds
        row[2] += md.get("flops") or 0
        row[3] += md.get("bytes_accessed") or 0
        total = by_program.setdefault(program, [kind, 0, 0.0])
        total[1] += 1
        total[2] += seconds
        if scope == UNSCOPED:
            key = (kind, md.get("name", "").partition(" = ")[0],
                   md.get("tf_op") or "", md.get("source") or "")
            one = unscoped.setdefault(key, [0, 0.0])
            one[0] += 1
            one[1] += seconds
    op_s = sum(r[1] for r in rows.values())
    busy_ns, _ = trace_reduce.union_seconds(
        [(start / 1e3, (start + duration) / 1e3)
         for _, start, duration in plane["ops"]])
    longest = sorted(unscoped.items(), key=lambda kv: -kv[1][1])
    return {
        "plane": plane["name"],
        # Whether the programs named any block: a tree from before the
        # scopes, or executables out of a compile cache that such a tree
        # filled (JAX leaves metadata out of the cache's key), name none.
        "scoped": any(scope != UNSCOPED for _, scope in rows),
        # The rows add up to op_seconds: every op that is not control flow,
        # once. busy_s is trace_reduce's union of the same line's intervals,
        # control flow among them.
        "op_seconds": op_s, "busy_s": busy_ns / 1e9,
        "control_flow_seconds": control_s,
        "rows": [{"program": kind, "scope": scope, "calls": r[0],
                  "seconds": r[1], "share_of_ops_pct": 100.0 * r[1] / op_s,
                  "xla_flops": r[2], "xla_bytes_accessed": r[3]}
                 for (kind, scope), r in sorted(
                     rows.items(), key=lambda kv: (KINDS.index(kv[0][0]),
                                                   -kv[1][1]))],
        "programs": [{"program": name, "kind": v[0], "calls": v[1],
                      "seconds": v[2]}
                     for name, v in sorted(by_program.items(),
                                           key=lambda kv: -kv[1][2])],
        "longest_unscoped": [
            {"program": kind, "op": op, "tf_op": tf_op, "source": source,
             "calls": v[0], "seconds": v[1]}
            for (kind, op, tf_op, source), v in longest[:LONGEST_UNSCOPED]],
    }


def by_program_and_scope(planes: list[dict]) -> list[dict]:
    return [reduce_plane(p) for p in planes if p["ops"]]


def reduce_dir(trace_dir: str) -> dict:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {"dir": trace_dir, "devices": [], "error": "no xplane.pb"}
    return {"dir": trace_dir, "bytes": os.path.getsize(paths[-1]),
            "devices": by_program_and_scope(read_planes(paths[-1]))}


# ---- for a reader of the table -------------------------------------------------

def as_text(table: dict) -> str:
    out = [f"{table['dir']} ({table.get('bytes', 0)} bytes)"]
    for dev in table["devices"]:
        out.append(
            f"{dev['plane']}: {dev['op_seconds']:.4f} s of ops that are not "
            f"control flow (the rows add up to it); busy {dev['busy_s']:.4f} s"
            f" by trace_reduce's union; scoped: {dev['scoped']}")
        out.append(f"  {'program':8} {'scope':18} {'calls':>8} {'seconds':>9} "
                   f"{'of ops':>7} {'of kind':>7}  xla GFLOP / GB (0: Pallas)")
        of_kind = {k: sum(r["seconds"] for r in dev["rows"]
                          if r["program"] == k) for k in KINDS}
        for r in dev["rows"]:
            out.append(
                f"  {r['program']:8} {r['scope']:18} {r['calls']:8d} "
                f"{r['seconds']:9.4f} {r['share_of_ops_pct']:6.1f}% "
                f"{100 * r['seconds'] / of_kind[r['program']]:6.1f}%  "
                f"{r['xla_flops'] / 1e9:.1f} / "
                f"{r['xla_bytes_accessed'] / 1e9:.2f}")
        out.append("  programs: " + ", ".join(
            f"{p['program']} {p['seconds']:.4f} s" for p in dev["programs"][:8]))
        for u in dev["longest_unscoped"]:
            out.append(f"  unscoped {u['seconds']:.4f} s x{u['calls']} "
                       f"[{u['program']}] {u['op']}  {u['tf_op']}  {u['source']}")
    return "\n".join(out)


def main(argv: list[str]) -> int:
    text = "--text" in argv
    for trace_dir in (a for a in argv if a != "--text"):
        table = reduce_dir(trace_dir)
        print(as_text(table) if text else json.dumps(table), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
