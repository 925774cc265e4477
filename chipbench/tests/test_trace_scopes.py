"""Device time by program and block: trace_scopes.by_program_and_scope on a
hand-made trace whose answer is plain arithmetic, then on a small recorded
one (tests/data/trace_scopes_small.json: the events of two decode steps, of
one 256-token prefill and of the small programs between them, 51 ms cut from
a v5e `--trace 2` run of mixtral-8x7b-cut.batch-full on PR 50's tree, in
trace_scopes' plain form); the wire reader against a whole recorded
`.xplane.pb` where the checkout has one under chiprun_out/ (they are not
committed); and the reader of the metric files on a fabricated table."""

import glob
import json
import os
import time

import pytest

import layer
import trace_scopes

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
DECODE, PREFILL, KEEP = 11, 22, 33


def _md(name, tf_op, program, **more):
    return {"name": f"%{name} = f32[8]{{0}} {more.pop('opcode', 'fusion')}(f32[8] %p)",
            "tf_op": tf_op, "program_id": program, "hlo_category": "x",
            "flops": 10, "bytes_accessed": 100, "source": "models/llama.py:1",
            **more}


def _plane():
    metadata = {
        "1": _md("while.1", "jit(_decode_chunk_impl)/while:", DECODE,
                 opcode="while"),
        "2": _md("fusion.1", "jit(_decode_chunk_impl)/while/body/closed_call/"
                 "blk.ffn.experts/blk.ffn.experts.glue/gather:", DECODE),
        "3": _md("moe_grouped_swiglu.1", "jit(_decode_chunk_impl)/while/body/"
                 "blk.ffn.experts/pallas_call:", DECODE, flops=0,
                 bytes_accessed=0, opcode="custom-call"),
        "4": _md("fusion.2", "jit(_decode_chunk_impl)/while/body/squeeze:",
                 DECODE),
        "5": _md("fusion.1", "jit(prefix_prefill_s8_p4)/blk.attn.core/"
                 "dot_general:", PREFILL),
        "6": _md("copy.3", "", PREFILL, opcode="copy"),
        "7": _md("fusion.9", "jit(keep_tokens)/select_n:", KEEP),
        # A program the slice holds no "XLA Modules" event of (it began
        # before the trace did): booked by how its tf_op starts.
        "8": _md("fusion.4", "jit(prefill_b16)/blk.head/dot_general:", 44),
    }
    ps = 10 ** 6
    return {"name": "/device:TPU:0",
            "modules": [[f"jit__decode_chunk_impl({DECODE})", 0, 100 * ps],
                        [f"jit_prefix_prefill_s8_p4({PREFILL})", 100 * ps, 50 * ps],
                        [f"jit_keep_tokens({KEEP})", 150 * ps, 1 * ps]],
            "ops": [[1, 0, 100 * ps],             # the while spans its body
                    [2, 0, 10 * ps], [3, 10 * ps, 40 * ps], [4, 50 * ps, 5 * ps],
                    [2, 55 * ps, 10 * ps], [3, 65 * ps, 35 * ps],
                    [5, 100 * ps, 30 * ps], [6, 130 * ps, 20 * ps],
                    [7, 150 * ps, 1 * ps], [8, 151 * ps, 4 * ps]],
            "op_metadata": metadata}


def test_an_op_goes_to_its_program_and_its_innermost_scope():
    dev, = trace_scopes.by_program_and_scope([_plane()])
    rows = {(r["program"], r["scope"]): r for r in dev["rows"]}
    us = 1e-6
    assert rows[("decode", "ffn.experts.glue")]["seconds"] == pytest.approx(20 * us)
    assert rows[("decode", "ffn.experts")]["seconds"] == pytest.approx(75 * us)
    assert rows[("decode", "ffn.experts")]["calls"] == 2
    assert rows[("decode", "unscoped")]["seconds"] == pytest.approx(5 * us)
    assert rows[("prefill", "attn.core")]["seconds"] == pytest.approx(30 * us)
    assert rows[("prefill", "unscoped")]["seconds"] == pytest.approx(20 * us)
    assert rows[("prefill", "head")]["seconds"] == pytest.approx(4 * us)
    assert rows[("other", "unscoped")]["seconds"] == pytest.approx(1 * us)
    assert len(rows) == 7
    # XLA's own cost, summed over calls; nothing for the Pallas call.
    assert rows[("decode", "ffn.experts.glue")]["xla_flops"] == 20
    assert rows[("decode", "ffn.experts")]["xla_bytes_accessed"] == 0
    # Control flow is left out, and the rows add up to what is left.
    assert dev["control_flow_seconds"] == pytest.approx(100 * us)
    assert dev["op_seconds"] == pytest.approx(155 * us)
    assert sum(r["seconds"] for r in dev["rows"]) == pytest.approx(dev["op_seconds"])
    assert sum(r["share_of_ops_pct"] for r in dev["rows"]) == pytest.approx(100)
    assert dev["busy_s"] == pytest.approx(155 * us)
    assert dev["scoped"]
    longest = dev["longest_unscoped"]
    assert [u["op"] for u in longest] == ["%copy.3", "%fusion.2", "%fusion.9"]
    assert longest[1]["tf_op"].endswith("while/body/squeeze:")
    assert longest[0]["source"] == "models/llama.py:1"
    by_name = {p["program"]: p for p in dev["programs"]}
    assert by_name["jit__decode_chunk_impl"]["seconds"] == pytest.approx(100 * us)
    assert by_name["jit(prefill_b16)"]["kind"] == "prefill"


@pytest.mark.parametrize("name,kind", [
    ("jit__decode_chunk_impl", "decode"), ("jit(_decode_chunk_impl)", "decode"),
    ("jit_prefill_b256", "prefill"), ("jit_prefix_prefill_s1024_p512", "prefill"),
    ("jit_mm_prefill_b64_m8", "prefill"), ("jit(prefix_prefill_s16_p4)", "prefill"),
    ("jit_keep_tokens", "other"), ("jit__threefry_split", "other"),
    ("jit_kv_import", "other"), ("", "other")])
def test_program_kinds(name, kind):
    assert trace_scopes.program_kind(name) == kind


def test_scope_of_takes_the_last_blk_component():
    assert trace_scopes.scope_of(
        "jit(f)/while/body/blk.attn.core/cond/branch_1_fun/jit(k)/pallas_call:"
    ) == "attn.core"
    assert trace_scopes.scope_of("jit(f)/blk.ffn.experts/blk.ffn.router/dot:") \
        == "ffn.router"
    assert trace_scopes.scope_of("jit(f)/while/body/squeeze:") == "unscoped"
    assert trace_scopes.scope_of("") == "unscoped"


def test_a_trace_without_scopes_says_so():
    plane = _plane()
    for md in plane["op_metadata"].values():
        md["tf_op"] = md["tf_op"].replace("blk.", "")
    dev, = trace_scopes.by_program_and_scope([plane])
    assert not dev["scoped"]
    assert {r["scope"] for r in dev["rows"]} == {"unscoped"}
    assert {r["program"]: r["seconds"] for r in dev["rows"]} == pytest.approx(
        {"decode": 100e-6, "prefill": 54e-6, "other": 1e-6})


# ---- the recorded sample ---------------------------------------------------------

@pytest.fixture(scope="module")
def small():
    with open(os.path.join(HERE, "data", "trace_scopes_small.json")) as f:
        return json.load(f)


def test_the_recorded_sample_is_booked_by_program_and_scope(small):
    dev, = trace_scopes.by_program_and_scope(small["planes"])
    assert dev["scoped"]
    rows = {(r["program"], r["scope"]): r for r in dev["rows"]}
    want = small["expected"]
    assert sum(r["seconds"] for r in dev["rows"]) == pytest.approx(
        dev["op_seconds"])
    assert dev["op_seconds"] == pytest.approx(want["op_seconds"])
    # Within 1% of trace_reduce's busy time on the same events.
    assert dev["op_seconds"] == pytest.approx(dev["busy_s"], rel=0.01)
    for key, seconds in want["rows"].items():
        program, scope = key.split("|")
        assert rows[(program, scope)]["seconds"] == pytest.approx(seconds), key
    assert len(rows) == len(want["rows"])
    # The experts lead a Mixtral decode step, under their own name; the
    # attention kernel is a Pallas call, which XLA cannot cost.
    decode = {s: r for (p, s), r in rows.items() if p == "decode"}
    assert max(decode, key=lambda s: decode[s]["seconds"]) == "ffn.experts"
    kernel, = [md for md in small["planes"][0]["op_metadata"].values()
               if md["name"].startswith("%paged_decode_attention_pallas")]
    assert trace_scopes.scope_of(kernel["tf_op"]) == "attn.core"
    assert kernel["flops"] == 0 == kernel["bytes_accessed"]
    assert {p for p, _ in rows} == {"decode", "prefill", "other"}
    assert all(u["tf_op"].count("blk.") == 0 for u in dev["longest_unscoped"])


# ---- the wire reader ---------------------------------------------------------------

def _recorded():
    return sorted(glob.glob(os.path.join(
        REPO, "chiprun_out", "chipbench", "*", "trace2", "trace0", "plugins",
        "profile", "*", "*.xplane.pb")))


def test_the_wire_reader_against_a_recorded_trace():
    """What `jax.profiler.ProfileData` reads of the same file (names, starts,
    durations, event counts), and beside it what it does not give: the ops'
    `tf_op` and `program_id`, which joins to an "XLA Modules" name."""
    paths = _recorded()
    if not paths:
        pytest.skip("no recorded trace under chiprun_out/ in this checkout")
    import trace_reduce

    path = min(paths, key=os.path.getsize)
    t0 = time.monotonic()
    plane, = trace_scopes.read_planes(path)
    took = time.monotonic() - t0
    assert took < 30
    ref, = trace_reduce.load_planes(path)
    lines = {ln["name"]: ln["events"] for ln in ref["lines"]}
    assert len(plane["ops"]) == len(lines["XLA Ops"]) > 1000
    assert len(plane["modules"]) == len(lines["XLA Modules"]) > 0
    assert [m[0] for m in plane["modules"]] == [
        f"{e[0]}" for e in lines["XLA Modules"]]
    for (mid, start_ps, dur_ps), (name, start_ns, dur_ns, _) in list(
            zip(plane["ops"], lines["XLA Ops"]))[::997]:
        md = plane["op_metadata"][str(mid)]
        assert md["name"].startswith(name + " = ")
        assert start_ps / 1e3 == pytest.approx(start_ns, abs=1)
        assert dur_ps / 1e3 == pytest.approx(dur_ns, abs=1)
    programs = {int(m[0].rsplit("(", 1)[1][:-1]): m[0].rsplit("(", 1)[0]
                for m in plane["modules"]}
    seen = 0
    for md in plane["op_metadata"].values():
        head = (md.get("tf_op") or "").split("/")[0]
        # (A few ops' paths do not start at their program: "gather:" alone,
        # in the recorded prefix prefills. The id is what books them.)
        if md.get("program_id") in programs and head.startswith("jit("):
            # "jit__decode_chunk_impl" runs what "jit(_decode_chunk_impl)/..."
            # was traced as.
            assert trace_scopes.program_kind(head) == trace_scopes.program_kind(
                programs[md["program_id"]]), md
            seen += 1
    assert seen > 100
    dev, = trace_scopes.by_program_and_scope([plane])
    assert dev["op_seconds"] == pytest.approx(dev["busy_s"], rel=0.01)
    assert {r["program"] for r in dev["rows"]} >= {"decode", "prefill"}


# ---- the metric files' reader --------------------------------------------------------

def _ctx(devices):
    ctx = layer.Context(
        records=[], seconds=1.0, chips=1, engine_scrapes=[],
        gateway_scrape=({}, {}), gauge_samples=[], traces=[], trace_span=None,
        model={}, device_kind="TPU v5 lite")
    ctx.notes["device_time_by_scope"] = [{"dir": "d", "devices": devices}]
    return ctx


def _device(rows, scoped=True):
    return {"plane": "/device:TPU:0", "scoped": scoped,
            "rows": [{"program": p, "scope": s, "seconds": t}
                     for p, s, t in rows]}


ROWS = [("decode", "ffn.experts", 5.0), ("decode", "ffn.experts.glue", 1.0),
        ("decode", "attn.core", 2.0), ("decode", "unscoped", 2.0),
        ("prefill", "attn.core", 3.0), ("prefill", "ffn.expertsX", 1.0),
        ("prefill", "unscoped", 1.0), ("other", "unscoped", 5.0)]


@pytest.mark.parametrize("spec,want", [
    (dict(programs=["prefill"], scopes="all", of="busy"), 25.0),
    (dict(programs=["decode"], scopes=["ffn.experts"], of="programs"), 60.0),
    (dict(programs=["decode"], scopes=["attn.core", "attn.index"],
          of="programs"), 20.0),
    (dict(programs=["prefill"], scopes=["ffn.experts"], of="programs"), 0.0),
    (dict(programs=["decode", "prefill"], scopes="unscoped", of="programs"),
     20.0),
    (dict(programs=["decode"], scopes=["state.update"], of="programs"), 0.0),
])
def test_scope_share_of_programs_and_of_busy(spec, want):
    read = layer.reader("trace_scope_share")
    ctx = _ctx([_device(ROWS)])
    assert read(dict(spec, kind="trace_scope_share"), ctx) == pytest.approx(want)
    # Two chips' seconds are summed.
    ctx = _ctx([_device(ROWS), _device(ROWS)])
    assert read(dict(spec, kind="trace_scope_share"), ctx) == pytest.approx(want)


def test_scope_share_has_nothing_to_read_without_scopes_or_a_trace():
    read = layer.reader("trace_scope_share")
    plain = [(p, "unscoped", t) for p, _, t in ROWS]
    ctx = _ctx([_device(plain, scoped=False)])
    scoped = dict(programs=["decode"], scopes=["ffn.experts"], of="programs")
    assert read(scoped, ctx) is None
    assert any("no blk. scope" in k for k in ctx.notes)
    assert read(dict(programs=["decode", "prefill"], scopes="unscoped",
                     of="programs"), ctx) is None
    # The program split needs no scope.
    assert read(dict(programs=["prefill"], scopes="all", of="busy"), ctx) \
        == pytest.approx(25.0)
    # No device trace (a CPU rehearsal): nothing, and nothing is run.
    bare = layer.Context(
        records=[], seconds=1.0, chips=1, engine_scrapes=[],
        gateway_scrape=({}, {}), gauge_samples=[], traces=[{"devices": []}],
        trace_span=None, model={}, device_kind="cpu")
    assert read(scoped, bare) is None and bare.notes["device_time_by_scope"] == []
    # A slice in which none of the named programs ran.
    ctx = _ctx([_device([("decode", "attn.core", 1.0)])])
    assert read(dict(programs=["prefill"], scopes=["head"], of="programs"),
                ctx) is None


def test_the_new_metric_files_name_scopes_the_models_emit():
    import sys

    sys.path.insert(0, REPO)
    from llm_d_inference_scheduler_tpu.models import scopes

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"].startswith("dev_")]
    assert len(mine) == 10 and bench["per_layer"][-1]["name"] == "dev_unscoped_share"
    for m in mine:
        spec = layer.metric_spec(m["name"])
        assert spec["kind"] == "trace_scope_share" and m["source"] == "device_trace"
        assert set(spec["programs"]) <= set(trace_scopes.KINDS)
        assert spec["of"] in ("programs", "busy")
        if isinstance(spec["scopes"], list):
            assert set(spec["scopes"]) <= set(scopes.BLOCKS), m["name"]
        else:
            assert spec["scopes"] in ("all", "unscoped")
        assert m["moves"] == "tpot_p95_ms" and m["workloads"]
    chunk = layer.metric_spec("decode_chunk_device_ms")
    assert chunk["kind"] == "trace_module_mean"
    assert trace_scopes.program_kind("jit__decode_chunk_impl") == "decode"
