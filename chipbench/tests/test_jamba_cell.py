"""What PR 52 adds to the benchmark, on the CPU: the configuration file against
the catalog and through the launcher's mapping, what makes a program without
the family refuse it, the plain reference's copy against the program at the
`tiny-jamba` preset, the traffic file through the generator, the new counter
ratio and the new kernel's counts and reader, and one rehearsal of run.py on
a small model of the family whose last line carries the cell's metrics.
Nothing here is pinned by equality that a later PR appends to."""

import importlib.util
import json
import os

import numpy as np
import pytest

import kernels
import kernels_ssm1
import layer
import prom
import rehearsal
import traffic
from test_run import CONTRACT_KEYS, no_leftovers, run_py

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "ai21-jamba2-3b.reason-long"
CONFIG = os.path.join(BENCH, "configs", "ai21-jamba2-3b.json")


def _doc():
    with open(CONFIG) as f:
        return json.load(f)


def test_configuration_file_maps_to_the_programs_config():
    from launch_engine import model_config_from_file

    m = model_config_from_file(CONFIG)
    assert (m.name, m.n_layers, m.d_model, m.d_ff, m.vocab_size) == (
        "ai21-jamba2-3b", 28, 2560, 8192, 65536)
    assert m.layer_pattern == "S" * 7 + "A" + "S" * 13 + "A" + "S" * 6
    assert (m.n_heads, m.n_kv_heads, m.head_dim, m.norm_eps) == (
        20, 1, 128, 1e-6)
    assert (m.ssm_inner, m.ssm_state, m.ssm_dt_rank, m.ssm_conv,
            m.ssm_row) == (5120, 16, 160, 4, (16, 5120))
    assert (m.n_state_layers, m.n_kv_layers, m.n_experts) == (26, 2, 0)
    doc = _doc()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f
                   if '"AI21-Jamba2-3B"' in line)
    assert doc["source"] == row["source_url"]
    # Every catalog key, flat and as published: nothing is cut.
    assert {k for k, v in row["config"].items()
            if doc.get(k, "absent") != v} == set() == set(doc["reduced"])
    # What the file adds to the published keys is said to be derived.
    assert "DERIVED" in doc["assumed"]["hybrid_override_pattern"]
    for key in ("head_dim", "rotary", "state", "weights", "tokenizer"):
        assert key in doc["assumed"]
    said = " ".join(doc["departures"]) + doc["deployment"]
    for word in ("tie_word_embeddings", "0.34 GB", "3.03 B", "one v5e chip"):
        assert word in said
    assert doc["reference"] == "jamba"
    assert doc["serve"]["engine_args"][:6] == [
        "--max-batch", "64", "--max-model-len", "5120", "--decode-chunk", "8"]
    assert "--prefill-chunk" in doc["serve"]["engine_args"]


def test_a_program_without_the_family_refuses_the_file_at_once():
    """The parent commit dispatches on `hybrid_override_pattern` first; its
    nemotron_h mapper refuses a tied head and any letter but M, E and *,
    before a weight is made (tried on the parent: exit 1 in seconds)."""
    doc = _doc()
    assert set(doc["hybrid_override_pattern"]) == {"S", "A"}
    assert not set(doc["hybrid_override_pattern"]) & set("ME*")
    assert doc["tie_word_embeddings"] is True
    assert len(doc["hybrid_override_pattern"]) == doc["num_hidden_layers"]


def _reference():
    path = os.path.join(BENCH, "configs", "reference_jamba.py")
    spec = importlib.util.spec_from_file_location("reference_jamba", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_program_forward_matches_plain_reference():
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from llm_d_inference_scheduler_tpu.models import family
    from llm_d_inference_scheduler_tpu.models.configs import get_config

    cfg = get_config("tiny-jamba")
    model = family(cfg)
    params = model.init_params(cfg, jax.random.key(7), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(9), (2, 37), 0, cfg.vocab_size)
    ours, _ = model.forward(params, cfg, tokens)
    ref = _reference()
    for row in range(2):
        want = ref.forward(
            params, tokens[row], n_layers=cfg.n_layers, attn_period=4,
            attn_offset=1, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, ssm_state=cfg.ssm_state,
            ssm_dt_rank=cfg.ssm_dt_rank, norm_eps=cfg.norm_eps, q_block=5)
        # float32 on both sides, different summation order
        # (test_reference.py's limits).
        np.testing.assert_allclose(np.asarray(ours[row]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def _bucket(n):
    b = 16
    while b < n:
        b *= 2
    return b


def _programs(n, window=1024):
    """The prefill programs a prompt of n tokens runs in windows of
    ``window``: (bucket, prior tokens) each."""
    return {(_bucket(min(n - at, window)), at) for at in range(0, n, window)}


def test_traffic_file_through_the_generator():
    mix = traffic.load_mix(traffic.mix_path(os.path.dirname(BENCH),
                                            "reason-long"))
    seed = 2 ** 31 + 99
    plan = traffic.build(mix, seed, 51.0)
    assert len(plan.chains) == 128 and plan.temperature == 0.0
    assert min(c.start_s for c in plan.chains) == -20.0 and not plan.preload
    reqs = [next(plan.chains[0].requests) for _ in range(512)]
    lens = sorted(r.prompt_tokens for r in reqs)
    assert mix["pool"] == 512 and 128 <= lens[0] and lens[-1] <= 2048
    assert 650 < sum(lens) / len(lens) < 730           # log-uniform's mean
    assert all(1024 <= r.max_tokens <= 3072 for r in reqs)
    assert 2030 < sum(r.max_tokens for r in reqs) / len(reqs) < 2070
    # A quarter of the prompts need a continuation window, which starts
    # from a carried state.
    assert 0.2 < sum(n > 1024 for n in lens) / len(lens) < 0.3
    # The longest prompt and the longest answer fit a lane.
    assert lens[-1] + 3072 <= 5120
    assert len({r.prompt[:24] for r in reqs}) == len(reqs)     # unshared
    # Every prefill and continuation program the pool can reach is warmed by
    # a prompt of the warm-up, and every decode bucket up to the 64 lanes by
    # a burst.
    reach = set().union(*(_programs(n) for n in lens))
    warm = set().union(*(_programs(r.prompt_tokens)
                         for group in traffic.warmup_requests(mix, seed)
                         for r in group))
    assert reach <= warm
    assert {b for b, at in warm if at} == {16, 32, 64, 128, 256, 512, 1024}
    assert [len(b) for b in traffic.burst_requests(mix, seed)] == [
        2, 4, 8, 16, 32, 64]
    assert mix["trace"]["seconds"] == 1.5


def _context(model, scrapes, **more):
    return layer.Context(
        records=[], seconds=10.0, chips=1, engine_scrapes=scrapes,
        gateway_scrape=({}, {}), gauge_samples=[], traces=[], trace_span=None,
        model=model, device_kind="TPU v5 lite", **more)


def test_the_scan_share_reads_the_new_series():
    before = prom.parse(
        'jetstream:ssm_scan_tokens_total{form="kernel"} 1000.0\n'
        'jetstream:ssm_scan_tokens_total{form="xla"} 0.0\n')
    after = prom.parse(
        'jetstream:ssm_scan_tokens_total{form="kernel"} 4000.0\n'
        'jetstream:ssm_scan_tokens_total{form="xla"} 1000.0\n')
    ctx = _context(_doc(), [(before, after)])
    assert layer.read_metric("ssm_scan_kernel_share", ctx) == pytest.approx(75.0)
    # A program without the counter (the parent): nothing to read, no error.
    ctx.engine_scrapes = [({}, {})]
    assert layer.read_metric("ssm_scan_kernel_share", ctx) is None


def test_the_kernels_counts_are_the_least_work():
    cost = kernels_ssm1.ssm1_state_update(64, 5120, 16)
    values = 64 * 5120 * 16
    assert cost["flops"] == 7 * values
    # The state once in and once out, each lane's rows, A and D once a call.
    assert cost["bytes"] == (2 * 4 * values + 64 * 4 * (3 * 5120 + 2 * 16)
                             + 4 * (16 * 5120 + 5120))
    least, bound = kernels.roofline_seconds(cost, "TPU v5 lite")
    assert bound == "memory"
    assert least == pytest.approx(cost["bytes"] / 819e9, rel=1e-6)
    # 26 layers of it: about a seventh of a 9 ms step.
    assert 1.2e-3 < 26 * least < 1.5e-3


class _Record:
    def __init__(self, first_s, last_s, prompt_tokens, pieces):
        self.first_s, self.last_s = first_s, last_s
        self.prompt_tokens = self.prompt_tokens_meant = prompt_tokens
        self.pieces = pieces


def test_the_roofline_reader_reads_the_ops_own_rows():
    doc = _doc()
    trace = {"devices": [{"ops": {
        "ssm1_state_update": {"count": 26 * 8, "seconds": 26 * 8 * 120e-6,
                              "detail": ""},
        "ssm1_selective_scan": {"count": 26, "seconds": 1.0, "detail": ""},
        "fusion.7": {"count": 99, "seconds": 1.0, "detail": "convert"}}}]}
    records = [_Record(0.0, 10.0, 200, [(0.0, 1)]) for _ in range(64)]
    ctx = _context(doc, [({}, {})])
    ctx.traces, ctx.trace_span, ctx.records = [trace], (1.0, 2.0), records
    got = layer.read_metric("ssm1_decode_roofline", ctx)
    least = kernels.roofline_seconds(
        kernels_ssm1.ssm1_state_update(64, 5120, 16), "TPU v5 lite")[0]
    assert got == pytest.approx(100 * least / 120e-6)
    assert 0 < got < 100
    assert ctx.notes["ssm1_state_update"]["calls"] == 26 * 8
    # No trace, no such op, or another family's configuration: nothing.
    ctx.traces = [{"devices": [{"ops": {"fusion.7": {
        "count": 1, "seconds": 1.0, "detail": ""}}}]}]
    assert layer.read_metric("ssm1_decode_roofline", ctx) is None
    ctx.traces = [trace]
    with open(os.path.join(BENCH, "configs", "nemotron-3-super-cut.json")) as f:
        ctx.model = json.load(f)
    assert layer.read_metric("ssm1_decode_roofline", ctx) is None


def _reported(bench, cell):
    def names(metrics):
        return [m["name"] for m in metrics
                if "workloads" not in m or cell in m["workloads"]]
    return names(bench["end_to_end"]), set(names(bench["per_layer"]))


def test_the_cell_reports_what_the_issue_lists():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ai21-jamba2-3b", "reason-long", 1)
    config = {c["name"]: c for c in bench["configs"]}["ai21-jamba2-3b"]
    assert config["reduced"] == []
    end_to_end, per_layer = _reported(bench, CELL)
    assert set(end_to_end) >= {"tpot_p95_ms", "out_tokens_per_s", "setup_s"}
    assert per_layer >= {
        "ssm1_decode_roofline", "ssm_scan_kernel_share",
        "dev_prefill_state_share", "dev_decode_state_share",
        "dev_unscoped_share", "ssm_step_token_share",
        "ssm_kernel_update_share", "paged_attention_roofline",
        "kv_pool_usage_pct", "eng_batch_fill", "eng_refill_ahead_share",
        "dev_prefill_share", "xla_builds_in_window.batch",
        "device_idle_share", "eng_loop_host_pct", "decode_chunk_device_ms"}
    # What reads a prefill program's own time finds none in a traced slice
    # in which no prompt window ran (one tail in three here: a request holds
    # its lane 16-48 s), so the cell is on none of those lists; the new
    # share is of the chip's busy time and reads 0 there.
    assert not per_layer & {"prefill_device_ms.batch",
                            "dev_prefill_attention_share",
                            "dev_prefill_head_share"}
    with open(os.path.join(BENCH, "layer_metrics",
                           "dev_prefill_state_share.json")) as f:
        assert json.load(f)["of"] == "busy"
    # Another op, other families' mechanisms.
    assert not {n for n in per_layer if n.startswith(
        ("ssm_decode_roofline", "mla_", "dsa_", "swa_", "moe_", "eng_moe_",
         "kv_page_run", "kv_window_"))}


# A small model of the family in the published spelling (the `tiny-jamba`
# preset's widths): prompts past 32 tokens take a continuation window.
TINY_JAMBA = {
    "source": "the program's `tiny-jamba` widths (tests only, never a cell)",
    "model_type": "jamba", "hidden_size": 48, "vocab_size": 512,
    "num_hidden_layers": 8, "attn_layer_period": 4, "attn_layer_offset": 1,
    "hybrid_override_pattern": "SASSSASS",
    "num_attention_heads": 6, "num_key_value_heads": 1,
    "intermediate_size": 72, "rms_norm_eps": 1e-06, "hidden_act": "silu",
    "max_position_embeddings": 256, "mamba_d_state": 6, "mamba_dt_rank": 5,
    "mamba_expand": 2, "mamba_d_conv": 4, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "num_experts": 1, "num_experts_per_tok": 1,
    "sliding_window": None, "tie_word_embeddings": True,
    "reduced": [], "reference": "jamba",
    "serve": {"model_name": "tiny-jamba-bench", "replicas": 1,
              "gateway": "monolithic", "tokenizer": "byte",
              "engine_args": ["--max-batch", "4", "--max-model-len", "256",
                              "--decode-chunk", "4", "--prefill-chunk", "32"]}}

TINY_REASON = {
    "kind": "closed_clients", "clients": 6, "ramp_s": 1.0, "pool": 32,
    "prompt_tokens": {"dist": "loguniform", "lo": 20, "hi": 100},
    "output_tokens": {"dist": "uniform", "lo": 8, "hi": 24},
    "trace": {"seconds": 0.5},
    # (Every window bucket x prior bucket a prompt of 20-100 tokens in windows
    # of 32 can reach.)
    "warmup": {"plain_prompt_tokens": [30, 40, 60, 70, 90, 100], "max_tokens": 2,
               "bursts": [{"concurrent": k, "prompt_tokens": 30,
                           "max_tokens": 12} for k in (2, 4)]}}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark with one cell more, a small model of the family under
    the real cell's metrics: new files and new entries only."""
    root = str(tmp_path_factory.mktemp("bench-jamba"))
    path = rehearsal.make_copy(root)
    with open(os.path.join(root, "chipbench", "configs", "tiny-jamba.json"),
              "x") as f:
        json.dump(TINY_JAMBA, f)
    with open(os.path.join(root, "chipbench", "traffic",
                           "tiny-reason-long.json"), "x") as f:
        json.dump(TINY_REASON, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-jamba", "source": TINY_JAMBA["source"],
        "file": "chipbench/configs/tiny-jamba.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-jamba.tiny-reason-long", "config": "tiny-jamba",
        "traffic": "tiny-reason-long", "chips": 1, "why": "rehearsal"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("tiny-jamba.tiny-reason-long")
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def test_rehearsal_prints_the_cells_metrics_on_the_last_line(copy):
    rc, lines = run_py(copy, "--workload", "tiny-jamba.tiny-reason-long",
                       "--seed", str(2 ** 31 + 11), "--seconds", "5",
                       "--trace", "2", "--platform", "cpu", timeout=400)
    assert rc == 0, lines[-3:]
    last = json.loads(lines[-1])
    assert set(last) == CONTRACT_KEYS
    assert last["device"]["platform"] == "cpu"
    assert last["correct"] and last["failed"] == 0 and last["attempted"] > 0
    m = last["metrics"]
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        bench = json.load(f)
    end_to_end, per_layer = _reported(bench, "tiny-jamba.tiny-reason-long")
    assert set(end_to_end) <= set(m)
    # Everything the cell lists whose source is no device trace is on the
    # line (a CPU line has no device plane).
    from_trace = {x["name"] for x in bench["per_layer"]
                  if x["source"] == "device_trace"}
    # (At these widths the rule hands the CPU the plain forms: neither
    # share has a `kernel` series to read there, and both are left out.)
    no_series = {"ssm_scan_kernel_share", "ssm_kernel_update_share"}
    assert per_layer - from_trace - no_series <= set(m)
    assert 0 < m["ssm_step_token_share"]["value"] < 100
    assert m["xla_builds_in_window.batch"]["value"] == 0
    settings = next(json.loads(ln)["settings"] for ln in lines
                    if '"state_pool_bytes"' in ln)
    assert (settings["state_scan"], settings["state_update"]) == (
        "xla", "gathered")
    assert (settings["kv_layers"], settings["state_layers"]) == (2, 6)
    assert settings["state_slot_bytes"] == 6 * (6 * 96 * 4 + 3 * 96 * 2)
    assert no_leftovers()
