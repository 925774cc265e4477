"""What PR 45 adds to the benchmark, on the CPU: the configuration file against
the catalog and through the launcher's mapping, the traffic file against the
issue's parameters and the programs its 96 shapes and its warm-up reach, the
window kernel's counts by hand and through its reader, the counter ratio and
the gauge's mean, and one rehearsal of run.py on a small model of the family
(a window of 7 tokens, 6 rows kept of contexts up to 170) whose last line
carries the cell's new metrics."""

import json
import os
import random

import pytest

import kernels
import kernels_swa
import layer
import prom
import rehearsal
import traffic
from client import Record
from test_run import CONTRACT_KEYS, no_leftovers, run_py

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "dots3-note-prev-cut.longctx-wide"
CONFIG = os.path.join(BENCH, "configs", "dots3-note-prev-cut.json")
REDUCED = ["num_hidden_layers", "layer_types", "n_routed_experts",
           "vocab_size"]
NEW = {"swa_decode_roofline", "swa_attended_row_share",
       "kv_window_pool_usage_pct"}


def test_configuration_file_is_the_catalogs_but_for_the_cut():
    from launch_engine import model_config_from_file

    m = model_config_from_file(CONFIG)
    assert (m.name, m.n_layers, m.first_k_dense, m.layer_pattern,
            m.n_kv_layers, m.n_window_layers, m.d_model, m.d_ff,
            m.vocab_size) == \
        ("dots3-note-prev-cut", 5, 1, "**WWW", 2, 3, 5120, 13824, 19008)
    assert (m.n_heads, m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim, m.latent_dim, m.rope_theta) == \
        (128, 1024, 512, 128, 64, 128, 576, 8e7)
    w = m.window_attn
    assert (w.n_heads, w.q_lora_rank, w.kv_lora_rank, w.qk_nope_head_dim,
            w.qk_rope_head_dim, w.v_head_dim, w.rope_theta, w.window,
            w.gate) == (64, 1024, 1024, 192, 64, 128, 5e4, 513, True)
    assert m.of_window().latent_dim == 1088 and m.attn_gate
    assert m.mla_scale_q_lora and m.mla_scale_kv_lora and not m.rope_yarn
    assert (m.index_topk, m.index_n_heads, m.index_head_dim) == (2048, 64, 128)
    assert (m.n_experts, m.n_group, m.experts_per_token, m.held_experts,
            m.moe_d_ff, m.n_shared_experts, m.routed_scaling_factor) == \
        (256, 1, 8, (0, 32), 1536, 1, 1.0)
    with open(CONFIG) as f:
        doc = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f
                   if '"name": "dots3-note-prev"' in line)
    assert doc["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items()
               if doc.get(k, "absent") != v}
    assert changed == set(doc["reduced"]) == set(REDUCED)
    assert doc["layer_types"] == row["config"]["layer_types"][:5]
    assert [doc[k] for k in REDUCED if k != "layer_types"] == [5, 32, 19008]
    assert [doc[k + "_published"] for k in REDUCED if k != "layer_types"] \
        == [46, 256, 152064]
    assert doc["expert_parallel_rank"] == 0 and doc["reference"] == "dots3_note"
    assert os.path.isfile(os.path.join(BENCH, "configs",
                                       "reference_dots3_note.py"))
    assert {"from_the_config_alone", "weights", "tokenizer"} <= set(
        doc["assumed"])
    said = " ".join(doc["departures"]) + doc["deployment"]
    for word in ("8 v5e chips", "exchange", "8.17 GB", "4,087 M",
                 "2 tokens a decode step", "eight data-parallel batches",
                 "pairs column i with i + 32", "prefix hits are off",
                 "34 pages a request", "13 : 33"):
        assert word in said, word
    assert doc["serve"]["engine_args"] == [
        "--max-batch", "64", "--max-model-len", "18432", "--decode-chunk",
        "8", "--prefill-chunk", "1024"]
    # The cut's arithmetic, from the program's own parameter shapes.
    import jax

    from llm_d_inference_scheduler_tpu.models import mla

    shapes = jax.eval_shape(lambda k: mla.init_params(m, k), jax.random.key(0))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa: E731
    assert round(count(shapes) / 1e6) == 4087
    assert round(count(shapes["dense"]) / 1e6, 1) == 356.4
    assert round(count(shapes["layers"]) / 1e6, 1) == 923.9
    assert round(count(shapes["window"]) / 3e6, 1) == 870.7


def test_the_parents_mapping_read_the_file_as_five_full_layers():
    """What the parent commit's `_mla_config_from_hf` saw in the file: a
    DeepSeek-V3-family config by its `kv_lora_rank`, none of whose other keys
    it refused (ISSUE 45, "The parent"): it would have served five full
    layers over a pool the chip cannot hold. This tree's mapping refuses what
    it does not compute instead (tests/test_hf_convert.py)."""
    with open(CONFIG) as f:
        doc = json.load(f)
    assert doc["kv_lora_rank"] == 512 and doc["rope_scaling"] is None
    assert "n_group" not in doc and "zero_expert_num" not in doc
    # Five full layers for 64 lanes of 18,432 tokens, latent rows and keys.
    assert 5 * (1 + 64 * 1152) * 16 * (1280 + 256) > 9.0e9


def _pow2(n, least=1):
    p = least
    while p < n:
        p *= 2
    return p


def _programs(prompt_tokens, window=1024, block=16, widest=1152):
    out, at = set(), window
    while at < prompt_tokens:
        n = min(window, prompt_tokens - at)
        out.add((_pow2(n, 16), min(_pow2(at // block), widest)))
        at += n
    return out


def test_traffic_file_is_the_issues_table_and_warms_what_the_pool_reaches():
    mix = traffic.load_mix(traffic.mix_path(os.path.dirname(BENCH),
                                            "longctx-wide"))
    assert (mix["kind"], mix["clients"], mix["pool"], mix["ramp_s"],
            mix["temperature"], mix["trace"]["seconds"]) == \
        ("closed_clients", 128, 96, 45.0, 0.0, 1.5)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 4096,
                                    "hi": 16384}
    assert mix["output_tokens"] == {"dist": "uniform", "lo": 512, "hi": 1536}
    order = random.Random(f"chipbench/order/closed_clients/{mix['order']}")
    prompts = traffic.grid(mix["prompt_tokens"], 96, order)
    outputs = traffic.grid(mix["output_tokens"], 96, order)
    assert len(set(prompts)) == 96 and min(prompts) > 4096
    assert max(p + o for p, o in zip(prompts, outputs)) < 18432
    reached = set().union(*(_programs(p) for p in prompts))
    warmed = set().union(*(_programs(p)
                           for p in mix["warmup"]["plain_prompt_tokens"]))
    assert reached <= warmed
    assert [b["concurrent"] for b in mix["warmup"]["bursts"]] == [
        2, 4, 8, 16, 32, 64]
    plan = traffic.build(mix, 2 ** 31 + 5, 51.0)
    assert len(plan.chains) == 128


def test_kernels_swa_counts_by_hand():
    """64 lanes of 10,000 tokens: a lane's query reads 513 rows of 1,088
    values, 2,176 B each, at 2 x 64 x (1,088 + 1,024) FLOPs a row (124 a
    byte: memory bounds it on a v5e, whose ridge is at 240)."""
    one = kernels_swa.window_attention_decode(1.0, 1.0, 513, 64, 1088, 1024)
    assert one == {"flops": 2 * 64 * 2112,
                   "bytes": 2176 + 2 * (64 * 2112 + 1088)}
    cost = kernels_swa.window_attention_decode(640000.0, 64.0, 513, 64, 1088,
                                               1024)
    rows = 64 * 513
    assert cost["flops"] == 2 * 64 * 2112 * rows
    assert cost["bytes"] == rows * 2176 + 64 * 2 * (64 * 2112 + 1088)
    assert kernels.roofline_seconds(cost, "TPU v5 lite")[1] == "memory"
    # Contexts inside the window: every row is attended to.
    short = kernels_swa.window_attention_decode(64 * 300.0, 64.0, 513, 64,
                                                1088, 1024)
    assert short["flops"] == 2 * 64 * 2112 * 64 * 300


def _rec(due, prompt=9000):
    r = Record(f"r{due}", -1, 0, due, due, prompt, 1000)
    r.status, r.prompt_tokens, r.completion_tokens = 200, prompt, 1000
    r.first_s, r.last_s, r.done_s = due + 0.1, due + 5.0, due + 5.0
    r.pieces = [(r.first_s, 1), (r.last_s, 999)]
    return r


@pytest.fixture
def ctx():
    before = prom.parse(
        'jetstream:swa_rows_total{kind="context"} 1000.0\n'
        'jetstream:swa_rows_total{kind="attended"} 400.0\n')
    after = prom.parse(
        'jetstream:swa_rows_total{kind="context"} 21000.0\n'
        'jetstream:swa_rows_total{kind="attended"} 1400.0\n')
    with open(CONFIG) as f:
        model = json.load(f)
    usage = "jetstream:kv_window_cache_usage_perc"
    return layer.Context(
        records=[_rec(0.0), _rec(0.5), _rec(9.0)], seconds=10.0, chips=1,
        engine_scrapes=[(before, after)], gateway_scrape=({}, {}),
        gauge_samples=[(t, [prom.parse(f"{usage} {v}\n")])
                       for t, v in ((1.0, 0.5), (2.0, 0.7))],
        traces=[], trace_span=None, model=model, device_kind="TPU v5 lite")


def test_counter_ratio_gauge_and_a_program_without_either(ctx):
    assert layer.read_metric("swa_attended_row_share", ctx) == pytest.approx(5.0)
    assert layer.read_metric("kv_window_pool_usage_pct", ctx) == \
        pytest.approx(60.0)
    ctx.engine_scrapes = [({}, {})]      # the parent, or another block
    ctx.gauge_samples = [(1.0, [{}])]
    assert layer.read_metric("swa_attended_row_share", ctx) is None
    assert layer.read_metric("kv_window_pool_usage_pct", ctx) is None


def test_the_roofline_through_its_reader(ctx):
    assert layer.read_metric("swa_decode_roofline", ctx) is None   # no trace
    ctx.traces = [{"devices": [{"window_s": 1.5, "busy_s": 1.4, "ops": {
        "%swa_latent_decode_attention.7": {
            "count": 600, "seconds": 0.03,
            "detail": "custom-call bf16[64,64,1024]{2,1,0} custom-call(s32[2112]{0} %x)"},
        "%custom-call.9": {
            "count": 300, "seconds": 0.015,
            "detail": "custom-call bf16[64,64,1024]{2,1,0} custom-call(s32[2112]{0} %x), custom_call_target=\"tpu_custom_call\", name=swa_latent_decode_attention"},
        "%swa_window_attention.3": {
            "count": 40, "seconds": 0.5,
            "detail": "custom-call bf16[1,64,1024,128]{3,2,1,0} custom-call(s32[2]{0} %y)"},
        "%fusion.12": {
            "count": 900, "seconds": 0.004,
            "detail": "fusion bf16[64,8192]{1,0} fusion(bf16[64,64,1024]{2,1,0} %swa_latent_decode_attention.7)"}},
        "idle_by_next_program": {}}]}]
    ctx.trace_span = (1.0, 2.5)
    share = layer.read_metric("swa_decode_roofline", ctx)
    note = ctx.notes["swa_decode"]
    # Two lanes of 9,001 tokens in the slice: 513 rows each; the prefill
    # windows' op and the fusion that takes the kernel's result are not read.
    assert note["calls"] == 900 and note["mean_lanes"] == pytest.approx(2.0)
    least = (2 * 513 * 2176 + 2 * 2 * (64 * 2112 + 1088)) / 819e9
    assert note["bound"] == "memory"
    assert note["least_seconds_per_call"] == pytest.approx(least)
    assert share == pytest.approx(100 * 900 * least / 0.045) and 0 < share < 100
    # Another configuration's trace, or the parent's: nothing, no error.
    ctx.model = {"kv_lora_rank": 512, "qk_rope_head_dim": 64}
    assert layer.read_metric("swa_decode_roofline", ctx) is None


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
        "dots3-note-prev-cut", "longctx-wide", 1)
    assert "13:33" in cell["why"] and len(cell["why"]) <= 200
    config = {c["name"]: c for c in bench["configs"]}["dots3-note-prev-cut"]
    assert config["reduced"] == REDUCED and len(config["why"]) <= 200
    end_to_end, per_layer = _reported(bench, CELL)
    assert end_to_end == ["tpot_p95_ms", "out_tokens_per_s", "setup_s"]
    assert per_layer >= NEW | {
        "dsa_attended_row_share", "dsa_selected_query_share",
        "dsa_indexer_roofline", "dsa_attention_roofline",
        "eng_moe_held_pair_share", "eng_moe_grouped_share",
        "mla_absorbed_token_share", "eng_batch_fill", "kv_pool_usage_pct",
        "kv_page_run_share", "decode_chunk_ms", "device_idle_share",
        "eng_loop_host_pct", "eng_chunk_overlap_share",
        "eng_refill_ahead_share", "xla_builds_in_window.batch",
        "prefill_device_ms.batch", "idle_in_book_pct", "idle_in_prepare_pct",
        "idle_unattributed_pct", "eng_longest_chunk_ms",
        "eng_stall_device_wait_s", "eng_stall_host_s",
        "eng_event_loop_lag_ms", "eng_event_loop_lag_max_ms",
        "gw_event_loop_lag_max_ms", "gw_stream_gap_max_ms",
        "client_stream_gap_max_ms"}
    assert not per_layer & {"paged_attention_roofline", "mla_decode_roofline",
                            "ssm_decode_roofline", "moe_zero_pair_share"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".json"))
    assert by_name["swa_decode_roofline"]["layer"] == "Kernels"
    assert by_name["kv_window_pool_usage_pct"]["layer"] == \
        by_name["kv_pool_usage_pct"]["layer"]


# A small model of the family in the published spelling: a window of 7, 6
# rows kept, 16 experts of which this chip holds 4.
TINY_SWA = {
    "source": "the program's `tiny-swa` widths (tests only, never a cell)",
    "model_type": "dots3_note", "hidden_size": 96, "vocab_size": 512,
    "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "layer_types": ["full_attention", "full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention"],
    "num_attention_heads": 3, "num_key_value_heads": 3,
    "intermediate_size": 160, "moe_intermediate_size": 40,
    "kv_lora_rank": 24, "q_lora_rank": 20, "qk_nope_head_dim": 20,
    "qk_rope_head_dim": 8, "v_head_dim": 12, "n_shared_experts": 2,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "expert_parallel_rank": 1, "num_experts_per_tok": 3,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True, "moe_layer_freq": 1,
    "attention_bias": False, "hidden_act": "silu",
    "tie_word_embeddings": False, "index_topk": 6, "index_n_heads": 4,
    "index_head_dim": 16, "apply_mla_qkv_lora_rescale": True,
    "attention_gate_type": "headwise", "swa_attention_gate_type": "headwise",
    "sliding_window_size": 7, "swa_num_attention_heads": 2,
    "swa_num_key_value_heads": 2, "swa_q_lora_rank": 28,
    "swa_kv_lora_rank": 40, "swa_qk_nope_head_dim": 12,
    "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 16, "swa_rope_theta": 500,
    "rope_scaling": None, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "reduced": [], "reference": "dots3_note",
    "serve": {"model_name": "tiny-swa-bench", "replicas": 1,
              "gateway": "monolithic", "tokenizer": "byte",
              "engine_args": ["--max-batch", "4", "--max-model-len", "256",
                              "--decode-chunk", "4", "--prefill-chunk",
                              "32"]}}

TINY_LONGCTX = {
    "kind": "closed_clients", "clients": 6, "ramp_s": 1.0, "pool": 12,
    "prompt_tokens": {"dist": "loguniform", "lo": 40, "hi": 150},
    "output_tokens": {"dist": "uniform", "lo": 8, "hi": 20},
    "trace": {"seconds": 0.5},
    "warmup": {"plain_prompt_tokens": [40, 70, 100, 150], "max_tokens": 2,
               "bursts": [{"concurrent": k, "prompt_tokens": 30,
                           "max_tokens": 12} for k in (2, 4)]}}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark with one cell more, a small model of the family under
    the real cell's metrics: new files and new entries only."""
    root = str(tmp_path_factory.mktemp("bench-swa"))
    path = rehearsal.make_copy(root)
    with open(os.path.join(root, "chipbench", "configs", "tiny-swa.json"),
              "x") as f:
        json.dump(TINY_SWA, f)
    with open(os.path.join(root, "chipbench", "traffic",
                           "tiny-longctx.json"), "x") as f:
        json.dump(TINY_LONGCTX, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-swa", "source": TINY_SWA["source"],
        "file": "chipbench/configs/tiny-swa.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-swa.tiny-longctx", "config": "tiny-swa",
        "traffic": "tiny-longctx", "chips": 1, "why": "rehearsal"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("tiny-swa.tiny-longctx")
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def test_rehearsal_prints_the_new_metrics_on_the_last_line(copy):
    rc, lines = run_py(copy, "--workload", "tiny-swa.tiny-longctx",
                       "--seed", str(2 ** 31 + 45), "--seconds", "5",
                       "--trace", "2", "--platform", "cpu", timeout=500)
    assert rc == 0, lines[-3:]
    last = json.loads(lines[-1])
    assert set(last) == CONTRACT_KEYS
    assert last["device"]["platform"] == "cpu"
    assert last["correct"] and last["failed"] == 0 and last["attempted"] > 0
    m = last["metrics"]
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        end_to_end, per_layer = _reported(json.load(f),
                                          "tiny-swa.tiny-longctx")
    assert set(end_to_end) <= set(m)
    # Everything the cell lists that needs no device trace is on the line.
    not_here = {"device_idle_share", "prefill_device_ms.batch",
                "idle_in_book_pct", "idle_in_prepare_pct",
                "idle_unattributed_pct", "eng_moe_grouped_share",
                "dsa_indexer_roofline", "dsa_attention_roofline",
                "swa_decode_roofline"}
    assert per_layer - not_here <= set(m)
    # Prompts of 40-150 against a window of 7: a window layer reads a
    # twentieth to a sixth of what a full layer would.
    assert 3 < m["swa_attended_row_share"]["value"] < 20
    assert 0 < m["kv_window_pool_usage_pct"]["value"] <= 100
    assert 60 < m["dsa_selected_query_share"]["value"] < 100
    assert m["xla_builds_in_window.batch"]["value"] == 0
    facts = [json.loads(ln) for ln in lines if '"set_up_fact"' in ln][0]
    settings = facts["settings"]
    assert (settings["kv_layers_full"], settings["kv_layers_window"],
            settings["window"], settings["experts_first"],
            settings["experts_held"]) == (2, 3, 7, 4, 4)
    assert settings["window_pool_bytes"] > 0 and not settings["prefix_caching"]
    assert no_leftovers()
