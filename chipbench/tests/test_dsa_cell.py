"""What PR 41 adds to the benchmark, on the CPU: the configuration file against
the catalog and through the launcher's mapping, the traffic file against the
issue's table and the programs its 48 shapes and its warm-up reach, the two
kernels' counts by hand and through their reader, the two counter ratios, and
one rehearsal of run.py on a small model of the family (24 rows kept of
contexts up to 170) whose last line carries the cell's metrics."""

import json
import os
import random

import pytest

import kernels
import kernels_dsa
import layer
import prom
import rehearsal
import traffic
from client import Record
from test_run import CONTRACT_KEYS, no_leftovers, run_py

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "deepseek-v3.2-exp-cut.longctx-reason"
CONFIG = os.path.join(BENCH, "configs", "deepseek-v3.2-exp-cut.json")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size"]


def test_configuration_file_is_the_catalogs_but_for_the_cut():
    from launch_engine import model_config_from_file

    m = model_config_from_file(CONFIG)
    assert (m.name, m.n_layers, m.first_k_dense, m.n_kv_layers, m.d_model,
            m.d_ff, m.vocab_size) == \
        ("deepseek-v3.2-exp-cut", 5, 1, 5, 7168, 18432, 16160)
    assert (m.n_heads, m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim, m.latent_dim, m.head_dim) == \
        (128, 1536, 512, 128, 64, 128, 576, 192)
    assert (m.index_topk, m.index_n_heads, m.index_head_dim, m.index_dim) == \
        (2048, 64, 128, 128)
    assert m.rope_yarn == (40.0, 4096.0, 32.0, 1.0, 1.0)
    assert (m.n_experts, m.n_group, m.topk_group, m.experts_per_token,
            m.held_experts, m.moe_d_ff, m.n_shared_experts,
            m.routed_scaling_factor) == (256, 8, 4, 8, (0, 16), 2048, 1, 2.5)
    with open(CONFIG) as f:
        doc = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f
                   if '"name": "DeepSeek-V3.2-Exp"' in line)
    assert doc["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items()
               if doc.get(k, "absent") != v}
    assert changed == set(doc["reduced"]) == set(REDUCED)
    assert [doc[k] for k in REDUCED] == [5, 1, 16, 16160]
    assert [doc[k + "_published"] for k in REDUCED] == [61, 3, 256, 129280]
    assert doc["expert_parallel_rank"] == 0 and doc["reference"] == "deepseek_v32"
    assert os.path.isfile(os.path.join(BENCH, "configs",
                                       "reference_deepseek_v32.py"))
    assert {"from_memory", "weights", "tokenizer"} <= set(doc["assumed"])
    said = " ".join(doc["departures"]) + doc["deployment"]
    for word in ("16 v5e chips", "exchange", "Hadamard", "FP8", "132",
                 "multi-token-prediction", "pairs column i with i + 32",
                 "1 token a decode step", "16 data-parallel batches"):
        assert word in said, word
    assert doc["serve"]["engine_args"][:8] == [
        "--max-batch", "32", "--max-model-len", "18432", "--decode-chunk",
        "8", "--prefill-chunk", "1024"]


def test_a_program_without_the_block_refuses_the_file_by_name():
    """What the parent commit's mapping (`_MLA_ONLY`, PR 32) reads in the
    file: a DeepSeek-V3-family config by its `kv_lora_rank`, with three keys
    it did not compute; it raised on the first at once, before any weight."""
    with open(CONFIG) as f:
        doc = json.load(f)
    assert doc["kv_lora_rank"] and doc["q_lora_rank"] == 1536
    assert doc["rope_scaling"]["type"] == "yarn" and doc["n_group"] == 8
    assert "zero_expert_num" not in doc and "text_config" not in doc


def _pow2(n, least=1):
    p = least
    while p < n:
        p *= 2
    return p


def _programs(prompt_tokens, window=1024, block=16, widest=1152):
    """The continuation programs (suffix bucket, prior-table bucket) the
    engine runs for a prompt written in windows."""
    out, at = set(), window
    while at < prompt_tokens:
        n = min(window, prompt_tokens - at)
        out.add((_pow2(n, 16), min(_pow2(at // block), widest)))
        at += n
    return out


def test_traffic_file_is_the_issues_table_and_warms_what_the_pool_reaches():
    mix = traffic.load_mix(traffic.mix_path(os.path.dirname(BENCH),
                                            "longctx-reason"))
    assert (mix["kind"], mix["clients"], mix["pool"], mix["ramp_s"],
            mix["temperature"], mix["trace"]["seconds"]) == \
        ("closed_clients", 64, 48, 30.0, 0.0, 1.5)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 4096,
                                    "hi": 16384}
    assert mix["output_tokens"] == {"dist": "uniform", "lo": 512, "hi": 1536}
    # `order` names which fixed shuffle of the 48 shapes the cycle is: the
    # one of a million whose every start gives a window nearly the same work
    # (PERF.md section 6, PR 41); the shapes themselves are the grid's.
    assert mix["order"] == 659469
    order = random.Random(f"chipbench/order/closed_clients/{mix['order']}")
    prompts = traffic.grid(mix["prompt_tokens"], 48, order)
    outputs = traffic.grid(mix["output_tokens"], 48, order)
    assert len(set(prompts)) == 48 and min(prompts) > 4096 > 2048
    assert 8800 < sum(prompts) / 48 < 8950 and sum(outputs) / 48 == 1024
    assert max(p + o for p, o in zip(prompts, outputs)) < 18432
    # Every window after the second selects: 4 to 16 windows a prompt.
    assert {-(-p // 1024) for p in prompts} == set(range(5, 17))
    reached = set().union(*(_programs(p) for p in prompts))
    warmed = set().union(*(_programs(p)
                           for p in mix["warmup"]["plain_prompt_tokens"]))
    assert len(reached) == 16 and reached <= warmed
    assert [b["concurrent"] for b in mix["warmup"]["bursts"]] == [2, 4, 8, 16, 32]
    # Every seed walks the same 48 shapes from another start.
    plan = traffic.build(mix, 2 ** 31 + 5, 51.0)
    assert len(plan.chains) == 64
    walked = [next(plan.chains[0].requests).prompt_tokens for _ in range(48)]
    assert sorted(walked) == sorted(prompts)


def test_kernels_dsa_counts_by_hand():
    """32 lanes of 10,000 tokens: the indexer reads 256 B a token and spends
    2 x 64 x 128 FLOPs on it (64 FLOPs a byte: memory bounds it); attention
    reads 2,048 selected rows a lane, 1,152 B each, at 2 x 128 x 1,088 FLOPs
    a row (242 a byte: at the v5e's ridge)."""
    one = kernels_dsa.indexer_decode(1.0, 0.0, 64, 128)
    assert one == {"flops": 2 * 64 * 128, "bytes": 256}
    cost = kernels_dsa.indexer_decode(320000.0, 32.0, 64, 128)
    assert cost["bytes"] == 320000 * 256 + 32 * 64 * (256 + 4)
    assert kernels.roofline_seconds(cost, "TPU v5 lite")[1] == "memory"
    sel = kernels_dsa.selected_attention_decode(320000.0, 32.0, 2048, 128,
                                                576, 512)
    rows = 32 * 2048
    assert sel["flops"] == 2 * 128 * 1088 * rows
    assert sel["bytes"] == rows * 1152 + 32 * 2 * (128 * 1088 + 576)
    # Contexts under index_topk: every row is attended to.
    short = kernels_dsa.selected_attention_decode(32 * 1000.0, 32.0, 2048,
                                                  128, 576, 512)
    assert short["flops"] == 2 * 128 * 1088 * 32000


def _rec(due, prompt=9000):
    r = Record(f"r{due}", -1, 0, due, due, prompt, 1000)
    r.status, r.prompt_tokens, r.completion_tokens = 200, prompt, 1000
    r.first_s, r.last_s, r.done_s = due + 0.1, due + 5.0, due + 5.0
    r.pieces = [(r.first_s, 1), (r.last_s, 999)]
    return r


@pytest.fixture
def ctx():
    before = prom.parse(
        'jetstream:dsa_rows_total{kind="scored"} 1000.0\n'
        'jetstream:dsa_rows_total{kind="attended"} 400.0\n'
        'jetstream:dsa_query_tokens_total{form="selected"} 10.0\n'
        'jetstream:dsa_query_tokens_total{form="all"} 10.0\n')
    after = prom.parse(
        'jetstream:dsa_rows_total{kind="scored"} 9000.0\n'
        'jetstream:dsa_rows_total{kind="attended"} 2400.0\n'
        'jetstream:dsa_query_tokens_total{form="selected"} 970.0\n'
        'jetstream:dsa_query_tokens_total{form="all"} 50.0\n')
    with open(CONFIG) as f:
        model = json.load(f)
    return layer.Context(
        records=[_rec(0.0), _rec(0.5), _rec(9.0)], seconds=10.0, chips=1,
        engine_scrapes=[(before, after)], gateway_scrape=({}, {}),
        gauge_samples=[], traces=[], trace_span=None, model=model,
        device_kind="TPU v5 lite")


def test_counter_ratios_and_a_program_without_the_counters(ctx):
    assert layer.read_metric("dsa_attended_row_share", ctx) == pytest.approx(25.0)
    assert layer.read_metric("dsa_selected_query_share", ctx) == pytest.approx(96.0)
    ctx.engine_scrapes = [({}, {})]      # the parent, or another block
    assert layer.read_metric("dsa_attended_row_share", ctx) is None
    assert layer.read_metric("dsa_selected_query_share", ctx) is None


def test_the_two_rooflines_through_their_reader(ctx):
    assert layer.read_metric("dsa_indexer_roofline", ctx) is None   # no trace
    # Rows as trace_reduce.py writes them (a chip run of PR 41): the kernel
    # under its own name, once under a generic one, and a fusion that takes
    # the attention kernel's result, which names it and is no call of it.
    ctx.traces = [{"devices": [{"window_s": 1.5, "busy_s": 1.4, "ops": {
        "%dsa_index_scores_decode.24": {
            "count": 300, "seconds": 0.015,
            "detail": "custom-call f32[32,5,4096]{2,1,0} custom-call(s32[36864]{0} %get-tuple-element.4104)"},
        "%custom-call.3": {
            "count": 100, "seconds": 0.005,
            "detail": "custom-call f32[32,5,4096]{2,1,0} custom-call(s32[36864]{0} %x), custom_call_target=\"tpu_custom_call\", name=dsa_index_scores_decode"},
        "%dsa_index_scores_window.15": {
            "count": 40, "seconds": 0.5,
            "detail": "custom-call f32[1,1024,9216]{2,1,0} custom-call(bf16[1,65536,128]{2,1,0} %bitcast.8)"},
        "%dsa_paged_decode_attention.13": {
            "count": 400, "seconds": 0.2,
            "detail": "custom-call bf16[32,128,512]{2,1,0} custom-call(s32[36864]{0} %get-tuple-element.4104)"},
        "%fusion.633": {
            "count": 400, "seconds": 0.003,
            "detail": "fusion bf16[128,4,8,128]{3,2,1,0} fusion(bf16[512,128,256]{0,2,1} %bitcast.698, bf16[32,128,512]{2,1,0} %dsa_paged_decode_attention.13)"}},
        "idle_by_next_program": {}}]}]
    ctx.trace_span = (1.0, 2.5)
    share = layer.read_metric("dsa_indexer_roofline", ctx)
    note = ctx.notes["dsa_indexer_decode"]
    # Two lanes of 9,001 tokens in the slice; the windows' calls are not read.
    assert note["calls"] == 400 and note["mean_lanes"] == pytest.approx(2.0)
    least = (18002 * 256 + 2 * 64 * 260) / 819e9
    assert note["bound"] == "memory"
    assert note["least_seconds_per_call"] == pytest.approx(least)
    assert share == pytest.approx(100 * 400 * least / 0.02) and share < 100
    share = layer.read_metric("dsa_attention_roofline", ctx)
    note = ctx.notes["dsa_attention_decode"]
    rows = 2 * 2048
    assert note["least_seconds_per_call"] == pytest.approx(max(
        2 * 128 * 1088 * rows / 197e12,
        (rows * 1152 + 2 * 2 * (128 * 1088 + 576)) / 819e9))
    assert note["calls"] == 400 and note["kernel_seconds"] == 0.2
    assert 0 < share < 100
    # Another configuration's trace, or the parent's: nothing, no error.
    ctx.model = {"kv_lora_rank": 512, "qk_rope_head_dim": 64}
    assert layer.read_metric("dsa_attention_roofline", ctx) is None


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
        "deepseek-v3.2-exp-cut", "longctx-reason", 1)
    assert "1 token a step (deployment 16)" in cell["why"]
    config = {c["name"]: c for c in bench["configs"]}["deepseek-v3.2-exp-cut"]
    assert config["reduced"] == REDUCED
    end_to_end, per_layer = _reported(bench, CELL)
    assert end_to_end == ["tpot_p95_ms", "out_tokens_per_s", "setup_s"]
    assert per_layer >= {
        "dsa_attended_row_share", "dsa_selected_query_share",
        "dsa_indexer_roofline", "dsa_attention_roofline",
        "eng_moe_held_pair_share", "eng_moe_grouped_share",
        "mla_absorbed_token_share", "eng_batch_fill", "kv_pool_usage_pct",
        "decode_chunk_ms", "device_idle_share", "eng_loop_host_pct",
        "eng_chunk_overlap_share", "eng_refill_ahead_share",
        "xla_builds_in_window.batch", "prefill_device_ms.batch",
        "idle_in_book_pct", "idle_in_prepare_pct", "idle_unattributed_pct",
        "eng_longest_chunk_ms", "eng_stall_device_wait_s", "eng_stall_host_s",
        "eng_event_loop_lag_ms", "eng_event_loop_lag_max_ms",
        "gw_event_loop_lag_max_ms", "gw_stream_gap_max_ms",
        "client_stream_gap_max_ms"}
    # It calls none of the kernels the other rooflines read.
    assert not per_layer & {"paged_attention_roofline", "mla_decode_roofline",
                            "ssm_decode_roofline", "moe_zero_pair_share"}
    for name in ("dsa_attended_row_share", "dsa_selected_query_share",
                 "dsa_indexer_roofline", "dsa_attention_roofline"):
        entry = {m["name"]: m for m in bench["per_layer"]}[name]
        assert entry["workloads"] == [CELL]
    assert [m["name"] for m in bench["per_layer"][-4:]] == [
        "dsa_attended_row_share", "dsa_selected_query_share",
        "dsa_indexer_roofline", "dsa_attention_roofline"]


# A small model of the family in the published spelling: 24 rows kept, 16
# experts in 4 groups of which this chip holds 4, YaRN over an original
# context of 32.
TINY_DSA = {
    "source": "the program's `tiny-dsa` widths (tests only, never a cell)",
    "model_type": "deepseek_v32", "hidden_size": 96, "vocab_size": 512,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 3, "num_key_value_heads": 3,
    "intermediate_size": 160, "moe_intermediate_size": 40,
    "kv_lora_rank": 24, "q_lora_rank": 20, "qk_nope_head_dim": 20,
    "qk_rope_head_dim": 8, "v_head_dim": 12, "n_shared_experts": 2,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "expert_parallel_rank": 1, "num_experts_per_tok": 3, "n_group": 4,
    "topk_group": 2, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True, "moe_layer_freq": 1,
    "attention_bias": False, "hidden_act": "silu",
    "tie_word_embeddings": False, "index_topk": 24, "index_n_heads": 4,
    "index_head_dim": 16,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0,
                     "original_max_position_embeddings": 32},
    "max_position_embeddings": 256, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "reduced": [], "reference": "deepseek_v32",
    "serve": {"model_name": "tiny-dsa-bench", "replicas": 1,
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
    root = str(tmp_path_factory.mktemp("bench-dsa"))
    path = rehearsal.make_copy(root)
    with open(os.path.join(root, "chipbench", "configs", "tiny-dsa.json"),
              "x") as f:
        json.dump(TINY_DSA, f)
    with open(os.path.join(root, "chipbench", "traffic",
                           "tiny-longctx.json"), "x") as f:
        json.dump(TINY_LONGCTX, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-dsa", "source": TINY_DSA["source"],
        "file": "chipbench/configs/tiny-dsa.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-dsa.tiny-longctx", "config": "tiny-dsa",
        "traffic": "tiny-longctx", "chips": 1, "why": "rehearsal"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("tiny-dsa.tiny-longctx")
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def test_rehearsal_prints_the_cells_metrics_on_the_last_line(copy):
    rc, lines = run_py(copy, "--workload", "tiny-dsa.tiny-longctx",
                       "--seed", str(2 ** 31 + 41), "--seconds", "5",
                       "--trace", "2", "--platform", "cpu", timeout=500)
    assert rc == 0, lines[-3:]
    last = json.loads(lines[-1])
    assert set(last) == CONTRACT_KEYS
    assert last["device"]["platform"] == "cpu"
    assert last["correct"] and last["failed"] == 0 and last["attempted"] > 0
    m = last["metrics"]
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        end_to_end, per_layer = _reported(json.load(f),
                                          "tiny-dsa.tiny-longctx")
    assert set(end_to_end) <= set(m)
    # Everything the cell lists that needs no device trace is on the line
    # (the CPU runs every program dense over the experts and both plain
    # forms: no `grouped` series, no kernel to time).
    not_here = {"device_idle_share", "prefill_device_ms.batch",
                "idle_in_book_pct", "idle_in_prepare_pct",
                "idle_unattributed_pct", "eng_moe_grouped_share",
                "dsa_indexer_roofline", "dsa_attention_roofline"}
    assert per_layer - not_here <= set(m)
    # Prompts of 40-150 against 24 rows kept: nearly every query selects,
    # and attends to a fifth to a half of what it may see.
    assert 60 < m["dsa_selected_query_share"]["value"] < 100
    assert 15 < m["dsa_attended_row_share"]["value"] < 70
    assert 0 < m["mla_absorbed_token_share"]["value"] < 100
    assert 5 < m["eng_moe_held_pair_share"]["value"] < 70
    assert m["xla_builds_in_window.batch"]["value"] == 0
    facts = [json.loads(ln) for ln in lines if '"set_up_fact"' in ln][0]
    settings = facts["settings"]
    assert (settings["index_topk"], settings["index_token_bytes"],
            settings["kv_token_bytes"], settings["experts_first"],
            settings["experts_held"]) == (24, 32, 256, 4, 4)
    assert settings["index_pool_bytes"] > 0 and settings["prefix_caching"]
    # Every continuation program the pool's shapes reach ran in warm-up.
    shapes = " ".join(facts["compiled_shapes_replica0"])
    assert 'op="prefix_prefill"' in shapes
    assert no_leftovers()
