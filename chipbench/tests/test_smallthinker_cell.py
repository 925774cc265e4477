"""What PR 48 adds to the benchmark, on the CPU: the configuration file against
the catalog and through the launcher's mapping, the cut's parameter count from
the program's own shapes, the traffic file against the issue's parameters and
the programs its 96 shapes and its warm-up reach, the K/V window kernel's
counts by hand and through its reader, and one rehearsal of run.py on a small
model of the family (a window of 11 tokens over contexts up to 170) whose last
line carries the cell's metrics."""

import json
import os
import random

import pytest

import kernels
import kernels_swa_kv
import layer
import rehearsal
import traffic
from test_dots3_cell import _programs, _rec, _reported
from test_run import CONTRACT_KEYS, no_leftovers, run_py

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "smallthinker-21b-a3b-cut.longctx-16k"
CONFIG = os.path.join(BENCH, "configs", "smallthinker-21b-a3b-cut.json")
REDUCED = ["num_hidden_layers", "rope_layout", "sliding_window_layout"]


def test_configuration_file_is_the_catalogs_but_for_the_cut():
    from launch_engine import model_config_from_file

    m = model_config_from_file(CONFIG)
    assert (m.name, m.n_layers, m.layer_pattern, m.n_kv_layers,
            m.n_window_layers, m.d_model, m.d_ff, m.vocab_size) == \
        ("smallthinker-21b-a3b-cut", 8, "*WWW*WWW", 2, 6, 2560, 768, 151936)
    assert (m.n_heads, m.n_kv_heads, m.head_dim, m.rope_theta, m.kv_window,
            m.window, m.full_nope, m.norm_eps, m.max_seq_len) == \
        (28, 4, 128, 1.5e6, 4096, 4096, True, 1e-6, 16384)
    assert (m.n_experts, m.experts_per_token, m.router_input, m.expert_act,
            m.held_experts, m.n_expert_layers) == \
        (64, 6, "attn", "reglu", (0, 64), 8)
    assert not m.qk_norm and not m.kv_lora_rank and not m.tallies_choices
    with open(CONFIG) as f:
        doc = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f
                   if '"name": "SmallThinker-21BA3B-Instruct"' in line)
    assert doc["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items()
               if doc.get(k, "absent") != v}
    assert changed == set(doc["reduced"]) == set(REDUCED)
    assert doc["rope_layout"] == row["config"]["rope_layout"][:8] \
        == doc["sliding_window_layout"] == [0, 1, 1, 1, 0, 1, 1, 1]
    assert (doc["num_hidden_layers"], doc["num_hidden_layers_published"]) \
        == (8, 52)
    assert doc["reference"] == "smallthinker"
    assert os.path.isfile(os.path.join(BENCH, "configs",
                                       "reference_smallthinker.py"))
    assert {"from_the_config_alone", "weights", "tokenizer"} <= set(
        doc["assumed"])
    said = " ".join(doc["departures"]) + doc["deployment"] \
        + doc["assumed"]["from_the_config_alone"]
    for word in ("seven pipeline stages", "v5e-8", "all 64 experts",
                 "7.93 GB", "3,966,937,600", "13 : 39", "3 rows a decode step",
                 "pairs column i with i + 64", "prefix hits are off",
                 "257 pages a request", "NORMED attention input",
                 "counts the query's own position", "BEFORE the softmax"):
        assert word in said, word
    assert doc["serve"]["engine_args"] == [
        "--max-batch", "32", "--max-model-len", "16384", "--decode-chunk",
        "8", "--prefill-chunk", "1024"]
    # The cut's arithmetic, from the program's own parameter shapes.
    import jax

    from llm_d_inference_scheduler_tpu.kvcache import pages
    from llm_d_inference_scheduler_tpu.models import llama

    shapes = jax.eval_shape(lambda k: llama.init_params(m, k),
                            jax.random.key(0))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes) == 3_966_937_600
    assert count(shapes["layers"]) == 8 * 398_627_840
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == 777_912_320
    # The pools the file states, from the engine's own geometry.
    geom = pages.PageGeometry.for_engine(m, 32, 16384)
    assert (geom.pool_bytes, geom.window.pool_bytes, geom.window.n_blocks,
            geom.window.lane_pages) == (2_147_549_184, 1_869_742_080, 9510,
                                        257)


def test_the_parents_mapping_knows_none_of_the_files_keys():
    """What the parent commit's `config_from_hf` met in the file: no
    `num_local_experts`, no `intermediate_size` (a dense Llama's width, which
    it reads first): it raises at once and the engine exits (ISSUE 48, "Try
    the parent")."""
    with open(CONFIG) as f:
        doc = json.load(f)
    assert "intermediate_size" not in doc and "num_local_experts" not in doc
    assert "kv_lora_rank" not in doc and "layer_types" not in doc
    assert doc["moe_num_primary_experts"] == 64


def test_traffic_file_is_the_issues_table_and_warms_what_the_pool_reaches():
    mix = traffic.load_mix(traffic.mix_path(os.path.dirname(BENCH),
                                            "longctx-16k"))
    assert (mix["kind"], mix["clients"], mix["pool"], mix["ramp_s"],
            mix["temperature"], mix["trace"]["seconds"]) == \
        ("closed_clients", 64, 96, 45.0, 0.0, 1.5)
    assert mix["prompt_tokens"] == {"dist": "loguniform", "lo": 4096,
                                    "hi": 12288}
    assert mix["output_tokens"] == {"dist": "uniform", "lo": 512, "hi": 1536}
    order = random.Random(f"chipbench/order/closed_clients/{mix['order']}")
    prompts = traffic.grid(mix["prompt_tokens"], 96, order)
    outputs = traffic.grid(mix["output_tokens"], 96, order)
    assert len(set(prompts)) == 96 and min(prompts) > 4096
    assert 4600 < min(p + o for p, o in zip(prompts, outputs))
    assert max(p + o for p, o in zip(prompts, outputs)) < 13900 < 16384
    # (A table row holds 16,384 / 16 = 1,024 entries here.)
    reached = set().union(*(_programs(p, widest=1024) for p in prompts))
    warmed = set().union(*(_programs(p, widest=1024)
                           for p in mix["warmup"]["plain_prompt_tokens"]))
    assert reached <= warmed
    assert [b["concurrent"] for b in mix["warmup"]["bursts"]] == [
        2, 4, 8, 16, 32]
    plan = traffic.build(mix, 2 ** 31 + 5, 51.0)
    assert len(plan.chains) == 64


def test_kernels_swa_kv_counts_by_hand():
    """32 lanes of 10,000 tokens: a lane's query reads 4,096 rows of K and V,
    2,048 B each, at 4 x 28 x 128 FLOPs a row (7 a byte: memory bounds it on
    a v5e, whose ridge is at 240)."""
    one = kernels_swa_kv.window_kv_attention_decode(1.0, 1.0, 4096, 28, 4, 128)
    assert one == {"flops": 4 * 28 * 128,
                   "bytes": 2048 + 2 * 128 * (2 * 28 + 2 * 4)}
    cost = kernels_swa_kv.window_kv_attention_decode(320000.0, 32.0, 4096,
                                                     28, 4, 128)
    rows = 32 * 4096
    assert cost["flops"] == 4 * 28 * 128 * rows
    assert cost["bytes"] == rows * 2048 + 32 * 2 * 128 * 64
    assert cost["flops"] / (rows * 2048) == 7.0
    assert kernels.roofline_seconds(cost, "TPU v5 lite")[1] == "memory"
    # Contexts inside the window: every row is attended to, and the counts
    # are the full layers' kernel's (kernels.paged_attention_decode).
    short = kernels_swa_kv.window_kv_attention_decode(32 * 300.0, 32.0, 4096,
                                                      28, 4, 128)
    assert short == kernels.paged_attention_decode(32 * 300.0, 32.0, 28, 4,
                                                   128)


@pytest.fixture
def ctx():
    with open(CONFIG) as f:
        model = json.load(f)
    return layer.Context(
        records=[_rec(0.0), _rec(0.5), _rec(9.0)], seconds=10.0, chips=1,
        engine_scrapes=[({}, {})], gateway_scrape=({}, {}), gauge_samples=[],
        traces=[], trace_span=None, model=model, device_kind="TPU v5 lite")


def test_the_roofline_through_its_reader(ctx):
    assert layer.read_metric("swa_kv_decode_roofline", ctx) is None  # no trace
    ctx.traces = [{"devices": [{"window_s": 1.5, "busy_s": 1.4, "ops": {
        "%swa_paged_decode_attention.7": {
            "count": 600, "seconds": 0.03,
            "detail": "custom-call bf16[2,28,128]{2,1,0} custom-call(s32[514]{0} %x)"},
        "%custom-call.9": {
            "count": 300, "seconds": 0.015,
            "detail": "custom-call bf16[2,28,128]{2,1,0} custom-call(s32[514]{0} %x), custom_call_target=\"tpu_custom_call\", name=swa_paged_decode_attention"},
        "%paged_decode_attention_pallas.3": {
            "count": 300, "seconds": 0.5,
            "detail": "custom-call bf16[2,28,128]{2,1,0} custom-call(s32[2048]{0} %y)"},
        "%fusion.12": {
            "count": 900, "seconds": 0.004,
            "detail": "fusion bf16[2,3584]{1,0} fusion(bf16[2,28,128]{2,1,0} %swa_paged_decode_attention.7)"}},
        "idle_by_next_program": {}}]}]
    ctx.trace_span = (1.0, 2.5)
    share = layer.read_metric("swa_kv_decode_roofline", ctx)
    note = ctx.notes["swa_kv_decode"]
    # Two lanes of 9,001 tokens in the slice: 4,096 rows each; the full
    # layers' op and the fusion that takes the kernel's result are not read.
    assert note["calls"] == 900 and note["mean_lanes"] == pytest.approx(2.0)
    least = (2 * 4096 * 2048 + 2 * 2 * 128 * 64) / 819e9
    assert note["bound"] == "memory"
    assert note["least_seconds_per_call"] == pytest.approx(least)
    assert share == pytest.approx(100 * 900 * least / 0.045) and 0 < share < 100
    # The full layers' share reads its own op alone (the accepted reader).
    assert layer.read_metric("paged_attention_roofline", ctx) is not None
    assert ctx.notes["paged_attention_decode"]["calls"] == 300
    # Another configuration's trace, or the parent's: nothing, no error.
    ctx.model = {"kv_lora_rank": 512, "sliding_window_size": 513}
    assert layer.read_metric("swa_kv_decode_roofline", ctx) is None
    ctx.model = {"num_attention_heads": 32, "num_key_value_heads": 8,
                 "hidden_size": 4096}
    assert layer.read_metric("swa_kv_decode_roofline", ctx) is None


def test_the_cell_reports_what_the_issue_lists():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b-cut", "longctx-16k", 1)
    assert "13:39" in cell["why"] and len(cell["why"]) <= 200
    config = {c["name"]: c for c in bench["configs"]}[
        "smallthinker-21b-a3b-cut"]
    assert config["reduced"] == REDUCED and len(config["why"]) <= 200
    end_to_end, per_layer = _reported(bench, CELL)
    assert end_to_end == ["tpot_p95_ms", "out_tokens_per_s", "setup_s"]
    # (Supersets and first entries: a later PR appends names and cells.)
    assert per_layer >= {
        "swa_kv_decode_roofline", "paged_attention_roofline",
        "swa_attended_row_share", "kv_window_pool_usage_pct",
        "kv_pool_usage_pct", "eng_batch_fill", "eng_moe_grouped_share",
        "eng_refill_ahead_share", "prefill_device_ms.batch",
        "xla_builds_in_window.batch", "decode_chunk_ms", "device_idle_share",
        "eng_loop_host_pct", "eng_chunk_overlap_share", "idle_in_book_pct",
        "idle_in_prepare_pct", "idle_unattributed_pct",
        "eng_longest_chunk_ms", "eng_stall_device_wait_s", "eng_stall_host_s",
        "eng_event_loop_lag_ms", "eng_event_loop_lag_max_ms",
        "gw_event_loop_lag_max_ms", "gw_stream_gap_max_ms",
        "client_stream_gap_max_ms"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    new = by_name["swa_kv_decode_roofline"]
    assert new["workloads"][0] == CELL
    assert (new["layer"], new["moves"], new["source"], new["unit"]) == (
        "Kernels", "tpot_p95_ms", "device_trace", "%")
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                       "swa_kv_decode_roofline.json"))
    # The latent op's share stays the latent cell's.
    assert CELL not in by_name["swa_decode_roofline"]["workloads"]
    assert not per_layer & {"mla_decode_roofline", "dsa_attention_roofline",
                            "kv_page_run_share", "eng_moe_held_pair_share",
                            "moe_unread_expert_share"}


# A small model of the family in the published spelling: a window of 11, 7
# query heads a KV head, 8 experts of which a token takes 3.
TINY = {
    "source": "the program's `tiny-swa-kv` widths (tests only, never a cell)",
    "head_dim": 16, "hidden_size": 64, "max_position_embeddings": 256,
    "model_name": "tiny", "moe_ffn_hidden_size": 48,
    "moe_num_active_primary_experts": 3, "moe_num_primary_experts": 8,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 14, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1, 0, 1, 1, 1], "rope_scaling": None,
    "rope_theta": 10000, "sliding_window_layout": [0, 1, 1, 1, 0, 1, 1, 1],
    "sliding_window_size": 11, "tie_word_embeddings": False,
    "vocab_size": 512, "reduced": [], "reference": "smallthinker",
    "serve": {"model_name": "tiny-swa-kv-bench", "replicas": 1,
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
    root = str(tmp_path_factory.mktemp("bench-swa-kv"))
    path = rehearsal.make_copy(root)
    with open(os.path.join(root, "chipbench", "configs", "tiny-swa-kv.json"),
              "x") as f:
        json.dump(TINY, f)
    with open(os.path.join(root, "chipbench", "traffic",
                           "tiny-longctx-kv.json"), "x") as f:
        json.dump(TINY_LONGCTX, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-swa-kv", "source": TINY["source"],
        "file": "chipbench/configs/tiny-swa-kv.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-swa-kv.tiny-longctx-kv", "config": "tiny-swa-kv",
        "traffic": "tiny-longctx-kv", "chips": 1, "why": "rehearsal"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("tiny-swa-kv.tiny-longctx-kv")
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def test_rehearsal_prints_the_cells_metrics_on_the_last_line(copy):
    rc, lines = run_py(copy, "--workload", "tiny-swa-kv.tiny-longctx-kv",
                       "--seed", str(2 ** 31 + 48), "--seconds", "5",
                       "--trace", "2", "--platform", "cpu", timeout=500)
    assert rc == 0, lines[-3:]
    last = json.loads(lines[-1])
    assert set(last) == CONTRACT_KEYS
    assert last["device"]["platform"] == "cpu"
    assert last["correct"] and last["failed"] == 0 and last["attempted"] > 0
    m = last["metrics"]
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        end_to_end, per_layer = _reported(json.load(f),
                                          "tiny-swa-kv.tiny-longctx-kv")
    assert set(end_to_end) <= set(m)
    # Everything the cell lists that needs no device trace is on the line
    # (the tiny widths are no whole lanes: the FFN stays dense here).
    not_here = {"device_idle_share", "prefill_device_ms.batch",
                "idle_in_book_pct", "idle_in_prepare_pct",
                "idle_unattributed_pct", "eng_moe_grouped_share",
                "paged_attention_roofline", "swa_kv_decode_roofline"}
    assert per_layer - not_here <= set(m)
    # Prompts of 40-150 against a window of 11: a window layer reads a
    # fourteenth to a quarter of what a full layer would.
    assert 5 < m["swa_attended_row_share"]["value"] < 30
    assert 0 < m["kv_window_pool_usage_pct"]["value"] <= 100
    assert m["xla_builds_in_window.batch"]["value"] == 0
    facts = [json.loads(ln) for ln in lines if '"set_up_fact"' in ln][0]
    settings = facts["settings"]
    assert (settings["kv_layers_full"], settings["kv_layers_window"],
            settings["window"], settings["router_input"],
            settings["expert_activation"]) == (2, 6, 11, "attn", "reglu")
    assert settings["window_pool_bytes"] > 0 and not settings["prefix_caching"]
    assert no_leftovers()
