"""What PR 39 adds to the benchmark, on the CPU: the configuration file against
the catalog and through the launcher's mapping, the plain reference's copy
against the program at the `tiny-longcat` preset, the share of zero-compute
choices and the latent kernel's roofline at 64 heads through their readers,
and one rehearsal of run.py on a small model of the family whose last line
carries the cell's metrics."""

import importlib.util
import json
import os

import numpy as np
import pytest

import kernels
import kernels_mla
import layer
import prom
import rehearsal
from client import Record
from test_run import CONTRACT_KEYS, no_leftovers, run_py

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "longcat-flash-omni-cut.reason-batch"
CONFIG = os.path.join(BENCH, "configs", "longcat-flash-omni-cut.json")


def test_configuration_file_maps_to_the_programs_config():
    from launch_engine import model_config_from_file

    m = model_config_from_file(CONFIG)
    assert (m.name, m.n_layers, m.attn_sublayers, m.n_kv_layers, m.d_model,
            m.d_ff, m.vocab_size) == \
        ("longcat-flash-omni-cut", 4, 2, 8, 6144, 12288, 16384)
    assert (m.n_heads, m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim, m.latent_dim, m.head_dim) == \
        (64, 1536, 512, 128, 64, 128, 576, 192)
    assert (m.mla_scale_q_lora, m.mla_scale_kv_lora, m.rope_theta,
            m.norm_eps) == (True, True, 1e7, 1e-5)
    # The router scores 512 experts and 256 that compute nothing and picks
    # 12; this chip holds the first 16 experts.
    assert (m.router_scoring, m.n_experts, m.n_zero_experts, m.router_width,
            m.experts_per_token, m.held_experts, m.moe_d_ff,
            m.routed_scaling_factor) == \
        ("softmax", 512, 256, 768, 12, (0, 16), 2048, 6.0)
    with open(CONFIG) as f:
        doc = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f
                   if '"name": "LongCat-Flash-Omni"' in line)
    assert doc["source"] == row["source_url"]
    # Every catalog key, flat and as published, but for the cut.
    changed = {k for k, v in row["config"].items()
               if doc.get(k, "absent") != v}
    assert changed == set(doc["reduced"]) == {
        "num_layers", "n_routed_experts", "vocab_size"}
    assert (doc["num_layers"], doc["n_routed_experts"], doc["vocab_size"]) \
        == (4, 16, 16384)
    assert (doc["num_layers_published"], doc["n_routed_experts_published"],
            doc["vocab_size_published"], doc["expert_parallel_rank"]) == \
        (28, 512, 131072, 0)
    assert {"norm_topk_prob", "weights", "tokenizer", "rotary"} <= set(
        doc["assumed"])
    said = " ".join(doc["departures"]) + doc["deployment"]
    for word in ("32 v5e chips", "exchange", "zero-compute", "1 token",
                 "7 pipeline stages"):
        assert word in said
    assert doc["serve"]["engine_args"] == [
        "--max-batch", "64", "--max-model-len", "2048", "--decode-chunk", "8"]


def test_the_parents_mapping_ends_on_the_file_at_once():
    """A program without the family (the parent commit) takes the file for a
    DeepSeek-V3-family config by its `kv_lora_rank` and refuses the first key
    it does not compute, the low-rank query, by name: before any weight is
    made. (The message is PR 32's, `_MLA_ONLY`'s.)"""
    with open(CONFIG) as f:
        doc = json.load(f)
    assert doc["kv_lora_rank"] and doc["q_lora_rank"] == 1536
    assert "hybrid_override_pattern" not in doc and "text_config" not in doc


def _reference():
    path = os.path.join(BENCH, "configs", "reference_longcat_flash.py")
    spec = importlib.util.spec_from_file_location("reference_longcat_flash",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_program_forward_matches_plain_reference():
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from llm_d_inference_scheduler_tpu.models import family
    from llm_d_inference_scheduler_tpu.models.configs import get_config

    cfg = get_config("tiny-longcat")
    model = family(cfg)
    params = model.init_params(cfg, jax.random.key(7), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(9), (2, 24), 0, cfg.vocab_size)
    ours, _ = model.forward(params, cfg, tokens)
    ref = _reference()
    for row in range(2):
        want = ref.forward(
            params, tokens[row], n_heads=cfg.n_heads,
            kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim, rope_theta=cfg.rope_theta,
            norm_eps=cfg.norm_eps, experts_per_token=cfg.experts_per_token,
            routed_scaling_factor=cfg.routed_scaling_factor,
            n_experts=cfg.n_experts, q_block=5)
        # float32 on both sides, different summation order
        # (test_reference.py's limits).
        np.testing.assert_allclose(np.asarray(ours[row]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def _rec(due, prompt=500):
    r = Record(f"r{due}", -1, 0, due, due, prompt, 200)
    r.status, r.prompt_tokens, r.completion_tokens = 200, prompt, 200
    r.first_s, r.last_s, r.done_s = due + 0.1, due + 5.0, due + 5.0
    r.pieces = [(r.first_s, 1), (r.last_s, 199)]
    return r


@pytest.fixture
def ctx():
    before = prom.parse(
        'jetstream:moe_routed_pairs_total{held="yes"} 100.0\n'
        'jetstream:moe_routed_pairs_total{held="no"} 3100.0\n'
        'jetstream:moe_routed_pairs_total{held="zero"} 1600.0\n')
    after = prom.parse(
        'jetstream:moe_routed_pairs_total{held="yes"} 350.0\n'
        'jetstream:moe_routed_pairs_total{held="no"} 10850.0\n'
        'jetstream:moe_routed_pairs_total{held="zero"} 5600.0\n')
    with open(CONFIG) as f:
        model = json.load(f)
    return layer.Context(
        records=[_rec(0.0), _rec(0.5), _rec(9.0)], seconds=10.0, chips=1,
        engine_scrapes=[(before, after)], gateway_scrape=({}, {}),
        gauge_samples=[], traces=[], trace_span=None, model=model,
        device_kind="TPU v5 lite")


def test_counter_ratios(ctx):
    # 4,000 of 12,000 choices compute nothing; 250 of the 8,000 others are
    # held here (1 in 32).
    assert layer.read_metric("moe_zero_pair_share", ctx) == pytest.approx(
        100 / 3)
    assert layer.read_metric("eng_moe_held_pair_share", ctx) == pytest.approx(
        3.125)
    # A program whose router has no such outputs (nemotron-3-super-cut, or
    # the parent) never exposes `zero`: nothing to read, no error; what it
    # does expose reads as before.
    before, after = ctx.engine_scrapes[0]
    ctx.engine_scrapes = [({k: v for k, v in before.items() if "zero" not in k[1]},
                           {k: v for k, v in after.items() if "zero" not in k[1]})]
    assert layer.read_metric("moe_zero_pair_share", ctx) is None
    assert layer.read_metric("eng_moe_held_pair_share", ctx) == pytest.approx(
        3.125)
    ctx.engine_scrapes = [({}, {})]
    assert layer.read_metric("moe_zero_pair_share", ctx) is None


def test_the_latent_kernels_roofline_at_64_heads(ctx):
    """The reader takes the heads from the configuration file: 2 x 64 x 1,088
    FLOPs against 1,152 bytes a context token is 121 FLOPs a byte, still
    under the v5e's ridge (240), so memory bounds the kernel at 64 heads as
    it does at Kimi's 16."""
    one = kernels_mla.latent_attention_decode(1.0, 0.0, 64, 576, 512)
    assert one == {"flops": 2 * 64 * (576 + 512), "bytes": 576 * 2}
    assert 120 < one["flops"] / one["bytes"] < 122
    cost = kernels_mla.latent_attention_decode(64 * 700.0, 64.0, 64, 576, 512)
    assert kernels.roofline_seconds(cost, "TPU v5 lite")[1] == "memory"
    ctx.traces = [{"devices": [{"window_s": 1.5, "busy_s": 1.4, "ops": {
        "custom-call.3": {"count": 640, "seconds": 0.004,
                          "detail": "jit(f)/mla_paged_decode_attention/pallas_call"}},
        "idle_by_next_program": {}}]}]
    ctx.trace_span = (1.0, 2.5)
    share = layer.read_metric("mla_decode_roofline", ctx)
    note = ctx.notes["latent_attention_decode"]
    assert note["bound"] == "memory" and note["mean_lanes"] == pytest.approx(2.0)
    least = (1002 * 576 * 2 + 2 * 2 * (64 * 1088 + 576)) / 819e9
    assert note["least_seconds_per_call"] == pytest.approx(least)
    assert share == pytest.approx(100 * 640 * least / 0.004) and share < 100


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
        "longcat-flash-omni-cut", "reason-batch", 1)
    assert "1 token a step (deployment 32)" in cell["why"]
    config = {c["name"]: c for c in bench["configs"]}["longcat-flash-omni-cut"]
    assert config["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    end_to_end, per_layer = _reported(bench, CELL)
    assert end_to_end == ["tpot_p95_ms", "out_tokens_per_s", "setup_s"]
    assert per_layer >= {
        "moe_zero_pair_share", "eng_moe_held_pair_share",
        "eng_moe_grouped_share", "mla_absorbed_token_share",
        "mla_decode_roofline", "eng_batch_fill", "kv_pool_usage_pct",
        "decode_chunk_ms", "device_idle_share", "eng_loop_host_pct",
        "eng_chunk_overlap_share", "eng_refill_ahead_share",
        "xla_builds_in_window.batch", "prefill_device_ms.batch",
        "idle_in_book_pct", "idle_in_prepare_pct", "idle_unattributed_pct",
        "eng_longest_chunk_ms", "client_stream_gap_max_ms"}
    assert not per_layer & {"paged_attention_roofline", "ssm_decode_roofline",
                            "ssm_step_token_share", "ssm_kernel_update_share"}
    zero = {m["name"]: m for m in bench["per_layer"]}["moe_zero_pair_share"]
    assert zero["workloads"] == [CELL] and zero["moves"] == "out_tokens_per_s"
    # The one traffic file serves two configurations.
    assert [w["name"] for w in bench["workloads"]
            if w["traffic"] == "reason-batch"] == [
        "nemotron-3-super-cut.reason-batch", CELL]


# A small model of the family in the published spelling: sixteen experts of
# which this chip holds four, and eight outputs that compute nothing, so the
# zero share reads about a third and the held share about a quarter.
TINY_LONGCAT = {
    "source": "the program's `tiny-longcat` widths (tests only, never a cell)",
    "hidden_size": 96, "vocab_size": 512, "num_layers": 2,
    "num_attention_heads": 3, "ffn_hidden_size": 160,
    "expert_ffn_hidden_size": 40, "kv_lora_rank": 24, "q_lora_rank": 20,
    "qk_nope_head_dim": 20, "qk_rope_head_dim": 8, "v_head_dim": 12,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 4,
    "n_routed_experts_published": 16, "expert_parallel_rank": 1,
    "zero_expert_num": 8, "zero_expert_type": "identity", "moe_topk": 5,
    "attention_method": "MLA", "attention_bias": False,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "reduced": [], "reference": "longcat_flash",
    "serve": {"model_name": "tiny-longcat-bench", "replicas": 1,
              "gateway": "monolithic", "tokenizer": "byte",
              "engine_args": ["--max-batch", "4", "--max-model-len", "256",
                              "--decode-chunk", "4"]}}

TINY_REASON = {
    "kind": "closed_clients", "clients": 6, "ramp_s": 1.0, "pool": 32,
    "prompt_tokens": {"dist": "loguniform", "lo": 20, "hi": 100},
    "output_tokens": {"dist": "uniform", "lo": 8, "hi": 24},
    "trace": {"seconds": 0.5},
    "warmup": {"plain_prompt_tokens": [30, 60, 100], "max_tokens": 2,
               "bursts": [{"concurrent": k, "prompt_tokens": 30,
                           "max_tokens": 12} for k in (2, 4)]}}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark with one cell more, a small model of the family under
    the real cell's metrics: new files and new entries only."""
    root = str(tmp_path_factory.mktemp("bench-longcat"))
    path = rehearsal.make_copy(root)
    with open(os.path.join(root, "chipbench", "configs", "tiny-longcat.json"),
              "x") as f:
        json.dump(TINY_LONGCAT, f)
    with open(os.path.join(root, "chipbench", "traffic",
                           "tiny-reason-lc.json"), "x") as f:
        json.dump(TINY_REASON, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-longcat", "source": TINY_LONGCAT["source"],
        "file": "chipbench/configs/tiny-longcat.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-longcat.tiny-reason-lc", "config": "tiny-longcat",
        "traffic": "tiny-reason-lc", "chips": 1, "why": "rehearsal"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("tiny-longcat.tiny-reason-lc")
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def test_rehearsal_prints_the_cells_metrics_on_the_last_line(copy):
    rc, lines = run_py(copy, "--workload", "tiny-longcat.tiny-reason-lc",
                       "--seed", str(2 ** 31 + 11), "--seconds", "5",
                       "--trace", "2", "--platform", "cpu", timeout=400)
    assert rc == 0, lines[-3:]
    last = json.loads(lines[-1])
    assert set(last) == CONTRACT_KEYS
    assert last["device"]["platform"] == "cpu"
    assert last["correct"] and last["failed"] == 0 and last["attempted"] > 0
    m = last["metrics"]
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        end_to_end, per_layer = _reported(json.load(f),
                                          "tiny-longcat.tiny-reason-lc")
    assert set(end_to_end) <= set(m)
    # Everything the cell lists that needs no device trace is on the line.
    # (The CPU runs every program dense over the experts and attends through
    # the XLA gather: the grouped share has no `grouped` series to read
    # there, and the kernel's roofline no kernel.)
    not_here = {"device_idle_share", "prefill_device_ms.batch",
                "idle_in_book_pct", "idle_in_prepare_pct",
                "idle_unattributed_pct", "eng_moe_grouped_share",
                "mla_decode_roofline"}
    assert per_layer - not_here <= set(m)
    assert 20 < m["moe_zero_pair_share"]["value"] < 50     # 8 of 24 outputs
    # 4 of 16 held: a quarter under even routing, and far from even at this
    # size (sixteen experts, a drawn bias of the scores' own order, byte text).
    assert 5 < m["eng_moe_held_pair_share"]["value"] < 70
    assert 0 < m["mla_absorbed_token_share"]["value"] < 100
    assert m["xla_builds_in_window.batch"]["value"] == 0
    settings = [json.loads(ln)["settings"] for ln in lines
                if '"set_up_fact"' in ln][0]
    assert (settings["kv_layers"], settings["experts_first"],
            settings["experts_held"], settings["zero_experts"]) == (4, 4, 4, 8)
    assert no_leftovers()
