"""What PR 54 adds to the benchmark, on the CPU: the configuration file against
the catalog and through the launcher's mapping, what makes a program without
the family refuse it, the plain reference's copy against the program at the
`tiny-lfm2` preset, the traffic file through the generator, the two new scope
shares, and one rehearsal of run.py on a small model of the family whose last
line carries the cell's metrics. Nothing here is pinned by equality that a
later PR appends to."""

import importlib.util
import json
import os

import numpy as np
import pytest

import layer
import rehearsal
import traffic
from test_run import CONTRACT_KEYS, no_leftovers, run_py

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "lfm2-8b-a1b-cut.rag-extract"
CONFIG = os.path.join(BENCH, "configs", "lfm2-8b-a1b-cut.json")


def _doc():
    with open(CONFIG) as f:
        return json.load(f)


def test_configuration_file_maps_to_the_programs_config():
    from launch_engine import model_config_from_file

    m = model_config_from_file(CONFIG)
    assert (m.name, m.n_layers, m.d_model, m.d_ff, m.moe_d_ff,
            m.vocab_size) == ("lfm2-8b-a1b-cut", 16, 2048, 7168, 1792, 65536)
    assert m.layer_pattern == "CCQCCCQCCCQCCCQC"
    assert (m.n_heads, m.n_kv_heads, m.head_dim, m.kv_heads_a_row) == (
        32, 8, 64, 2)
    assert (m.n_experts, m.experts_per_token, m.first_k_dense,
            m.n_expert_layers) == (32, 4, 2, 14)
    assert (m.n_state_layers, m.n_kv_layers, m.ssm_conv, m.ssm_row) == (
        12, 4, 3, ())
    doc = _doc()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f if '"LFM2-8B-A1B"' in line)
    assert doc["source"] == row["source_url"]
    # Every catalog key, flat and as published, but the two that are cut.
    assert {k for k, v in row["config"].items()
            if doc.get(k, "absent") != v} == set(doc["reduced"]) == {
                "num_hidden_layers", "layer_types"}
    assert doc["layer_types"] == row["config"]["layer_types"][:16]
    assert doc["layer_types_published"] == row["config"]["layer_types"]
    assert doc["num_hidden_layers_published"] == 24
    # A whole number of periods: 12 convolutions to 4 attention layers, the
    # model's 18 to 6; the two dense layers and 14 layers past them.
    assert doc["layer_types"].count("conv") == 12
    for key in ("tie_word_embeddings", "router_epsilon", "rotary", "tail",
                "weights", "tokenizer"):
        assert key in doc["assumed"]
    said = " ".join(doc["departures"]) + doc["deployment"]
    for word in ("24 -> 16", "two pipeline stages", "two tensors",
                 "side by side", "1e-6", "one v5e chip"):
        assert word in said, word
    assert doc["reference"] == "lfm2_moe"
    assert doc["serve"]["engine_args"] == [
        "--max-batch", "64", "--max-model-len", "4608", "--decode-chunk", "8",
        "--prefill-chunk", "1024"]


def test_a_program_without_the_family_refuses_the_file_at_once():
    """The parent commit knows a family by one of five keys or by a
    `model_type` of its plain mapping; this file has none of them, so
    `config_from_hf` raises before a weight is made (tried on the parent:
    exit 1 in seconds)."""
    doc = _doc()
    assert doc["model_type"] == "lfm2_moe"
    for key in ("hybrid_override_pattern", "zero_expert_num", "kv_lora_rank",
                "moe_num_primary_experts", "text_config"):
        assert key not in doc
    assert doc["model_type"] not in ("llama", "mixtral", "qwen3", "jamba")


def _reference():
    path = os.path.join(BENCH, "configs", "reference_lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("reference_lfm2_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_program_forward_matches_plain_reference():
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from llm_d_inference_scheduler_tpu.models import family
    from llm_d_inference_scheduler_tpu.models.configs import get_config

    cfg = get_config("tiny-lfm2")
    model = family(cfg)
    params = model.init_params(cfg, jax.random.key(7), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(9), (2, 37), 0, cfg.vocab_size)
    ours, _ = model.forward(params, cfg, tokens)
    ref = _reference()
    for row in range(2):
        want = ref.forward(
            params, tokens[row],
            layer_types=["conv", "conv", "full_attention"] * 2,
            num_dense_layers=cfg.first_k_dense, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            top_k=cfg.experts_per_token, rope_theta=cfg.rope_theta,
            norm_eps=cfg.norm_eps, q_block=5)
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
    ``window``: (bucket, prior pages' bucket) each."""
    def prior(at):
        p = 1
        while p < at // 16:
            p *= 2
        return p if at else 0
    return {(_bucket(min(n - at, window)), prior(at))
            for at in range(0, n, window)}


def test_traffic_file_through_the_generator():
    mix = traffic.load_mix(traffic.mix_path(os.path.dirname(BENCH),
                                            "rag-extract"))
    seed = 2 ** 31 + 99
    plan = traffic.build(mix, seed, 51.0)
    assert len(plan.chains) == 128 and plan.temperature == 0.0
    assert min(c.start_s for c in plan.chains) == -10.0 and not plan.preload
    reqs = [next(plan.chains[0].requests) for _ in range(512)]
    lens = sorted(r.prompt_tokens for r in reqs)
    assert mix["pool"] == 512 and 1024 <= lens[0] and lens[-1] <= 4096
    assert 2100 < sum(lens) / len(lens) < 2350         # log-uniform's mean
    assert all(64 <= r.max_tokens <= 256 for r in reqs)
    assert 150 < sum(r.max_tokens for r in reqs) / len(reqs) < 170
    # Every prompt but one of exactly a window needs a continuation window,
    # which starts from a carried tail; half of them need two or three.
    assert sum(n > 1024 for n in lens) >= len(lens) - 1
    assert 0.4 < sum(n > 2048 for n in lens) / len(lens) < 0.6
    # The longest prompt and the longest answer fit a lane.
    assert lens[-1] + 256 <= 4608
    assert len({r.prompt[:24] for r in reqs}) == len(reqs)     # unshared
    # Every prefill and continuation program the pool can reach is warmed by
    # a prompt of the warm-up, and every decode bucket up to the 64 lanes by
    # a burst of prompts that take the window's own first program.
    reach = set().union(*(_programs(n) for n in lens))
    warm = set().union(*(_programs(r.prompt_tokens)
                         for group in traffic.warmup_requests(mix, seed)
                         for r in group))
    assert reach <= warm
    assert {p for _, p in warm} == {0, 64, 128, 256}
    assert {b for b, p in warm if p} == {16, 32, 64, 128, 256, 512, 1024}
    bursts = traffic.burst_requests(mix, seed)
    assert [len(b) for b in bursts] == [2, 4, 8, 16, 32, 64]
    assert {r.prompt_tokens for b in bursts for r in b} == {1024}
    assert mix["trace"]["seconds"] == 1.5


def test_the_new_shares_name_scopes_the_models_emit():
    from llm_d_inference_scheduler_tpu.models import scopes

    for name, program in (("dev_prefill_conv_share", "prefill"),
                          ("dev_decode_conv_share", "decode")):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["kind"] == "trace_scope_share"
        assert (spec["programs"], spec["of"]) == ([program], "programs")
        assert set(spec["scopes"]) == {"state.proj", "state.update"}
        assert set(spec["scopes"]) <= set(scopes.BLOCKS)
    # No trace, or the parent's program: nothing to read, and no error.
    ctx = layer.Context(
        records=[], seconds=10.0, chips=1, engine_scrapes=[({}, {})],
        gateway_scrape=({}, {}), gauge_samples=[], traces=[], trace_span=None,
        model=_doc(), device_kind="TPU v5 lite")
    assert layer.read_metric("dev_prefill_conv_share", ctx) is None
    assert layer.read_metric("dev_decode_conv_share", ctx) is None


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
        "lfm2-8b-a1b-cut", "rag-extract", 1)
    assert len(cell["why"]) <= 200
    config = {c["name"]: c for c in bench["configs"]}["lfm2-8b-a1b-cut"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    end_to_end, per_layer = _reported(bench, CELL)
    assert set(end_to_end) >= {"tpot_p95_ms", "out_tokens_per_s", "setup_s"}
    assert per_layer >= {
        "dev_prefill_conv_share", "dev_decode_conv_share",
        "paged_attention_roofline", "eng_moe_grouped_share",
        "ssm_step_token_share", "dev_prefill_share", "decode_chunk_device_ms",
        "dev_decode_experts_share", "dev_decode_dense_share",
        "dev_decode_attention_share", "dev_decode_state_share",
        "dev_decode_head_share", "dev_prefill_attention_share",
        "dev_prefill_experts_share", "dev_prefill_head_share",
        "dev_prefill_state_share", "dev_unscoped_share",
        "prefill_device_ms.batch", "xla_builds_in_window.batch",
        "kv_pool_usage_pct", "eng_batch_fill", "eng_refill_ahead_share",
        "device_idle_share", "eng_loop_host_pct", "eng_chunk_overlap_share",
        "idle_in_book_pct", "idle_in_prepare_pct", "idle_unattributed_pct"}
    # Other families' mechanisms and other kernels.
    assert not {n for n in per_layer if n.startswith(
        ("mla_", "dsa_", "swa_", "kv_page_run", "kv_window_", "kv_prefill_",
         "ssm_decode_roofline", "ssm1_", "ssm_kernel_", "ssm_scan_",
         "eng_moe_held", "moe_"))}


# A small model of the family in the published spelling (the `tiny-lfm2`
# preset's widths but a head of d_model / heads): prompts past 32 tokens take
# continuation windows.
TINY_LFM2 = {
    "source": "the program's `tiny-lfm2` widths (tests only, never a cell)",
    "model_type": "lfm2_moe", "hidden_size": 128, "vocab_size": 512,
    "num_hidden_layers": 6,
    "layer_types": ["conv", "conv", "full_attention"] * 2,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 128,
    "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 3,
    "norm_eps": 1e-05, "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "rope_theta": 10000,
    "conv_L_cache": 3, "conv_bias": False, "max_position_embeddings": 256,
    "reduced": [], "reference": "lfm2_moe",
    "serve": {"model_name": "tiny-lfm2-bench", "replicas": 1,
              "gateway": "monolithic", "tokenizer": "byte",
              "engine_args": ["--max-batch", "4", "--max-model-len", "256",
                              "--decode-chunk", "4", "--prefill-chunk", "32"]}}

TINY_RAG = {
    "kind": "closed_clients", "clients": 6, "ramp_s": 1.0, "pool": 32,
    "prompt_tokens": {"dist": "loguniform", "lo": 32, "hi": 128},
    "output_tokens": {"dist": "uniform", "lo": 4, "hi": 12},
    "trace": {"seconds": 0.5},
    # (Every window bucket x prior bucket a prompt of 32-128 tokens in windows
    # of 32 can reach.)
    "warmup": {"plain_prompt_tokens": [32, 48, 64, 80, 96, 112, 128],
               "max_tokens": 2,
               "bursts": [{"concurrent": k, "prompt_tokens": 32,
                           "max_tokens": 12} for k in (2, 4)]}}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The benchmark with one cell more, a small model of the family under
    the real cell's metrics: new files and new entries only."""
    root = str(tmp_path_factory.mktemp("bench-lfm2"))
    path = rehearsal.make_copy(root)
    with open(os.path.join(root, "chipbench", "configs", "tiny-lfm2.json"),
              "x") as f:
        json.dump(TINY_LFM2, f)
    with open(os.path.join(root, "chipbench", "traffic",
                           "tiny-rag-extract.json"), "x") as f:
        json.dump(TINY_RAG, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-lfm2", "source": TINY_LFM2["source"],
        "file": "chipbench/configs/tiny-lfm2.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-lfm2.tiny-rag-extract", "config": "tiny-lfm2",
        "traffic": "tiny-rag-extract", "chips": 1, "why": "rehearsal"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("tiny-lfm2.tiny-rag-extract")
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def test_rehearsal_prints_the_cells_metrics_on_the_last_line(copy):
    rc, lines = run_py(copy, "--workload", "tiny-lfm2.tiny-rag-extract",
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
    end_to_end, per_layer = _reported(bench, "tiny-lfm2.tiny-rag-extract")
    assert set(end_to_end) <= set(m)
    # Everything the cell lists whose source is no device trace is on the
    # line (a CPU line has no device plane).
    from_trace = {x["name"] for x in bench["per_layer"]
                  if x["source"] == "device_trace"}
    # (On the CPU every program is dense over the experts: there is no
    # `grouped` series to read, and the share is left out.)
    assert per_layer - from_trace - {"eng_moe_grouped_share"} <= set(m)
    assert 0 < m["ssm_step_token_share"]["value"] < 100
    assert m["xla_builds_in_window.batch"]["value"] == 0
    settings = next(json.loads(ln)["settings"] for ln in lines
                    if '"state_pool_bytes"' in ln)
    assert settings["state_update"] is None and "state_scan" not in settings
    assert (settings["kv_layers"], settings["state_layers"]) == (2, 4)
    # Two heads of 64 a page row: K and V, 2 heads x 64 values, bf16.
    assert settings["kv_token_bytes"] == 2 * 2 * 64 * 2
    assert settings["state_slot_bytes"] == 4 * 2 * 128 * 2
    assert no_leftovers()
