"""What PR 34 adds to the benchmark, on the CPU: the configuration file against
the catalog and through the launcher's mapping, the plain reference's copy
against the program at the `tiny-hybrid` preset, the traffic file through the
generator, the two counter ratios, and one rehearsal of run.py on a small
model of the family whose last line carries the cell's metrics."""

import importlib.util
import json
import os

import numpy as np
import pytest

import layer
import prom
import rehearsal
import traffic
from test_run import CONTRACT_KEYS, no_leftovers, run_py

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "nemotron-3-super-cut.reason-batch"
CONFIG = os.path.join(BENCH, "configs", "nemotron-3-super-cut.json")


def test_configuration_file_maps_to_the_programs_config():
    from launch_engine import model_config_from_file

    m = model_config_from_file(CONFIG)
    assert (m.name, m.n_layers, m.layer_pattern, m.d_model, m.vocab_size) == \
        ("nemotron-3-super-cut", 11, "EMEMEMEMEM*", 4096, 131072)
    assert (m.n_heads, m.n_kv_heads, m.head_dim, m.norm_eps) == (32, 2, 128, 1e-5)
    assert (m.ssm_heads, m.ssm_head_dim, m.ssm_state, m.ssm_groups, m.ssm_conv,
            m.ssm_chunk, m.ssm_dt_range) == (128, 64, 128, 8, 4, 128,
                                             (0.001, 0.1, 0.0001))
    # The router scores all 512 and picks 22; this chip holds the first 128.
    assert (m.n_experts, m.experts_per_token, m.held_experts,
            m.routed_scaling_factor) == (512, 22, (0, 128), 5.0)
    assert (m.moe_latent_dim, m.moe_d_ff, m.shared_d_ff, m.n_shared_experts) \
        == (1024, 2688, 5376, 1)
    with open(CONFIG) as f:
        doc = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f
                   if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line)
    assert doc["source"] == row["source_url"]
    # Every catalog key, flat and as published, but for the cut.
    changed = {k for k, v in row["config"].items()
               if doc.get(k, "absent") != v}
    assert changed == set(doc["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts"}
    assert (doc["num_hidden_layers"], doc["n_routed_experts"],
            doc["n_routed_experts_published"]) == (11, 128, 512)
    # One whole period of the published pattern, in its published ratio.
    period = doc["hybrid_override_pattern"]
    assert period in row["config"]["hybrid_override_pattern"]
    assert row["config"]["hybrid_override_pattern"][26:37] == period
    assert [period.count(c) for c in "ME*"] == [5, 5, 1]
    assert "rotary" in doc["assumed"] and "state" in doc["assumed"]
    said = " ".join(doc["departures"]) + doc["deployment"]
    for word in ("multi-token-prediction", "512", "4 v5e chips", "exchange"):
        assert word in said
    assert doc["serve"]["engine_args"] == [
        "--max-batch", "64", "--max-model-len", "2048", "--decode-chunk", "8"]


def test_the_parents_mapping_ends_on_the_file_at_once():
    """A program without the family (the parent commit) reads the file as a
    llama-family config and ends on the norm's epsilon, which this family
    spells `layer_norm_epsilon`: before any weight is made."""
    with open(CONFIG) as f:
        doc = json.load(f)
    assert "rms_norm_eps" not in doc and "layer_norm_epsilon" in doc
    assert "kv_lora_rank" not in doc and "num_local_experts" not in doc


def _reference():
    path = os.path.join(BENCH, "configs", "reference_nemotron_h.py")
    spec = importlib.util.spec_from_file_location("reference_nemotron_h", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_program_forward_matches_plain_reference():
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from llm_d_inference_scheduler_tpu.models import family
    from llm_d_inference_scheduler_tpu.models.configs import get_config

    cfg = get_config("tiny-hybrid")
    model = family(cfg)
    params = model.init_params(cfg, jax.random.key(7), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(9), (2, 37), 0, cfg.vocab_size)
    ours, _ = model.forward(params, cfg, tokens)
    ref = _reference()
    for row in range(2):
        want = ref.forward(
            params, tokens[row], pattern=cfg.layer_pattern,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, ssm_heads=cfg.ssm_heads,
            ssm_head_dim=cfg.ssm_head_dim, ssm_state=cfg.ssm_state,
            ssm_groups=cfg.ssm_groups, norm_eps=cfg.norm_eps,
            experts_per_token=cfg.experts_per_token,
            routed_scaling_factor=cfg.routed_scaling_factor, q_block=5)
        # float32 on both sides, different summation order
        # (test_reference.py's limits).
        np.testing.assert_allclose(np.asarray(ours[row]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def _bucket(n):
    b = 16
    while b < n:
        b *= 2
    return b


def test_traffic_file_through_the_generator():
    mix = traffic.load_mix(traffic.mix_path(os.path.dirname(BENCH),
                                            "reason-batch"))
    seed = 2 ** 31 + 99
    plan = traffic.build(mix, seed, 51.0)
    assert len(plan.chains) == 128 and plan.temperature == 0.0
    assert min(c.start_s for c in plan.chains) == -12.0 and not plan.preload
    reqs = [next(plan.chains[0].requests) for _ in range(512)]
    lens = sorted(r.prompt_tokens for r in reqs)
    assert mix["pool"] == 512 and 128 <= lens[0] and lens[-1] <= 1024
    assert 400 < sum(lens) / len(lens) < 470          # log-uniform's mean
    assert 500 < sum(r.max_tokens for r in reqs) / len(reqs) < 524
    assert all(256 <= r.max_tokens <= 768 for r in reqs)
    # The longest prompt and the longest answer fit a lane.
    assert lens[-1] + 768 <= 2048
    assert len({r.prompt[:24] for r in reqs}) == len(reqs)    # unshared
    # Every prefill program the pool can reach is warmed by a prompt of the
    # warm-up, and every decode bucket up to the 64 lanes by a burst.
    reach = {_bucket(n) for n in lens}
    warm = {_bucket(r.prompt_tokens)
            for group in traffic.warmup_requests(mix, seed) for r in group}
    assert reach <= warm == {128, 256, 512, 1024}
    assert [len(b) for b in traffic.burst_requests(mix, seed)] == [
        2, 4, 8, 16, 32, 64]
    assert mix["trace"]["seconds"] == 1.5


def test_counter_ratios():
    before = prom.parse(
        'jetstream:ssm_tokens_total{form="scan"} 1000.0\n'
        'jetstream:ssm_tokens_total{form="step"} 3000.0\n'
        'jetstream:moe_routed_pairs_total{held="yes"} 100.0\n'
        'jetstream:moe_routed_pairs_total{held="no"} 300.0\n')
    after = prom.parse(
        'jetstream:ssm_tokens_total{form="scan"} 7000.0\n'
        'jetstream:ssm_tokens_total{form="step"} 7000.0\n'
        'jetstream:moe_routed_pairs_total{held="yes"} 2600.0\n'
        'jetstream:moe_routed_pairs_total{held="no"} 7800.0\n')
    with open(CONFIG) as f:
        model = json.load(f)
    ctx = layer.Context(
        records=[], seconds=10.0, chips=1, engine_scrapes=[(before, after)],
        gateway_scrape=({}, {}), gauge_samples=[], traces=[], trace_span=None,
        model=model, device_kind="TPU v5 lite")
    assert layer.read_metric("ssm_step_token_share", ctx) == pytest.approx(40.0)
    assert layer.read_metric("eng_moe_held_pair_share", ctx) == pytest.approx(25.0)
    # A program without the counters (the parent): nothing to read, no error.
    ctx.engine_scrapes = [({}, {})]
    assert layer.read_metric("ssm_step_token_share", ctx) is None
    assert layer.read_metric("eng_moe_held_pair_share", ctx) is None


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
        "nemotron-3-super-cut", "reason-batch", 1)
    config = {c["name"]: c for c in bench["configs"]}["nemotron-3-super-cut"]
    assert config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts"]
    end_to_end, per_layer = _reported(bench, CELL)
    assert end_to_end == ["tpot_p95_ms", "out_tokens_per_s", "setup_s"]
    assert per_layer >= {
        "eng_batch_fill", "decode_chunk_ms", "device_idle_share",
        "eng_loop_host_pct", "eng_chunk_overlap_share",
        "eng_moe_grouped_share", "xla_builds_in_window.batch",
        "prefill_device_ms.batch", "idle_in_book_pct", "idle_in_prepare_pct",
        "idle_unattributed_pct", "ssm_step_token_share",
        "eng_moe_held_pair_share"}
    assert not per_layer & {"paged_attention_roofline", "mla_decode_roofline",
                            "mla_absorbed_token_share"}


# A small model of the family in the published spelling: sixteen experts of
# which this chip holds four, so the held share reads about a quarter.
TINY_HYBRID = {
    "source": "the program's `tiny-hybrid` widths (tests only, never a cell)",
    "model_type": "nemotron_h", "hidden_size": 64, "vocab_size": 512,
    "num_hidden_layers": 5, "hybrid_override_pattern": "MEM*E",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 48, "layer_norm_epsilon": 1e-05, "norm_eps": 1e-05,
    "max_position_embeddings": 256, "rope_theta": 10000,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "expand": 2,
    "ssm_state_size": 16, "n_groups": 2, "conv_kernel": 4, "chunk_size": 16,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
    "mamba_hidden_act": "silu", "mamba_proj_bias": False,
    "use_conv_bias": True, "use_bias": False, "attention_bias": False,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "sliding_window": None,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "expert_parallel_rank": 1, "num_experts_per_tok": 4,
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 80, "n_shared_experts": 1,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 5, "tie_word_embeddings": False,
    "reduced": [], "reference": "nemotron_h",
    "serve": {"model_name": "tiny-hybrid-bench", "replicas": 1,
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
    root = str(tmp_path_factory.mktemp("bench-ssm"))
    path = rehearsal.make_copy(root)
    with open(os.path.join(root, "chipbench", "configs", "tiny-hybrid.json"),
              "x") as f:
        json.dump(TINY_HYBRID, f)
    with open(os.path.join(root, "chipbench", "traffic", "tiny-reason.json"),
              "x") as f:
        json.dump(TINY_REASON, f)
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-hybrid", "source": TINY_HYBRID["source"],
        "file": "chipbench/configs/tiny-hybrid.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"].append({
        "name": "tiny-hybrid.tiny-reason", "config": "tiny-hybrid",
        "traffic": "tiny-reason", "chips": 1, "why": "rehearsal"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("tiny-hybrid.tiny-reason")
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def test_rehearsal_prints_the_cells_metrics_on_the_last_line(copy):
    rc, lines = run_py(copy, "--workload", "tiny-hybrid.tiny-reason", "--seed",
                       str(2 ** 31 + 11), "--seconds", "5", "--trace", "2",
                       "--platform", "cpu", timeout=400)
    assert rc == 0, lines[-3:]
    last = json.loads(lines[-1])
    assert set(last) == CONTRACT_KEYS
    assert last["device"]["platform"] == "cpu"
    assert last["correct"] and last["failed"] == 0 and last["attempted"] > 0
    m = last["metrics"]
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        end_to_end, per_layer = _reported(json.load(f),
                                          "tiny-hybrid.tiny-reason")
    assert set(end_to_end) <= set(m)
    # Everything the cell lists that needs no device trace is on the line.
    # (The CPU runs every program dense over the experts: the grouped share
    # has no `grouped` series to read there and is left out.)
    not_here = {"device_idle_share", "prefill_device_ms.batch",
                "idle_in_book_pct", "idle_in_prepare_pct",
                "idle_unattributed_pct", "eng_moe_grouped_share"}
    assert per_layer - not_here <= set(m)
    assert 0 < m["ssm_step_token_share"]["value"] < 100
    assert 10 < m["eng_moe_held_pair_share"]["value"] < 45   # 4 of 16 held
    assert m["xla_builds_in_window.batch"]["value"] == 0
    health = [json.loads(ln) for ln in lines if '"state_pool_bytes"' in ln]
    assert health, "the engine's settings carry the state pool's sizes"
    assert no_leftovers()
