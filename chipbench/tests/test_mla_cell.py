"""What PR 32 adds to the benchmark, on the CPU: the configuration file through
the launcher's mapping, the plain reference's copy against the program at the
`tiny-mla` preset, the traffic file through the generator, the counts and the
reader behind `mla_decode_roofline`, and the two counter ratios."""

import importlib.util
import json
import os

import numpy as np
import pytest

import kernels
import kernels_mla
import layer
import prom
import traffic
from client import Record

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "kimi-vl-a3b-cut.longdoc-batch"


def test_configuration_file_maps_to_the_programs_config():
    from launch_engine import model_config_from_file

    path = os.path.join(BENCH, "configs", "kimi-vl-a3b-cut.json")
    m = model_config_from_file(path)
    assert (m.name, m.n_layers, m.first_k_dense, m.d_model, m.n_heads,
            m.d_ff, m.moe_d_ff, m.vocab_size) == \
        ("kimi-vl-a3b-cut", 9, 1, 2048, 16, 11264, 1408, 163840)
    assert (m.n_experts, m.experts_per_token, m.n_shared_experts,
            m.routed_scaling_factor) == (64, 6, 2, 2.446)
    assert (m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim,
            m.v_head_dim, m.latent_dim, m.head_dim) == (512, 128, 64, 128, 576, 192)
    assert (m.rope_theta, m.norm_eps, m.moe_impl) == (800000.0, 1e-5, "dense")
    with open(path) as f:
        doc = json.load(f)
    # The catalog's keys at the top level, as the driver's check reads them
    # (it refused the file with the keys nested under `text_config`). The
    # parent's mapping takes such a file for a dense 16-KV-head model, whose
    # page pool for 32 lanes of 8,192 tokens (19 GB) no chip holds: it ends
    # at start, out of memory.
    assert "text_config" not in doc and doc["model_type"] == "kimi_vl"
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(line) for line in f
                   if '"Kimi-VL-A3B-Instruct"' in line)
    assert doc["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items()
               if doc.get(k, "absent") != v}
    assert changed == set(doc["reduced"]) == {"num_hidden_layers"}
    assert doc["serve"]["engine_args"] == [
        "--max-batch", "32", "--max-model-len", "8192", "--decode-chunk", "8",
        "--prefill-chunk", "1024"]


def _reference():
    path = os.path.join(BENCH, "configs", "reference_mla_moe.py")
    spec = importlib.util.spec_from_file_location("reference_mla_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_program_forward_matches_plain_reference():
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from llm_d_inference_scheduler_tpu.models import family
    from llm_d_inference_scheduler_tpu.models.configs import get_config

    cfg = get_config("tiny-mla")
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


def _programs(n_tokens, window=1024, block=16, widest=512):
    """The prefill programs a prompt reaches under --prefill-chunk 1024, as
    TpuEngine._advance_prefills names them."""
    out, written = [], 0
    while written < n_tokens:
        w = min(window, n_tokens - written)
        if written == 0:
            out.append(("prefill", _bucket(w)))
        else:
            prior = 1
            while prior < written // block:
                prior *= 2
            out.append(("prefix_prefill", _bucket(w), min(prior, widest)))
        written += w
    return out


def test_traffic_file_through_the_generator():
    mix = traffic.load_mix(traffic.mix_path(os.path.dirname(BENCH),
                                            "longdoc-batch"))
    seed = 2 ** 31 + 99
    plan = traffic.build(mix, seed, 51.0)
    assert len(plan.chains) == 64 and plan.temperature == 0.0
    assert min(c.start_s for c in plan.chains) == -8.0 and not plan.preload
    # The pool is 512 as issued (and as batch-full has it): a window holds
    # a seed-chosen stretch of it, so runs differ by which prompts they met
    # as well as by the system's jitter (PERF.md section 6, PR 32).
    reqs = [next(plan.chains[0].requests) for _ in range(512)]
    lens = sorted(r.prompt_tokens for r in reqs)
    assert mix["pool"] == 512 and 2048 <= lens[0] and lens[-1] <= 7168
    assert 4.4 < sum(-(-n // 1024) for n in lens) / 512 < 4.7
    assert 4000 < sum(lens) / len(lens) < 4200
    assert 250 < sum(r.max_tokens for r in reqs) / len(reqs) < 262
    assert all(128 <= r.max_tokens <= 384 for r in reqs)
    assert all(len(r.prompt) == r.prompt_tokens - 1 for r in reqs)
    assert len({r.prompt[:24] for r in reqs}) == len(reqs)    # unshared
    # Every prefill program the pool can reach is warmed by a prompt of the
    # warm-up, so nothing is built inside a window; and every decode bucket.
    reach = {p for n in lens for p in _programs(n)}
    warm = {p for group in traffic.warmup_requests(mix, seed)
            for r in group for p in _programs(r.prompt_tokens)}
    assert reach == warm and len(warm) == 23
    assert [len(b) for b in traffic.burst_requests(mix, seed)] == [2, 4, 8, 16, 32]
    assert mix["trace"]["seconds"] == 1.5
    other = traffic.build(mix, 7, 51.0)
    assert {next(c.requests).prompt_tokens for c in other.chains} <= set(lens)


def test_latent_kernel_counts():
    one = kernels_mla.latent_attention_decode(1.0, 0.0, 16, 576, 512)
    assert one == {"flops": 2 * 16 * (576 + 512), "bytes": 576 * 2}
    lane = kernels_mla.latent_attention_decode(0.0, 1.0, 16, 576, 512)
    assert lane["bytes"] == 2 * (16 * 576 + 16 * 512 + 576) and lane["flops"] == 0
    # 30.2 FLOPs a byte: under the v5e's ridge (240), so memory bounds it.
    cost = kernels_mla.latent_attention_decode(32 * 4200.0, 32.0, 16, 576, 512)
    assert kernels.roofline_seconds(cost, "TPU v5 lite")[1] == "memory"


def _rec(due, prompt=3000):
    r = Record(f"r{due}", -1, 0, due, due, prompt, 200)
    r.status, r.prompt_tokens, r.completion_tokens = 200, prompt, 200
    r.first_s, r.last_s, r.done_s = due + 0.1, due + 5.0, due + 5.0
    r.pieces = [(r.first_s, 1), (r.last_s, 199)]
    return r


@pytest.fixture
def ctx():
    before = prom.parse(
        'jetstream:moe_ffn_tokens_total{form="grouped"} 1000.0\n'
        'jetstream:moe_ffn_tokens_total{form="dense"} 3000.0\n'
        'jetstream:mla_attention_tokens_total{form="expanded"} 1500.0\n'
        'jetstream:mla_attention_tokens_total{form="absorbed"} 2500.0\n')
    after = prom.parse(
        'jetstream:moe_ffn_tokens_total{form="grouped"} 4000.0\n'
        'jetstream:moe_ffn_tokens_total{form="dense"} 5000.0\n'
        'jetstream:mla_attention_tokens_total{form="expanded"} 5500.0\n'
        'jetstream:mla_attention_tokens_total{form="absorbed"} 3500.0\n')
    with open(os.path.join(BENCH, "configs", "kimi-vl-a3b-cut.json")) as f:
        model = json.load(f)
    return layer.Context(
        records=[_rec(0.0), _rec(0.5), _rec(9.0)], seconds=10.0, chips=1,
        engine_scrapes=[(before, after)], gateway_scrape=({}, {}),
        gauge_samples=[], traces=[], trace_span=None, model=model,
        device_kind="TPU v5 lite")


def test_counter_ratios(ctx):
    assert layer.read_metric("eng_moe_grouped_share", ctx) == pytest.approx(60.0)
    assert layer.read_metric("mla_absorbed_token_share", ctx) == pytest.approx(20.0)
    # A program without the counter (the parent): nothing to read, no error.
    ctx.engine_scrapes = [({}, {})]
    assert layer.read_metric("mla_absorbed_token_share", ctx) is None
    assert layer.read_metric("eng_moe_grouped_share", ctx) is None


def test_roofline_reader_on_a_canned_trace(ctx):
    assert layer.read_metric("mla_decode_roofline", ctx) is None   # no trace
    ctx.traces = [{"devices": [{"window_s": 1.5, "busy_s": 1.4, "ops": {
        "custom-call.3": {"count": 72, "seconds": 0.02,
                          "detail": "jit(f)/mla_paged_decode_attention/pallas_call"},
        "fusion.9": {"count": 72, "seconds": 1.0, "detail": "dot"}},
        "idle_by_next_program": {}}]}]
    ctx.trace_span = (1.0, 2.5)
    share = layer.read_metric("mla_decode_roofline", ctx)
    note = ctx.notes["latent_attention_decode"]
    # Two requests decode through the slice, each at prompt 3,000 + 1 token.
    assert note["calls"] == 72 and note["bound"] == "memory"
    assert note["mean_lanes"] == pytest.approx(2.0)
    assert note["mean_context_tokens_per_call"] == pytest.approx(6002.0)
    least = (6002 * 576 * 2 + 2 * 2 * (16 * 1088 + 576)) / 819e9
    assert note["least_seconds_per_call"] == pytest.approx(least)
    assert share == pytest.approx(100 * 72 * least / 0.02) and share < 100
    # The kernel is not in the trace (another configuration, or the XLA
    # gather): nothing to read.
    ctx.traces[0]["devices"][0]["ops"].pop("custom-call.3")
    assert layer.read_metric("mla_decode_roofline", ctx) is None
    # A configuration without a latent cache: nothing, whatever the trace.
    ctx.model = {"num_attention_heads": 32, "num_key_value_heads": 8}
    assert layer.read_metric("mla_decode_roofline", ctx) is None


def test_the_cell_reports_what_the_issue_lists():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "kimi-vl-a3b-cut"

    def reported(metrics):
        return [m["name"] for m in metrics
                if "workloads" not in m or CELL in m["workloads"]]

    assert reported(bench["end_to_end"]) == [
        "tpot_p95_ms", "out_tokens_per_s", "setup_s"]
    assert set(reported(bench["per_layer"])) == {
        "eng_batch_fill", "decode_chunk_ms", "device_idle_share",
        "eng_loop_host_pct", "xla_builds_in_window.batch", "idle_in_book_pct",
        "idle_in_prepare_pct", "idle_unattributed_pct",
        "prefill_device_ms.batch", "mla_decode_roofline",
        "eng_moe_grouped_share", "mla_absorbed_token_share"}
    assert [m["name"] for m in bench["per_layer"][-3:]] == [
        "mla_decode_roofline", "eng_moe_grouped_share",
        "mla_absorbed_token_share"]
