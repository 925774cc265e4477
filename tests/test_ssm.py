"""The nemotron_h family (models/hybrid.py, NVIDIA Nemotron-3-Super's language
model) at a small size: the block against the plain reference, the two forms
of its state-space layer against each other, the state pool beside the pages,
an expert layer that holds a share of its experts, the engine end to end, and
everything the engine turns off or refuses for it."""

import asyncio
import dataclasses
import functools
import importlib.util
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
from llm_d_inference_scheduler_tpu.kvcache import pages, state
from llm_d_inference_scheduler_tpu.models import configs, family, hybrid, mla
from llm_d_inference_scheduler_tpu.models.convert_hf import (
    config_from_hf, convert_state_dict)
from llm_d_inference_scheduler_tpu.models.routing import route

REPO = pathlib.Path(__file__).resolve().parent.parent
CFG = dataclasses.replace(configs.get_config("tiny-hybrid"), dtype="float32")
# float32 on both sides, different summation order (test_mla.py's).
TOL = dict(rtol=2e-4, atol=2e-4)
N_TOKENS = 45           # two chunks of 16 and a ragged third


def _reference():
    path = REPO / "chipbench" / "configs" / "reference_nemotron_h.py"
    spec = importlib.util.spec_from_file_location("reference_nemotron_h", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _sizes(cfg):
    return dict(pattern=cfg.layer_pattern, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                ssm_heads=cfg.ssm_heads, ssm_head_dim=cfg.ssm_head_dim,
                ssm_state=cfg.ssm_state, ssm_groups=cfg.ssm_groups,
                norm_eps=cfg.norm_eps,
                experts_per_token=cfg.experts_per_token,
                routed_scaling_factor=cfg.routed_scaling_factor)


@functools.lru_cache(maxsize=None)
def _fixture():
    params = hybrid.init_params(CFG, jax.random.key(7), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(9), (2, N_TOKENS), 0,
                                CFG.vocab_size)
    logits, (fresh, _), routes = hybrid.forward(
        params, CFG, tokens, want_kv=True, want_routes=True)
    return params, tokens, logits, fresh, routes


def _cache(n_slots=2, max_len=64):
    geom = pages.PageGeometry.for_engine(CFG, n_slots, max_len)
    assert geom.state == state.StateGeometry.for_engine(CFG, n_slots)
    cache, none = pages.alloc(geom)
    assert none is None and isinstance(cache, state.Cache)
    return cache


TABLES = jnp.asarray([[3, 1, 5, 0], [2, 6, 4, 0]], jnp.int32)


def _prefilled(n_tokens, bucket=None):
    """A cache whose slots 0 and 1 hold both sequences' first ``n_tokens``,
    prefilled in a bucket of ``bucket`` positions (padded past n_tokens)."""
    params, tokens, *_ = _fixture()
    bucket = bucket or -(-n_tokens // 16) * 16
    padded = jnp.zeros((2, bucket), jnp.int32).at[:, :n_tokens].set(
        tokens[:, :n_tokens])
    lens = jnp.full((2,), n_tokens, jnp.int32)
    _, (fresh, _) = hybrid.forward(params, CFG, padded, want_kv=True,
                                   seq_len=lens)
    cache, _ = pages.write_sequences(
        state.at_slots(_cache(), [0, 1]), None, fresh, None, TABLES, lens)
    return state.take_counts(cache)[0]


# ---------- the block against the plain reference ----------

def test_family_picks_the_module_by_the_pattern():
    assert family(CFG) is hybrid
    assert family(configs.get_config("tiny-mla")) is mla
    assert CFG.layer_pattern == "MEM*E"
    assert (CFG.n_state_layers, CFG.n_kv_layers, CFG.ssm_inner,
            CFG.ssm_conv_dim) == (2, 1, 128, 192)


@pytest.mark.parametrize("row", [0, 1])
def test_chunked_form_matches_the_sequential_reference(row):
    """45 positions in chunks of 16: the matrix form inside a chunk, the
    recurrence over chunk ends, a ragged last chunk."""
    params, tokens, logits, fresh, routes = _fixture()
    ref = _reference()
    hidden, ref_routes, last = ref.hidden(params, tokens[row], q_block=7,
                                          want_state=True, **_sizes(CFG))
    np.testing.assert_allclose(np.asarray(logits[row]),
                               np.asarray(ref.logits(params, hidden)), **TOL)
    np.testing.assert_allclose(
        np.asarray(ref.forward(params, tokens[row], **_sizes(CFG))),
        np.asarray(logits[row]), **TOL)
    ours = routes.reshape(routes.shape[0], 2, -1, CFG.experts_per_token)
    assert (np.sort(np.asarray(ours[:, row]), -1)
            == np.sort(np.asarray(ref_routes), -1)).all()
    # The state the scan ends on is the state the last token left.
    np.testing.assert_allclose(np.asarray(fresh.ssm[:, row]),
                               np.asarray(last), **TOL)


def _without(part):
    """Parameters of a program that leaves ``part`` of the mathematics out,
    by making it the identity in what the program is given."""
    params, *_ = _fixture()
    ssm, moe = dict(params["ssm"]), dict(params["moe"])
    if part == "convolution bias":
        ssm["conv_b"] = jnp.zeros_like(ssm["conv_b"])
    elif part == "skip D":
        ssm["D"] = jnp.zeros_like(ssm["D"])
    elif part == "gated norm weight":
        ssm["norm"] = jnp.ones_like(ssm["norm"])
    elif part == "dt bias":
        ssm["dt_bias"] = jnp.zeros_like(ssm["dt_bias"])
    elif part == "selection bias":
        moe["router_bias"] = jnp.zeros_like(moe["router_bias"])
    elif part == "shared expert":
        moe["w2s"] = jnp.zeros_like(moe["w2s"])
    elif part == "routed experts":
        moe["w2"] = jnp.zeros_like(moe["w2"])
    return {**params, "ssm": ssm, "moe": moe}


@pytest.mark.parametrize("part", [
    "convolution bias", "skip D", "gated norm weight", "dt bias",
    "selection bias", "shared expert", "routed experts"])
def test_the_comparison_sees_each_part(part):
    """The drawn weights make every part of a layer matter: a program
    without it misses the reference by far more than the tolerance."""
    _, tokens, logits, *_ = _fixture()
    ours, _ = hybrid.forward(_without(part), CFG, tokens[:1])
    assert float(jnp.abs(ours[0] - logits[0]).max()) > 50 * TOL["atol"]


def test_attention_has_no_rotary_embedding():
    """Positions reach the model through the state-space layers alone: the
    forward pass takes no positions and rope_theta changes nothing."""
    params, tokens, logits, *_ = _fixture()
    other, _ = hybrid.forward(
        params, dataclasses.replace(CFG, rope_theta=123.0), tokens,
        positions=jnp.zeros_like(tokens))
    np.testing.assert_array_equal(np.asarray(other), np.asarray(logits))


# ---------- the scan form, the step form, the state pool ----------

@pytest.mark.parametrize("n,bucket", [(21, 32), (16, 64), (37, 64), (45, 128)])
def test_a_padded_bucket_leaves_the_true_length_state(n, bucket):
    """Padding changes nothing: D = 0 there (no decay, no input) and the
    tail is gathered at the true length."""
    params, tokens, _, _, _ = _fixture()
    exact = hybrid.forward(params, CFG, tokens[:, :n], want_kv=True)[1][0]
    padded = jnp.zeros((2, bucket), jnp.int32).at[:, :n].set(tokens[:, :n])
    logits, (fresh, _) = hybrid.forward(
        params, CFG, padded, want_kv=True,
        seq_len=jnp.full((2,), n, jnp.int32))
    np.testing.assert_allclose(np.asarray(fresh.ssm), np.asarray(exact.ssm),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(fresh.conv), np.asarray(exact.conv),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(logits[:, :n]),
                               np.asarray(_fixture()[2][:, :n]), **TOL)


def test_prefill_then_decode_through_state_and_pages_equals_the_full_forward():
    params, tokens, logits, fresh, routes = _fixture()
    start = 21                                   # mid-page, mid-chunk
    cache = _prefilled(start, bucket=32)
    step = jax.jit(functools.partial(hybrid.decode_step, want_routes=True),
                   static_argnums=1)
    for t in range(start, N_TOKENS):
        got, cache, none, chose = step(
            params, CFG, tokens[:, t], jnp.full((2,), t, jnp.int32),
            state.at_slots(cache, [0, 1]), None, TABLES)
        assert none is None
        cache, held, *_ = state.take_counts(cache)
        assert int(held) == 2 * 2 * CFG.experts_per_token   # every expert held
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(logits[:, t]), **TOL)
        whole = routes.reshape(routes.shape[0], 2, -1, 4)[:, :, t]
        assert (np.sort(np.asarray(chose), -1)
                == np.sort(np.asarray(whole), -1)).all()
    # What the steps left in the slots is what the whole prefill ends on,
    # and nobody's row took nothing.
    np.testing.assert_allclose(np.asarray(cache.ssm[:, :2]),
                               np.asarray(fresh.ssm), **TOL)
    np.testing.assert_allclose(
        np.asarray(cache.conv[:, :2]).reshape(fresh.conv.shape),
        np.asarray(fresh.conv), **TOL)
    assert not np.asarray(cache.ssm[:, 2]).any()


def test_a_second_prefill_window_continues_from_slot_state():
    params, tokens, logits, fresh, _ = _fixture()
    cache = _prefilled(16)
    for lo, n, bucket, prior in [(16, 16, 16, 1), (32, 13, 16, 2)]:
        for lane in (0, 1):
            window = jnp.zeros((1, bucket), jnp.int32).at[0, :n].set(
                tokens[lane, lo:lo + n])
            got, cache, none = hybrid.prefill_with_prefix(
                params, CFG, window, jnp.asarray([n]), jnp.asarray([lo]),
                state.at_slots(cache, [lane]), None, TABLES[lane:lane + 1],
                TABLES[lane:lane + 1, :prior])
            assert none is None
            cache, *_ = state.take_counts(cache)
            np.testing.assert_allclose(
                np.asarray(got[0]), np.asarray(logits[lane, lo + n - 1]),
                **TOL)
    np.testing.assert_allclose(np.asarray(cache.ssm[:, :2]),
                               np.asarray(fresh.ssm), **TOL)


def test_state_geometry_at_the_cells_sizes():
    big = configs.get_config("nemotron-3-super-cut")
    geom = state.StateGeometry.for_engine(big, 64)
    assert geom.ssm_shape == (5, 65, 128, 64, 128)
    assert geom.conv_shape == (5, 65, 3 * 10240)
    assert geom.slot_bytes == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert geom.pool_bytes == 65 * geom.slot_bytes == 1_383_116_800
    # One layer in eleven keeps pages: 2 KV heads of 128, K and V, bf16.
    kv = pages.PageGeometry.for_engine(big, 64, 2048)
    assert kv.shape == (1, 1 + 64 * 128, 16, 2, 128)
    assert kv.token_bytes == 1024
    assert state.StateGeometry.for_engine(
        configs.get_config("qwen3-4b"), 16) is None


def test_a_state_pool_lies_beside_unsharded_kv_pages_only():
    geom = pages.PageGeometry.for_engine(CFG, 2, 64)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                             ("dp", "tp"))
    with pytest.raises(ValueError, match="no sharding rule"):
        pages.alloc(geom, sharding=pages.page_sharding(mesh))
    # A plain pool passes through the two seams untouched.
    k, v = pages.alloc(dataclasses.replace(geom, state=None, counted=False))
    assert state.at_slots(k, [0]) is k and state.take_counts(k) == (k, None, None, None)


def test_models_hybrid_knows_no_pool_layout():
    from test_kvcache import PKG, layout_knowledge

    assert layout_knowledge((PKG / "models/hybrid.py").read_text()) == []


# ---------- an expert layer that holds a share of its experts ----------

def _layer_input():
    params, tokens, *_ = _fixture()
    return params["embed"][tokens[0]] * 3.0                    # [45, 64]


def _share(params, cfg, rank, held):
    """(cfg, the ``moe`` stack) of the chip that holds experts rank * held
    .. of every layer."""
    moe = dict(params["moe"])
    for name in ("w1", "w2"):
        moe[name] = moe[name][:, rank * held:(rank + 1) * held]
    return dataclasses.replace(cfg, experts_held=held,
                               experts_first=rank * held), moe


@pytest.mark.parametrize("layer", [0, 1])
def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference(layer):
    """Four chips hold four experts each of the sixteen: their parts of the
    result, with the shared expert (and the two latent projections, which
    are linear) counted once, are the whole layer's as the reference
    computes it uncut."""
    params, *_ = _fixture()
    h = _layer_input()
    ref = _reference()
    lp = {k: v[layer] for k, v in params["moe"].items()}
    shared = jnp.square(jax.nn.relu(h @ lp["w1s"])) @ lp["w2s"]
    parts, held_pairs = [], 0
    for rank in range(4):
        cfg, moe = _share(params, CFG, rank, 4)
        y, chose, held, _ = hybrid.latent_moe(cfg, moe, layer, h)
        parts.append(y - shared)
        held_pairs += int(held)
        assert chose.shape == (N_TOKENS, 4)           # routed over all 16
    assert held_pairs == N_TOKENS * CFG.experts_per_token
    with jax.default_matmul_precision("highest"):
        whole, _ = ref._experts(
            lp, lambda name, e: lp[name][e], 0, CFG.n_experts, h,
            experts_per_token=CFG.experts_per_token,
            routed_scaling_factor=CFG.routed_scaling_factor)
        # The reference held to a share gives that share's part.
        one, _ = ref._experts(
            lp, lambda name, e: lp[name][4 + e], 4, 4, h,
            experts_per_token=CFG.experts_per_token,
            routed_scaling_factor=CFG.routed_scaling_factor)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), **TOL)
    np.testing.assert_allclose(np.asarray(parts[1] + shared),
                               np.asarray(one), **TOL)
    # No share is nothing, and no share is the whole.
    for part in parts:
        assert 0.05 < float(jnp.abs(part).max()) < float(
            jnp.abs(whole - shared).max()) * 0.95


@pytest.mark.parametrize("rank", [0, 2, 3])
def test_grouped_and_dense_forms_agree_on_a_held_range(rank):
    """Widths the kernel can tile (interpreted): the rows of absent experts
    are dropped ahead of the group layout, none of a held one's is."""
    cfg = dataclasses.replace(CFG, moe_latent_dim=128, moe_d_ff=128)
    params = hybrid.init_params(cfg, jax.random.key(2), dtype=jnp.float32)
    h = _layer_input()
    cfg, moe = _share(params, cfg, rank, 4)
    dense, chose, held, read = hybrid.latent_moe(cfg, moe, 1, h)
    grouped, chose_g, held_g, read_g = hybrid.latent_moe(
        dataclasses.replace(cfg, moe_impl="grouped_interpret"), moe, 1, h)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense), **TOL)
    assert (np.asarray(chose) == np.asarray(chose_g)).all()
    assert 0 < int(held) == int(held_g) < chose.size
    assert read is read_g is None
    # The form of few rows: the same again, and the experts it read counted.
    few, chose_f, held_f, read_f = hybrid.latent_moe(
        dataclasses.replace(cfg, moe_impl="chosen_interpret"), moe, 1, h)
    np.testing.assert_allclose(np.asarray(few), np.asarray(dense), **TOL)
    local = np.asarray(chose_f) - cfg.held_experts[0]
    assert (int(held_f), int(read_f)) == (int(held), len(set(
        local[(local >= 0) & (local < cfg.held_experts[1])].tolist())))
    # A whole model in the grouped form, every expert held.
    tokens = _fixture()[1][:1]
    cfg = dataclasses.replace(CFG, moe_latent_dim=128, moe_d_ff=128)
    want, _ = hybrid.forward(params, cfg, tokens)
    got, _ = hybrid.forward(
        params, dataclasses.replace(cfg, moe_impl="grouped_interpret"), tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_the_router_is_the_one_mla_routes_with():
    assert mla.route is route is hybrid.route
    params, *_ = _fixture()
    lp = {k: v[0] for k, v in params["moe"].items()}
    idx, gates = route(CFG, lp, _layer_input())
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 5.0, rtol=1e-5)
    assert idx.shape == (N_TOKENS, 4) and int(idx.max()) < CFG.n_experts


# ---------- the published keys ----------

PUBLISHED = dict(
    attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
    head_dim=128, hidden_size=4096,
    hybrid_override_pattern=configs._NEMOTRON_PATTERN, intermediate_size=2688,
    layer_norm_epsilon=1e-05, mamba_head_dim=64, mamba_hidden_act="silu",
    mamba_num_heads=128, mamba_proj_bias=False,
    max_position_embeddings=262144, mlp_bias=False, mlp_hidden_act="relu2",
    model_type="nemotron_h", moe_intermediate_size=2688, moe_latent_size=1024,
    moe_shared_expert_intermediate_size=5376, moe_shared_expert_overlap=False,
    mtp_hybrid_override_pattern="*E", n_group=1, n_groups=8,
    n_routed_experts=512, n_shared_experts=1, norm_eps=1e-05,
    norm_topk_prob=True, num_attention_heads=32, num_experts_per_tok=22,
    num_hidden_layers=88, num_key_value_heads=2, num_logits_to_keep=1,
    num_nextn_predict_layers=1, partial_rotary_factor=1,
    rescale_prenorm_residual=True, residual_in_fp32=False, rope_theta=10000,
    routed_scaling_factor=5, sliding_window=None, ssm_state_size=128,
    tie_word_embeddings=False, time_step_floor=0.0001, time_step_max=0.1,
    time_step_min=0.001, topk_group=1, use_bias=False, use_conv_bias=True,
    use_mamba_kernels=True, vocab_size=131072)


def test_config_from_hf_reads_the_flat_published_keys():
    got = config_from_hf(types.SimpleNamespace(**PUBLISHED),
                         name="nemotron-3-super")
    assert got == configs.get_config("nemotron-3-super")
    assert (got.layer_pattern.count("M"), got.layer_pattern.count("E"),
            got.layer_pattern.count("*")) == (40, 40, 8)
    assert got.held_experts == (0, 512)
    cut = config_from_hf(types.SimpleNamespace(**{
        **PUBLISHED, "num_hidden_layers": 11,
        "hybrid_override_pattern": "EMEMEMEMEM*", "n_routed_experts": 128,
        "n_routed_experts_published": 512, "expert_parallel_rank": 2}),
        name="nemotron-3-super-cut")
    assert cut == dataclasses.replace(
        configs.get_config("nemotron-3-super-cut"), experts_first=256)
    assert cut.n_experts == 512 and cut.held_experts == (256, 128)


@pytest.mark.parametrize("key,value", [
    ("n_group", 8), ("mamba_proj_bias", True), ("sliding_window", 4096),
    ("mlp_hidden_act", "silu"), ("use_conv_bias", False),
    ("hybrid_override_pattern", "M-" * 44), ("num_hidden_layers", 87),
    ("mamba_num_heads", 96)])
def test_config_from_hf_refuses_what_the_block_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf(types.SimpleNamespace(**{**PUBLISHED, key: value}))


def test_convert_state_dict_refuses_the_family():
    with pytest.raises(NotImplementedError, match="layer pattern"):
        convert_state_dict({}, CFG)


# ---------- the engine ----------

@pytest.fixture
def served():
    """tiny-hybrid in float32 under a name of its own (greedy tokens of two
    programs are comparable in float32 only)."""
    name = "tiny-hybrid-f32"
    configs._REGISTRY[name] = dataclasses.replace(CFG, name=name)
    yield name
    del configs._REGISTRY[name]


@pytest.mark.parametrize("option,value", [
    ("tp_size", 2), ("ep_size", 2), ("pp_size", 2), ("dist_num_processes", 2),
    ("role", "prefill"), ("role", "decode")])
def test_engine_refuses_at_start_by_name(option, value):
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    with pytest.raises(ValueError, match=f"state pool.*{option}={value}"):
        TpuEngine(EngineConfig(model="tiny-hybrid", backend="tpu", max_batch=2,
                               max_model_len=64, kv_events_port=0,
                               **{option: value}))


def _counters(eng, name, label=None):
    return {(s.labels[label] if label else ""): s.value
            for m in eng.telemetry.registry.collect() for s in m.samples
            if s.name == name}


def test_engine_serves_through_windows_with_prefix_hits_off(served):
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    long = [1] + [(j * 17) % 450 + 3 for j in range(150)]
    short = [1] + [(j * 5) % 450 + 3 for j in range(30)]
    base = dict(model=served, backend="tpu", max_batch=4, max_model_len=256,
                decode_chunk=4, kv_events_port=0, seed=7,
                enable_prefix_caching=True)

    async def serve(cfg):
        eng = TpuEngine(cfg)
        await eng.start()
        try:
            async def one(rid, prompt, n):
                out = eng.submit(EngineRequest(
                    request_id=rid, prompt_token_ids=list(prompt),
                    max_tokens=n, temperature=0.0, ignore_eos=True))
                toks, cached = [], 0
                while True:
                    ev = await asyncio.wait_for(out.get(), timeout=300)
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                        cached = max(cached, ev.cached_tokens or 0)
                    if ev.finish_reason is not None:
                        return toks, cached

            first = await asyncio.gather(one("L", long, 6), one("S", short, 12))
            again = await one("L2", long, 6)
            with pytest.raises(ValueError, match="state pool"):
                eng.submit(EngineRequest(
                    request_id="pd", prompt_token_ids=short,
                    kv_transfer_params={"do_remote_decode": True}))
            counted = {
                "ssm": _counters(eng, "jetstream:ssm_tokens_total", "form"),
                "pairs": _counters(eng, "jetstream:moe_routed_pairs_total",
                                   "held"),
                "started": _counters(eng, "jetstream:ssm_slot_prefills_total"),
                "hits": _counters(eng, "jetstream:prefix_cached_tokens_total")}
            return first, again, counted, eng.describe()["settings"]
        finally:
            await eng.stop()

    whole = asyncio.run(serve(EngineConfig(**base)))
    chunked = asyncio.run(serve(EngineConfig(**base, prefill_chunk=32,
                                             warmup=True)))
    (lw, sw), aw, counted, settings = whole
    (lc, sc), ac, counted_c, _ = chunked
    assert (lc[0], sc[0]) == (lw[0], sw[0])          # the same tokens
    assert aw[0] == lw[0] == ac[0]
    # Asked for, prefix caching stays off: a rerun of the prompt hits nothing.
    assert aw[1] == ac[1] == 0 and not counted["hits"].get("")
    assert settings["prefix_caching"] is False
    assert any("prefix hits" in off for off in settings["off_for_state_layers"])
    assert settings["state_slot_bytes"] == 2 * (8 * 16 * 16 * 4 + 3 * 192 * 4)
    assert settings["state_pool_bytes"] == 5 * settings["state_slot_bytes"]
    assert settings["kv_pool_bytes"] == 2 * 1 * 65 * 16 * 2 * 16 * 4
    # Three requests started three slots, in one window or in five.
    assert counted["started"][""] == counted_c["started"][""] == 3
    # Prompts pad to 256, 32 and 256 positions; 6 + 12 + 6 tokens less the
    # three first ones were decoded in chunks of 4 steps of 2 or 4 lanes.
    assert counted["ssm"]["scan"] == 256 + 32 + 256
    assert counted["ssm"]["step"] >= 21
    assert counted_c["ssm"]["scan"] > 5 * 32 + 32 + 5 * 32   # and warm-up's
    # Every expert is held: every choice of every row of those programs.
    pairs = counted["pairs"]
    assert pairs["no"] == 0
    assert pairs["yes"] == (counted["ssm"]["scan"] + counted["ssm"]["step"]) \
        * CFG.experts_per_token * 2


def test_held_pairs_are_counted_on_the_device(served):
    """A quarter of the experts held: about a quarter of the choices."""
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    name = served + "-share"
    configs._REGISTRY[name] = dataclasses.replace(
        CFG, name=name, experts_held=4, experts_first=8)

    async def serve():
        eng = TpuEngine(EngineConfig(
            model=name, backend="tpu", max_batch=2, max_model_len=128,
            decode_chunk=4, kv_events_port=0, seed=3))
        await eng.start()
        try:
            out = eng.submit(EngineRequest(
                request_id="r", prompt_token_ids=list(range(3, 60)),
                max_tokens=9, temperature=0.0, ignore_eos=True))
            while (await asyncio.wait_for(out.get(), 300)).finish_reason is None:
                pass
            return _counters(eng, "jetstream:moe_routed_pairs_total", "held")
        finally:
            await eng.stop()

    try:
        pairs = asyncio.run(serve())
    finally:
        del configs._REGISTRY[name]
    total = pairs["yes"] + pairs["no"]
    assert total >= (64 + 8) * 4 * 2 and 0.1 < pairs["yes"] / total < 0.45


def _req(rid, seed, n_prompt, max_tokens, stop=None):
    prompt = [1] + [(j * seed) % 450 + 3 for j in range(n_prompt)]
    return EngineRequest(request_id=rid, prompt_token_ids=prompt,
                         max_tokens=max_tokens, temperature=0.0,
                         ignore_eos=True,
                         stop_token_ids=(stop,) if stop is not None else ())


def test_a_slot_reused_under_a_chunk_in_flight_serves_as_a_fresh_engine(served):
    """A ends on a stop token in the middle of a chunk with the next chunk
    in flight: that chunk's lane overshoots A's end and rewrites A's slot
    state. C, waiting, is admitted into that slot; its first window
    overwrites the state behind the overshoot on the in-order stream, and
    C's tokens are those a fresh engine serves it, token for token."""
    from test_engine import _by_hand

    by_hand = functools.partial(_by_hand, model=served, max_batch=2)
    free, _, _ = by_hand([_req("A", 29, 40, 24)])
    stop = free["A"][6]
    assert stop not in free["A"][:6]
    alone, _, _ = by_hand([_req("C", 37, 35, 13)])

    reqs = [_req("A", 29, 40, 24, stop), _req("B", 31, 37, 27),
            _req("C", 37, 35, 13)]
    toks, why, eng = by_hand(reqs)
    assert why == {"A": "stop", "B": "length", "C": "length"}
    assert toks["A"] == free["A"][:6]         # the stop token is not served
    assert toks["C"] == alone["C"]
    # A's lane of the chunk in flight was thrown away whole, and C started
    # the slot afresh: three first windows in all.
    reg = eng.telemetry.registry
    assert reg.get_sample_value("jetstream:decode_lanes_discarded_total") == 1
    assert reg.get_sample_value("jetstream:ssm_slot_prefills_total") == 3
    assert reg.get_sample_value("jetstream:decode_chunks_total",
                                {"dispatch": "ahead"}) >= 2
    # In windows too: every window of C is its slot's.
    chunked, _, _ = by_hand(reqs, prefill_chunk=16)
    assert chunked["C"] == alone["C"] and chunked["A"] == toks["A"]
