"""LongCat-Flash's double layer (models/mla.py with ``attn_sublayers`` 2;
LongCat-Flash-Omni's language model) at a small size with widths aligned to
nothing: the block against the plain reference, prefill then decode through
the latent pool, a prefix-continuation window, the two forms of its expert
layer, the experts that compute nothing, a chip's share of the experts, which
sublayer writes which cache layer, the mapping of the published keys, and the
engine end to end with its counters."""

import asyncio
import dataclasses
import functools
import importlib.util
import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
from llm_d_inference_scheduler_tpu.kvcache import pages, state
from llm_d_inference_scheduler_tpu.models import configs, family, mla
from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf
from llm_d_inference_scheduler_tpu.models.routing import route

REPO = pathlib.Path(__file__).resolve().parent.parent
CFG = dataclasses.replace(configs.get_config("tiny-longcat"), dtype="float32")
K = CFG.experts_per_token
# float32 on both sides, different summation order (test_reference.py's).
TOL = dict(rtol=2e-4, atol=2e-4)
TABLES = jnp.asarray([[3, 1, 5, 0], [2, 6, 4, 0]], jnp.int32)


def _reference():
    path = REPO / "chipbench" / "configs" / "reference_longcat_flash.py"
    spec = importlib.util.spec_from_file_location("reference_longcat_flash",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _sizes(cfg):
    return dict(n_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                experts_per_token=cfg.experts_per_token,
                routed_scaling_factor=cfg.routed_scaling_factor,
                n_experts=cfg.n_experts, first_expert=cfg.experts_first,
                scale_q=cfg.mla_scale_q_lora, scale_kv=cfg.mla_scale_kv_lora)


def _share(params, cfg, rank, held):
    """(cfg, params) of the chip that holds experts rank * held .. of every
    layer."""
    layers = dict(params["layers"])
    for name in ("w1", "w2", "w3"):
        layers[name] = layers[name][:, rank * held:(rank + 1) * held]
    return (dataclasses.replace(cfg, experts_held=held,
                                experts_first=rank * held),
            {**params, "layers": layers})


def _expert_layer(params, layer):
    """What layer ``layer`` has once a layer: its router and its experts."""
    return {k: params["layers"][k][layer]
            for k in ("router", "router_bias", "w1", "w3", "w2")}


@functools.lru_cache(maxsize=None)
def _fixture(held=0, rank=0):
    """The whole model (``held`` 0), or a chip's share of its experts."""
    params = mla.init_params(CFG, jax.random.key(7), dtype=jnp.float32)
    cfg = CFG
    if held:
        cfg, params = _share(params, CFG, rank, held)
    tokens = jax.random.randint(jax.random.key(9), (2, 48), 0, CFG.vocab_size)
    logits, (fresh, _), routes = jax.jit(functools.partial(
        mla.forward, cfg=cfg, want_kv=True, want_routes=True))(
            params, tokens=tokens)
    return cfg, params, tokens, logits, fresh, routes


def _cache_with(fresh, n_tokens):
    """A counting cache whose latent pool holds the first ``n_tokens`` rows
    of both sequences."""
    geom = pages.PageGeometry.for_engine(CFG, 2, 64)
    assert geom.shape == (4, 9, 16, 128)        # two cache layers a layer
    assert geom.counted and geom.counts_zero    # the model's router says
    cache, none = pages.alloc(geom)
    assert none is None and cache.ssm is None and cache.v is None
    bucket = -(-n_tokens // 16) * 16    # a prefill hands over whole pages
    cut = dataclasses.replace(fresh, k=fresh.k[:, :, :bucket])
    cache, _ = pages.write_sequences(
        state.at_slots(cache, [0, 1]), None, cut, None, TABLES,
        jnp.asarray([n_tokens] * 2))
    return cache


# ---------- the block against the plain reference ----------

def test_family_geometry_and_counts_of_layers():
    assert family(CFG) is mla and CFG.tallies_choices
    assert CFG.n_kv_layers == 4 and CFG.n_expert_layers == 2
    assert CFG.router_width == 24 and CFG.latent_dim == 32
    kimi = configs.get_config("tiny-mla")
    assert not kimi.tallies_choices and kimi.n_kv_layers == 3
    assert kimi.n_expert_layers == 2
    assert configs.get_config("tiny-hybrid").n_expert_layers == 2
    assert configs.get_config("tiny").n_expert_layers == 0


@pytest.mark.parametrize("held,rank", [(0, 0), (4, 2)])
def test_forward_matches_the_plain_reference(held, rank):
    cfg, params, tokens, logits, fresh, routes = _fixture(held, rank)
    ref = _reference()
    row = 1 if held else 0
    if held:      # the two entry points of the reference are one computation
        want = ref.forward(params, tokens[row], **_sizes(cfg))
    else:
        hidden, ref_routes = ref.hidden(params, tokens[row], q_block=20,
                                        **_sizes(cfg))
        want = ref.logits(params, hidden)
        ours = routes.reshape(routes.shape[0], 2, -1, K)
        assert (np.sort(np.asarray(ours[:, row]), -1)
                == np.sort(np.asarray(ref_routes), -1)).all()
    np.testing.assert_allclose(np.asarray(logits[row]), np.asarray(want),
                               **TOL)
    # The counts that ride out with the rows are the routes' own.
    chose = np.asarray(routes)
    first, count = cfg.held_experts
    assert int(fresh.held) == ((chose >= first) & (chose < first + count)).sum()
    assert int(fresh.zero) == (chose >= cfg.n_experts).sum() > 0


def _without(part):
    """(params, cfg) of a program that leaves ``part`` of the mathematics
    out, by making it the identity in what the program is given."""
    cfg, params, *_ = _fixture()
    layers = dict(params["layers"])
    if part == "selection bias":
        layers["router_bias"] = jnp.zeros_like(layers["router_bias"])
    elif part == "query norm weight":
        layers["q_norm"] = jnp.ones_like(layers["q_norm"])
    elif part == "second sublayer's dense FFN":
        layers["w2d"] = layers["w2d"].at[1::2].set(0.0)
    elif part == "gate scale":
        cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    elif part == "query scale":
        cfg = dataclasses.replace(cfg, mla_scale_q_lora=False)
    elif part == "latent scale":
        cfg = dataclasses.replace(cfg, mla_scale_kv_lora=False)
    elif part == "zero-compute experts' term":
        cfg = dataclasses.replace(cfg, n_zero_experts=0, n_experts=24,
                                  experts_held=16)
    return {**params, "layers": layers}, cfg


@pytest.mark.parametrize("part", [
    "selection bias", "query norm weight", "second sublayer's dense FFN",
    "gate scale", "query scale", "latent scale",
    "zero-compute experts' term"])
def test_the_comparison_sees_each_part(part):
    """The drawn weights make every part of the layer matter: a program
    without it misses the reference by far more than the tolerance."""
    _, _, tokens, logits, *_ = _fixture()
    changed, cfg = _without(part)
    ours, _ = mla.forward(changed, cfg, tokens[:1])
    assert float(jnp.abs(ours[0] - logits[0]).max()) > 50 * TOL["atol"]


# ---------- the router ----------

def test_gates_are_softmax_scores_times_the_scale_not_normalised():
    cfg, params, tokens, *_ = _fixture()
    lp = _expert_layer(params, 0)
    h = params["embed"][tokens[0]] * 3.0
    idx, gates = route(cfg, lp, h)
    scores = jax.nn.softmax(h @ lp["router"], axis=-1)
    assert scores.shape[-1] == 24          # 16 experts + 8 that compute nothing
    want_idx = np.argsort(-np.asarray(scores + lp["router_bias"]), -1)[:, :K]
    assert (np.sort(np.asarray(idx), -1) == np.sort(want_idx, -1)).all()
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(gates), chosen * 6.0, rtol=1e-5)
    assert not np.allclose(np.asarray(gates).sum(-1), 6.0, rtol=1e-2)
    # The bias changes selections (it is of the scores' spread) and leaves
    # the choices spread over the outputs.
    plain, _ = route(cfg, {**lp, "router_bias": jnp.zeros((24,))}, h)
    assert (np.sort(np.asarray(plain), -1) != np.sort(np.asarray(idx), -1)).any()
    assert len(np.unique(np.asarray(idx))) >= 18
    with pytest.raises(ValueError, match="router_scoring"):
        route(dataclasses.replace(cfg, router_scoring="tanh"), lp, h)


@pytest.mark.parametrize("push,zeros", [(1.0, K), (-1.0, 0)])
def test_a_token_whose_choices_are_all_zero_experts_and_one_whose_none_are(
        push, zeros):
    """The bias selects: pushed up, every choice is an expert that computes
    nothing and the layer's output is the token itself times its gates;
    pushed down none is, and the zero term vanishes."""
    cfg, params, tokens, *_ = _fixture()
    lp = _expert_layer(params, 1)
    lp["router_bias"] = lp["router_bias"].at[cfg.n_experts:].add(push)
    h = params["embed"][tokens[0]] * 3.0
    m, idx, counts = mla._ffn(cfg, lp, h)
    assert ((np.asarray(idx) >= cfg.n_experts).sum(-1) == zeros).all()
    assert counts.tolist() == [h.shape[0] * (K - zeros), h.shape[0] * zeros]
    _, gates = route(cfg, lp, h)
    if zeros:
        np.testing.assert_allclose(
            np.asarray(m), np.asarray(gates.sum(-1, keepdims=True) * h), **TOL)
    ref = _reference()
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(lp, lambda name, e: lp[name][e], 0, 16, 16, h,
                              experts_per_token=K, routed_scaling_factor=6.0)
        no_zero, _ = ref.experts(lp, lambda name, e: lp[name][e], 0, 16, 16,
                                 h, experts_per_token=K,
                                 routed_scaling_factor=6.0, zero=False)
    np.testing.assert_allclose(np.asarray(m), np.asarray(want), **TOL)
    assert np.allclose(np.asarray(no_zero), np.asarray(want), **TOL) == (not zeros)


# ---------- a chip's share of the experts ----------

@pytest.mark.parametrize("layer", [0, 1])
def test_the_four_shares_and_the_zero_term_once_add_up_to_the_uncut_layer(
        layer):
    """Four chips hold four experts each of the sixteen: the routed parts
    they give, plus the zero-compute experts' term (which every chip
    computes alike, where the token is) counted once, are the whole layer's
    expert output as the reference computes it uncut."""
    cfg, params, tokens, *_ = _fixture()
    h = params["embed"][tokens[0]] * 3.0
    T = h.shape[0]
    ref = _reference()
    lp = _expert_layer(params, layer)
    sizes = dict(experts_per_token=K, routed_scaling_factor=6.0)
    with jax.default_matmul_precision("highest"):
        whole, chose = ref.experts(lp, lambda name, e: lp[name][e], 0, 16, 16,
                                   h, **sizes)
        zero_term, _ = ref.experts(lp, None, 0, 0, 16, h, **sizes)
        one, _ = ref.experts(lp, lambda name, e: lp[name][4 + e], 4, 4, 16,
                             h, **sizes)
    parts, held_pairs = [], 0
    for rank in range(4):
        share_cfg, share = _share(params, cfg, rank, 4)
        slp = _expert_layer(share, layer)
        m, idx, counts = mla._ffn(share_cfg, slp, h)
        assert (np.asarray(idx) == np.asarray(chose)).all()  # routed over all
        parts.append(m - zero_term)
        held_pairs += int(counts[0])
        assert int(counts[1]) == (np.asarray(chose) >= 16).sum()
    assert held_pairs + (np.asarray(chose) >= 16).sum() == T * K
    np.testing.assert_allclose(np.asarray(sum(parts) + zero_term),
                               np.asarray(whole), **TOL)
    np.testing.assert_allclose(np.asarray(parts[1] + zero_term),
                               np.asarray(one), **TOL)
    # No share is nothing, and no share is the whole.
    for part in parts:
        assert 0.01 < float(jnp.abs(part).max()) < float(
            jnp.abs(whole - zero_term).max()) * 0.95


@pytest.mark.parametrize("held,rank", [(0, 0), (4, 1)])
def test_dense_over_held_and_grouped_forms_agree(held, rank):
    """Widths the kernel can tile (interpreted): a choice of an absent or a
    zero-compute expert is dropped ahead of the group layout, none of a held
    one's is, and the zero term stands beside either form -- and beside the
    form of few rows, which reads the experts a row chose and counts them."""
    cfg = dataclasses.replace(CFG, d_model=128, moe_d_ff=128)
    params = mla.init_params(cfg, jax.random.key(2), dtype=jnp.float32)
    if held:
        cfg, params = _share(params, cfg, rank, held)
    h = params["embed"][jnp.arange(40)] * 3.0
    lp = _expert_layer(params, 1)
    dense, chose, counts = mla._ffn(cfg, lp, h)
    grouped, chose_g, counts_g = mla._ffn(
        dataclasses.replace(cfg, moe_impl="grouped_interpret"), lp, h)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense), **TOL)
    assert (np.asarray(chose) == np.asarray(chose_g)).all()
    assert counts.tolist() == counts_g.tolist()
    assert 0 < counts[1] < chose.size
    few, chose_f, counts_f = mla._ffn(
        dataclasses.replace(cfg, moe_impl="chosen_interpret"), lp, h)
    np.testing.assert_allclose(np.asarray(few), np.asarray(dense), **TOL)
    assert (np.asarray(chose) == np.asarray(chose_f)).all()
    first, count = cfg.held_experts
    local = np.asarray(chose) - first
    assert counts_f.tolist() == [*counts.tolist(), len(
        set(local[(local >= 0) & (local < count)].tolist()))]


# ---------- prefill, pages, decode ----------

@pytest.mark.parametrize("kernel", [False, True])
def test_prefill_then_paged_decode_equals_the_full_forward(kernel):
    cfg, params, tokens, logits, fresh, routes = _fixture(4, 2)
    start = 21                                   # mid-page
    cache = _cache_with(fresh, start)
    attend = functools.partial(pages.latent_decode_attention, kernel=kernel,
                               interpret=kernel)
    step = jax.jit(functools.partial(mla.decode_step, attention_fn=attend,
                                     want_routes=True), static_argnums=1)
    whole = np.asarray(routes.reshape(routes.shape[0], 2, -1, K))
    first, count = cfg.held_experts
    for t in range(start, start + 6):
        got, cache, none, chose = step(
            params, cfg, tokens[:, t], jnp.full((2,), t, jnp.int32),
            state.at_slots(cache, [0, 1]), None, TABLES)
        assert none is None
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(logits[:, t]), **TOL)
        assert (np.sort(np.asarray(chose), -1)
                == np.sort(whole[:, :, t], -1)).all()
        cache, held, zero, _ = state.take_counts(cache)
        chose = np.asarray(chose)
        assert int(held) == ((chose >= first) & (chose < first + count)).sum()
        assert int(zero) == (chose >= cfg.n_experts).sum()
    # Every row the steps wrote is the row the whole prefill computed, in
    # all four cache layers.
    want = _cache_with(fresh, start + 6)
    np.testing.assert_allclose(np.asarray(cache.k[:, 1:]),
                               np.asarray(want.k[:, 1:]), **TOL)


def test_a_window_that_continues_a_cached_prefix_equals_the_whole_prefill():
    cfg, params, tokens, logits, fresh, _ = _fixture(4, 2)
    cache = _cache_with(fresh, 16)               # the first window, plain
    row = TABLES[:1]
    for lo, n, bucket, prior in [(16, 16, 16, 1), (32, 7, 16, 2)]:
        window = jnp.zeros((1, bucket), jnp.int32).at[0, :n].set(
            tokens[0, lo:lo + n])
        got, cache, none = mla.prefill_with_prefix(
            params, cfg, window, jnp.asarray([n]), jnp.asarray([lo]),
            state.at_slots(cache, [0]), None, row, row[:, :prior])
        assert none is None and int(cache.held) >= 0 and int(cache.zero) > 0
        np.testing.assert_allclose(np.asarray(got[0]),
                                   np.asarray(logits[0, lo + n - 1]), **TOL)
    want = _cache_with(fresh, 39)
    blocks = np.asarray(row[0, :3])
    np.testing.assert_allclose(
        np.asarray(cache.k[:, blocks]).reshape(4, 48, -1)[:, :39],
        np.asarray(want.k[:, blocks]).reshape(4, 48, -1)[:, :39], **TOL)


@functools.lru_cache(maxsize=None)
def _row_writers():
    """(a prefill's rows, the cache after that prefill and one decode step),
    jitted once for every set of parameters the test below tries."""
    geom = pages.PageGeometry.for_engine(CFG, 2, 64)

    @jax.jit
    def run(params, tokens):
        _, (fresh, _) = mla.forward(params, CFG, tokens[:, :16], want_kv=True)
        cache, _ = pages.alloc(geom)
        cache, _ = pages.write_sequences(
            state.at_slots(cache, [0]), None, fresh, None, TABLES[:1],
            jnp.asarray([16]))
        _, cache, _ = mla.decode_step(params, CFG, tokens[:, 16],
                                      jnp.asarray([16]), cache, None,
                                      TABLES[:1])
        return fresh.k, cache.k

    return run


@pytest.mark.parametrize("layer,sub", [(0, 0), (0, 1), (1, 0), (1, 1)])
def test_sublayer_i_of_layer_l_writes_cache_layer_2l_plus_i_and_no_other(
        layer, sub):
    """With that sublayer's cache projection zeroed its rows are zeros (a
    norm of nothing, a rotation of nothing) -- in cache layer 2 l + i of a
    prefill's rows and of what a decode step writes, and in no other."""
    _, params, tokens, *_ = _fixture()
    layers = dict(params["layers"])
    layers["wkva"] = layers["wkva"].at[2 * layer + sub].set(0.0)
    rows, pool = _row_writers()({**params, "layers": layers}, tokens[:1])
    quiet = [bool((rows[j] == 0).all()) for j in range(4)]
    assert quiet == [j == 2 * layer + sub for j in range(4)]
    written = np.asarray(pool[:, int(TABLES[0, 1]), 0])      # position 16
    assert [bool((written[j] == 0).all()) for j in range(4)] == quiet


# ---------- the published keys ----------

def _published():
    with open(REPO / "chipbench" / "configs" / "longcat-flash-omni-cut.json") as f:
        doc = json.load(f)
    return {k: v for k, v in doc.items()
            if k not in ("source", "reduced", "assumed", "departures",
                         "deployment", "serve", "reference")}


def test_config_from_hf_maps_the_cells_file():
    cfg = config_from_hf(types.SimpleNamespace(**_published()), name="cut")
    assert cfg == configs.ModelConfig(
        name="cut", vocab_size=16384, d_model=6144, n_layers=4, n_heads=64,
        n_kv_heads=64, d_ff=12288, rope_theta=1e7, max_seq_len=131072,
        norm_eps=1e-5, n_experts=512, experts_per_token=12, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        moe_d_ff=2048, routed_scaling_factor=6.0, experts_held=16,
        experts_first=0, attn_sublayers=2, q_lora_rank=1536,
        mla_scale_q_lora=True, mla_scale_kv_lora=True,
        router_scoring="softmax", n_zero_experts=256)
    assert family(cfg) is mla and cfg.n_kv_layers == 8
    assert cfg.held_experts == (0, 16) and cfg.router_width == 768
    geom = pages.PageGeometry.for_engine(cfg, 64, 2048)
    assert geom.shape == (8, 8193, 16, 640) and geom.token_bytes == 1280
    assert geom.pool_bytes == 8193 * 16 * 640 * 2 * 8      # 1.34 GB


@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", True), ("zero_expert_type", "copy"),
    ("attention_method", "MHA"), ("router_bias", True), ("q_lora_rank", None),
    ("rope_scaling", {"type": "yarn"})])
def test_config_from_hf_refuses_what_the_double_layer_does_not_compute(
        key, value):
    with pytest.raises(ValueError, match=key):
        config_from_hf(types.SimpleNamespace(**{**_published(), key: value}))


def test_a_family_the_tree_does_not_know_stays_a_clean_error():
    """What the parent said of this configuration's file, the tree still says
    of the next unknown one: a DeepSeek-V3-family config (no zero_expert_num)
    whose router scores with a softmax is refused by name, at once."""
    hf = {k: v for k, v in _published().items()
          if not k.startswith("zero_expert")}
    hf.update(scoring_func="softmax")
    with pytest.raises(ValueError, match="scoring_func='softmax' is not "
                                         "supported"):
        config_from_hf(types.SimpleNamespace(**hf), name="unknown")


# ---------- the engine ----------

@pytest.fixture
def served():
    """tiny-longcat in float32, a chip's share of it (8 of 16 experts from
    expert 4 on), under a name of its own."""
    name = "tiny-longcat-f32"
    configs._REGISTRY[name] = dataclasses.replace(
        CFG, name=name, experts_held=8, experts_first=4)
    yield name
    del configs._REGISTRY[name]


def _counters(eng, name, label):
    return {s.labels[label]: s.value
            for m in eng.telemetry.registry.collect() for s in m.samples
            if s.name == name}


def test_engine_serves_through_windows_prefix_cache_and_kernel(served):
    """Prompts in windows of 32 (expanded attention over a cached prefix),
    the decode kernel interpreted, a rerun that hits the prefix cache: the
    greedy first tokens are the plain forward's, and every choice of the
    router is booked once."""
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    long = [1] + [(j * 17) % 450 + 3 for j in range(70)]
    short = [1] + [(j * 5) % 450 + 3 for j in range(30)]

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

            first = await asyncio.gather(one("L", long, 6), one("S", short, 9))
            again = await one("L2", long, 6)
            with pytest.raises(ValueError, match="latent"):
                eng.submit(EngineRequest(
                    request_id="pd", prompt_token_ids=short,
                    kv_transfer_params={"do_remote_decode": True}))
            # Causal: the padding behind a prompt changes nothing before it.
            both = jnp.asarray([long, short + [0] * (len(long) - len(short))])
            logits = jax.jit(lambda p, t: mla.forward(p, eng.mcfg, t)[0])(
                eng.params, both)
            plain = [int(logits[0, len(long) - 1].argmax()),
                     int(logits[1, len(short) - 1].argmax())]
            return (first, again, plain,
                    _counters(eng, "jetstream:moe_routed_pairs_total", "held"),
                    _counters(eng, "jetstream:mla_attention_tokens_total",
                              "form"),
                    _counters(eng, "jetstream:moe_ffn_tokens_total", "form"),
                    eng.describe()["settings"])
        finally:
            await eng.stop()

    (lw, sw), again, plain, pairs, attn, ffn, settings = asyncio.run(serve(
        EngineConfig(model=served, backend="tpu", max_batch=2,
                     max_model_len=128, decode_chunk=4, kv_events_port=0,
                     seed=7, prefill_chunk=32, pallas_attention=True,
                     pallas_interpret=True)))
    assert [lw[0][0], sw[0][0]] == plain and len(lw[0]) == 6
    assert again[0] == lw[0] and again[1] >= 64      # the rerun hit the cache
    # Every choice of every program is booked once, under one of the three:
    # rows x 5 choices x 2 expert layers, a token once a program.
    assert pairs["yes"] > 0 and pairs["no"] > 0 and pairs["zero"] > 0
    assert sum(pairs.values()) == sum(ffn.values()) * K * 2
    assert attn["expanded"] > 0 and attn["absorbed"] > 0
    assert sum(attn.values()) == sum(ffn.values())
    assert settings["kv_layers"] == 4 and settings["kv_token_bytes"] == 128 * 4
    assert settings["kv_pool_bytes"] == 4 * 17 * 16 * 512
    assert (settings["experts_first"], settings["experts_held"],
            settings["zero_experts"]) == (4, 8, 8)
    assert settings["pallas_attention"] and settings["prefix_caching"]
