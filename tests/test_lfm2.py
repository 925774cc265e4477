"""The lfm2_moe family (models/hybrid.py's "C" and "Q" layers: LiquidAI's
LFM2-8B-A1B) at a small size: the block against the plain reference, a prompt
in windows and then decode through the pages and the tails, the padding of a
bucket, lanes in different slots at different lengths, a slot reused, the page
layout of two 64-wide heads a row and its walk, the experts' forms, the
mapper and what it refuses, the checkpoint's names, what the programs are
counted as, the tail-only state pool, and the engine end to end."""

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

from llm_d_inference_scheduler_tpu.engine.request import EngineRequest
from llm_d_inference_scheduler_tpu.kvcache import pages, state
from llm_d_inference_scheduler_tpu.models import bind, configs, family, hybrid
from llm_d_inference_scheduler_tpu.models.convert_hf import (
    config_from_hf, convert_state_dict)
from llm_d_inference_scheduler_tpu.ops.attention import paged_decode_attention

REPO = pathlib.Path(__file__).resolve().parent.parent
CFG = dataclasses.replace(configs.get_config("tiny-lfm2"), dtype="float32")
# float32 on both sides, different summation order (test_mla.py's).
TOL = dict(rtol=2e-4, atol=2e-4)
N_TOKENS = 45
KINDS = ["conv", "conv", "full_attention"] * 2


def _reference():
    path = REPO / "chipbench" / "configs" / "reference_lfm2_moe.py"
    spec = importlib.util.spec_from_file_location("reference_lfm2_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


SIZES = dict(layer_types=tuple(KINDS), num_dense_layers=CFG.first_k_dense,
             n_heads=CFG.n_heads, n_kv_heads=CFG.n_kv_heads,
             head_dim=CFG.head_dim, top_k=CFG.experts_per_token,
             rope_theta=CFG.rope_theta, norm_eps=CFG.norm_eps,
             scaling=CFG.routed_scaling_factor)


@functools.lru_cache(maxsize=None)
def _fixture():
    params = hybrid.init_params(CFG, jax.random.key(7), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(9), (2, N_TOKENS), 0,
                                CFG.vocab_size)
    logits, (fresh, _), routes = hybrid.forward(
        params, CFG, tokens, want_kv=True, want_routes=True)
    return params, tokens, logits, fresh, routes


@functools.lru_cache(maxsize=None)
def _want(row=0, **switches):
    """The reference's logits for sequence ``row``, held to the program's
    expert choices (a near-tie parted the other way is another function)."""
    params, tokens, _, _, routes = _fixture()
    forced = routes.reshape(routes.shape[0], 2, N_TOKENS, -1)[:, row]
    return np.asarray(_reference().forward(params, tokens[row], **SIZES,
                                           routes=forced, **switches))


def _cache(n_slots=2, max_len=64):
    geom = pages.PageGeometry.for_engine(CFG, n_slots, max_len)
    cache, none = pages.alloc(geom)
    assert none is None and isinstance(cache, state.Cache)
    return cache


TABLES = jnp.asarray([[3, 1, 5, 0], [2, 6, 4, 0]], jnp.int32)


def _prefilled(n_tokens, bucket=None, cache=None, slots=(0, 1)):
    """A cache whose slots hold both sequences' first ``n_tokens``,
    prefilled in a bucket of ``bucket`` positions (padded past n_tokens),
    and the window's logits."""
    params, tokens, *_ = _fixture()
    bucket = bucket or -(-n_tokens // 16) * 16
    padded = jnp.zeros((2, bucket), jnp.int32).at[:, :n_tokens].set(
        tokens[:, :n_tokens])
    lens = jnp.full((2,), n_tokens, jnp.int32)
    logits, (fresh, _) = hybrid.forward(params, CFG, padded, want_kv=True,
                                        seq_len=lens)
    cache, _ = pages.write_sequences(
        state.at_slots(_cache() if cache is None else cache, list(slots)),
        None, fresh, None, TABLES, lens)
    return state.take_counts(cache)[0], logits


def _window(cache, row, first, n, bucket, slot=None):
    """Sequence ``row``'s tokens ``first .. first + n`` as a continuation
    window in a bucket of ``bucket``: (its last real position's logits, the
    cache)."""
    params, tokens, *_ = _fixture()
    padded = jnp.zeros((1, bucket), jnp.int32).at[0, :n].set(
        tokens[row, first:first + n])
    logits, cache, _ = hybrid.prefill_with_prefix(
        params, CFG, padded, jnp.full((1,), n, jnp.int32),
        jnp.full((1,), first, jnp.int32),
        state.at_slots(cache, [row if slot is None else slot]), None,
        TABLES[row:row + 1])
    return logits[0], state.take_counts(cache)[0]


def _decode(cache, rows, positions, slots, attention_fn=None):
    params, tokens, *_ = _fixture()
    rows, positions = np.asarray(rows), np.asarray(positions)
    more = {} if attention_fn is None else dict(attention_fn=attention_fn)
    logits, cache, _ = hybrid.decode_step(
        params, CFG, tokens[rows, positions],
        jnp.asarray(positions, jnp.int32), state.at_slots(cache, list(slots)),
        None, TABLES[rows], **more)
    return logits, state.take_counts(cache)[0]


# ---------- the block against the plain reference ----------

def test_family_and_geometry():
    assert family(CFG) is hybrid and CFG.conv_mixers
    assert CFG.layer_pattern == "CCQCCQ"
    assert (CFG.n_state_layers, CFG.n_recurrent_layers, CFG.n_kv_layers,
            CFG.n_expert_layers) == (4, 0, 2, 5)
    assert (CFG.head_dim, CFG.kv_heads_a_row, CFG.ssm_row) == (64, 2, ())
    geom = pages.PageGeometry.for_engine(CFG, 3, 64)
    # A tail and nothing else: two rows of d_model values a slot a layer.
    assert geom.state.ssm_shape is None
    assert geom.state.conv_shape == (4, 4, 2 * 128)
    # Two adjacent KV heads of 64 side by side, a page row of 128.
    assert geom.shape == (2, 1 + 3 * 4, 16, 2, 128)
    assert geom.token_bytes == 2 * 4 * 64 * 4
    # The other hybrid presets' heads pack no row: their pools are theirs.
    for name in ("tiny-hybrid", "tiny-jamba"):
        assert configs.get_config(name).kv_heads_a_row == 1


@pytest.mark.parametrize("row", [0, 1])
def test_forward_equals_the_plain_reference(row):
    _, _, logits, *_ = _fixture()
    np.testing.assert_allclose(np.asarray(logits[row]), _want(row), **TOL)


def test_the_references_own_choices_are_the_programs():
    """Held to nothing, the f32 reference chooses the experts the f32
    program chose, and no forced choice lies under its threshold."""
    params, tokens, logits, _, routes = _fixture()
    ref = _reference()
    np.testing.assert_allclose(
        np.asarray(logits[0]),
        np.asarray(ref.forward(params, tokens[0], **SIZES)), **TOL)
    forced = routes.reshape(routes.shape[0], 2, N_TOKENS, -1)[:, 0]
    _, short = ref.hidden(params, tokens[0], **SIZES, routes=forced,
                          want_shortfall=True)
    assert float(short) < 1e-5


@pytest.mark.parametrize("switch", [
    dict(swap_bc=True), dict(qk_norm=False), dict(rotary=False),
    dict(bias_in_gates=True)], ids=lambda s: next(iter(s)))
def test_the_comparison_sees_each_control(switch):
    """The reference's switches (the chip comparison's planted faults) move
    the logits far past the tolerance."""
    _, _, logits, *_ = _fixture()
    diff = np.abs(np.asarray(logits[0]) - _want(0, **switch)).max()
    assert diff > 50 * TOL["atol"]


def test_a_router_in_bf16_is_seen():
    """Scores rounded to bf16 move every gate by a part in 256 (and part
    near-ties the other way, at sizes that have them): the reference, left
    to choose with them, stops agreeing with the program."""
    params, tokens, logits, *_ = _fixture()
    got = np.asarray(_reference().forward(params, tokens[0], **SIZES,
                                          router_bf16=True))
    assert np.abs(np.asarray(logits[0]) - got).max() > 5 * TOL["atol"]


def test_the_last_tails_are_the_references():
    params, tokens, _, fresh, _ = _fixture()
    assert fresh.ssm is None
    _, tails = _reference().hidden(params, tokens[0], **SIZES,
                                   want_tail=True)
    np.testing.assert_allclose(np.asarray(fresh.conv[:, 0]),
                               np.asarray(tails), **TOL)


def test_the_head_is_the_embedding_transposed():
    params, *_ = _fixture()
    assert np.array_equal(np.asarray(params["lm_head"]),
                          np.asarray(params["embed"]).T)


# ---------- through the tails and the pages ----------

@pytest.mark.parametrize("n,bucket", [(16, 16), (21, 32), (9, 32), (1, 16)])
def test_padding_rows_of_a_bucket_leave_the_tail_alone(n, bucket):
    """The tail a padded bucket leaves is the one the same tokens leave with
    no padding behind them (a one-token window's reaches back into zeros)."""
    params, tokens, *_ = _fixture()
    _, (exact, _) = hybrid.forward(params, CFG, tokens[:, :n], want_kv=True)
    cache, _ = _prefilled(n, bucket)
    assert cache.ssm is None
    np.testing.assert_allclose(
        np.asarray(cache.conv[:, :2]),
        np.asarray(exact.conv).reshape(4, 2, -1), **TOL)


def test_a_prompt_in_three_windows_then_decode_equals_the_full_forward():
    """A first window of 16, a continuation window of 16 and a last one of 5
    in a bucket of 16 (padded; each later window starts from the slot's
    carried tail and reads the pages), then decode steps of both sequences
    through the pages and the tails: every logit the reference's full
    forward pass gives at that position."""
    cache, first = _prefilled(16)
    for row in (0, 1):
        np.testing.assert_allclose(np.asarray(first[row, :16]),
                                   _want(row)[:16], **TOL)
        got, cache = _window(cache, row, 16, 16, 16)
        np.testing.assert_allclose(np.asarray(got), _want(row)[31], **TOL)
        got, cache = _window(cache, row, 32, 5, 16)
        np.testing.assert_allclose(np.asarray(got), _want(row)[36], **TOL)
    for pos in range(37, N_TOKENS):
        logits, cache = _decode(cache, [0, 1], [pos, pos], [0, 1])
        for row in (0, 1):
            np.testing.assert_allclose(np.asarray(logits[row]),
                                       _want(row)[pos], **TOL)


def test_a_window_that_drops_the_carried_tail_is_seen():
    """The chip comparison's first planted fault: a later window from
    zeros."""
    cache, _ = _prefilled(16)
    cache = dataclasses.replace(cache, conv=jnp.zeros_like(cache.conv))
    got, _ = _window(cache, 0, 16, 16, 16)
    assert np.abs(np.asarray(got) - _want(0)[31]).max() > 50 * TOL["atol"]


def test_three_windows_equal_the_same_prompt_in_one_window():
    """16 + 16 + 9 tokens in three windows, the last padded, leave the slot
    what 41 tokens in one window leave it, and give the last token the same
    logits."""
    whole, logits = _prefilled(41, 48)
    cache, _ = _prefilled(16)
    _, cache = _window(cache, 0, 16, 16, 16)
    got, cache = _window(cache, 0, 32, 9, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(logits[0, 40]),
                               **TOL)
    np.testing.assert_allclose(np.asarray(cache.conv[:, 0]),
                               np.asarray(whole.conv[:, 0]), **TOL)
    # Sequence 1's slot was nobody's business.
    np.testing.assert_array_equal(np.asarray(cache.conv[:, 1]),
                                  np.asarray(_prefilled(16)[0].conv[:, 1]))


def test_lanes_in_other_slots_at_other_lengths_in_one_step():
    """Sequence 0 at 20 tokens in slot 2 and sequence 1 at 33 in slot 0,
    decoded in one step, lane order not slot order."""
    cache = _cache(n_slots=3)
    cache, _ = _prefilled(20, 32, cache=cache, slots=(2, 0))
    _, cache = _window(cache, 1, 20, 13, 16, slot=0)
    logits, cache = _decode(cache, [1, 0], [33, 20], [0, 2])
    np.testing.assert_allclose(np.asarray(logits[0]), _want(1)[33], **TOL)
    np.testing.assert_allclose(np.asarray(logits[1]), _want(0)[20], **TOL)
    logits, _ = _decode(cache, [0, 1], [21, 34], [2, 0])
    np.testing.assert_allclose(np.asarray(logits[0]), _want(0)[21], **TOL)
    np.testing.assert_allclose(np.asarray(logits[1]), _want(1)[34], **TOL)


def test_a_slot_reused_by_a_shorter_request_starts_afresh():
    """Slots that held 41 tokens each take 9-token prompts: the first window
    writes the tails whole and reads nothing of what was there."""
    long, _ = _prefilled(41, 48)
    reused, _ = _prefilled(9, 16, cache=long)
    fresh, _ = _prefilled(9, 16)
    np.testing.assert_array_equal(np.asarray(reused.conv[:, :2]),
                                  np.asarray(fresh.conv[:, :2]))
    logits, _ = _decode(reused, [0, 1], [9, 9], [0, 1])
    for row in (0, 1):
        np.testing.assert_allclose(np.asarray(logits[row]), _want(row)[9],
                                   **TOL)


# ---------- two heads of 64 a page row ----------

def _paged_case(seed=0, B=3, H=8, Hkv=4, D=64, L=2, N=40, block=16):
    ks = jax.random.split(jax.random.key(seed), 5)
    kp = jax.random.normal(ks[0], (L, N, block, Hkv, D), jnp.float32)
    vp = jax.random.normal(ks[1], (L, N, block, Hkv, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, H, D), jnp.float32)
    ck = jax.random.normal(ks[3], (B, Hkv, D), jnp.float32)
    cv = jax.random.normal(ks[4], (B, Hkv, D), jnp.float32)
    tables = jnp.asarray(np.stack(
        [1 + lane * 12 + np.arange(12) for lane in range(B)]).astype(np.int32))
    return q, kp, vp, tables, jnp.asarray([5, 100, 190], jnp.int32), ck, cv


def _side_by_side(pool):
    return pool.reshape(*pool.shape[:3], pool.shape[3] // 2, -1)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "walk"])
def test_the_walk_over_two_heads_a_row_is_plain_attention(kernel):
    """Pages that hold two KV heads of 64 side by side as a row of 128,
    every head's keys and values its own (random): the walk (through the
    interpreter) and the plain form give what attention over 4 heads of 64
    gives, and the walk with the halves of a row exchanged does not."""
    q, kp, vp, tables, lens, ck, cv = _paged_case()
    layer = jnp.int32(1)
    want = paged_decode_attention(q, kp, vp, layer, tables, lens, cur_k=ck,
                                  cur_v=cv)
    got = pages.decode_attention(q, _side_by_side(kp), _side_by_side(vp),
                                 layer, tables, lens, ck, cv, kernel=kernel,
                                 interpret=True)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    swapped = [p[..., ::-1, :] for p in (kp.reshape(*kp.shape[:3], 2, 2, 64),
                                         vp.reshape(*vp.shape[:3], 2, 2, 64))]
    bad = pages.decode_attention(
        q, *(p.reshape(*kp.shape[:3], 2, 128) for p in swapped), layer,
        tables, lens, ck, cv, kernel=kernel, interpret=True)
    assert np.abs(np.asarray(bad) - np.asarray(want)).max() > 0.05


def test_rows_go_into_and_come_out_of_such_pages_as_the_models_own():
    """``pages.write`` takes [.., Hkv, 64] rows into a [.., Hkv / 2, 128]
    pool and ``read_prefix`` hands them back as the model's."""
    geom = pages.PageGeometry.for_engine(CFG, 2, 64)
    (cache, _) = pages.alloc(geom)
    rows = jax.random.normal(jax.random.key(3), (2, 1, 20, 4, 64))
    slots = pages.sequence_slots(cache.k, TABLES[:1], jnp.asarray([20]), 20)
    k, v = pages.write(cache.k, cache.v, rows, -rows, *slots)
    assert k.shape == geom.shape
    back_k, back_v = pages.read_prefix(k, v, TABLES[:1, :2], layer=1,
                                       heads=(4, 64))
    np.testing.assert_array_equal(np.asarray(back_k[0, :20]),
                                  np.asarray(rows[1, 0]))
    np.testing.assert_array_equal(np.asarray(back_v[0, :20]),
                                  -np.asarray(rows[1, 0]))


def test_decode_through_the_walk_equals_decode_through_the_gather():
    cache, _ = _prefilled(21, 32)
    walk = functools.partial(pages.decode_attention, kernel=True,
                             interpret=True)
    plain, _ = _decode(cache, [0, 1], [21, 21], [0, 1])
    got, _ = _decode(cache, [0, 1], [21, 21], [0, 1], attention_fn=walk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain), **TOL)
    np.testing.assert_allclose(np.asarray(got[0]), _want(0)[21], **TOL)


def test_the_engine_chooses_the_walk_where_a_row_is_whole_lanes():
    geom = pages.PageGeometry.for_engine(CFG, 2, 64)
    facts = dict(asked=None, interpret=False, sharded=False)
    assert pages.use_kernel(geom.shape[-1], platform="tpu", **facts)
    assert not pages.use_kernel(geom.shape[-1], platform="cpu", **facts)
    assert not pages.use_kernel(CFG.head_dim, platform="tpu", **facts)


# ---------- the experts' forms ----------

def test_the_grouped_form_serves_the_same_logits():
    """The routed experts through ops/pallas_moe.py's grouped kernel (the
    interpreter) against dense over all of them."""
    params, tokens, logits, *_ = _fixture()
    bound = bind(CFG, platform="cpu", interpret=True)
    assert not bound.moe_chosen(2) and bound.moe_grouped(512)
    assert bound.model_for(64).moe_impl == "dense"
    got, _ = hybrid.forward(params, bound.grouped, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(logits), **TOL)


def test_the_cells_widths_pass_the_grouped_forms_test():
    served = bind(_published_config(), platform="tpu")
    assert served.model_for(1024).moe_impl == "grouped"
    assert served.model_for(64).moe_impl == "dense"
    assert served.pairs_per_row == 4 * 14


# ---------- the mapper ----------

@functools.lru_cache(maxsize=None)
def _published():
    with open(REPO / "chipbench" / "configs" / "lfm2-8b-a1b-cut.json") as f:
        doc = json.load(f)
    return {k: v for k, v in doc.items()
            if k not in ("source", "reduced", "assumed", "departures",
                         "deployment", "serve", "reference")}


def _published_config():
    return config_from_hf(types.SimpleNamespace(**_published()), "lfm2")


def test_config_from_hf_reads_the_published_keys():
    got = _published_config()
    assert got.layer_pattern == "CCQCCCQCCCQCCCQC"
    assert (got.n_layers, got.n_state_layers, got.n_kv_layers,
            got.n_expert_layers, got.first_k_dense) == (16, 12, 4, 14, 2)
    assert (got.d_model, got.d_ff, got.moe_d_ff, got.vocab_size) == (
        2048, 7168, 1792, 65536)
    assert (got.n_heads, got.n_kv_heads, got.head_dim) == (32, 8, 64)
    assert (got.n_experts, got.experts_per_token, got.held_experts) == (
        32, 4, (0, 32))
    assert (got.ssm_conv, got.ssm_conv_dim, got.ssm_row) == (3, 2048, ())
    assert (got.rope_theta, got.norm_eps, got.qk_norm) == (1e6, 1e-5, True)
    assert (got.router_scoring, got.n_group, got.routed_scaling_factor) == (
        "sigmoid", 1, 1.0)
    assert family(got) is hybrid
    # The file states the published depth and order beside the cut.
    doc = _published()
    assert doc["num_hidden_layers_published"] == 24
    assert doc["layer_types_published"][:16] == doc["layer_types"]
    # The cell's pools: 2,048 B a token a layer (8 x 64 x 2 x 2) over four
    # attention layers, two heads a page row; 12 x 8,192 B of tails a slot.
    geom = pages.PageGeometry.for_engine(got, 64, 4608)
    assert geom.shape == (4, 18433, 16, 4, 128)
    settings = geom.describe()
    assert (settings["kv_layers"], settings["kv_token_bytes"]) == (4, 2048)
    assert settings["kv_pool_bytes"] == 18433 * 16 * 8192
    assert settings["state_slot_bytes"] == 98_304
    assert settings["state_pool_bytes"] == 65 * 98_304
    assert settings["off_for_state_layers"]
    described = bind(got, platform="tpu").describe()
    assert described["state_layers"] == 12
    assert described["state_update"] is None
    assert "state_scan" not in described


@pytest.mark.parametrize("change,match", [
    (dict(conv_bias=True), "conv_bias=True"),
    (dict(norm_topk_prob=False), "norm_topk_prob=False"),
    (dict(use_expert_bias=False), "use_expert_bias=False"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings=False"),
    (dict(layer_types=["conv", "sliding_attention"] * 8), "sliding_attention"),
    (dict(num_hidden_layers=24), "lists 16 layers"),
    (dict(conv_L_cache=1), "conv_L_cache=1"),
    (dict(num_experts_per_tok=32), "has to choose")],
    ids=lambda v: v if isinstance(v, str) else next(iter(v)))
def test_config_from_hf_refuses_what_is_not_built(change, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf(types.SimpleNamespace(**{**_published(), **change}),
                       "lfm2")


# ---------- the checkpoint's names ----------

def _as_published(params):
    """``params`` under the family's checkpoint names (``nn.Linear`` weights
    [out, in], the convolution a depth-wise Conv1d [channels, 1, taps])."""
    out = {"model.embed_tokens.weight": params["embed"],
           "model.embedding_norm.weight": params["final_norm"]}
    n_conv = n_attn = 0
    for i, kind in enumerate(CFG.layer_pattern):
        at = f"model.layers.{i}."
        if kind == "C":
            lp = {n: a[n_conv] for n, a in params["conv"].items()}
            n_conv += 1
            out |= {at + "conv.in_proj.weight": lp["w_in"].T,
                    at + "conv.conv.weight": lp["conv_w"].T[:, None, :],
                    at + "conv.out_proj.weight": lp["w_out"].T}
        else:
            lp = {n: a[n_attn] for n, a in params["attn"].items()}
            n_attn += 1
            out |= {at + f"self_attn.{n}_proj.weight": lp[w].T
                    for n, w in (("q", "wq"), ("k", "wk"), ("v", "wv"),
                                 ("out", "wo"))}
            out |= {at + "self_attn.q_layernorm.weight": lp["q_norm"],
                    at + "self_attn.k_layernorm.weight": lp["k_norm"]}
        out[at + "operator_norm.weight"] = lp["ln"]
        if i < CFG.first_k_dense:
            fp = {n: a[i] for n, a in params["ffn"].items()}
            out |= {at + f"feed_forward.{w}.weight": fp[w].T
                    for w in ("w1", "w3", "w2")}
        else:
            fp = {n: a[i - CFG.first_k_dense]
                  for n, a in params["experts"].items()}
            out |= {at + "feed_forward.gate.weight": fp["router"].T,
                    at + "feed_forward.expert_bias": fp["router_bias"]}
            for e in range(CFG.n_experts):
                out |= {at + f"feed_forward.experts.{e}.{w}.weight":
                        fp[w][e].T for w in ("w1", "w3", "w2")}
        out[at + "ffn_norm.weight"] = fp["ln_mlp"]
    return {k: np.asarray(v) for k, v in out.items()}


def test_convert_state_dict_maps_the_familys_names():
    params, tokens, logits, *_ = _fixture()
    got = convert_state_dict(_as_published(params), CFG)
    assert (jax.tree.structure(got) == jax.tree.structure(params))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    again, _ = hybrid.forward(got, CFG, tokens[:1])
    np.testing.assert_allclose(np.asarray(again[0]), np.asarray(logits[0]),
                               **TOL)
    with pytest.raises(KeyError, match="embedding_norm"):
        convert_state_dict({k: v for k, v in _as_published(params).items()
                            if "embedding_norm" not in k}, CFG)


# ---------- what the programs are counted as ----------

def test_program_counts_book_the_state_and_expert_series():
    served = bind(CFG, platform="cpu")
    assert served.program_counts("decode", 4, 8) == [
        ("moe_ffn_tokens", "dense", 32), ("ssm_tokens", "step", 32)]
    assert served.program_counts("prefill", 64, 1, real=2) == [
        ("moe_ffn_tokens", "dense", 64), ("ssm_tokens", "scan", 64),
        ("ssm_slot_prefills", None, 2)]
    assert served.program_counts("prefix_prefill", 32, 1, real=1) == [
        ("moe_ffn_tokens", "dense", 32), ("ssm_tokens", "scan", 32)]
    cell = bind(_published_config(), platform="tpu")
    assert cell.program_counts("prefill", 1024, 1, real=1)[0] == (
        "moe_ffn_tokens", "grouped", 1024)
    # Neither kernel of ops/pallas_ssm.py is called: neither series moves.
    for kind in ("decode", "prefill", "prefix_prefill"):
        assert not [c for c in cell.program_counts(kind, 64, 8, real=1)
                    if c[0] in ("ssm_state_updates", "ssm_scan_tokens")]


# ---------- the tail-only state pool ----------

def test_a_state_pool_whose_recurrent_part_is_empty():
    geom = state.StateGeometry.for_engine(CFG, 5)
    assert (geom.row_shape, geom.ssm_shape, geom.state) == ((), None, 0)
    assert geom.conv_shape == (4, 6, 256)
    assert geom.slot_bytes == 4 * 2 * 128 * 4
    assert geom.pool_bytes == 6 * geom.slot_bytes
    cache = state.alloc(geom, jnp.zeros((1,)), jnp.zeros((1,)))
    assert cache.ssm is None and cache.conv.shape == geom.conv_shape
    stepped = state.at_slots(cache, [3, 1])
    got, tail = state.read(stepped, 2)
    assert got is None and tail.shape == (2, 256)
    tails = [jnp.full((2, 2, 128), float(layer + 1)) for layer in range(4)]
    written = state.write(stepped, None, tails)
    assert written.ssm is None
    np.testing.assert_array_equal(np.asarray(written.conv[2, [3, 1]]), 3.0)
    np.testing.assert_array_equal(np.asarray(written.conv[:, [0, 2, 4, 5]]),
                                  0.0)
    # The other families' geometry is untouched.
    jamba = state.StateGeometry.for_engine(configs.get_config("tiny-jamba"), 2)
    assert jamba.ssm_shape == (6, 3, 6, 96)


# ---------- the engine ----------

@pytest.fixture
def served():
    name = "tiny-lfm2-f32"
    configs._REGISTRY[name] = dataclasses.replace(CFG, name=name)
    yield name
    del configs._REGISTRY[name]


def _req(rid, seed, n_prompt, max_tokens):
    prompt = [1] + [(j * seed) % 450 + 3 for j in range(n_prompt)]
    return EngineRequest(request_id=rid, prompt_token_ids=prompt,
                         max_tokens=max_tokens, temperature=0.0,
                         ignore_eos=True)


def _series(eng, name):
    return {s.labels.get("form", ""): s.value
            for m in eng.telemetry.registry.collect() for s in m.samples
            if s.name == name}


def test_an_engine_serves_the_same_tokens_through_the_walk(served):
    """Greedy streams through prompts in windows of 32 (continuation windows
    among them) and decode chunks, with the plain forms and with the paged
    walk through the interpreter; /health's settings and the series."""
    from test_engine import _by_hand

    by_hand = functools.partial(_by_hand, model=served, max_batch=2,
                                prefill_chunk=32)
    reqs = [_req("A", 29, 70, 9), _req("B", 31, 20, 12), _req("C", 37, 33, 5)]
    plain, why, eng = by_hand(reqs)
    settings = eng.describe()["settings"]
    assert (settings["kv_layers"], settings["state_layers"]) == (2, 4)
    assert settings["kv_token_bytes"] == 2 * 4 * 64 * 4
    assert settings["state_slot_bytes"] == 4 * 2 * 128 * 4
    assert settings["state_pool_bytes"] == 3 * settings["state_slot_bytes"]
    assert settings["state_update"] is None
    assert settings["prefix_caching"] is False
    assert settings["pallas_attention"] is False
    tokens = _series(eng, "jetstream:ssm_tokens_total")
    assert tokens["scan"] > 71 + 21 + 34 and tokens["step"] > 0
    assert _series(eng, "jetstream:ssm_slot_prefills_total")[""] == 3
    assert set(_series(eng, "jetstream:moe_ffn_tokens_total")) == {"dense"}
    # Its kernels are not this model's: their series stay where they were.
    assert not sum(_series(
        eng, "jetstream:ssm_state_updates_total").values())
    assert not sum(_series(eng, "jetstream:ssm_scan_tokens_total").values())

    walked, why_w, eng = by_hand(reqs, pallas_interpret=True,
                                 pallas_attention=True)
    assert eng.describe()["settings"]["pallas_attention"] is True
    assert walked == plain and why_w == why
