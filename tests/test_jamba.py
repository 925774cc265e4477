"""The jamba family (models/hybrid.py's "S" and "A" layers: AI21-Jamba2-3B's
language model) at a small size: the block against the plain reference, a
prompt in windows and then decode through the state pool's second layout and
the pages, the padding of a bucket, the mapper and what it refuses, what the
programs are counted as, and the engine end to end with both kernels through
the interpreter."""

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
from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf

REPO = pathlib.Path(__file__).resolve().parent.parent
CFG = dataclasses.replace(configs.get_config("tiny-jamba"), dtype="float32")
# float32 on both sides, different summation order (test_mla.py's).
TOL = dict(rtol=2e-4, atol=2e-4)
N_TOKENS = 45


def _reference():
    path = REPO / "chipbench" / "configs" / "reference_jamba.py"
    spec = importlib.util.spec_from_file_location("reference_jamba", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


SIZES = dict(n_layers=CFG.n_layers, attn_period=4, attn_offset=1,
             n_heads=CFG.n_heads, n_kv_heads=CFG.n_kv_heads,
             head_dim=CFG.head_dim, ssm_state=CFG.ssm_state,
             ssm_dt_rank=CFG.ssm_dt_rank, norm_eps=CFG.norm_eps)


@functools.lru_cache(maxsize=None)
def _fixture():
    params = hybrid.init_params(CFG, jax.random.key(7), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(9), (2, N_TOKENS), 0,
                                CFG.vocab_size)
    logits, (fresh, _) = hybrid.forward(params, CFG, tokens, want_kv=True)
    return params, tokens, logits, fresh


@functools.lru_cache(maxsize=None)
def _want(row=0, **switches):
    params, tokens, *_ = _fixture()
    return np.asarray(_reference().forward(params, tokens[row], **SIZES,
                                           **switches))


def _cache(n_slots=2, max_len=64):
    geom = pages.PageGeometry.for_engine(CFG, n_slots, max_len)
    cache, none = pages.alloc(geom)
    assert none is None and isinstance(cache, state.Cache)
    return cache


TABLES = jnp.asarray([[3, 1, 5, 0], [2, 6, 4, 0]], jnp.int32)


def _prefilled(n_tokens, bucket=None):
    """A cache whose slots 0 and 1 hold both sequences' first ``n_tokens``,
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
        state.at_slots(_cache(), [0, 1]), None, fresh, None, TABLES, lens)
    return state.take_counts(cache)[0], logits


def _window(cache, row, first, n, bucket):
    """Sequence ``row``'s tokens ``first .. first + n`` as a continuation
    window in a bucket of ``bucket``: (its last real position's logits, the
    cache)."""
    params, tokens, *_ = _fixture()
    padded = jnp.zeros((1, bucket), jnp.int32).at[0, :n].set(
        tokens[row, first:first + n])
    logits, cache, _ = hybrid.prefill_with_prefix(
        params, CFG, padded, jnp.full((1,), n, jnp.int32),
        jnp.full((1,), first, jnp.int32), state.at_slots(cache, [row]), None,
        TABLES[row:row + 1])
    return logits[0], state.take_counts(cache)[0]


# ---------- the block against the plain reference ----------

def test_family_and_geometry():
    assert family(CFG) is hybrid
    assert CFG.layer_pattern == "SASSSASS"
    assert (CFG.n_state_layers, CFG.n_kv_layers, CFG.ssm_inner) == (6, 2, 96)
    geom = pages.PageGeometry.for_engine(CFG, 3, 64)
    assert geom.state.ssm_shape == (6, 4, 6, 96)        # [.., state, inner]
    assert geom.state.conv_shape == (6, 4, 3 * 96)
    # ONE KV head, kept twice a page (ModelConfig.kv_heads_kept).
    assert geom.shape == (2, 1 + 3 * 4, 16, 2, 8)


@pytest.mark.parametrize("row", [0, 1])
def test_forward_equals_the_plain_reference(row):
    *_, logits, _ = _fixture()
    np.testing.assert_allclose(np.asarray(logits[row]), _want(row), **TOL)


@pytest.mark.parametrize("switch", [dict(norms=False), dict(rotary=True)])
def test_the_comparison_sees_each_control(switch):
    """The reference's two switches (the chip comparison's planted faults)
    move the logits far past the tolerance."""
    *_, logits, _ = _fixture()
    diff = np.abs(np.asarray(logits[0]) - _want(0, **switch)).max()
    assert diff > 100 * TOL["atol"]


def test_the_last_states_are_the_references():
    params, tokens, _, fresh = _fixture()
    _, last = _reference().hidden(params, tokens[0], **SIZES, want_state=True)
    # The program keeps a state transposed: [state, inner].
    np.testing.assert_allclose(np.asarray(fresh.ssm[:, 0]),
                               np.swapaxes(np.asarray(last), 1, 2), **TOL)


def test_the_head_is_the_embedding_transposed():
    params, *_ = _fixture()
    assert np.array_equal(np.asarray(params["lm_head"]),
                          np.asarray(params["embed"]).T)


# ---------- through the pool and the pages ----------

@pytest.mark.parametrize("n,bucket", [(16, 16), (21, 32), (9, 32)])
def test_padding_rows_of_a_bucket_leave_the_state_alone(n, bucket):
    """The state and the tail a padded bucket leaves are the ones the same
    tokens leave with no padding behind them."""
    params, tokens, *_ = _fixture()
    _, (exact, _) = hybrid.forward(params, CFG, tokens[:, :n], want_kv=True)
    cache, _ = _prefilled(n, bucket)
    np.testing.assert_allclose(np.asarray(cache.ssm[:, :2]),
                               np.asarray(exact.ssm), **TOL)
    np.testing.assert_allclose(
        np.asarray(cache.conv[:, :2]),
        np.asarray(exact.conv).reshape(6, 2, -1), **TOL)


def test_prefill_in_windows_then_decode_equals_the_full_forward():
    """A first window of 16, a continuation window of 13 in a bucket of 16
    (which starts from the slot's state and reads the pages), then 16 decode
    steps of both sequences through the pool and the pages: every logit the
    reference's full forward pass gives at that position."""
    params, tokens, *_ = _fixture()
    cache, first = _prefilled(16)
    for row in (0, 1):
        np.testing.assert_allclose(np.asarray(first[row, :16]),
                                   _want(row)[:16], **TOL)
        got, cache = _window(cache, row, 16, 13, 16)
        np.testing.assert_allclose(np.asarray(got), _want(row)[28], **TOL)
    for pos in range(29, N_TOKENS):
        logits, cache, _ = hybrid.decode_step(
            params, CFG, tokens[:, pos], jnp.full((2,), pos, jnp.int32),
            state.at_slots(cache, [0, 1]), None, TABLES)
        cache = state.take_counts(cache)[0]
        for row in (0, 1):
            np.testing.assert_allclose(np.asarray(logits[row]),
                                       _want(row)[pos], **TOL)


def test_a_continuation_window_equals_the_same_prompt_in_one_window():
    """32 + 9 tokens in two windows leave the slot what 41 tokens in one
    window leave it, and give the last token the same logits."""
    whole, logits = _prefilled(41, 48)
    cache, _ = _prefilled(32)
    got, cache = _window(cache, 0, 32, 9, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(logits[0, 40]),
                               **TOL)
    np.testing.assert_allclose(np.asarray(cache.ssm[:, 0]),
                               np.asarray(whole.ssm[:, 0]), **TOL)
    np.testing.assert_allclose(np.asarray(cache.conv[:, 0]),
                               np.asarray(whole.conv[:, 0]), **TOL)
    # Sequence 1's slot was nobody's business.
    np.testing.assert_array_equal(np.asarray(cache.ssm[:, 1]),
                                  np.asarray(_prefilled(32)[0].ssm[:, 1]))


def test_both_kernels_through_the_interpreter_serve_the_same_logits():
    """The block at widths the kernels' rule accepts (inner 256, state 8),
    the prompt windows' scan and the decode steps' update in place through
    the interpreter, against the plain forms."""
    wide = dataclasses.replace(CFG, d_model=128, n_heads=4, ssm_state=8)
    kernels = bind(wide, platform="cpu", interpret=True).mcfg
    assert (kernels.ssm_impl, kernels.ssm_scan_impl) == (
        "kernel_interpret", "kernel_interpret")
    assert bind(wide, platform="cpu").mcfg.ssm_scan_impl == "xla"
    params = hybrid.init_params(wide, jax.random.key(3), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(4), (2, 40), 0, 512)
    lens = jnp.full((2,), 21, jnp.int32)
    got = {}
    for cfg in (wide, kernels):
        cache, _ = pages.alloc(pages.PageGeometry.for_engine(cfg, 2, 64))
        padded = jnp.zeros((2, 32), jnp.int32).at[:, :21].set(tokens[:, :21])
        first, (fresh, _) = hybrid.forward(params, cfg, padded, want_kv=True,
                                           seq_len=lens)
        cache, _ = pages.write_sequences(state.at_slots(cache, [1, 0]), None,
                                         fresh, None, TABLES, lens)
        out = [first[:, :21]]
        step = jnp.zeros((1, 16), jnp.int32).at[0, :11].set(tokens[0, 21:32])
        last, cache, _ = hybrid.prefill_with_prefix(
            params, cfg, step, jnp.full((1,), 11, jnp.int32), lens[:1],
            state.at_slots(state.take_counts(cache)[0], [1]), None,
            TABLES[:1])
        out.append(last)
        for pos in range(32, 36):
            logits, cache, _ = hybrid.decode_step(
                params, cfg, tokens[:1, pos], jnp.full((1,), pos, jnp.int32),
                state.at_slots(state.take_counts(cache)[0], [1]), None,
                TABLES[:1])
            out.append(logits)
        got[cfg.ssm_impl] = [np.asarray(o) for o in out] + [
            np.asarray(cache.ssm[:, :2])]
    for a, b in zip(got["gathered"], got["kernel_interpret"]):
        np.testing.assert_allclose(b, a, **TOL)


# ---------- the mapper ----------

@functools.lru_cache(maxsize=None)
def _published():
    with open(REPO / "chipbench" / "configs" / "ai21-jamba2-3b.json") as f:
        doc = json.load(f)
    return {k: v for k, v in doc.items()
            if k not in ("source", "reduced", "assumed", "departures",
                         "deployment", "serve", "reference")}


def test_config_from_hf_reads_the_published_keys():
    got = config_from_hf(types.SimpleNamespace(**_published()), "jamba")
    assert got.layer_pattern == "S" * 7 + "A" + "S" * 13 + "A" + "S" * 6
    assert (got.n_layers, got.n_state_layers, got.n_kv_layers) == (28, 26, 2)
    assert (got.d_model, got.d_ff, got.vocab_size) == (2560, 8192, 65536)
    assert (got.n_heads, got.n_kv_heads, got.head_dim) == (20, 1, 128)
    assert (got.ssm_inner, got.ssm_state, got.ssm_dt_rank, got.ssm_conv) == (
        5120, 16, 160, 4)
    assert got.ssm_row == (16, 5120) and got.norm_eps == 1e-6
    assert not got.n_experts and family(got) is hybrid
    # The cell's pools: 26 x (327,680 + 30,720) a slot, 65 slots; 2,048 B a
    # token over the two attention layers (the one KV head kept twice).
    geom = pages.PageGeometry.for_engine(got, 64, 5120)
    assert geom.state.slot_bytes == 9_318_400
    assert geom.state.pool_bytes == 605_696_000
    assert geom.shape == (2, 20481, 16, 2, 128)
    assert geom.n_layers * geom.token_bytes == 2048
    assert geom.pool_bytes == 671_121_408
    assert bind(got, platform="tpu").mcfg.ssm_impl == "kernel"
    assert bind(got, platform="tpu").mcfg.ssm_scan_impl == "kernel"
    assert bind(got, platform="cpu").describe()["state_scan"] == "xla"
    # The file's derived order is optional, and checked where stated.
    flat = {k: v for k, v in _published().items()
            if k != "hybrid_override_pattern"}
    assert config_from_hf(types.SimpleNamespace(**flat), "jamba") == got


@pytest.mark.parametrize("change,match", [
    (dict(num_experts=16), "num_experts=16"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias=True"),
    (dict(sliding_window=4096), "sliding_window=4096"),
    (dict(mamba_conv_bias=False), "mamba_conv_bias=False"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings=False"),
    (dict(hidden_act="gelu"), "hidden_act='gelu'"),
    (dict(attn_layer_offset=4), "is not the order"),
    (dict(num_attention_heads=5), "on one KV head"),
    (dict(hybrid_override_pattern="M" * 28), "is not the order")])
def test_config_from_hf_refuses_what_is_not_built(change, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf(types.SimpleNamespace(**{**_published(), **change}),
                       "jamba")


def test_config_from_hf_refuses_a_model_type_it_has_no_mapping_for():
    """Any file it did not recognise used to become a Llama."""
    llama = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, intermediate_size=128,
                 rms_norm_eps=1e-5, vocab_size=512)
    for model_type in ("brumby", "laguna", None):
        with pytest.raises(ValueError, match="llama, mixtral, qwen3"):
            config_from_hf(types.SimpleNamespace(**llama,
                                                 model_type=model_type))
    assert config_from_hf(types.SimpleNamespace(
        **llama, model_type="llama")).n_layers == 2


# ---------- what the programs are counted as ----------

def test_program_counts_book_the_state_series():
    served = bind(CFG, platform="cpu")
    assert served.program_counts("decode", 4, 8) == [
        ("ssm_tokens", "step", 32),
        ("ssm_state_updates", "gathered", 32 * 6)]
    assert served.program_counts("prefill", 64, 1, real=2) == [
        ("ssm_tokens", "scan", 64), ("ssm_slot_prefills", None, 2),
        ("ssm_scan_tokens", "xla", 64)]
    assert served.program_counts("prefix_prefill", 32, 1, real=1) == [
        ("ssm_tokens", "scan", 32), ("ssm_scan_tokens", "xla", 32)]
    wide = bind(dataclasses.replace(CFG, d_model=128, n_heads=4, ssm_state=8),
                platform="tpu")
    assert ("ssm_scan_tokens", "kernel", 16) in wide.program_counts(
        "prefill", 16, 1, real=1)
    assert ("ssm_state_updates", "kernel", 12) in wide.program_counts(
        "decode", 2, 1)
    # The other state family books no scan series.
    nemotron = bind(configs.get_config("tiny-hybrid"), platform="cpu")
    assert not [c for c in nemotron.program_counts("prefill", 16, 1, real=1)
                if c[0] == "ssm_scan_tokens"]


# ---------- the engine ----------

@pytest.fixture
def served():
    """tiny-jamba in float32 at widths the kernels' rule accepts (inner 256,
    state 8), under a name of its own."""
    name = "tiny-jamba-wide-f32"
    configs._REGISTRY[name] = dataclasses.replace(
        CFG, name=name, d_model=128, n_heads=4, ssm_state=8)
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


def test_an_engine_serves_the_same_tokens_with_both_kernels(served):
    """Greedy streams through prompts in windows of 32 (a continuation
    window among them) and decode chunks, with the plain forms and with both
    kernels through the interpreter; the series say which ran."""
    from test_engine import _by_hand

    by_hand = functools.partial(_by_hand, model=served, max_batch=2,
                                prefill_chunk=32)
    reqs = [_req("A", 29, 50, 9), _req("B", 31, 20, 12), _req("C", 37, 33, 5)]
    plain, why, eng = by_hand(reqs)
    settings = eng.describe()["settings"]
    assert (settings["state_update"], settings["state_scan"]) == (
        "gathered", "xla")
    assert (settings["kv_layers"], settings["state_layers"]) == (2, 6)
    assert settings["state_slot_bytes"] == 6 * (8 * 256 * 4 + 3 * 256 * 4)
    assert settings["state_pool_bytes"] == 3 * settings["state_slot_bytes"]
    assert settings["prefix_caching"] is False
    scanned = _series(eng, "jetstream:ssm_scan_tokens_total")
    assert set(scanned) == {"xla"}
    # Every padded prompt token goes through the scan once, under one form.
    assert scanned["xla"] == _series(
        eng, "jetstream:ssm_tokens_total")["scan"] > 51 + 21 + 34
    assert _series(eng, "jetstream:ssm_slot_prefills_total")[""] == 3
    assert set(_series(eng, "jetstream:ssm_state_updates_total")) == {
        "gathered"}

    kernel, why_k, eng = by_hand(reqs, pallas_interpret=True)
    assert (eng.mcfg.ssm_impl, eng.mcfg.ssm_scan_impl) == (
        "kernel_interpret", "kernel_interpret")
    assert kernel == plain and why_k == why
    assert set(_series(eng, "jetstream:ssm_scan_tokens_total")) == {"kernel"}
    assert set(_series(eng, "jetstream:ssm_state_updates_total")) == {
        "kernel"}
    assert eng.describe()["settings"]["state_scan"] == "kernel_interpret"


def test_other_engines_report_no_scan_keys():
    keys = bind(configs.get_config("tiny-hybrid"), platform="cpu").describe()
    assert "state_scan" not in keys and "state_layers" not in keys
