"""HF checkpoint conversion: logits parity vs transformers + tokenizer registry.

The strongest correctness evidence the engine half can have: our stacked-layer
JAX forward must reproduce a real HuggingFace Llama/Mixtral's logits from the
converted weights (RoPE convention, GQA, SwiGLU, RMSNorm eps all verified at
once). Reference behavior analogue: the reference router serves whatever vLLM
loaded from the same HF checkpoints (SURVEY.md preamble).
"""

from __future__ import annotations

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
torch = pytest.importorskip("torch")

from llm_d_inference_scheduler_tpu.models import llama
from llm_d_inference_scheduler_tpu.models.convert_hf import (
    config_from_hf,
    convert_state_dict,
)


def _parity(hf_model, hf_cfg, tokens_np, atol=2e-4):
    cfg = config_from_hf(hf_cfg)
    params = convert_state_dict(hf_model.state_dict(), cfg, dtype="float32")

    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens_np)).logits.float().numpy()

    ours, _ = llama.forward(params, cfg, jnp.asarray(tokens_np))
    ours = np.asarray(ours)

    assert ours.shape == ref.shape
    # Normalize scale: compare log-softmax (absolute logit offsets are
    # irrelevant to sampling and can differ by accumulation order).
    def lsm(x):
        x = x - x.max(axis=-1, keepdims=True)
        return x - np.log(np.exp(x).sum(axis=-1, keepdims=True))

    np.testing.assert_allclose(lsm(ours), lsm(ref), atol=atol, rtol=0)


def test_llama_logits_parity():
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(0)
    hf_cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        rms_norm_eps=1e-5, rope_theta=10_000.0, max_position_embeddings=128,
        tie_word_embeddings=False, attention_bias=False, mlp_bias=False,
    )
    model = LlamaForCausalLM(hf_cfg).eval().float()
    tokens = np.random.default_rng(0).integers(0, 256, size=(2, 9), dtype=np.int64)
    _parity(model, hf_cfg, tokens)


def test_llama_tied_embeddings():
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(1)
    hf_cfg = LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        rms_norm_eps=1e-6, rope_theta=10_000.0, tie_word_embeddings=True,
        attention_bias=False, mlp_bias=False,
    )
    model = LlamaForCausalLM(hf_cfg).eval().float()
    sd = {k: v for k, v in model.state_dict().items() if k != "lm_head.weight"}
    cfg = config_from_hf(hf_cfg)
    params = convert_state_dict(sd, cfg, dtype="float32")
    # Tied head == embed transpose.
    np.testing.assert_allclose(np.asarray(params["lm_head"]),
                               np.asarray(params["embed"]).T)
    tokens = np.random.default_rng(1).integers(0, 128, size=(1, 5), dtype=np.int64)
    _parity(model, hf_cfg, tokens)


def test_mixtral_logits_parity():
    from transformers import MixtralConfig, MixtralForCausalLM

    torch.manual_seed(2)
    hf_cfg = MixtralConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        rms_norm_eps=1e-5, rope_theta=10_000.0, tie_word_embeddings=False,
    )
    model = MixtralForCausalLM(hf_cfg).eval().float()
    cfg = config_from_hf(hf_cfg)
    assert cfg.n_experts == 4 and cfg.experts_per_token == 2
    tokens = np.random.default_rng(2).integers(0, 128, size=(2, 7), dtype=np.int64)
    _parity(model, hf_cfg, tokens, atol=5e-4)


def test_convert_cli_roundtrip(tmp_path):
    """CLI writes an Orbax checkpoint the engine's loader restores."""
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(3)
    hf_cfg = LlamaConfig(
        vocab_size=64, hidden_size=16, intermediate_size=32,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=1,
        tie_word_embeddings=False, attention_bias=False, mlp_bias=False,
    )
    src = tmp_path / "hf"
    LlamaForCausalLM(hf_cfg).eval().save_pretrained(src, safe_serialization=True)

    from llm_d_inference_scheduler_tpu.models.convert_hf import main

    out = tmp_path / "orbax"
    main([str(src), str(out), "--dtype", "float32"])

    import json

    mc = json.loads((out / "model_config.json").read_text())
    assert mc["d_model"] == 16 and mc["n_layers"] == 1

    from llm_d_inference_scheduler_tpu.engine.checkpoint import load_params
    from llm_d_inference_scheduler_tpu.models.configs import ModelConfig

    cfg = ModelConfig(**{k: v for k, v in mc.items()})
    params = load_params(str(out), cfg)
    assert params["embed"].shape == (64, 16)


def test_engine_serves_converted_checkpoint(tmp_path):
    """Greedy decode through the full engine (paged KV, chunked decode)
    matches HF generate on a converted checkpoint — token-exact."""
    import asyncio

    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(4)
    hf_cfg = LlamaConfig(
        vocab_size=300, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        tie_word_embeddings=False, attention_bias=False, mlp_bias=False,
        rope_theta=10_000.0,
    )
    model = LlamaForCausalLM(hf_cfg).eval().float()
    src = tmp_path / "hf"
    model.save_pretrained(src, safe_serialization=True)

    from llm_d_inference_scheduler_tpu.models.convert_hf import main

    out = tmp_path / "orbax"
    main([str(src), str(out), "--dtype", "float32"])

    prompt = [5, 17, 42, 99, 7]
    n_gen = 6
    with torch.no_grad():
        ref = model.generate(
            torch.tensor([prompt]), max_new_tokens=n_gen, do_sample=False,
            pad_token_id=0)[0, len(prompt):].tolist()

    from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    cfg = EngineConfig(model=str(out), backend="tpu", max_batch=2,
                       max_model_len=64, decode_chunk=4)
    assert cfg.checkpoint_path == ""  # discovered by the engine, not preset

    async def run():
        eng = TpuEngine(cfg)
        assert eng.cfg.checkpoint_path == str(out)
        await eng.start()
        try:
            req = EngineRequest(request_id="hf-e2e", prompt_token_ids=prompt,
                                max_tokens=n_gen, temperature=0.0,
                                ignore_eos=True)
            outq = eng.submit(req)
            got = []
            while True:
                ev = await outq.get()
                if ev.token_id is not None:
                    got.append(ev.token_id)
                if ev.finish_reason is not None:
                    break
            return got
        finally:
            await eng.stop()

    got = asyncio.run(run())
    assert got == ref


def test_hf_tokenizer_registry(tmp_path):
    """A saved HF fast tokenizer loads via get_tokenizer and round-trips."""
    from tokenizers import Tokenizer, models, pre_tokenizers, decoders
    from transformers import PreTrainedTokenizerFast

    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    from tokenizers.trainers import BpeTrainer

    trainer = BpeTrainer(
        vocab_size=300, special_tokens=["<s>", "</s>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet())
    tok.train_from_iterator(
        ["hello world", "hello there", "the quick brown fox"], trainer)
    fast = PreTrainedTokenizerFast(
        tokenizer_object=tok, bos_token="<s>", eos_token="</s>")
    d = tmp_path / "tok"
    fast.save_pretrained(d)

    from llm_d_inference_scheduler_tpu.engine.tokenizer import get_tokenizer

    t = get_tokenizer(f"hf:{d}", vocab_size=1024)
    assert t.eos_id is not None
    ids = t.encode("hello world", add_bos=True)
    assert ids[0] == t.bos_id
    assert t.decode(ids) == "hello world"

    # Vocab larger than the model's is rejected.
    with pytest.raises(ValueError):
        get_tokenizer(f"hf:{d}", vocab_size=10)


def test_qwen3_logits_parity():
    """Qwen3 family: per-head QK-norm + explicit head_dim (the reference's
    own benchmark harness targets Qwen/Qwen3-32B —
    config/manifests/benchmark/benchmark.yaml:19-47)."""
    from transformers import Qwen3Config, Qwen3ForCausalLM

    torch.manual_seed(5)
    hf_cfg = Qwen3Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=24,  # decoupled from hidden/heads = 16
        rms_norm_eps=1e-6, rope_theta=10_000.0, max_position_embeddings=128,
        tie_word_embeddings=False, attention_bias=False,
    )
    model = Qwen3ForCausalLM(hf_cfg).eval().float()
    cfg = config_from_hf(hf_cfg)
    assert cfg.qk_norm and cfg.head_dim == 24
    tokens = np.random.default_rng(2).integers(0, 256, size=(2, 7), dtype=np.int64)
    _parity(model, hf_cfg, tokens)


def test_qwen3_engine_serves_token_exact(tmp_path):
    """Greedy decode through the full engine (paged KV, QK-norm in the
    decode-step scan) matches HF generate on a converted Qwen3 checkpoint."""
    import asyncio

    from transformers import Qwen3Config, Qwen3ForCausalLM

    torch.manual_seed(6)
    hf_cfg = Qwen3Config(
        vocab_size=300, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, rms_norm_eps=1e-6, rope_theta=10_000.0,
        tie_word_embeddings=True,
    )
    model = Qwen3ForCausalLM(hf_cfg).eval().float()
    src = tmp_path / "hf"
    model.save_pretrained(src, safe_serialization=True)

    from llm_d_inference_scheduler_tpu.models.convert_hf import main

    out = tmp_path / "orbax"
    main([str(src), str(out), "--dtype", "float32"])

    prompt = [5, 17, 42, 99, 7, 211]
    n_gen = 6
    with torch.no_grad():
        ref = model.generate(
            torch.tensor([prompt]), max_new_tokens=n_gen, do_sample=False,
            pad_token_id=0)[0, len(prompt):].tolist()

    from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    cfg = EngineConfig(model=str(out), backend="tpu", max_batch=2,
                       max_model_len=64, decode_chunk=4)

    async def run():
        eng = TpuEngine(cfg)
        await eng.start()
        try:
            outq = eng.submit(EngineRequest(
                request_id="qwen-e2e", prompt_token_ids=prompt,
                max_tokens=n_gen, temperature=0.0, ignore_eos=True))
            got = []
            while True:
                ev = await outq.get()
                if ev.token_id is not None:
                    got.append(ev.token_id)
                if ev.finish_reason is not None:
                    break
            return got
        finally:
            await eng.stop()

    assert asyncio.run(run()) == ref


# ---------- model_type dots3_note: two kinds of layer, a gate a head ----------

def _dots3_published(**changes):
    import json
    import pathlib
    import types

    path = (pathlib.Path(__file__).resolve().parent.parent / "chipbench"
            / "configs" / "dots3-note-prev-cut.json")
    doc = json.loads(path.read_text())
    doc = {k: v for k, v in doc.items()
           if k not in ("source", "reduced", "assumed", "departures",
                        "deployment", "serve", "reference")}
    doc.update(changes)
    return types.SimpleNamespace(**{k: v for k, v in doc.items()
                                    if v != "absent"})


def test_config_from_hf_maps_the_two_kinds_of_layer():
    from llm_d_inference_scheduler_tpu.models import configs

    cfg = config_from_hf(_dots3_published(), name="cut")
    assert cfg == configs.ModelConfig(
        name="cut", vocab_size=19008, d_model=5120, n_layers=5, n_heads=128,
        n_kv_heads=128, d_ff=13824, rope_theta=8e7, max_seq_len=524288,
        norm_eps=1e-5, n_experts=256, experts_per_token=8, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        first_k_dense=1, moe_d_ff=1536, n_shared_experts=1,
        routed_scaling_factor=1.0, experts_held=32, experts_first=0,
        q_lora_rank=1024, mla_scale_q_lora=True, mla_scale_kv_lora=True,
        index_topk=2048, index_n_heads=64, index_head_dim=128,
        attn_gate=True, layer_pattern="**WWW",
        window_attn=configs.AttnKind(
            n_heads=64, q_lora_rank=1024, kv_lora_rank=1024,
            qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=128,
            rope_theta=5e4, window=513, gate=True))
    # No layer of the second kind: the block the tree had, with its gate.
    full = config_from_hf(_dots3_published(
        layer_types=["full_attention"] * 5,
        **{k: "absent" for k in vars(_dots3_published())
           if k.startswith("swa_") or k == "sliding_window_size"}), name="f")
    assert full.window_attn is None and not full.layer_pattern
    assert full.attn_gate and full.mla_scale_kv_lora
    # The configurations the mapping served before read as they did.
    plain = config_from_hf(_dots3_published(
        layer_types="absent", attention_gate_type="absent",
        apply_mla_qkv_lora_rescale="absent",
        **{k: "absent" for k in vars(_dots3_published())
           if k.startswith("swa_") or k == "sliding_window_size"}), name="p")
    assert not (plain.attn_gate or plain.mla_scale_q_lora
                or plain.layer_pattern or plain.window_attn)


@pytest.mark.parametrize("change, says", [
    (dict(layer_types=["full_attention", "full_attention",
                       "sliding_attention", "chunked_attention",
                       "sliding_attention"]), "chunked_attention"),
    (dict(layer_types=["full_attention"] * 4), "layer_types"),
    (dict(layer_types=["sliding_attention"] + ["full_attention"] * 4),
     "leading dense layers"),
    (dict(attention_gate_type="elementwise"), "attention_gate_type"),
    (dict(swa_attention_gate_type="elementwise"), "swa_attention_gate_type"),
    (dict(swa_index_topk=512), "swa_index_topk"),
    (dict(swa_rope_scaling={"type": "yarn"}), "swa_rope_scaling"),
    (dict(swa_kv_lora_rank="absent"), "swa_kv_lora_rank"),
    (dict(sliding_window_size="absent"), "sliding_window_size"),
    (dict(swa_num_key_value_heads=8), "swa_num_key_value_heads"),
    (dict(layer_types="absent"), "without layer_types"),
    (dict(layer_types=["full_attention"] * 5), "no sliding_attention"),
])
def test_config_from_hf_refuses_a_layer_kind_gate_or_swa_key_it_does_not_compute(
        change, says):
    """Not served as something else: the parent read this model's file as
    five full layers."""
    with pytest.raises(ValueError, match=says):
        config_from_hf(_dots3_published(**change), name="cut")
