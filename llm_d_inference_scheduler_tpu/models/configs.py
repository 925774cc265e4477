"""Model architecture configs for the engine half.

The reference router schedules onto external vLLM servers and has no model code;
these configs define the TPU-native engines that replace them (SURVEY.md §7).
Dimensions follow the public Llama-3 architecture card.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    rope_theta: float = 500_000.0
    max_seq_len: int = 8192
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # Paged KV cache geometry (engine half).
    kv_block_size: int = 16
    # Mixture-of-experts (Mixtral-family): n_experts == 0 means dense FFN.
    n_experts: int = 0
    experts_per_token: int = 2
    # MoE FFN form: "dense" (dense-over-experts einsums — the correctness
    # baseline, required under expert-parallel shard_map) | "grouped" (the
    # chosen experts' rows alone, ops/pallas_moe.py) | "grouped_interpret"
    # (same kernel, interpreter — CPU tests). The engine sets it program by
    # program from the shape (pallas_moe.use_grouped); tests force a form.
    moe_impl: str = "dense"
    # Qwen3 family: explicit head_dim decoupled from d_model/n_heads, and
    # per-head RMSNorm on q/k before RoPE.
    head_dim_override: int = 0
    qk_norm: bool = False
    # Latent attention (the DeepSeek-V3 family's MLA; models/mla.py is its
    # block): kv_lora_rank > 0 names the family. A token's cache row is its
    # kv_lora_rank latent values and its qk_rope_head_dim rotated key values,
    # shared by every head; a query head is qk_nope_head_dim + qk_rope_head_dim
    # wide and a value head v_head_dim.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # That family's FFN: first_k_dense leading layers are dense at d_ff; the
    # rest hold n_experts routed experts of width moe_d_ff beside one shared
    # expert of width n_shared_experts * moe_d_ff. The router scores with a
    # sigmoid, selects by score plus a bias, and scales the normalised gates.
    first_k_dense: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0

    @property
    def head_dim(self) -> int:
        if self.kv_lora_rank:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def latent_dim(self) -> int:
        """Values a token keeps a layer in a latent page pool; 0 = K and V."""
        return self.kv_lora_rank + self.qk_rope_head_dim if self.kv_lora_rank else 0

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads


LLAMA3_8B = ModelConfig(
    name="llama3-8b",
    vocab_size=128_256,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
)

LLAMA3_70B = ModelConfig(
    name="llama3-70b",
    vocab_size=128_256,
    d_model=8192,
    n_layers=80,
    n_heads=64,
    n_kv_heads=8,
    d_ff=28672,
)

# Small config used for CI tests, compile checks, and the single-chip dry run.
TINY = ModelConfig(
    name="tiny",
    vocab_size=512,
    d_model=128,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=256,
    max_seq_len=256,
    rope_theta=10_000.0,
)

# Mid-size config for single-chip benchmarking when full 8B weights are not
# materialisable (random-init bench still exercises the same kernels/layout).
LLAMA3_1B = ModelConfig(
    name="llama3-1b",
    vocab_size=128_256,
    d_model=2048,
    n_layers=16,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
)

# Public Llama-3.2-3B architecture card: head_dim 128 (lane-aligned → the
# Pallas paged-attention kernel applies), ~6.4 GB bf16 — fits one v5e chip.
LLAMA3_3B = ModelConfig(
    name="llama3-3b",
    vocab_size=128_256,
    d_model=3072,
    n_layers=28,
    n_heads=24,
    n_kv_heads=8,
    d_ff=8192,
)

# Qwen3 family (public architecture cards): per-head QK-norm, explicit
# head_dim 128 (lane-aligned → Pallas decode kernel), rope 1M, eps 1e-6.
# Qwen3-32B is the model the reference's own benchmark harness targets
# (config/manifests/benchmark/benchmark.yaml:19-47: Qwen/Qwen3-32B).
QWEN3_32B = ModelConfig(
    name="qwen3-32b",
    vocab_size=151_936,
    d_model=5120,
    n_layers=64,
    n_heads=64,
    n_kv_heads=8,
    d_ff=25_600,
    rope_theta=1_000_000.0,
    norm_eps=1e-6,
    head_dim_override=128,
    qk_norm=True,
)

QWEN3_4B = ModelConfig(
    name="qwen3-4b",
    vocab_size=151_936,
    d_model=2560,
    n_layers=36,
    n_heads=32,
    n_kv_heads=8,
    d_ff=9728,
    rope_theta=1_000_000.0,
    norm_eps=1e-6,
    head_dim_override=128,
    qk_norm=True,
)

# Small Qwen3-shaped config for CI tests (QK-norm + head_dim override live).
TINY_QWEN = ModelConfig(
    name="tiny-qwen",
    vocab_size=512,
    d_model=128,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=256,
    max_seq_len=256,
    rope_theta=10_000.0,
    norm_eps=1e-6,
    head_dim_override=48,
    qk_norm=True,
)

# Mixtral-family MoE (public 8x7B architecture card).
MIXTRAL_8X7B = ModelConfig(
    name="mixtral-8x7b",
    vocab_size=32_000,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
    rope_theta=1_000_000.0,
    n_experts=8,
    experts_per_token=2,
)

# Small MoE config for CI tests and the expert-parallel dry run.
TINY_MOE = ModelConfig(
    name="tiny-moe",
    vocab_size=512,
    d_model=128,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=256,
    max_seq_len=256,
    rope_theta=10_000.0,
    n_experts=4,
    experts_per_token=2,
)

# Kimi-VL-A3B-Instruct's language model (public config.json, text_config):
# latent attention, one dense layer, then 26 layers of 64 narrow routed
# experts (6 a token) beside a shared one. The vision tower is not built.
KIMI_VL_A3B = ModelConfig(
    name="kimi-vl-a3b",
    vocab_size=163_840,
    d_model=2048,
    n_layers=27,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    rope_theta=800_000.0,
    max_seq_len=131_072,
    n_experts=64,
    experts_per_token=6,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    first_k_dense=1,
    moe_d_ff=1408,
    n_shared_experts=2,
    routed_scaling_factor=2.446,
)

# The same family at small widths aligned to nothing (CI tests): 24 + 8 = 32
# values a cache row, 1 dense layer + 2 expert layers, 8 experts, 3 a token.
TINY_MLA = ModelConfig(
    name="tiny-mla",
    vocab_size=512,
    d_model=96,
    n_layers=3,
    n_heads=3,
    n_kv_heads=3,
    d_ff=160,
    max_seq_len=256,
    rope_theta=10_000.0,
    n_experts=8,
    experts_per_token=3,
    kv_lora_rank=24,
    qk_nope_head_dim=20,
    qk_rope_head_dim=8,
    v_head_dim=12,
    first_k_dense=1,
    moe_d_ff=40,
    n_shared_experts=2,
    routed_scaling_factor=2.446,
)

_REGISTRY = {c.name: c for c in (LLAMA3_8B, LLAMA3_70B, LLAMA3_1B, LLAMA3_3B,
                                 TINY, MIXTRAL_8X7B, TINY_MOE,
                                 QWEN3_32B, QWEN3_4B, TINY_QWEN,
                                 KIMI_VL_A3B, TINY_MLA)}


def get_config(name: str) -> ModelConfig:
    try:
        return _REGISTRY[name]
    except KeyError:
        pass
    # A converted-checkpoint directory (models/convert_hf.py writes
    # model_config.json next to the Orbax weights) is a valid model name:
    # serve real HF checkpoints without registering them here.
    import json
    import os

    cand = os.path.join(name, "model_config.json")
    if os.path.isfile(cand):
        with open(cand) as f:
            fields = json.load(f)
        known = set(ModelConfig.__dataclass_fields__)
        return ModelConfig(**{k: v for k, v in fields.items() if k in known})
    raise ValueError(f"unknown model config {name!r}; have {sorted(_REGISTRY)}")
