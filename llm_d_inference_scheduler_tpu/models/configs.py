"""Model architecture configs for the engine half.

The reference router schedules onto external vLLM servers and has no model code;
these configs define the TPU-native engines that replace them (SURVEY.md §7).
Dimensions follow the public Llama-3 architecture card.
"""

from __future__ import annotations

import dataclasses
import functools


@dataclasses.dataclass(frozen=True)
class AttnKind:
    """What a second kind of latent-attention layer has of its own where a
    model mixes two (``ModelConfig.window_attn``): the widths a layer of the
    first kind reads off the configuration's flat fields of the same names,
    and the window. :meth:`ModelConfig.of_window` is the configuration as
    such a layer's code reads it, so no block has a second copy of anything."""

    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    # Tokens a query attends to, its own position among them: a query at t
    # sees s with 0 <= t - s < window.
    window: int
    # A sigmoid gate a head on the attention's output (``attn_gate``).
    gate: bool = False


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
    # chosen experts' rows alone, ops/pallas_moe.py) | "chosen" (a program of
    # few rows: dense over the held experts some row chose) | "grouped_interpret"
    # / "chosen_interpret" (same kernels, interpreter — CPU tests). models.bind
    # hands the engine all three, program by program from the shape
    # (pallas_moe.use_grouped, use_chosen); tests force a form.
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
    # A layer pattern (the nemotron_h family; models/hybrid.py is its block)
    # names the family: one character a layer, each layer ONE mixer under its
    # own residual -- "M" a Mamba-2 state-space layer, "E" a LatentMoE expert
    # layer, "*" attention (GQA, no rotary embedding). n_layers is its length.
    # The jamba family's layers are two more letters of the same block: "S" a
    # Mamba-1 state-space mixer and "A" attention (the same, no rotary
    # embedding), EACH followed by a dense SwiGLU of d_ff under a residual of
    # its own. The lfm2_moe family's are two more: "C" a gated short
    # convolution (``ssm_conv`` wide over d_model channels; it keeps the
    # convolution's tail and NO recurrent state) and "Q" attention with an
    # RMSNorm a head on q and k and rotary embedding (``qk_norm``,
    # ``rope_theta``), each followed by an FFN under a residual of its own:
    # the dense SwiGLU of d_ff in the first ``first_k_dense`` layers, else
    # n_experts routed SwiGLU experts of moe_d_ff (models/routing.py chooses).
    layer_pattern: str = ""
    # "M": ssm_heads heads of ssm_head_dim, a state of ssm_state values a
    # head-channel, B and C shared by the heads of one of ssm_groups groups,
    # a causal depth-wise convolution ssm_conv wide ahead of them; a prefill
    # computes in chunks of ssm_chunk positions (the matrix form).
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # The step sizes dt_bias is drawn for (random weights only).
    ssm_dt_range: tuple[float, float, float] = (0.001, 0.1, 1e-4)  # min, max, floor
    # How a decode step fetches its slots' rows of the state pool (kvcache/
    # state.recur): "gathered" (by slot, in XLA: the CPU's way and the plain
    # form) | "kernel" (in place in the pool, ops/pallas_ssm.py) |
    # "kernel_interpret" (the kernel through the interpreter: CPU tests).
    # models.bind sets it from what the engine is (pallas_ssm.use_kernel);
    # tests force one.
    ssm_impl: str = "gathered"
    # "S", Mamba-1 (ssm_dt_rank > 0 names it): ssm_expand x d_model channels,
    # each with a state of ssm_state values and a decay of its own a state
    # value (no heads, no groups, no matrix form); the convolution (ssm_conv
    # wide, with a bias) runs over x alone; the step size comes through a
    # projection of rank ssm_dt_rank with a bias; RMSNorms on dt, B and C.
    ssm_dt_rank: int = 0
    ssm_expand: int = 0
    # How such a model's prompt windows run the recurrence (models/hybrid.py):
    # "xla" (``lax.scan`` over positions: the CPU's way and the plain form) |
    # "kernel" (ops/pallas_ssm.selective_scan: the state tile resident in VMEM
    # over the window's rows) | "kernel_interpret". models.bind sets it.
    ssm_scan_impl: str = "xla"
    # "E": routed experts of width moe_d_ff between a projection down to
    # moe_latent_dim and one back up, not gated (relu squared), beside a
    # shared expert of width shared_d_ff on the model's own width.
    moe_latent_dim: int = 0
    shared_d_ff: int = 0
    # The experts this chip holds of the n_experts the router scores:
    # [experts_first, experts_first + experts_held); 0 held = all of them.
    # What the others would have added is left out (expert parallelism
    # without its exchange: models/hybrid.py).
    experts_held: int = 0
    experts_first: int = 0
    # LongCat-Flash's layer (models/mla.py serves it too; attn_sublayers == 2
    # names it): two latent-attention sublayers, each with a dense SwiGLU of
    # d_ff behind it, and ONE expert layer that reads the first sublayer's
    # FFN input and is added after the second sublayer's FFN (the shortcut).
    # A layer keeps two cache layers. Its query is low-rank (q_lora_rank > 0:
    # W_qa, an RMSNorm, W_qb) and the query and the normed latent are scaled
    # by sqrt(d_model / their rank) where the two flags say so.
    attn_sublayers: int = 1
    q_lora_rank: int = 0
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # That family's router (models/routing.py): "softmax" scores over ALL its
    # outputs, the chosen scores' gates not normalised, where the other
    # families' "sigmoid" scores are normalised over the chosen. Its outputs
    # are the n_experts experts and then n_zero_experts experts that compute
    # nothing: a choice of one returns the token itself times its gate.
    router_scoring: str = "sigmoid"
    n_zero_experts: int = 0
    # DeepSeek-V3's group-limited selection (models/routing.py): the router's
    # outputs lie in n_group equal groups, a group scores the sum of its two
    # largest biased scores, and a token chooses inside its topk_group best
    # groups. n_group 1 is no grouping.
    n_group: int = 1
    topk_group: int = 1
    # YaRN (ops/rope.py): (factor, original_max_position_embeddings,
    # beta_fast, beta_slow, mscale_all_dim); empty = plain rotary embedding.
    rope_yarn: tuple[float, ...] = ()
    # DeepSeek-V3.2's learned sparse attention (models/mla.py; index_topk > 0
    # names it, and nothing else says that a block selects): an indexer of
    # index_n_heads heads of index_head_dim scores every cached token, and a
    # query attends to the index_topk rows that score highest. A token keeps
    # its indexer key (index_head_dim values) in a second page pool beside
    # its latent row (kvcache/pages.py).
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    # The form of such a block's indexer: "xla" (ops/sparse_attention.py:
    # the CPU's way and the plain form) | "kernel" (ops/pallas_dsa.py: the
    # indexer's scores over a window's keys and over a lane's key pages, and
    # decode attention with the selection as a mask) | "kernel_interpret"
    # (CPU tests). models.bind sets it from what the engine is.
    index_impl: str = "xla"
    # The form of every latent block's expanded attention (a prefill, a
    # window that continues a cached prefix; models/mla.py), selecting or
    # not: "xla" (the scores whole, [heads, queries, rows] in f32: the CPU's
    # way and the plain form) | "kernel" (ops/pallas_dsa.py: a tile of
    # queries against a tile of rows, the softmax running in VMEM) |
    # "kernel_interpret". models.bind sets it.
    expanded_impl: str = "xla"
    # A gate a head on the attention's output, ahead of W_o: ``o_j *=
    # sigmoid(h W_g)_j`` from the layer's normed input (models/mla.py).
    attn_gate: bool = False
    # Window and full latent attention in one model (models/mla.py): with
    # kv_lora_rank > 0 the layer pattern says which kind a layer is, "*" the
    # kind the flat fields above describe (the whole context, or the rows an
    # indexer selects) and "W" a layer of ``window_attn``'s widths that
    # attends to the last ``window_attn.window`` tokens through a page pool of
    # its own (kvcache/pages.py).
    window_attn: AttnKind | None = None
    # The form of the window layers' programs: "xla" | "kernel"
    # (ops/pallas_latent_attention.py's walk over the window's pages,
    # ops/pallas_dsa.py's tiles under the band) | "kernel_interpret".
    # models.bind sets it.
    swa_impl: str = "xla"
    # Window and full attention in one K/V model (models/llama.py; kv_window
    # > 0 names it, SmallThinker's language model is one): the layer pattern
    # says which kind a layer is, "*" the whole context through the page
    # pools every K/V model has and "W" the last ``kv_window`` tokens, the
    # query's own among them, through K/V pools of their own
    # (kvcache/pages.py). ``full_nope``: the "*" layers carry no position
    # code (q and k are not rotated); the "W" layers rotate as ever.
    # ``swa_impl`` is the form of the "W" layers' decode walk here too.
    kv_window: int = 0
    full_nope: bool = False
    # What an expert layer's router reads in models/llama.py: "ffn", the
    # FFN's normed input (Mixtral) | "attn", the normed input of the SAME
    # layer's attention, so that the experts are known before attention ends.
    router_input: str = "ffn"
    # The experts' activation there: "swiglu" silu(x w1) * (x w3) | "reglu"
    # relu(x w1) * (x w3).
    expert_act: str = "swiglu"

    @property
    def mixer_pattern(self) -> bool:
        """Whether the layer pattern names one mixer a layer (models/
        hybrid.py), and not which of two kinds of attention a layer is."""
        return bool(self.layer_pattern) and not (self.kv_lora_rank
                                                 or self.kv_window)

    @property
    def window(self) -> int:
        """Tokens a window layer's query sees, its own among them (0: no
        layer attends to a window)."""
        return self.window_attn.window if self.window_attn else self.kv_window

    @property
    def conv_mixers(self) -> bool:
        """Whether the mixers that keep a slot row are gated short
        convolutions ("C"): a tail and no recurrent state."""
        return self.mixer_pattern and "C" in self.layer_pattern

    @property
    def n_state_layers(self) -> int:
        """Layers that keep a row of the state pool a sequence (a recurrent
        state and a convolution's tail, or the tail alone; 0: pages alone)."""
        if not self.mixer_pattern:
            return 0
        return sum(self.layer_pattern.count(c) for c in "MSC")

    @property
    def n_recurrent_layers(self) -> int:
        """Those of them whose row holds a recurrent state (0: the rows, if
        any, are tails alone)."""
        return 0 if self.conv_mixers else self.n_state_layers

    @property
    def n_kv_layers(self) -> int:
        """Layers that keep pages of keys and values (cache layers)."""
        if self.mixer_pattern:
            return sum(self.layer_pattern.count(c) for c in "*AQ")
        return (self.layer_pattern.count("*") if self.layer_pattern
                else self.n_layers * self.attn_sublayers)

    @property
    def kv_heads_kept(self) -> int:
        """KV heads a page holds. models/hybrid.py keeps a model's ONE KV
        head twice: a bf16 page's minor tile is two rows of 128 lanes, so a
        pool ``[.., block, 1, 128]`` lies padded to two heads in HBM whatever
        is written there, and the paged decode kernel cannot slice a padded
        dim; kept twice, the bytes are the same and the walk is the
        two-KV-head program (each copy serves half the query heads)."""
        if self.mixer_pattern and self.n_kv_heads == 1:
            return 2
        return self.n_kv_heads

    @property
    def kv_heads_a_row(self) -> int:
        """Adjacent KV heads a page keeps side by side as ONE row of 128
        lanes (1: a head a row, every model with heads of 128 or more).
        models/hybrid.py keeps heads narrower than a lane so (two heads of
        64: ``[.., block, Hkv / 2, 128]``): a bf16 pool whose minor dim is 64
        lies padded to 128 lanes in HBM whatever is written there, twice the
        model's bytes, and the paged decode kernel's page copies want whole
        lanes. The bytes are the model's and the walk is the program of half
        as many heads twice as wide (kvcache/pages.decode_attention)."""
        per = 128 // self.head_dim if 0 < self.head_dim < 128 else 1
        if (self.mixer_pattern and per > 1 and 128 % self.head_dim == 0
                and self.kv_heads_kept % per == 0):
            return per
        return 1

    @property
    def n_window_layers(self) -> int:
        """Cache layers that keep a window of the context alone (0: none)."""
        return self.layer_pattern.count("W") if self.window else 0

    def of_window(self) -> "ModelConfig":
        """The configuration as a window layer's attention reads it: the
        flat attention fields at ``window_attn``'s values, and no indexer."""
        return _of_window(self)

    @property
    def n_expert_layers(self) -> int:
        """Layers with a router (0: a dense model)."""
        if not self.n_experts:
            return 0
        if self.mixer_pattern and not self.conv_mixers:
            return self.layer_pattern.count("E")
        return self.n_layers - self.first_k_dense

    @property
    def router_width(self) -> int:
        """Outputs of the router: the experts, then the zero-compute ones."""
        return self.n_experts + self.n_zero_experts

    @property
    def tallies_choices(self) -> bool:
        """Whether the step programs count their router's choices on the
        device (held here / zero-compute; kvcache/state.Cache carries the
        counts out): models/hybrid.py's always do, models/mla.py's where not
        every choice is an expert held here, and where the block selects rows
        (its two pools ride in that value anyway); models/llama.py's never:
        every choice there is an expert held here."""
        return bool((self.layer_pattern and not self.kv_window)
                    or self.experts_held or self.n_zero_experts
                    or self.index_topk)

    @property
    def held_experts(self) -> tuple[int, int]:
        """(first, count) of the experts held here."""
        return self.experts_first, self.experts_held or self.n_experts

    @property
    def ssm_inner(self) -> int:
        if self.ssm_dt_rank:
            return self.ssm_expand * self.d_model
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_row(self) -> tuple[int, ...]:
        """One sequence's recurrent state in one layer (kvcache/state.py's
        two layouts)."""
        if self.ssm_dt_rank:
            return (self.ssm_state, self.ssm_inner)
        if self.conv_mixers:
            return ()       # a tail and nothing else
        return (self.ssm_heads, self.ssm_head_dim, self.ssm_state)

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the convolution runs over: x, then B and C a group
        (Mamba-1: x alone; a gated short convolution: the model's width)."""
        if self.conv_mixers:
            return self.d_model
        if self.ssm_dt_rank:
            return self.ssm_inner
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

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
    def index_dim(self) -> int:
        """Values a token keeps a layer in the indexer's key pool; 0 = none."""
        return self.index_head_dim if self.index_topk else 0

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads


@functools.lru_cache(maxsize=None)
def _of_window(cfg: ModelConfig) -> ModelConfig:
    w = cfg.window_attn
    return dataclasses.replace(
        cfg, n_heads=w.n_heads, n_kv_heads=w.n_heads,
        q_lora_rank=w.q_lora_rank, kv_lora_rank=w.kv_lora_rank,
        qk_nope_head_dim=w.qk_nope_head_dim,
        qk_rope_head_dim=w.qk_rope_head_dim, v_head_dim=w.v_head_dim,
        rope_theta=w.rope_theta, rope_yarn=(), attn_gate=w.gate,
        index_topk=0)


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

# LongCat-Flash's double layer at small widths aligned to nothing (CI tests):
# 2 layers = 4 cache layers, a query rank of 20, a router of 16 experts + 8
# zero-compute ones, 5 a token; every expert held (tests cut a share).
TINY_LONGCAT = ModelConfig(
    name="tiny-longcat",
    vocab_size=512,
    d_model=96,
    n_layers=2,
    n_heads=3,
    n_kv_heads=3,
    d_ff=160,
    max_seq_len=256,
    rope_theta=10_000.0,
    n_experts=16,
    experts_per_token=5,
    kv_lora_rank=24,
    qk_nope_head_dim=20,
    qk_rope_head_dim=8,
    v_head_dim=12,
    moe_d_ff=40,
    routed_scaling_factor=6.0,
    attn_sublayers=2,
    q_lora_rank=20,
    mla_scale_q_lora=True,
    mla_scale_kv_lora=True,
    router_scoring="softmax",
    n_zero_experts=8,
)

# DeepSeek-V3.2-Exp's block at small widths aligned to nothing (CI tests): a
# low-rank query, YaRN, a router of 16 experts in 4 groups of which 2 are
# kept, and an indexer of 4 heads of 16 whose queries attend to 24 rows.
TINY_DSA = dataclasses.replace(
    TINY_MLA, name="tiny-dsa", n_experts=16, experts_per_token=3,
    q_lora_rank=20, n_group=4, topk_group=2,
    rope_yarn=(40.0, 32, 32.0, 1.0, 1.0), index_topk=24, index_n_heads=4,
    index_head_dim=16)

# Window and full latent attention mixed, at small widths aligned to nothing
# (CI tests): a dense layer and an expert layer that select 6 rows, then three
# window layers of other widths (2 heads, a rank of 40, 12 + 8 a query head)
# that see 7 tokens; a gate a head on both kinds, both low-rank rescales, a
# page of 4. Every expert held (tests cut a share).
TINY_SWA = dataclasses.replace(
    TINY_MLA, name="tiny-swa", n_layers=5, n_experts=16, experts_per_token=3,
    q_lora_rank=20, mla_scale_q_lora=True, mla_scale_kv_lora=True,
    index_topk=6, index_n_heads=4, index_head_dim=16, kv_block_size=4,
    attn_gate=True, layer_pattern="**WWW",
    window_attn=AttnKind(n_heads=2, q_lora_rank=28, kv_lora_rank=40,
                         qk_nope_head_dim=12, qk_rope_head_dim=8,
                         v_head_dim=16, rope_theta=500.0, window=7,
                         gate=True))

# Window and full attention mixed in a K/V model, SmallThinker's block at small
# widths (CI tests): two periods of a full layer with no position code and
# three window layers that rotate and see 11 tokens, 7 query heads a KV head,
# a page of 4, a router of 8 experts that reads the attention's input, ReGLU.
TINY_SWA_KV = ModelConfig(
    name="tiny-swa-kv",
    vocab_size=512,
    d_model=64,
    n_layers=8,
    n_heads=14,
    n_kv_heads=2,
    d_ff=48,
    max_seq_len=256,
    rope_theta=10_000.0,
    norm_eps=1e-6,
    kv_block_size=4,
    head_dim_override=16,
    n_experts=8,
    experts_per_token=3,
    layer_pattern="*WWW*WWW",
    kv_window=11,
    full_nope=True,
    router_input="attn",
    expert_act="reglu",
)

# NVIDIA-Nemotron-3-Super-120B-A12B's language model (public config.json,
# model_type nemotron_h): 88 layers of one mixer each -- 40 Mamba-2, 40
# LatentMoE (512 experts of 2688 in a 1024-wide latent space, 22 a token,
# beside a shared expert of 5376), 8 attention (32 query / 2 KV heads of
# 128, no rotary embedding). Its draft head (multi-token prediction) is not
# built.
_NEMOTRON_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
NEMOTRON_3_SUPER = ModelConfig(
    name="nemotron-3-super",
    vocab_size=131_072,
    d_model=4096,
    n_layers=88,
    n_heads=32,
    n_kv_heads=2,
    d_ff=2688,
    rope_theta=10_000.0,
    max_seq_len=262_144,
    head_dim_override=128,
    n_experts=512,
    experts_per_token=22,
    moe_d_ff=2688,
    n_shared_experts=1,
    routed_scaling_factor=5.0,
    layer_pattern=_NEMOTRON_PATTERN,
    ssm_heads=128,
    ssm_head_dim=64,
    ssm_state=128,
    ssm_groups=8,
    moe_latent_dim=1024,
    shared_d_ff=5376,
)

# One period of it (pattern positions 26-36) with a quarter of each layer's
# experts: what one of four chips that share each layer's experts holds of
# one of eight stages (chipbench/configs/nemotron-3-super-cut.json).
NEMOTRON_3_SUPER_CUT = dataclasses.replace(
    NEMOTRON_3_SUPER, name="nemotron-3-super-cut", n_layers=11,
    layer_pattern="EMEMEMEMEM*", experts_held=128)

# The same family at small widths (CI tests): every kind of layer, two
# state-space layers, 16 experts of which 4 a token, all held.
TINY_HYBRID = ModelConfig(
    name="tiny-hybrid",
    vocab_size=512,
    d_model=64,
    n_layers=5,
    n_heads=4,
    n_kv_heads=2,
    d_ff=48,
    max_seq_len=256,
    head_dim_override=16,
    n_experts=16,
    experts_per_token=4,
    moe_d_ff=48,
    n_shared_experts=1,
    routed_scaling_factor=5.0,
    layer_pattern="MEM*E",
    ssm_heads=8,
    ssm_head_dim=16,
    ssm_state=16,
    ssm_groups=2,
    ssm_chunk=16,
    moe_latent_dim=32,
    shared_d_ff=80,
)

# The jamba family at small widths (CI tests): two periods of four layers
# with the attention layer second, Mamba-1 mixers of 2 x 48 = 96 channels, a
# state of 6 and a step-size rank of 5 (aligned to nothing), 6 query heads on
# ONE KV head, a dense SwiGLU behind every mixer.
TINY_JAMBA = ModelConfig(
    name="tiny-jamba",
    vocab_size=512,
    d_model=48,
    n_layers=8,
    n_heads=6,
    n_kv_heads=1,
    d_ff=72,
    max_seq_len=256,
    norm_eps=1e-6,
    layer_pattern="SASSSASS",
    ssm_state=6,
    ssm_dt_rank=5,
    ssm_expand=2,
)

# The lfm2_moe family at small widths (CI tests): two periods of two gated
# short convolutions and an attention layer, 8 query heads on 4 KV heads of
# 64 (two page rows of 128, two heads each: ``kv_heads_a_row``), a
# convolution 3 wide, the first layer's FFN dense and the others' 8 routed
# experts of 128 (a width the grouped kernel tiles), 3 a token.
TINY_LFM2 = ModelConfig(
    name="tiny-lfm2",
    vocab_size=512,
    d_model=128,
    n_layers=6,
    n_heads=8,
    n_kv_heads=4,
    d_ff=96,
    max_seq_len=256,
    rope_theta=10_000.0,
    head_dim_override=64,
    qk_norm=True,
    n_experts=8,
    experts_per_token=3,
    first_k_dense=1,
    moe_d_ff=128,
    layer_pattern="CCQCCQ",
    ssm_conv=3,
)

_REGISTRY = {c.name: c for c in (LLAMA3_8B, LLAMA3_70B, LLAMA3_1B, LLAMA3_3B,
                                 TINY, MIXTRAL_8X7B, TINY_MOE,
                                 QWEN3_32B, QWEN3_4B, TINY_QWEN,
                                 KIMI_VL_A3B, TINY_MLA, TINY_LONGCAT, TINY_DSA,
                                 TINY_SWA, TINY_SWA_KV,
                                 NEMOTRON_3_SUPER,
                                 NEMOTRON_3_SUPER_CUT, TINY_HYBRID,
                                 TINY_JAMBA, TINY_LFM2)}


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
        fields = {k: tuple(v) if isinstance(v, list) else v
                  for k, v in fields.items() if k in known}
        if isinstance(fields.get("window_attn"), dict):
            fields["window_attn"] = AttnKind(**fields["window_attn"])
        return ModelConfig(**fields)
    raise ValueError(f"unknown model config {name!r}; have {sorted(_REGISTRY)}")
