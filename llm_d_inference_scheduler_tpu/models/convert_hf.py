"""HuggingFace checkpoint → stacked-layer JAX params conversion.

The reference router serves whatever weights its external vLLM pods loaded;
our engine half owns weight loading, so real checkpoints (Llama/Mixtral
families in HF layout) need a mapping onto :mod:`.llama`'s stacked pytree:

- HF ``nn.Linear.weight`` is ``[out, in]`` applied as ``x @ W.T``; our params
  are ``[in, out]`` applied as ``x @ W`` — every projection transposes.
- Per-layer weights stack on a leading L axis (``lax.scan`` layout).
- HF Llama checkpoints already use the rotate-half RoPE layout (the
  interleaved→half permutation happened at Meta→HF conversion), which is
  exactly :func:`..ops.rope.apply_rope`'s convention — weights copy straight
  through, verified by the logits-parity test (tests/test_hf_convert.py).
- Mixtral's ``block_sparse_moe`` maps to the experts axis: HF per-expert
  w1/w3 (gate/up) and w2 (down) stack to ``[L, E, D, F]`` / ``[L, E, F, D]``;
  the router gate maps to ``[L, D, E]``.

Use :func:`convert_state_dict` in-process (tests) or the CLI
(``python -m llm_d_inference_scheduler_tpu.models.convert_hf``) to write an
Orbax checkpoint the engine restores via ``--checkpoint-path``.
"""

from __future__ import annotations

import numpy as np

from .configs import AttnKind, ModelConfig

__all__ = ["config_from_hf", "convert_state_dict", "main"]


def _refuse_other_options(hf, name: str, only: dict, module: str) -> None:
    """A config that sets an option to something ``module`` does not compute
    is refused, not served as something else."""
    for key, value in only.items():
        got = getattr(hf, key, value)
        if got != value:
            raise ValueError(f"{name}: {key}={got!r} is not supported "
                             f"({module} computes {key}={value!r} only)")


def _held_share(hf) -> dict:
    """The ModelConfig fields of a chip's share of each layer's experts, from
    the two keys that state it beside the published ones:
    ``n_routed_experts_published`` (what the router scores, where
    ``n_routed_experts`` counts the experts held) and
    ``expert_parallel_rank`` (which share: the experts from rank x held
    on)."""
    held = hf.n_routed_experts
    total = getattr(hf, "n_routed_experts_published", held)
    return dict(n_experts=total, experts_held=held if held != total else 0,
                experts_first=getattr(hf, "expert_parallel_rank", 0) * held)


# What models/mla.py computes of the DeepSeek-V3 family's options; a config
# that says otherwise is refused, not served as something else.
_MLA_ONLY = {"scoring_func": "sigmoid",
             "topk_method": "noaux_tc", "norm_topk_prob": True,
             "moe_layer_freq": 1, "attention_bias": False,
             "hidden_act": "silu", "tie_word_embeddings": False}


def _yarn(hf, name: str) -> tuple[float, ...]:
    """``rope_scaling`` as ModelConfig.rope_yarn; none, or YaRN as the
    DeepSeek family states it (cos/sin unscaled: mscale == mscale_all_dim)."""
    scaling = getattr(hf, "rope_scaling", None)
    if not scaling:
        return ()
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind != "yarn" or scaling.get("mscale", 1) != scaling.get(
            "mscale_all_dim", 1):
        raise ValueError(
            f"{name}: rope_scaling={scaling!r} is not supported (ops/rope.py "
            "computes none, or type 'yarn' with mscale == mscale_all_dim)")
    return (float(scaling["factor"]),
            float(scaling["original_max_position_embeddings"]),
            float(scaling.get("beta_fast", 32)),
            float(scaling.get("beta_slow", 1)),
            float(scaling.get("mscale_all_dim", 1)))


# The keys that state a second kind of layer and a gate (``model_type``
# dots3_note): what models/mla.py computes of each, and every key of the
# window kind that has to be there. A config that names a layer kind, a gate
# or a ``swa_*`` key beyond these is refused, not served as something else.
_LAYER_KINDS = {"full_attention": "*", "sliding_attention": "W"}
_GATES = {None: False, "none": False, "headwise": True}
_SWA_KEYS = ("swa_num_attention_heads", "swa_q_lora_rank", "swa_kv_lora_rank",
             "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
             "swa_rope_theta")
_SWA_OTHER = {"swa_num_key_value_heads", "swa_attention_gate_type"}


def _layer_kinds(hf, name: str) -> dict:
    """The ModelConfig fields of a model whose layers are of two kinds
    (``layer_types``, the ``swa_*`` widths, ``sliding_window_size``), of its
    gates (``attention_gate_type``, ``swa_attention_gate_type``) and of its
    low-rank rescale (``apply_mla_qkv_lora_rescale``: LongCat-Flash's two
    factors, on both kinds); nothing for a config that states none of it."""
    gates = {}
    for key in ("attention_gate_type", "swa_attention_gate_type"):
        kind = getattr(hf, key, None)
        if kind not in _GATES:
            raise ValueError(
                f"{name}: {key}={kind!r} is not supported (models/mla.py "
                f"computes a gate a head, 'headwise', or none)")
        gates[key] = _GATES[kind]
    rescale = bool(getattr(hf, "apply_mla_qkv_lora_rescale", False))
    out = dict(attn_gate=gates["attention_gate_type"],
               mla_scale_q_lora=rescale, mla_scale_kv_lora=rescale)
    swa = sorted(k for k in vars(hf) if k.startswith("swa_"))
    kinds = getattr(hf, "layer_types", None)
    if kinds is None:
        if swa or getattr(hf, "sliding_window_size", None):
            raise ValueError(
                f"{name}: {swa or ['sliding_window_size']} without "
                "layer_types: no layer says it is of that kind")
        return out
    other = sorted(set(kinds) - set(_LAYER_KINDS))
    if other or len(kinds) != hf.num_hidden_layers:
        raise ValueError(
            f"{name}: layer_types names {other or len(kinds)} (models/mla.py "
            f"computes {sorted(_LAYER_KINDS)}, one for each of the "
            f"{hf.num_hidden_layers} layers)")
    pattern = "".join(_LAYER_KINDS[k] for k in kinds)
    unknown = sorted(set(swa) - set(_SWA_KEYS) - _SWA_OTHER)
    if unknown:
        raise ValueError(f"{name}: {unknown} is not supported (models/mla.py "
                         f"reads {sorted(_SWA_KEYS)} of a window layer)")
    if "W" not in pattern:
        if swa:
            raise ValueError(f"{name}: {swa} with no sliding_attention layer")
        return out
    missing = [k for k in (*_SWA_KEYS, "sliding_window_size")
               if not getattr(hf, k, None)]
    if missing or "W" in pattern[:hf.first_k_dense_replace]:
        raise ValueError(
            f"{name}: a sliding_attention layer needs {missing} too, and the "
            "leading dense layers are of the full kind")
    heads = hf.swa_num_attention_heads
    if getattr(hf, "swa_num_key_value_heads", heads) != heads:
        raise ValueError(f"{name}: swa_num_key_value_heads must equal "
                         "swa_num_attention_heads (latent attention)")
    return dict(
        **out, layer_pattern=pattern,
        window_attn=AttnKind(
            n_heads=heads, q_lora_rank=hf.swa_q_lora_rank,
            kv_lora_rank=hf.swa_kv_lora_rank,
            qk_nope_head_dim=hf.swa_qk_nope_head_dim,
            qk_rope_head_dim=hf.swa_qk_rope_head_dim,
            v_head_dim=hf.swa_v_head_dim,
            rope_theta=float(hf.swa_rope_theta),
            window=hf.sliding_window_size,
            gate=gates["swa_attention_gate_type"]))


def _mla_config_from_hf(hf, name: str) -> ModelConfig:
    """The DeepSeek-V3 family (latent attention, sigmoid-routed experts
    beside a shared one): Kimi-VL's language model is one, DeepSeek-V3.2's
    (``model_type`` deepseek_v32: a low-rank query, YaRN, group-limited
    routing and the indexer's three ``index_*`` keys) another, and one whose
    layers are of two kinds (``model_type`` dots3_note: :func:`_layer_kinds`)
    a third; a chip's share of the experts as :func:`_held_share` reads it."""
    _refuse_other_options(hf, name, _MLA_ONLY, "models/mla.py")
    kinds = _layer_kinds(hf, name)
    share = _held_share(hf)
    n_group = getattr(hf, "n_group", None) or 1
    topk_group = getattr(hf, "topk_group", None) or 1
    if share["n_experts"] % n_group or not 1 <= topk_group <= n_group or (
            n_group > 1 and share["n_experts"] // n_group < 2):
        raise ValueError(
            f"{name}: n_group={n_group} must divide the {share['n_experts']} "
            f"experts the router scores into groups of two or more, and "
            f"topk_group={topk_group} lie in 1..n_group")
    index = [getattr(hf, k, None) or 0
             for k in ("index_topk", "index_n_heads", "index_head_dim")]
    if any(index) and not (all(index) and getattr(hf, "q_lora_rank", None)):
        raise ValueError(
            f"{name}: an indexer needs index_topk, index_n_heads and "
            "index_head_dim together, and the low-rank query its own query "
            "is projected from (q_lora_rank)")
    return ModelConfig(
        name=name,
        vocab_size=hf.vocab_size,
        d_model=hf.hidden_size,
        n_layers=hf.num_hidden_layers,
        n_heads=hf.num_attention_heads,
        n_kv_heads=getattr(hf, "num_key_value_heads", hf.num_attention_heads),
        d_ff=hf.intermediate_size,
        rope_theta=float(getattr(hf, "rope_theta", 10_000.0)),
        max_seq_len=getattr(hf, "max_position_embeddings", 8192),
        norm_eps=hf.rms_norm_eps,
        experts_per_token=hf.num_experts_per_tok,
        kv_lora_rank=hf.kv_lora_rank,
        qk_nope_head_dim=hf.qk_nope_head_dim,
        qk_rope_head_dim=hf.qk_rope_head_dim,
        v_head_dim=hf.v_head_dim,
        first_k_dense=hf.first_k_dense_replace,
        moe_d_ff=hf.moe_intermediate_size,
        n_shared_experts=hf.n_shared_experts,
        routed_scaling_factor=float(hf.routed_scaling_factor),
        **share,
        q_lora_rank=getattr(hf, "q_lora_rank", None) or 0,
        n_group=n_group,
        topk_group=topk_group,
        rope_yarn=_yarn(hf, name),
        index_topk=index[0],
        index_n_heads=index[1],
        index_head_dim=index[2],
        **kinds,
    )


# What models/mla.py computes of LongCat-Flash's options.
_LONGCAT_ONLY = {"attention_method": "MLA", "zero_expert_type": "identity",
                 "attention_bias": False, "rope_scaling": None,
                 "norm_topk_prob": False, "router_bias": False,
                 "hidden_act": "silu", "tie_word_embeddings": False}


def _longcat_config_from_hf(hf, name: str) -> ModelConfig:
    """LongCat-Flash (a double layer of two latent-attention sublayers, a
    low-rank query, a softmax router over experts and zero-compute experts),
    from its published keys, flat: ``num_layers`` double layers,
    ``ffn_hidden_size`` the dense FFNs', ``expert_ffn_hidden_size`` an
    expert's, ``moe_topk`` choices a token; a chip's share of the experts as
    :func:`_held_share` reads it."""
    _refuse_other_options(hf, name, _LONGCAT_ONLY, "models/mla.py")
    if not getattr(hf, "q_lora_rank", None):
        raise ValueError(f"{name}: LongCat-Flash's query is low-rank "
                         "(q_lora_rank); models/mla.py has no full-rank "
                         "query for the double layer")
    return ModelConfig(
        name=name,
        vocab_size=hf.vocab_size,
        d_model=hf.hidden_size,
        n_layers=hf.num_layers,
        n_heads=hf.num_attention_heads,
        n_kv_heads=hf.num_attention_heads,
        d_ff=hf.ffn_hidden_size,
        rope_theta=float(getattr(hf, "rope_theta", 10_000.0)),
        max_seq_len=getattr(hf, "max_position_embeddings", 8192),
        norm_eps=hf.rms_norm_eps,
        experts_per_token=hf.moe_topk,
        kv_lora_rank=hf.kv_lora_rank,
        qk_nope_head_dim=hf.qk_nope_head_dim,
        qk_rope_head_dim=hf.qk_rope_head_dim,
        v_head_dim=hf.v_head_dim,
        moe_d_ff=hf.expert_ffn_hidden_size,
        routed_scaling_factor=float(hf.routed_scaling_factor),
        **_held_share(hf),
        attn_sublayers=2,
        q_lora_rank=hf.q_lora_rank,
        mla_scale_q_lora=bool(getattr(hf, "mla_scale_q_lora", False)),
        mla_scale_kv_lora=bool(getattr(hf, "mla_scale_kv_lora", False)),
        router_scoring="softmax",
        n_zero_experts=hf.zero_expert_num or 0,
    )


# What models/hybrid.py computes of the nemotron_h family's options.
_HYBRID_ONLY = {"n_group": 1, "topk_group": 1, "mamba_proj_bias": False,
                "sliding_window": None, "attention_bias": False,
                "mlp_bias": False, "use_bias": False, "use_conv_bias": True,
                "norm_topk_prob": True, "mamba_hidden_act": "silu",
                "mlp_hidden_act": "relu2", "n_shared_experts": 1,
                "tie_word_embeddings": False}


def _hybrid_config_from_hf(hf, name: str) -> ModelConfig:
    """The nemotron_h family (a pattern of Mamba-2, LatentMoE and attention
    layers), from its published keys, flat; a chip's share of each layer's
    experts as :func:`_held_share` reads it."""
    _refuse_other_options(hf, name, _HYBRID_ONLY, "models/hybrid.py")
    pattern = hf.hybrid_override_pattern
    if set(pattern) - set("ME*") or len(pattern) != hf.num_hidden_layers:
        raise ValueError(
            f"{name}: hybrid_override_pattern {pattern!r} must name "
            f"num_hidden_layers={hf.num_hidden_layers} layers, each M, E or * "
            "(models/hybrid.py has no other mixer, no dense MLP layer '-')")
    if hf.mamba_num_heads * hf.mamba_head_dim != hf.expand * hf.hidden_size:
        raise ValueError(f"{name}: mamba_num_heads x mamba_head_dim must be "
                         "expand x hidden_size")
    return ModelConfig(
        name=name,
        vocab_size=hf.vocab_size,
        d_model=hf.hidden_size,
        n_layers=hf.num_hidden_layers,
        n_heads=hf.num_attention_heads,
        n_kv_heads=hf.num_key_value_heads,
        d_ff=hf.intermediate_size,
        rope_theta=float(getattr(hf, "rope_theta", 10_000.0)),
        max_seq_len=getattr(hf, "max_position_embeddings", 8192),
        norm_eps=hf.layer_norm_epsilon,
        head_dim_override=hf.head_dim,
        experts_per_token=hf.num_experts_per_tok,
        moe_d_ff=hf.moe_intermediate_size,
        n_shared_experts=hf.n_shared_experts,
        routed_scaling_factor=float(hf.routed_scaling_factor),
        layer_pattern=pattern,
        ssm_heads=hf.mamba_num_heads,
        ssm_head_dim=hf.mamba_head_dim,
        ssm_state=hf.ssm_state_size,
        ssm_groups=hf.n_groups,
        ssm_conv=hf.conv_kernel,
        ssm_chunk=hf.chunk_size,
        ssm_dt_range=(hf.time_step_min, hf.time_step_max, hf.time_step_floor),
        moe_latent_dim=hf.moe_latent_size,
        shared_d_ff=hf.moe_shared_expert_intermediate_size,
        **_held_share(hf),
    )


# What models/llama.py computes of SmallThinker's options.
_SMALLTHINKER_ONLY = {"moe_primary_router_apply_softmax": True,
                      "rope_scaling": None, "tie_word_embeddings": False,
                      "attention_bias": False}


def _smallthinker_config_from_hf(hf, name: str) -> ModelConfig:
    """SmallThinker (``moe_num_primary_experts`` names it): K/V attention
    whose layers attend to a window (``sliding_window_layout`` 1) or to the
    whole context, rotate q and k or carry no position code (``rope_layout``
    0), a softmax router over the chosen that reads the attention's normed
    input, ReGLU experts of ``moe_ffn_hidden_size``. A layer that attends to
    a window rotates; the others all do or none does."""
    _refuse_other_options(hf, name, _SMALLTHINKER_ONLY, "models/llama.py")
    L = hf.num_hidden_layers
    window = list(getattr(hf, "sliding_window_layout", None) or [0] * L)
    rope = list(getattr(hf, "rope_layout", None) or [1] * L)
    for key, layout in (("sliding_window_layout", window),
                        ("rope_layout", rope)):
        if len(layout) != L or set(layout) - {0, 1}:
            raise ValueError(
                f"{name}: {key}={layout!r} must hold a 0 or a 1 for each of "
                f"the {L} layers")
    nope = [w for w, r in zip(window, rope) if not r]
    if any(nope) or (nope and (len(nope) != window.count(0)
                               or not any(window))):
        raise ValueError(
            f"{name}: rope_layout={rope!r} is not supported beside "
            f"sliding_window_layout={window!r} (models/llama.py rotates in "
            "every window layer, and beside them in all of the others or in "
            "none)")
    if any(window) and not getattr(hf, "sliding_window_size", None):
        raise ValueError(f"{name}: a window layer needs sliding_window_size")
    default_hd = hf.hidden_size // hf.num_attention_heads
    return ModelConfig(
        name=name,
        vocab_size=hf.vocab_size,
        d_model=hf.hidden_size,
        n_layers=L,
        n_heads=hf.num_attention_heads,
        n_kv_heads=hf.num_key_value_heads,
        d_ff=hf.moe_ffn_hidden_size,
        rope_theta=float(getattr(hf, "rope_theta", 10_000.0)),
        max_seq_len=getattr(hf, "max_position_embeddings", 8192),
        norm_eps=hf.rms_norm_eps,
        n_experts=hf.moe_num_primary_experts,
        experts_per_token=hf.moe_num_active_primary_experts,
        head_dim_override=(hf.head_dim if hf.head_dim != default_hd else 0),
        layer_pattern=("".join("*W"[w] for w in window) if any(window)
                       else ""),
        kv_window=hf.sliding_window_size if any(window) else 0,
        full_nope=bool(nope),
        router_input="attn",
        expert_act="reglu",
    )


# What models/hybrid.py computes of the jamba family's options.
_JAMBA_ONLY = {"num_experts": 1, "mamba_proj_bias": False,
               "mamba_conv_bias": True, "sliding_window": None,
               "hidden_act": "silu", "tie_word_embeddings": True}

# The letters of a jamba file's derived layer order (``hybrid_override_
# pattern``, the nemotron_h family's notation with two letters of its own):
# a Mamba-1 mixer, an attention mixer, each with a dense FFN behind it.
JAMBA_LAYERS = {"mamba": "S", "attention": "A"}


def _jamba_config_from_hf(hf, name: str) -> ModelConfig:
    """The jamba family (``model_type`` names it): Mamba-1 and attention
    layers by ``attn_layer_period`` / ``attn_layer_offset``, each with a
    dense SwiGLU (``num_experts`` 1). A mixture of experts, a bias on the
    mixer's projections, a convolution without one, a sliding window and an
    untied head are not built and refused. A file may state the order it
    derived (``hybrid_override_pattern``); it has to be the one the two keys
    give."""
    _refuse_other_options(hf, name, _JAMBA_ONLY, "models/hybrid.py")
    L = hf.num_hidden_layers
    pattern = "".join(
        JAMBA_LAYERS["attention" if i % hf.attn_layer_period
                     == hf.attn_layer_offset else "mamba"] for i in range(L))
    if hf.num_key_value_heads == 1 and hf.num_attention_heads % 2:
        raise ValueError(
            f"{name}: num_attention_heads={hf.num_attention_heads} on one KV "
            "head: models/hybrid.py keeps a single KV head twice a page "
            "(ModelConfig.kv_heads_kept), half the query heads on each copy")
    stated = getattr(hf, "hybrid_override_pattern", None)
    if stated is not None and stated != pattern:
        raise ValueError(
            f"{name}: hybrid_override_pattern {stated!r} is not the order "
            f"attn_layer_period={hf.attn_layer_period} and attn_layer_offset="
            f"{hf.attn_layer_offset} give {L} layers ({pattern!r})")
    return ModelConfig(
        name=name,
        vocab_size=hf.vocab_size,
        d_model=hf.hidden_size,
        n_layers=L,
        n_heads=hf.num_attention_heads,
        n_kv_heads=hf.num_key_value_heads,
        d_ff=hf.intermediate_size,
        max_seq_len=getattr(hf, "max_position_embeddings", 8192),
        norm_eps=hf.rms_norm_eps,
        layer_pattern=pattern,
        ssm_state=hf.mamba_d_state,
        ssm_conv=hf.mamba_d_conv,
        ssm_dt_rank=hf.mamba_dt_rank,
        ssm_expand=hf.mamba_expand,
    )


# What models/hybrid.py computes of the lfm2_moe family's options.
_LFM2_ONLY = {"conv_bias": False, "norm_topk_prob": True,
              "use_expert_bias": True, "tie_word_embeddings": True}

# The letters of an lfm2_moe file's ``layer_types``: a gated short
# convolution, an attention mixer that norms and rotates q and k, each with an
# FFN behind it.
LFM2_LAYERS = {"conv": "C", "full_attention": "Q"}


def _lfm2_config_from_hf(hf, name: str) -> ModelConfig:
    """The lfm2_moe family (``model_type`` names it): gated short
    convolutions ``conv_L_cache`` wide and GQA layers in the order
    ``layer_types`` lists, the first ``num_dense_layers`` with a dense SwiGLU
    and the others with ``num_experts`` routed experts of
    ``moe_intermediate_size`` behind a sigmoid router whose bias selects. A
    convolution with a bias, gates that are not normalised over the chosen, a
    router without the selection bias, an untied head and a layer type that
    is neither of the two are not built and refused."""
    _refuse_other_options(hf, name, _LFM2_ONLY, "models/hybrid.py")
    kinds = list(hf.layer_types)
    unknown = sorted(set(kinds) - set(LFM2_LAYERS))
    if unknown:
        raise ValueError(
            f"{name}: layer_types names {unknown}: models/hybrid.py builds "
            f"{sorted(LFM2_LAYERS)} of this family only")
    if len(kinds) != hf.num_hidden_layers:
        raise ValueError(
            f"{name}: layer_types lists {len(kinds)} layers and "
            f"num_hidden_layers says {hf.num_hidden_layers}")
    if hf.conv_L_cache < 2:
        raise ValueError(
            f"{name}: conv_L_cache={hf.conv_L_cache}: a convolution that "
            "reaches no earlier row keeps no tail, and the state pool's slot "
            "row is that tail")
    if not 0 < hf.num_experts_per_tok < hf.num_experts:
        raise ValueError(
            f"{name}: num_experts_per_tok={hf.num_experts_per_tok} of "
            f"num_experts={hf.num_experts}: a router has to choose")
    return ModelConfig(
        name=name,
        vocab_size=hf.vocab_size,
        d_model=hf.hidden_size,
        n_layers=hf.num_hidden_layers,
        n_heads=hf.num_attention_heads,
        n_kv_heads=hf.num_key_value_heads,
        d_ff=hf.intermediate_size,
        rope_theta=float(hf.rope_theta),
        max_seq_len=getattr(hf, "max_position_embeddings", 8192),
        norm_eps=hf.norm_eps,
        qk_norm=True,
        n_experts=hf.num_experts,
        experts_per_token=hf.num_experts_per_tok,
        first_k_dense=hf.num_dense_layers,
        moe_d_ff=hf.moe_intermediate_size,
        routed_scaling_factor=float(hf.routed_scaling_factor),
        layer_pattern="".join(LFM2_LAYERS[k] for k in kinds),
        ssm_conv=hf.conv_L_cache,
    )


# The ``model_type``s the plain mapping below serves.
_LLAMA_TYPES = ("llama", "mixtral", "qwen3")


def config_from_hf(hf_config, name: str = "converted") -> ModelConfig:
    """Map a transformers Llama/Mixtral/Qwen3 config, a DeepSeek-V3-family
    one (Kimi-VL's ``text_config``), a LongCat-Flash one (``zero_expert_num``
    names it), a nemotron_h one (a layer pattern), a SmallThinker one
    (``moe_num_primary_experts``), a jamba or an lfm2_moe one
    (``model_type``) to our ModelConfig. A ``model_type`` that none of these
    is gets refused."""
    text = getattr(hf_config, "text_config", None)
    if text is not None:
        # A multimodal config nests its language model; the towers beside it
        # are not this mapping's.
        import types

        hf_config = (types.SimpleNamespace(**text) if isinstance(text, dict)
                     else text)
    if getattr(hf_config, "model_type", None) == "jamba":
        return _jamba_config_from_hf(hf_config, name)
    if getattr(hf_config, "model_type", None) == "lfm2_moe":
        return _lfm2_config_from_hf(hf_config, name)
    if getattr(hf_config, "hybrid_override_pattern", None):
        return _hybrid_config_from_hf(hf_config, name)
    if getattr(hf_config, "zero_expert_num", None) is not None:
        return _longcat_config_from_hf(hf_config, name)
    if getattr(hf_config, "kv_lora_rank", None):
        return _mla_config_from_hf(hf_config, name)
    if getattr(hf_config, "moe_num_primary_experts", None):
        return _smallthinker_config_from_hf(hf_config, name)
    model_type = getattr(hf_config, "model_type", None)
    if model_type not in _LLAMA_TYPES:
        raise ValueError(
            f"{name}: no mapping for model_type {model_type!r}: the plain "
            f"mapping serves {', '.join(_LLAMA_TYPES)}; deepseek_v3-family "
            "(kv_lora_rank), LongCat-Flash (zero_expert_num), nemotron_h "
            "(hybrid_override_pattern), SmallThinker "
            "(moe_num_primary_experts), jamba and lfm2_moe files are known by "
            "those keys")
    n_experts = getattr(hf_config, "num_local_experts", 0) or 0
    qk_norm = model_type == "qwen3"
    explicit_hd = getattr(hf_config, "head_dim", None) or 0
    default_hd = hf_config.hidden_size // hf_config.num_attention_heads
    return ModelConfig(
        name=name,
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(hf_config, "num_key_value_heads",
                           hf_config.num_attention_heads),
        d_ff=hf_config.intermediate_size,
        rope_theta=getattr(hf_config, "rope_theta", 10_000.0),
        max_seq_len=getattr(hf_config, "max_position_embeddings", 8192),
        norm_eps=hf_config.rms_norm_eps,
        n_experts=n_experts,
        experts_per_token=getattr(hf_config, "num_experts_per_tok", 2),
        head_dim_override=(explicit_hd if explicit_hd != default_hd else 0),
        qk_norm=qk_norm,
    )


def _t(w) -> np.ndarray:
    """torch/np tensor → float32 numpy, linear-layout transposed to [in, out]."""
    if hasattr(w, "detach"):
        w = w.detach().to("cpu").float().numpy()
    return np.asarray(w, dtype=np.float32).T


def _vec(w) -> np.ndarray:
    if hasattr(w, "detach"):
        w = w.detach().to("cpu").float().numpy()
    return np.asarray(w, dtype=np.float32)


def convert_state_dict(state_dict: dict, cfg: ModelConfig,
                       dtype: str | None = None):
    """HF Llama/Mixtral state dict → stacked params pytree (jnp arrays)."""
    import jax.numpy as jnp

    if cfg.kv_lora_rank:
        raise NotImplementedError(
            f"{cfg.name}: no mapping of the latent-attention family's "
            "checkpoint names onto models/mla.py's parameter tree yet (its "
            "rope columns are stored interleaved and need un-interleaving); "
            "the engine serves this family on seeded random weights")
    if cfg.conv_mixers:
        tree = _lfm2_state_dict(state_dict, cfg)
        bias = tree["experts"].pop("router_bias")   # stays float32
        tree = _cast(tree, jnp.dtype(dtype or cfg.dtype))
        tree["experts"]["router_bias"] = jnp.asarray(bias, jnp.float32)
        return tree
    if cfg.layer_pattern or cfg.router_input != "ffn":
        raise NotImplementedError(
            f"{cfg.name}: no mapping of a layer pattern's checkpoint names "
            "(the nemotron_h family, SmallThinker) onto the parameter tree "
            "yet; the engine serves these on seeded random weights")
    out_dtype = jnp.dtype(dtype or cfg.dtype)
    L, E = cfg.n_layers, cfg.n_experts

    def get(key):
        if key not in state_dict:
            raise KeyError(f"checkpoint missing {key!r}")
        return state_dict[key]

    def stack(fn):
        return np.stack([fn(i) for i in range(L)])

    p = f"model.layers.{{i}}."
    layers = {
        "wq": stack(lambda i: _t(get(p.format(i=i) + "self_attn.q_proj.weight"))),
        "wk": stack(lambda i: _t(get(p.format(i=i) + "self_attn.k_proj.weight"))),
        "wv": stack(lambda i: _t(get(p.format(i=i) + "self_attn.v_proj.weight"))),
        "wo": stack(lambda i: _t(get(p.format(i=i) + "self_attn.o_proj.weight"))),
        "ln_attn": stack(lambda i: _vec(get(p.format(i=i) + "input_layernorm.weight"))),
        "ln_mlp": stack(lambda i: _vec(get(p.format(i=i) + "post_attention_layernorm.weight"))),
    }
    if cfg.qk_norm:
        # Qwen3 per-head RMSNorm weights, [head_dim] per layer.
        layers["q_norm"] = stack(
            lambda i: _vec(get(p.format(i=i) + "self_attn.q_norm.weight")))
        layers["k_norm"] = stack(
            lambda i: _vec(get(p.format(i=i) + "self_attn.k_norm.weight")))
    if E:
        moe = "block_sparse_moe."
        layers["router"] = stack(
            lambda i: _t(get(p.format(i=i) + moe + "gate.weight")))
        layers["w1"] = stack(lambda i: np.stack(
            [_t(get(p.format(i=i) + moe + f"experts.{e}.w1.weight")) for e in range(E)]))
        layers["w3"] = stack(lambda i: np.stack(
            [_t(get(p.format(i=i) + moe + f"experts.{e}.w3.weight")) for e in range(E)]))
        layers["w2"] = stack(lambda i: np.stack(
            [_t(get(p.format(i=i) + moe + f"experts.{e}.w2.weight")) for e in range(E)]))
    else:
        layers["w1"] = stack(lambda i: _t(get(p.format(i=i) + "mlp.gate_proj.weight")))
        layers["w3"] = stack(lambda i: _t(get(p.format(i=i) + "mlp.up_proj.weight")))
        layers["w2"] = stack(lambda i: _t(get(p.format(i=i) + "mlp.down_proj.weight")))

    embed = _vec(get("model.embed_tokens.weight"))
    if "lm_head.weight" in state_dict:
        lm_head = _t(state_dict["lm_head.weight"])
    else:  # tied embeddings
        lm_head = embed.T

    params = {
        "embed": embed,
        "layers": layers,
        "final_norm": _vec(get("model.norm.weight")),
        "lm_head": lm_head,
    }
    return _cast(params, out_dtype)


def _lfm2_state_dict(state_dict: dict, cfg: ModelConfig) -> dict:
    """The lfm2_moe family's checkpoint names onto models/hybrid.py's stacks
    (float32 numpy; the caller casts). A layer's ``operator_norm`` is its
    mixer's ``ln`` and its ``ffn_norm`` its FFN's ``ln_mlp``; ``conv.conv``
    is a depth-wise Conv1d ``[d_model, 1, taps]`` and lies ``[taps,
    d_model]`` here, tap j on the row ``taps - 1 - j`` back, as the
    published convolution applies it; ``conv.in_proj``'s 3 x d_model outputs
    are B, C and u in that order; ``feed_forward.w1`` / ``w3`` / ``w2`` are
    gate, up and down, of the dense layers and of every expert alike. The
    head is the embedding transposed."""
    def get(key):
        if key not in state_dict:
            raise KeyError(f"checkpoint missing {key!r}")
        return state_dict[key]

    def layer(i, rest):
        return get(f"model.layers.{i}.{rest}")

    def stack(at, fn, *like):
        """fn(layer) over the layers ``at``; none of them: an empty stack of
        rows shaped ``like``."""
        return (np.stack([fn(i) for i in at]) if at
                else np.zeros((0, *like), np.float32))

    def ffn(i, name, expert=None):
        where = ("feed_forward." if expert is None
                 else f"feed_forward.experts.{expert}.")
        return _t(layer(i, where + name + ".weight"))

    convs = [i for i, c in enumerate(cfg.layer_pattern) if c == "C"]
    attns = [i for i, c in enumerate(cfg.layer_pattern) if c == "Q"]
    dense = list(range(cfg.first_k_dense))
    sparse = list(range(cfg.first_k_dense, cfg.n_layers))
    E = range(cfg.n_experts)

    D, F = cfg.d_model, cfg.d_ff
    embed = _vec(get("model.embed_tokens.weight"))
    return {
        "embed": embed,
        "final_norm": _vec(get("model.embedding_norm.weight")),
        "lm_head": embed.T,
        "conv": {
            "ln": stack(convs, lambda i: _vec(layer(i, "operator_norm.weight"))),
            "w_in": stack(convs, lambda i: _t(layer(i, "conv.in_proj.weight"))),
            "conv_w": stack(convs, lambda i: _vec(
                layer(i, "conv.conv.weight"))[:, 0, :].T),
            "w_out": stack(convs, lambda i: _t(layer(i, "conv.out_proj.weight")))},
        "attn": {
            "ln": stack(attns, lambda i: _vec(layer(i, "operator_norm.weight"))),
            "wq": stack(attns, lambda i: _t(layer(i, "self_attn.q_proj.weight"))),
            "wk": stack(attns, lambda i: _t(layer(i, "self_attn.k_proj.weight"))),
            "wv": stack(attns, lambda i: _t(layer(i, "self_attn.v_proj.weight"))),
            "wo": stack(attns, lambda i: _t(layer(i, "self_attn.out_proj.weight"))),
            "q_norm": stack(attns, lambda i: _vec(
                layer(i, "self_attn.q_layernorm.weight"))),
            "k_norm": stack(attns, lambda i: _vec(
                layer(i, "self_attn.k_layernorm.weight")))},
        "ffn": {
            "ln_mlp": stack(dense, lambda i: _vec(layer(i, "ffn_norm.weight")),
                            D),
            "w1": stack(dense, lambda i: ffn(i, "w1"), D, F),
            "w3": stack(dense, lambda i: ffn(i, "w3"), D, F),
            "w2": stack(dense, lambda i: ffn(i, "w2"), F, D)},
        "experts": {
            "ln_mlp": stack(sparse, lambda i: _vec(layer(i, "ffn_norm.weight"))),
            "router": stack(sparse, lambda i: _t(
                layer(i, "feed_forward.gate.weight"))),
            "router_bias": stack(sparse, lambda i: _vec(
                layer(i, "feed_forward.expert_bias"))),
            "w1": stack(sparse, lambda i: np.stack(
                [ffn(i, "w1", e) for e in E])),
            "w3": stack(sparse, lambda i: np.stack(
                [ffn(i, "w3", e) for e in E])),
            "w2": stack(sparse, lambda i: np.stack(
                [ffn(i, "w2", e) for e in E]))},
    }


def _cast(tree, dtype):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda a: jnp.asarray(a, dtype=dtype), tree)


def load_hf_state_dict(src: str) -> dict:
    """Load an HF checkpoint directory's tensors (safetensors or torch bins)."""
    import glob
    import os

    st_files = sorted(glob.glob(os.path.join(src, "*.safetensors")))
    if st_files:
        from safetensors import safe_open

        sd = {}
        for f in st_files:
            with safe_open(f, framework="np") as fh:
                for k in fh.keys():
                    sd[k] = fh.get_tensor(k)
        return sd
    import torch

    bins = sorted(glob.glob(os.path.join(src, "pytorch_model*.bin")))
    if not bins:
        raise FileNotFoundError(f"no safetensors or torch bins under {src}")
    sd = {}
    for f in bins:
        sd.update(torch.load(f, map_location="cpu", weights_only=True))
    return sd


def main(argv: list[str] | None = None) -> None:
    import argparse
    import json
    import os

    ap = argparse.ArgumentParser(
        description="Convert an HF Llama/Mixtral checkpoint to an Orbax "
                    "checkpoint in the engine's stacked layout.")
    ap.add_argument("src", help="HF checkpoint dir (config.json + weights)")
    ap.add_argument("out", help="output Orbax checkpoint dir")
    ap.add_argument("--dtype", default=None, help="override param dtype")
    args = ap.parse_args(argv)

    import dataclasses

    import orbax.checkpoint as ocp
    from transformers import AutoConfig

    hf_cfg = AutoConfig.from_pretrained(args.src, local_files_only=True)
    cfg = config_from_hf(hf_cfg)
    if args.dtype:
        cfg = dataclasses.replace(cfg, dtype=args.dtype)
    params = convert_state_dict(load_hf_state_dict(args.src), cfg)
    # (engine/checkpoint.load_params reads it back: models/ imports nothing
    # from engine/.)
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.abspath(args.out), params)
    ckptr.wait_until_finished()
    with open(os.path.join(args.out, "model_config.json"), "w") as f:
        json.dump({k: getattr(cfg, k) for k in cfg.__dataclass_fields__}, f,
                  indent=2)
    print(f"wrote {args.out} ({cfg.n_layers}L d{cfg.d_model} "
          f"{'moe' if cfg.n_experts else 'dense'})")


if __name__ == "__main__":
    main()
