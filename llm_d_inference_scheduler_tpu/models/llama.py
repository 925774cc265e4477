"""Functional JAX Llama-family model for the TPU engine half.

The reference router has no model code (it schedules onto external vLLM pods —
SURVEY.md preamble); this module provides the TPU-native engine it routes to.

Design notes (TPU-first):
- Parameters are a plain pytree with layer weights STACKED on a leading axis so
  the training/prefill path runs ``lax.scan`` over layers: one traced layer
  body, L-step loop — fast compiles, XLA-friendly.
- The decode path scans over the same stacked params and a layer index. The
  paged KV pools stay whole: the scan's body closes over them, attention
  reads them at (layer, page), and the step's new rows go in with one scatter
  after the scan, so donated pools are updated in place. How a pool is laid
  out, read and written is ``kvcache/pages.py``'s business; this file hands
  the pools through.
- All matmuls run in the params' dtype (bf16 by default) with f32 softmax/norm
  accumulation; logits are f32.
- Attention is injected via ``attention_fn`` so the sequence-parallel path can
  substitute a ring-attention shard_map without changing the model.

**Two kinds of layer in one model** (``cfg.kv_window`` > 0; the layer pattern
says which a layer is; SmallThinker's language model). A "W" layer attends to
the last ``kv_window`` tokens through a K/V pool pair of its own under a table
of its own (kvcache/pages.py; ``state.Cache.win`` / ``.win_v`` / ``.wt``, of
which a request keeps the pages its window reaches), a "*" layer to the whole
context through the pools every K/V model has, without a position code where
``cfg.full_nope`` says so. The layers stay ONE stack under ONE scan: the
kind is a flag scanned with the layer (:func:`_kinds`), the rotation a
``where`` and the attention a ``lax.cond`` between the two kinds' reads, so
no run of layers is sliced out of the stacked weights. The router of such a
model may read the attention's normed input (``cfg.router_input`` "attn":
:func:`_route`, computed before the attention, applied to the FFN's input)
and its experts may be ReGLU (``cfg.expert_act``). A model without the flag
traces exactly the programs it always did.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..kvcache import pages, state
from ..ops import apply_rope, causal_attention, rms_norm, rope_table
from ..ops.attention import banded_attention
from . import scopes
from .configs import ModelConfig

Params = dict[str, Any]

# The stacked weights whose layout in device memory is the decode program's to
# choose (engine/core.TpuEngine._param_formats asks its compile and holds them
# so). With few rows the TPU compiler streams a projection's weight against
# the rows and wants the contracted axis minor, [L, H * Dh, D] in memory: it
# wants that of ``wq`` and ``wk`` in every llama-family decode program, with
# QK-norm or without, and handed [L, D, H * Dh] it copied both whole once a
# chunk, outside the layer loop (qwen3-4b: 0.94 GB read and written, 2.96 ms a
# chunk whatever its length, 0.14 since; PERF.md section 6, PR 57), and every
# prefill program copied a layer's slice of both once a layer. ``wv`` it reads
# as it comes.
# The shapes stay: a checkpoint, a sharding rule and a reference see what
# they always did.
LAID_BY_DECODE = ("wq", "wk", "wv")


def init_params(cfg: ModelConfig, key: jax.Array, dtype: jnp.dtype | None = None) -> Params:
    """Random-init parameters (stacked-layer layout).

    MoE configs (cfg.n_experts > 0, Mixtral family) stack the FFN weights
    with an extra experts axis [L, E, D, F] plus a per-layer router; the FFN
    hook (:func:`_ffn`) dispatches on the pytree structure at trace time, so
    every downstream path (train forward, prefill, paged decode) serves both
    families unchanged."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    L, D, F, V = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E = cfg.n_experts
    ks = jax.random.split(key, 10)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)).astype(dtype)

    ffn_shape = (L, E, D, F) if E else (L, D, F)
    down_shape = (L, E, F, D) if E else (L, F, D)
    layers = {
        "wq": w(ks[1], (L, D, Hq * Dh), D),
        "wk": w(ks[2], (L, D, Hkv * Dh), D),
        "wv": w(ks[3], (L, D, Hkv * Dh), D),
        "wo": w(ks[4], (L, Hq * Dh, D), Hq * Dh),
        "w1": w(ks[5], ffn_shape, D),
        "w2": w(ks[6], down_shape, F),
        "w3": w(ks[7], ffn_shape, D),
        "ln_attn": jnp.ones((L, D), dtype),
        "ln_mlp": jnp.ones((L, D), dtype),
    }
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, Dh), dtype)
        layers["k_norm"] = jnp.ones((L, Dh), dtype)
    if E:
        layers["router"] = w(ks[9], (L, D, E), D)
    return {
        "embed": w(ks[0], (V, D), D),
        "layers": layers,
        "final_norm": jnp.ones((D,), dtype),
        "lm_head": w(ks[8], (D, V), D),
    }


def _route(cfg: ModelConfig, lp: Params, h: jnp.ndarray
           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The router on ``h`` [..., D], whichever layer input that is: (the
    experts chosen [..., k], their gates [..., k] f32, a softmax over the
    chosen logits). The logits are f32 straight from the product
    (ops/pallas_moe.moe_ffn_grouped has the reason)."""
    with scopes.block("ffn.router"):
        logits = jnp.dot(h, lp["router"], preferred_element_type=jnp.float32)
        top_vals, top_idx = jax.lax.top_k(logits, cfg.experts_per_token)
        return top_idx, jax.nn.softmax(top_vals, axis=-1)


def _moe_ffn(cfg: ModelConfig, lp: Params, h: jnp.ndarray,
             route: tuple[jnp.ndarray, jnp.ndarray] | None = None
             ) -> jnp.ndarray:
    """Top-k mixture-of-experts FFN (Mixtral-style), dense-over-experts.

    Compute is formulated as batched einsums over the experts axis — static
    shapes, MXU-tiled, and shardable: with the experts dim of w1/w2/w3 laid
    out on the ``ep`` mesh axis each device computes its local experts and
    XLA reduces the weighted combine with one psum. (At production scale the
    dense form trades FLOPs for regularity; a Pallas grouped-matmul drops in
    behind this same signature.) ``route``: the experts and gates as a router
    that read another input chose them (:func:`_route`).
    """
    if route is None:
        with scopes.block("ffn.router"):
            logits = (h @ lp["router"]).astype(jnp.float32)      # [B, S, E]
            top_vals, top_idx = jax.lax.top_k(logits, cfg.experts_per_token)
            gates = jax.nn.softmax(top_vals, axis=-1)            # [B, S, k]
    else:
        top_idx, gates = route
    with scopes.block("ffn.experts"):
        onehot = jax.nn.one_hot(top_idx, cfg.n_experts, dtype=h.dtype)
        weights = jnp.einsum("bske,bsk->bse", onehot, gates.astype(h.dtype))

        up = jnp.einsum("bsd,edf->bsef", h, lp["w1"])
        gate = jnp.einsum("bsd,edf->bsef", h, lp["w3"])
        act = jax.nn.relu if cfg.expert_act == "reglu" else jax.nn.silu
        out = jnp.einsum("bsef,efd->bsed", act(up) * gate, lp["w2"])
        return jnp.einsum("bsed,bse->bsd", out, weights)


def qk_normed(cfg: ModelConfig, lp: Params, q: jnp.ndarray,
              k: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Qwen3-family per-head RMSNorm on q/k before RoPE — dispatched on the
    pytree (no-op for checkpoints without q_norm/k_norm), so every serving
    path (prefill, paged decode, prefix prefill, pp stages) covers both
    families through the one hook."""
    if "q_norm" in lp:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    return q, k


def _ffn(cfg: ModelConfig, lp: Params, h: jnp.ndarray,
         route: tuple[jnp.ndarray, jnp.ndarray] | None = None) -> jnp.ndarray:
    """Dense or MoE FFN — dispatched on pytree structure at trace time.
    ``route``: what a router that read the attention's input chose."""
    if "router" in lp:
        squeeze = h.ndim == 2  # decode step: [B, D]
        if squeeze:
            h = h[:, None]
        # (The router and the grouped form's glue name themselves inside.)
        with scopes.block("ffn.experts"):
            if cfg.moe_impl.startswith("grouped") and route is not None:
                from ..ops.pallas_moe import grouped_experts

                y = grouped_experts(
                    lp, h.reshape(-1, h.shape[-1]),
                    *(r.reshape(-1, r.shape[-1]) for r in route),
                    cfg.n_experts, layer=lp.get("layer"),
                    interpret=cfg.moe_impl == "grouped_interpret",
                    reglu=cfg.expert_act == "reglu").reshape(h.shape)
            elif cfg.moe_impl.startswith("grouped"):
                from ..ops.pallas_moe import moe_ffn_grouped

                assert cfg.expert_act == "swiglu"   # its own router, and SwiGLU
                y = moe_ffn_grouped(
                    lp, h, cfg.n_experts, cfg.experts_per_token,
                    layer=lp.get("layer"),
                    interpret=cfg.moe_impl == "grouped_interpret")
            else:
                y = _moe_ffn(cfg, lp, h, route)
        return y[:, 0] if squeeze else y
    with scopes.block("ffn.dense"):
        return (jax.nn.silu(h @ lp["w1"]) * (h @ lp["w3"])) @ lp["w2"]


def _ffn_input(cfg: ModelConfig, lp: Params, x: jnp.ndarray) -> jnp.ndarray:
    """The FFN's normed input, under the scope of what reads it first."""
    with scopes.block("ffn.router" if "router" in lp else "ffn.dense"):
        return rms_norm(x, lp["ln_mlp"], cfg.norm_eps)


def _over_layers(cfg: ModelConfig, layers: Params) -> tuple[Params, Params]:
    """(What a scan over the stacked layers slices, what its body closes
    over and merges into the slice.) The grouped MoE FFN is a Pallas call
    (and so is models/mla.py's form of few rows, over the chosen experts),
    and one layer's slice of the expert weights would reach it as a copy (a
    custom call's operand cannot be a fused slice): there the scan goes over
    everything else and a layer index, and the body keeps the expert weights
    whole, which the kernel reads at (layer, expert). In every other case the
    scan slices all of ``layers``, as it always did, and nothing is merged."""
    if "router" not in layers or cfg.moe_impl == "dense":
        return layers, {}
    whole = {n: layers[n] for n in ("w1", "w2", "w3")}
    sliced = {n: a for n, a in layers.items() if n not in whole}
    sliced["layer"] = jnp.arange(layers["router"].shape[0], dtype=jnp.int32)
    return sliced, whole


def _layer(
    cfg: ModelConfig,
    lp: Params,
    x: jnp.ndarray,  # [B, S, D]
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    attention_fn: Callable[..., jnp.ndarray],
    attn_kwargs: dict[str, Any],
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One transformer block. Returns (x_out, k, v) with k/v pre-rope-applied."""
    B, S, _ = x.shape
    Dh = cfg.head_dim

    with scopes.block("attn.proj"):
        h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
        q = (h @ lp["wq"]).reshape(B, S, cfg.n_heads, Dh)
        k = (h @ lp["wk"]).reshape(B, S, cfg.n_kv_heads, Dh)
        v = (h @ lp["wv"]).reshape(B, S, cfg.n_kv_heads, Dh)
        q, k = qk_normed(cfg, lp, q, k)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    with scopes.block("attn.core"):
        attn = attention_fn(q, k, v, **attn_kwargs)
    with scopes.block("attn.proj"):
        x = x + attn.reshape(B, S, -1) @ lp["wo"]

    x = x + _ffn(cfg, lp, _ffn_input(cfg, lp, x))
    return x, k, v


# A window no context reaches: what a layer that attends to the whole context
# passes where a window layer passes ``cfg.kv_window``.
_NO_WINDOW = 2 ** 30


def _kinds(cfg: ModelConfig) -> tuple[np.ndarray, np.ndarray] | None:
    """Of a model whose layers are of two kinds (``cfg.kv_window``): (which
    layers attend to a window [L] bool, a layer's number among its kind's
    cache layers [L] int32); None for every other model. What a scan over
    the layers carries beside them."""
    if not cfg.kv_window:
        return None
    window = np.array([ch == "W" for ch in cfg.layer_pattern])
    among = np.where(window, np.cumsum(window), np.cumsum(~window)) - 1
    return window, among.astype(np.int32)


def _mixed_block(cfg: ModelConfig, lp: Params, x: jnp.ndarray, cos, sin,
                 is_window: jnp.ndarray,
                 attend: Callable[..., jnp.ndarray]
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One block of a model whose layers are of two kinds, x [B, S, D]:
    :func:`_layer` with the router ahead of the attention where the model
    says so, the rotation only where this layer rotates, and
    ``attend(q, k, v)`` -> [B, S, H, Dh] the caller's (it knows the layer's
    kind and cache). Returns (x, k, v) as :func:`_layer`, and the experts
    the early router chose [B, S, k] (None where the FFN routes itself)."""
    B, S, _ = x.shape
    Dh = cfg.head_dim

    with scopes.block("attn.proj"):
        h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
        # (The router names itself, inside.)
        route = _route(cfg, lp, h) if cfg.router_input == "attn" else None
        q = (h @ lp["wq"]).reshape(B, S, cfg.n_heads, Dh)
        k = (h @ lp["wk"]).reshape(B, S, cfg.n_kv_heads, Dh)
        v = (h @ lp["wv"]).reshape(B, S, cfg.n_kv_heads, Dh)
        q, k = qk_normed(cfg, lp, q, k)
        if cfg.full_nope:   # no position code where the whole context is seen
            q = jnp.where(is_window, apply_rope(q, cos, sin), q)
            k = jnp.where(is_window, apply_rope(k, cos, sin), k)
        else:
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)

    with scopes.block("attn.core"):
        attn = attend(q, k, v)
    with scopes.block("attn.proj"):
        x = x + attn.reshape(B, S, -1) @ lp["wo"]
    x = x + _ffn(cfg, lp, _ffn_input(cfg, lp, x), route)
    return x, k, v, None if route is None else route[0]


def _by_kind(kinds, k: jnp.ndarray, v: jnp.ndarray):
    """Every layer's new K/V [L, ...] as (the full layers' K, V, the window
    layers' K, V)."""
    full, window = np.flatnonzero(~kinds[0]), np.flatnonzero(kinds[0])
    return k[full], v[full], k[window], v[window]


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, S]
    positions: jnp.ndarray | None = None,  # [B, S]
    *,
    want_kv: bool = False,
    want_hidden: bool = False,
    attention_fn: Callable[..., jnp.ndarray] = causal_attention,
    kv_valid: jnp.ndarray | None = None,  # [B, S] padding mask
    mm_embeds: jnp.ndarray | None = None,     # [B, M, D] multimodal vectors
    mm_positions: jnp.ndarray | None = None,  # [B, M] target positions
    seq_len: jnp.ndarray | None = None,  # [B]: read by models/hybrid.py alone
    want_routes: bool = False,
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray] | None]:
    """Full-sequence forward (training / prefill).

    Multimodal prefill (E/P/D phase 2): ``mm_embeds`` replace the token
    embeddings at ``mm_positions`` (encoder outputs spliced in at placeholder
    tokens; padding entries use out-of-range positions, dropped by the
    scatter). Returns (logits [B, S, V] f32, (K, V) each [L, B, S, Hkv, Dh]
    if want_kv).
    """
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    with scopes.block("attn.proj"):
        cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_theta)

    with scopes.block("embed"):
        x = params["embed"][tokens]  # [B, S, D]
        if mm_embeds is not None:
            x = x.at[jnp.arange(B)[:, None], mm_positions].set(
                mm_embeds.astype(x.dtype), mode="drop")
    attn_kwargs = dict(q_positions=positions, kv_positions=positions, kv_valid=kv_valid)

    layers, whole = _over_layers(cfg, params["layers"])
    kinds = _kinds(cfg)

    def body(x, lp):
        x, k, v = _layer(cfg, {**lp, **whole}, x, cos, sin, attention_fn,
                         attn_kwargs)
        return x, (k, v) if want_kv else None

    def mixed_body(x, layer_in):
        lp, is_window = layer_in

        def attend(q, k, v):
            return banded_attention(
                q, k, v, **attn_kwargs,
                window=jnp.where(is_window, cfg.kv_window, _NO_WINDOW))

        x, k, v, chose = _mixed_block(cfg, {**lp, **whole}, x, cos, sin,
                                      is_window, attend)
        return x, ((k, v) if want_kv else None,
                   chose if want_routes else None)

    routes = None
    if kinds is None:
        x, kv = jax.lax.scan(body, x, layers)
    else:
        x, (kv, routes) = jax.lax.scan(mixed_body, x, (layers, kinds[0]))
        if want_kv:
            # The two kinds' rows go to two pool pairs: in the value that
            # ``pages.write_sequences`` takes a cache's rows in.
            k, v, wk, wv = _by_kind(kinds, *kv)
            kv = state.Fresh(k, v, None, None, None, win=wk, win_v=wv), None
    with scopes.block("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if want_hidden:
            # Embeddings surface: final-norm hidden states, lm head skipped
            # (reference analogue: vLLM embedding models behind
            # /v1/embeddings, routed by the EPP's embeddings body shape —
            # types.go:74-75).
            return x.astype(jnp.float32), kv
        logits = (x @ params["lm_head"]).astype(jnp.float32)
    # ``want_routes`` (a model of two kinds of layer alone; here and on the
    # two step functions below) appends the experts every layer's early
    # router chose [L, B, S, k]: what a comparison holds its reference to.
    return (logits, kv, routes) if want_routes else (logits, kv)


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,       # [B] current input token per sequence
    positions: jnp.ndarray,    # [B] 0-based position of that token
    k_pages: jnp.ndarray,      # the page pools (kvcache/pages.py)
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    active: jnp.ndarray | None = None,  # [B] bool — padding-slot mask
    *,
    attention_fn: Callable[..., jnp.ndarray] = pages.decode_attention,
    want_routes: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step with paged KV; returns (logits [B, V] f32, k_pages, v_pages).

    TPU-first structure: a single ``lax.scan`` over the stacked layers (one
    traced layer body → L-step loop, so compile time is layer-count-free) that
    only READS the pages; the current token's per-layer K/V comes back as scan
    outputs and is written with one fused scatter afterwards — the page
    buffers are touched once per step, not once per layer. The current token
    attends to itself via the appended cur_k/cur_v attention column.

    The scan carries the activations and scans over (layer params, layer
    index) — never over the pages. The body closes over the stacked pools and
    ``attention_fn`` reads them at (layer, page); a pool scanned over would
    reach the Pallas kernel as one layer's slice, which XLA has to copy out
    first (a custom call's operand cannot be a fused slice). ``attention_fn``
    has ``pages.decode_attention``'s signature; the engine binds the kernel
    into it where it decided for the kernel.

    Inactive batch slots must point their block table at the dedicated trash
    block 0 (the allocator reserves it).
    """
    if cfg.kv_window:
        return _mixed_decode_step(params, cfg, tokens, positions, k_pages,
                                  block_tables, active, attention_fn,
                                  want_routes)
    B = tokens.shape[0]
    Dh = cfg.head_dim
    with scopes.block("attn.proj"):
        cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_theta)  # [B, half]
    seq_lens = positions + 1

    with scopes.block("kv.write"):
        cur_slots = pages.token_slots(k_pages, block_tables, positions)

    x = _embedded(params, tokens)  # [B, D]

    layers, whole = _over_layers(cfg, params["layers"])

    def body(x, layer_in):
        lp, layer = layer_in
        lp = {**lp, **whole}
        with scopes.block("attn.proj"):
            h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
            q = (h @ lp["wq"]).reshape(B, cfg.n_heads, Dh)
            k = (h @ lp["wk"]).reshape(B, cfg.n_kv_heads, Dh)
            v = (h @ lp["wv"]).reshape(B, cfg.n_kv_heads, Dh)
            q, k = qk_normed(cfg, lp, q, k)
            q = apply_rope(q[:, None], cos[:, None], sin[:, None])[:, 0]
            k = apply_rope(k[:, None], cos[:, None], sin[:, None])[:, 0]

        with scopes.block("attn.core"):
            attn = attention_fn(q, k_pages, v_pages, layer, block_tables,
                                seq_lens, k, v)
        with scopes.block("attn.proj"):
            x = x + attn.reshape(B, -1) @ lp["wo"]
        x = x + _ffn(cfg, lp, _ffn_input(cfg, lp, x))
        return x, (k, v)

    x, (k_cur, v_cur) = jax.lax.scan(
        body, x, (layers, pages.layer_indices(k_pages)))
    # One fused scatter of all layers' current-token KV, [L, B, Hkv, Dh].
    with scopes.block("kv.write"):
        k_pages, v_pages = pages.write(k_pages, v_pages, k_cur, v_cur,
                                       *cur_slots)

    return _logits(params, cfg, x, active), k_pages, v_pages


def _embedded(params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    with scopes.block("embed"):
        return params["embed"][tokens]


def _logits(params: Params, cfg: ModelConfig, x: jnp.ndarray,
            active: jnp.ndarray | None = None) -> jnp.ndarray:
    """The head on x [B, D]: final norm and ``lm_head``, logits [B, V] f32,
    a padding lane's (``active`` False) zeroed."""
    with scopes.block("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = (x @ params["lm_head"]).astype(jnp.float32)
        if active is not None:
            logits = jnp.where(active[:, None], logits, 0.0)
        return logits


def _last_logits(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                 suffix_len: jnp.ndarray) -> jnp.ndarray:
    """The head on the last real position of x [1, S, D]: [1, V] f32."""
    with scopes.block("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        last = jnp.take_along_axis(
            x, (suffix_len - 1)[:, None, None], axis=1)[:, 0]
        return (last @ params["lm_head"]).astype(jnp.float32)


def prefill_with_prefix(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,       # [1, S_bucket] suffix tokens (padded)
    suffix_len: jnp.ndarray,   # [1] valid suffix tokens
    prefix_len: jnp.ndarray,   # [1] tokens already present in the pages
    k_pages: jnp.ndarray,      # the page pools (kvcache/pages.py)
    v_pages: jnp.ndarray,
    block_table_row: jnp.ndarray,  # [1, max_blocks] — full table (KV scatter)
    prior_table_row: jnp.ndarray | None = None,  # [1, prefix_bucket] — gather
    want_routes: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Prefill continuing from cached prefix KV (automatic prefix caching).

    The suffix attends to the cached prefix (gathered from the pages) plus
    itself causally; its KV is scattered into the pages at positions
    prefix_len + t. ``prior_table_row`` bounds the gather window to the
    actual (bucketed) prefix size so a cache hit costs O(prefix), not
    O(max_context). Returns (last-token logits [1, V] f32, k_pages, v_pages).
    """
    B, S = tokens.shape
    assert B == 1
    if prior_table_row is None:
        prior_table_row = block_table_row
    if cfg.kv_window:
        return _mixed_prefill_with_prefix(
            params, cfg, tokens, suffix_len, prefix_len, k_pages,
            block_table_row, prior_table_row, want_routes)
    T = prior_table_row.shape[1] * pages.block_size(k_pages)
    Dh = cfg.head_dim

    positions = prefix_len[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # [1,S]
    with scopes.block("attn.proj"):
        cos, sin = rope_table(positions, Dh, cfg.rope_theta)
    suffix_valid = jnp.arange(S)[None, :] < suffix_len[:, None]          # [1,S]
    prior_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (1, T))
    prior_valid = prior_pos < prefix_len[:, None]                        # [1,T]
    kv_positions = jnp.concatenate([prior_pos, positions], axis=1)       # [1,T+S]
    kv_valid = jnp.concatenate([prior_valid, suffix_valid], axis=1)

    x = _embedded(params, tokens)  # [1, S, D]

    layers, whole = _over_layers(cfg, params["layers"])

    def body(x, layer_in):
        lp, kp, vp = layer_in
        lp = {**lp, **whole}
        with scopes.block("attn.proj"):
            h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
            q = (h @ lp["wq"]).reshape(1, S, cfg.n_heads, Dh)
            k = (h @ lp["wk"]).reshape(1, S, cfg.n_kv_heads, Dh)
            v = (h @ lp["wv"]).reshape(1, S, cfg.n_kv_heads, Dh)
            q, k = qk_normed(cfg, lp, q, k)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)

        with scopes.block("attn.core"):
            k_prior, v_prior = pages.read_prefix(kp, vp, prior_table_row)
            k_all = jnp.concatenate([k_prior, k], axis=1)
            v_all = jnp.concatenate([v_prior, v], axis=1)
            attn = causal_attention(
                q, k_all, v_all, q_positions=positions,
                kv_positions=kv_positions, kv_valid=kv_valid)
        with scopes.block("attn.proj"):
            x = x + attn.reshape(1, S, -1) @ lp["wo"]
        x = x + _ffn(cfg, lp, _ffn_input(cfg, lp, x))
        return x, (k, v)

    x, (k_new, v_new) = jax.lax.scan(body, x, (layers, k_pages, v_pages))

    # Scatter suffix KV at offset positions (padding → trash block 0).
    with scopes.block("kv.write"):
        k_pages, v_pages = pages.write_sequences(
            k_pages, v_pages, k_new, v_new, block_table_row, suffix_len,
            start=prefix_len)

    return _last_logits(params, cfg, x, suffix_len), k_pages, v_pages


# ---- two kinds of layer: the step programs ------------------------------------


def _mixed_decode_step(params: Params, cfg: ModelConfig, tokens, positions,
                       cache: state.Cache, block_tables, active,
                       attention_fn, want_routes: bool = False):
    """:func:`decode_step` for a model whose layers are of two kinds: the
    full layers read ``cache.k`` / ``cache.v`` through ``block_tables`` with
    ``attention_fn``, the window layers ``cache.win`` / ``cache.win_v``
    through the step's window tables ``cache.wt`` from the window's first
    page; each kind's new rows go to its own pools with one scatter a pool
    after the scan. Returns (logits, cache, None)."""
    B = tokens.shape[0]
    window, among = kinds = _kinds(cfg)
    with scopes.block("attn.proj"):
        cos, sin = rope_table(positions[:, None], cfg.head_dim, cfg.rope_theta)
    seq_lens = positions + 1
    layers, whole = _over_layers(cfg, params["layers"])

    def body(x, layer_in):
        lp, is_window, at = layer_in

        def attend(q, k, v):
            q, k, v = q[:, 0], k[:, 0], v[:, 0]
            return jax.lax.cond(
                is_window,
                lambda: pages.window_kv_decode_attention(
                    q, cache.win, cache.win_v, at, cache.wt, seq_lens, k, v,
                    window=cfg.kv_window, impl=cfg.swa_impl),
                lambda: attention_fn(q, cache.k, cache.v, at, block_tables,
                                     seq_lens, k, v))[:, None]

        x, k, v, chose = _mixed_block(cfg, {**lp, **whole}, x, cos, sin,
                                      is_window, attend)
        return x, (k[:, 0], v[:, 0], chose if want_routes else None)

    x, (*kv, routes) = jax.lax.scan(body, _embedded(params, tokens)[:, None],
                                    (layers, window, among))
    with scopes.block("kv.write"):
        k, v, wk, wv = _by_kind(kinds, *kv)
        k_pages, v_pages = pages.write(
            cache.k, cache.v, k, v,
            *pages.token_slots(cache.k, block_tables, positions))
        win, win_v = pages.write(
            cache.win, cache.win_v, wk, wv,
            *pages.token_slots(cache.win, cache.wt, positions))

    out = (_logits(params, cfg, x[:, 0], active),
           dataclasses.replace(cache, k=k_pages, v=v_pages, win=win,
                               win_v=win_v), None)
    return (*out, routes) if want_routes else out


def _mixed_prefill_with_prefix(params: Params, cfg: ModelConfig, tokens,
                               suffix_len, prefix_len, cache: state.Cache,
                               block_table_row, prior_table_row,
                               want_routes: bool = False):
    """:func:`prefill_with_prefix` for a model whose layers are of two kinds
    (a long prompt's next window: such an engine keeps no prefix cache). A
    full layer reads the whole prefix out of its pools through
    ``prior_table_row``; a window layer reads, out of its own pools, the
    pages that end where this window starts and its band still reaches
    (``pages.window_prefix_pages``). Both at (layer, page) of the stacked
    pools, which the scan closes over, in the form ``cfg.swa_impl`` says
    (set by models.bind; ``pages.prefill_attention``): one kernel that walks
    the pages the prompt holds, or the rows gathered whole and banded in
    XLA. Returns (last-token logits, cache, None)."""
    S = tokens.shape[1]
    window, among = kinds = _kinds(cfg)
    positions = prefix_len[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    with scopes.block("attn.proj"):
        cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_theta)
    near_ids, near_pos = pages.window_prefix_pages(
        cache.wt, prefix_len, pages.block_size(cache.k), cfg.kv_window)
    if cfg.swa_impl.startswith("kernel"):
        # The kernel walks the pages the prompt holds whatever the table's
        # width: handed the sequence's whole table, its text is one for
        # every prior bucket (and traced once for them all).
        prior_table_row = block_table_row
    layers, whole = _over_layers(cfg, params["layers"])

    def body(x, layer_in):
        lp, is_window, at = layer_in

        def attend(q, k, v):
            return pages.prefill_attention(
                q, k, v, (cache.k, cache.v), (cache.win, cache.win_v),
                is_window, at, prior_table_row, near_ids, near_pos[:, 0],
                prefix_len, suffix_len, window=cfg.kv_window,
                impl=cfg.swa_impl)

        x, k, v, chose = _mixed_block(cfg, {**lp, **whole}, x, cos, sin,
                                      is_window, attend)
        return x, (k, v, chose if want_routes else None)

    x, (*kv, routes) = jax.lax.scan(body, _embedded(params, tokens),
                                    (layers, window, among))
    with scopes.block("kv.write"):
        k, v, wk, wv = _by_kind(kinds, *kv)
        k_pages, v_pages = pages.write_sequences(
            cache.k, cache.v, k, v, block_table_row, suffix_len,
            start=prefix_len)
        win, win_v = pages.write_sequences(
            cache.win, cache.win_v, wk, wv, cache.wt, suffix_len,
            start=prefix_len)

    out = (_last_logits(params, cfg, x, suffix_len),
           dataclasses.replace(cache, k=k_pages, v=v_pages, win=win,
                               win_v=win_v), None)
    return (*out, routes) if want_routes else out
