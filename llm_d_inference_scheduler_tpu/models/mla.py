"""The DeepSeek-V3 family's decoder block for the TPU engine: latent attention
(MLA) and sigmoid-routed narrow experts beside a shared one. Kimi-VL-A3B's
language model is this block (its vision tower is not built).

The module has models/llama.py's four entry points with its signatures
(``init_params``, ``forward``, ``decode_step``, ``prefill_with_prefix``), so
the engine serves either through the same step functions; ``models.family``
picks the module from the config. Where a signature says ``k_pages,
v_pages`` this family hands a latent pool through as ``(pool, None)``
(kvcache/pages.py), and "KV" returned beside logits is ``(rows, None)``.

Attention, one mathematics in two forms. A token's cache row is ``[c | k_r]``:
its latent ``c`` (kv_lora_rank values, RMS-normed) and its rotated key part
``k_r`` (qk_rope_head_dim values), one row for every head. With ``W_kvb``
split a head into ``[W_uk | W_uv]``:

- *expanded* (a prefill, and a window that continues a cached prefix): every
  row is carried out to a head's ``k_nope = c W_uk`` and ``v = c W_uv``;
  scores ``(q_nope . k_nope + q_r . k_r) / sqrt(d_nope + d_rope)``. Costs
  rows x heads x (d_nope + d_v) products once a window, which a window's
  hundreds of queries share.
- *absorbed* (decode): the query is carried in instead, ``q_lat = q_nope
  W_uk^T``; scores ``(q_lat . c + q_r . k_r)`` under the same scale, ``o_lat =
  sum p c``, and ``o_lat W_uv`` afterwards. Nothing is expanded a cached row:
  each is read once and is key and value at once, which is the kernel
  (ops/pallas_latent_attention.py).

Which form runs is a rule of shapes, written once: a step with one query a
sequence (``decode_step``) is absorbed, a step with a run of queries a
sequence (``forward``, ``prefill_with_prefix``) is expanded. There is no
option for it.

The rotation pairs column i with i + d_rope/2 (ops/rope.py), on ``q_r`` and
``k_r`` only. The published implementation stores those columns interleaved
and un-interleaves them at run time before the same rotate-half: the
parameter tree here holds them un-interleaved, a fixed permutation of
``wq``'s and ``wkva``'s rope columns that is a checkpoint converter's to make
(not written: models/convert_hf.py refuses this family's state dict).

FFN. The first ``first_k_dense`` layers are a plain SwiGLU of width d_ff.
They are their own stack in the parameter tree (``params["dense"]``) and
their own scan: their FFN is not padded out to an expert layer's shape. The
other layers (``params["layers"]``): scores ``s = sigmoid(h W_r)`` in f32;
the experts_per_token experts with the largest ``s + b`` (``b`` the selection
bias: it selects and does not weigh); gates ``s_i / sum_chosen s x
routed_scaling_factor``; ``y = sum g_i SwiGLU_i(h) + SwiGLU_shared(h)``. The
routed part runs dense over the experts or grouped (ops/pallas_moe.py),
chosen by the engine per program as for Mixtral (``cfg.moe_impl``); the
shared expert is a plain SwiGLU beside either.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..kvcache import pages
from ..ops import apply_rope, rms_norm, rope_table
from ..ops.attention import NEG_INF
from .configs import ModelConfig
from .llama import _over_layers
from .routing import route

Params = dict[str, Any]

def init_params(cfg: ModelConfig, key: jax.Array,
                dtype: jnp.dtype | None = None) -> Params:
    """Random-init parameters: ``dense`` and ``layers`` are the two stacks
    (leading dense layers, expert layers), each with a leading layer axis.
    Norm weights and the selection bias are drawn too, not ones and zeros:
    a run on random weights then sees them."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    D, V, H, E = cfg.d_model, cfg.vocab_size, cfg.n_heads, cfg.n_experts
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    Fm, Fs = cfg.moe_d_ff, cfg.n_shared_experts * cfg.moe_d_ff
    keys = iter(jax.random.split(key, 40))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def norm(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)

    def attention(L):
        return {
            "wq": w((L, D, H * (dn + dr)), D),
            "wkva": w((L, D, r + dr), D),
            "kv_norm": norm((L, r)),
            "wkvb": w((L, r, H * (dn + dv)), r),
            "wo": w((L, H * dv, D), H * dv),
            "ln_attn": norm((L, D)),
            "ln_mlp": norm((L, D)),
        }

    Ld = cfg.first_k_dense
    Le = cfg.n_layers - Ld
    params = {"embed": w((V, D), D), "final_norm": norm((D,)),
              "lm_head": w((D, V), D)}
    if Ld:
        params["dense"] = {
            **attention(Ld),
            "w1": w((Ld, D, cfg.d_ff), D), "w3": w((Ld, D, cfg.d_ff), D),
            "w2": w((Ld, cfg.d_ff, D), cfg.d_ff)}
    params["layers"] = {
        **attention(Le),
        "router": w((Le, D, E), D),
        # The published bias is what load balancing left behind, of the
        # order of the scores' spread; drawn so that it changes selections.
        "router_bias": (0.1 * jax.random.normal(
            next(keys), (Le, E), jnp.float32)),
        "w1": w((Le, E, D, Fm), D), "w3": w((Le, E, D, Fm), D),
        "w2": w((Le, E, Fm, D), Fm),
        "w1s": w((Le, D, Fs), D), "w3s": w((Le, D, Fs), D),
        "w2s": w((Le, Fs, D), Fs)}
    return params


# ---- FFN ----------------------------------------------------------------------


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def _ffn(cfg: ModelConfig, lp: Params, h: jnp.ndarray
         ) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """A layer's FFN on h [..., D] — dense or experts, by the pytree — and
    the experts its tokens chose ([T, k]; None of a dense layer)."""
    if "router" not in lp:
        return _swiglu(h, lp["w1"], lp["w3"], lp["w2"]), None
    ht = h.reshape(-1, h.shape[-1])
    idx, gates = route(cfg, lp, ht)
    if cfg.moe_impl.startswith("grouped"):
        from ..ops.pallas_moe import grouped_experts

        y = grouped_experts(lp, ht, idx, gates, cfg.n_experts,
                            layer=lp.get("layer"),
                            interpret=cfg.moe_impl == "grouped_interpret")
    else:
        # Dense over the experts: every expert for every token, weighted by
        # its gate or by zero (models/llama._moe_ffn's form).
        weights = jnp.einsum(
            "tke,tk->te", jax.nn.one_hot(idx, cfg.n_experts, dtype=h.dtype),
            gates.astype(h.dtype))
        up = jnp.einsum("td,edf->tef", ht, lp["w1"])
        gate = jnp.einsum("td,edf->tef", ht, lp["w3"])
        out = jnp.einsum("tef,efd->ted", jax.nn.silu(up) * gate, lp["w2"])
        y = jnp.einsum("ted,te->td", out, weights)
    y = y + _swiglu(ht, lp["w1s"], lp["w3s"], lp["w2s"])
    return y.reshape(h.shape), idx


# ---- attention ------------------------------------------------------------------


def _split_kvb(cfg: ModelConfig, wkvb: jnp.ndarray
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """W_kvb [r, H * (dn + dv)] as (W_uk [r, H, dn], W_uv [r, H, dv])."""
    w = wkvb.reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _project(cfg: ModelConfig, lp: Params, h: jnp.ndarray, cos, sin
             ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """h [..., D] at the positions of cos/sin [..., d_rope/2] -> q_nope
    [..., H, dn], q_rope [..., H, dr] (rotated), and the tokens' cache rows
    [..., r + dr] = [normed latent | rotated key part]."""
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = (h @ lp["wq"]).reshape(*h.shape[:-1], cfg.n_heads, -1)
    kva = h @ lp["wkva"]
    c = rms_norm(kva[..., :r], lp["kv_norm"], cfg.norm_eps)
    q_rope = apply_rope(q[..., dn:], cos, sin)
    k_rope = apply_rope(kva[..., None, r:], cos, sin)[..., 0, :]
    return q[..., :dn], q_rope, jnp.concatenate([c, k_rope], axis=-1)


def _scale(cfg: ModelConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def expanded_attention(cfg: ModelConfig, lp: Params, q_nope, q_rope, rows,
                       mask) -> jnp.ndarray:
    """Queries [B, S, H, .] against cache rows [B, T, r + dr], every row
    carried out to its keys and values; ``mask`` [B, S, T] says which rows a
    query sees. Returns [B, S, H * dv]. Products in the operands' dtype with
    f32 accumulation, the softmax in f32."""
    B, S, H, _ = q_nope.shape
    r = cfg.kv_lora_rank
    w_uk, w_uv = _split_kvb(cfg, lp["wkvb"])
    c, k_rope = rows[..., :r], rows[..., r:]
    k_nope = jnp.einsum("btr,rhd->bthd", c, w_uk)
    v = jnp.einsum("btr,rhd->bthd", c, w_uv)
    f32 = dict(preferred_element_type=jnp.float32)
    scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope, **f32)
              + jnp.einsum("bshd,btd->bhst", q_rope, k_rope, **f32))
    scores = jnp.where(mask[:, None], scores * _scale(cfg), NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v, **f32)
    return out.astype(q_nope.dtype).reshape(B, S, -1)


def absorbed_attention(cfg: ModelConfig, lp: Params, q_nope, q_rope, cur_row,
                       attend: Callable[..., jnp.ndarray]) -> jnp.ndarray:
    """One query a sequence, q_nope [B, H, dn] / q_rope [B, H, dr], in the
    absorbed form: ``attend(q [B, H, r + dr], cur_row)`` -> [B, H, r] is the
    attention over cache rows as they lie (the paged pool, or any rows at
    all in the tests). Returns [B, H * dv]."""
    w_uk, w_uv = _split_kvb(cfg, lp["wkvb"])
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w_uk)
    o_lat = attend(jnp.concatenate([q_lat, q_rope], axis=-1), cur_row)
    out = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv)
    return out.reshape(out.shape[0], -1)


# ---- the stack ------------------------------------------------------------------


def _blocks(params: Params, cfg: ModelConfig, x: jnp.ndarray,
            attend: Callable[[Params, jnp.ndarray, jnp.ndarray],
                             tuple[jnp.ndarray, jnp.ndarray]]
            ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x through every block: the leading dense layers, then the expert
    layers, one scan each. ``attend(lp, h, layer)`` -> (attention output
    [..., H * dv], the tokens' cache rows); ``layer`` counts over both
    stacks, as the page pool does. Returns (x, rows [n_layers, ...], the
    experts chosen in every expert layer [n_expert_layers, T, k])."""
    rows, routes, first = [], None, 0
    for name in ("dense", "layers"):
        if name not in params:
            continue
        # Where the grouped kernel serves, the routed experts' weights stay
        # whole beside the scan (models/llama._over_layers, of one stack).
        sliced, whole = _over_layers(cfg, params[name])
        n = params[name]["wq"].shape[0]

        def body(x, layer_in):
            lp, layer = layer_in
            lp = {**lp, **whole}
            a, row = attend(lp, rms_norm(x, lp["ln_attn"], cfg.norm_eps),
                            layer)
            x = x + a @ lp["wo"]
            y, chosen = _ffn(cfg, lp, rms_norm(x, lp["ln_mlp"], cfg.norm_eps))
            return x + y, (row, chosen)

        x, (stack_rows, chosen) = jax.lax.scan(
            body, x, (sliced, first + jnp.arange(n, dtype=jnp.int32)))
        rows.append(stack_rows)
        routes = chosen if chosen is not None else routes
        first += n
    return x, jnp.concatenate(rows, axis=0), routes


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,                   # [B, S]
    positions: jnp.ndarray | None = None,  # [B, S]
    *,
    want_kv: bool = False,
    want_hidden: bool = False,
    kv_valid: jnp.ndarray | None = None,   # [B, S] padding mask
    mm_embeds: jnp.ndarray | None = None,
    mm_positions: jnp.ndarray | None = None,
    want_routes: bool = False,
    seq_len: jnp.ndarray | None = None,  # [B]: read by models/hybrid.py alone
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, None] | None]:
    """Full-sequence forward (prefill), expanded attention. Returns (logits
    [B, S, V] f32, (cache rows [L, B, S, r + dr], None) if want_kv).
    ``want_routes`` (here and on the two step functions below) appends the
    experts every token chose in every expert layer, [n_expert_layers, T, k]:
    scripts/compare_mla_reference.py's only hook into the program, to tell a
    near-tie that parted the other way from an error; no server passes it."""
    if mm_embeds is not None:
        raise NotImplementedError(
            "this family's vision tower and projector are not built: "
            "multimodal embeddings have nothing to come from")
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :],
                                     (B, S))
    cos, sin = rope_table(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    mask = positions[:, :, None] >= positions[:, None, :]          # [B, S, S]
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, :]

    def attend(lp, h, layer):
        q_nope, q_rope, rows = _project(cfg, lp, h, cos, sin)
        return expanded_attention(cfg, lp, q_nope, q_rope, rows, mask), rows

    x, rows, routes = _blocks(params, cfg, params["embed"][tokens], attend)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    kv = (rows, None) if want_kv else None
    out = (x if want_hidden else x @ params["lm_head"]).astype(jnp.float32)
    return (out, kv, routes) if want_routes else (out, kv)


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,        # [B]
    positions: jnp.ndarray,     # [B]
    k_pages: jnp.ndarray,       # the latent pool (kvcache/pages.py)
    v_pages: None,
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    active: jnp.ndarray | None = None,
    *,
    attention_fn: Callable[..., jnp.ndarray] = pages.latent_decode_attention,
    want_routes: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, None]:
    """One decode step through the latent pages, absorbed attention; returns
    (logits [B, V] f32, pool, None). As models/llama.decode_step: the scans
    read the stacked pool at (layer, page) and never carry it, every layer's
    new row goes in with one scatter afterwards, and the current token is
    attention's extra column. ``attention_fn`` has
    ``pages.latent_decode_attention``'s signature; the engine binds the
    kernel into it."""
    cos, sin = rope_table(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    seq_lens = positions + 1
    cur_slots = pages.token_slots(k_pages, block_tables, positions)

    def attend(lp, h, layer):
        q_nope, q_rope, row = _project(cfg, lp, h, cos, sin)

        def paged(q, cur_row):
            return attention_fn(q, k_pages, layer, block_tables, seq_lens,
                                cur_row, value_dim=cfg.kv_lora_rank,
                                scale=_scale(cfg))

        return absorbed_attention(cfg, lp, q_nope, q_rope, row, paged), row

    x, rows, routes = _blocks(params, cfg, params["embed"][tokens], attend)
    k_pages, _ = pages.write(k_pages, None, rows, None, *cur_slots)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    if active is not None:
        logits = jnp.where(active[:, None], logits, 0.0)
    out = (logits, k_pages, None)
    return (*out, routes) if want_routes else out


def prefill_with_prefix(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,        # [1, S_bucket] suffix tokens (padded)
    suffix_len: jnp.ndarray,    # [1]
    prefix_len: jnp.ndarray,    # [1] tokens already in the pages
    k_pages: jnp.ndarray,       # the latent pool
    v_pages: None,
    block_table_row: jnp.ndarray,               # [1, max_blocks]
    prior_table_row: jnp.ndarray | None = None,  # [1, prefix_bucket]
    *,
    want_routes: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, None]:
    """A window that continues a cached prefix (a prefix-cache hit, or a
    long prompt's next window), expanded attention: the cached rows are read
    back through kvcache at (layer, page), carried out beside the window's
    own, and the window's rows are written from ``prefix_len`` on. Returns
    (last-token logits [1, V] f32, pool, None)."""
    B, S = tokens.shape
    assert B == 1
    if prior_table_row is None:
        prior_table_row = block_table_row
    T = prior_table_row.shape[1] * pages.block_size(k_pages)

    positions = prefix_len[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    cos, sin = rope_table(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    prior_pos = jnp.arange(T, dtype=jnp.int32)[None, :]
    kv_pos = jnp.concatenate([prior_pos, positions], axis=1)        # [1, T+S]
    kv_valid = jnp.concatenate(
        [prior_pos < prefix_len[:, None],
         jnp.arange(S)[None, :] < suffix_len[:, None]], axis=1)
    mask = ((positions[:, :, None] >= kv_pos[:, None, :])
            & kv_valid[:, None, :])                                 # [1,S,T+S]

    def attend(lp, h, layer):
        q_nope, q_rope, rows = _project(cfg, lp, h, cos, sin)
        prior = pages.read_latent_prefix(k_pages, layer, prior_table_row,
                                         cfg.latent_dim)
        seen = jnp.concatenate([prior.astype(rows.dtype), rows], axis=1)
        return expanded_attention(cfg, lp, q_nope, q_rope, seen, mask), rows

    x, rows, routes = _blocks(params, cfg, params["embed"][tokens], attend)
    k_pages, _ = pages.write_sequences(k_pages, None, rows, None,
                                       block_table_row, suffix_len,
                                       start=prefix_len)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = jnp.take_along_axis(x, (suffix_len - 1)[:, None, None], axis=1)[:, 0]
    out = ((last @ params["lm_head"]).astype(jnp.float32), k_pages, None)
    return (*out, routes) if want_routes else out
