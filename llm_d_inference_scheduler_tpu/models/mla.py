"""The latent-attention (MLA) decoder blocks for the TPU engine, two of them.
The DeepSeek-V3 family's: latent attention and sigmoid-routed narrow experts
beside a shared one; Kimi-VL-A3B's language model is this block (its vision
tower is not built), and so is DeepSeek-V3.2's, whose queries attend to the
rows an indexer picked (**Selection**, below; its draft head is not built).
And LongCat-Flash's double layer (``cfg.attn_sublayers``
2, described last); LongCat-Flash-Omni's language model is that one (its
encoders and its codec decoder are not built).

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
  hundreds of queries share. Its scores are [heads, queries, rows]: on a TPU
  they are worked a tile of (queries, rows) at a time with a running softmax
  and never written whole (ops/pallas_dsa.py; ``cfg.expanded_impl``, set by
  ``models.bind`` for every configuration of this module, selecting or not),
  on the CPU they are one f32 tensor (0.6 GB at 16 heads, a 1,024-token
  window and 8k cached rows).
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
routed part runs dense over the experts, grouped, or -- a program of few
rows on a chip that holds a range of the experts -- dense over the experts
some row chose (ops/pallas_moe.py), chosen by the engine per program as for
Mixtral (``cfg.moe_impl``); the shared expert is a plain SwiGLU beside any.

**The double layer** (``params["layers"]`` alone; what a sublayer has --
its attention and its dense FFN -- is stacked a SUBLAYER, 2 L rows, row
``2 l + i`` the cache layer's own number, and what the expert layer has a
layer, L rows). For i in (0, 1):
``x += MLA_i(RMSNorm(x)) W_o[i]``, which writes cache layer ``2 l + i``; ``h =
RMSNorm(x)``; for i == 0 the expert layer's ``m = MoE(h)`` is taken here;
``x += SwiGLU_i(h)`` at width d_ff. After sublayer 1: ``x += m`` (the
shortcut: the experts of a layer compute beside its second attention and
both dense FFNs). Its attention has a low-rank query (``c_q = RMSNorm(h
W_qa)``, ``q = c_q W_qb``) and two scale factors, ``sqrt(d_model /
q_lora_rank)`` on q and ``sqrt(d_model / kv_lora_rank)`` on the normed latent
before it is cached, where the configuration's flags say so; everything after
the projection is the attention above. Its router (models/routing.py,
"softmax") scores ``n_experts + n_zero_experts`` outputs: a choice past the
experts names an expert that computes nothing, ``E(h) = h``, so ``m = sum over
chosen AND HELD experts of g_i SwiGLU_i(h) + (sum of the gates of the zero
choices) h``. No shared expert. The chip holds ``cfg.held_experts`` of the
experts (expert parallelism without its exchange, as models/hybrid.py: what
the absent ones would have added is left out); the zero term is computed
where the token is, on every chip, and is no chip's share.

**Selection** (``cfg.index_topk`` > 0 is what says a block selects; there is
no option). Beside its cache row a token keeps an indexer key ``k^I =
LayerNorm(h W_k^I)`` (index_head_dim values, the first d_rope rotated), in a
second page pool under the same block ids (kvcache/pages.py). A query's
indexer has index_n_heads light heads ``q^I = c_q W_qb^I`` (from the query
latent; the first d_rope columns of each rotated) and a weight a head ``w = h
W_w / sqrt(heads x head_dim)``; cached token s scores ``I[t, s] = sum_j w[t,
j] relu(q^I[t, j] . k^I[s])`` in f32, and the query attends to the
``min(index_topk, t + 1)`` tokens s <= t that score highest, ties to the
lower position: the attention above with every other row at minus infinity.
Both forms take the set as a mask over the rows they read (ops/
sparse_attention.py, ops/pallas_dsa.py): the absorbed form walks the lane's
pages and masks; the expanded form hands its tiles the set in place of the
causal mask (whole, its scores would be 8.6 GB at 128 heads, a 1,024-token
window and 16k rows), and skips a tile nothing of which was selected. The
indexer's own kernels follow ``cfg.index_impl``. A program whose rows cannot outnumber index_topk (a first
window of 1,024 under index_topk 2,048) computes the keys and caches them,
and neither scores nor selects: it IS dense latent attention. YaRN
(``cfg.rope_yarn``) stretches the rotary frequencies and scales the softmax
by its magnitude factor squared; the router selects inside its best groups
(models/routing.py).

**Two kinds of layer in one model** (``cfg.window_attn``; the layer pattern
says which a layer is, "*" or "W"). A "*" layer is the block above at the
configuration's flat widths. A "W" layer is the same latent attention at
``cfg.window_attn``'s widths (:meth:`ModelConfig.of_window`: its own head
count, ranks, head sizes and rotary base; no indexer) whose query at t attends
to s with ``0 <= t - s < window``. Its rows live in a page pool of their own
under a table of their own (kvcache/pages.py, ``state.Cache.win`` / ``.wt``),
of which a request keeps the pages its window reaches: decode walks those
alone (``swa_latent_decode_attention``), a prefill window reads the pages that
end where it starts beside its own rows (``swa_window_attention``), and a
first window the band of its own. The window layers are a third stack of the
parameter tree (``params["window"]``, expert layers of the window kind);
:func:`_segments` walks the stacks in the published order, a scan a run of
like layers. Where ``cfg.attn_gate`` (a kind's own flag) the attention's
output is gated a head, ``o_j *= sigmoid(h W_g)_j`` from the layer's normed
input, ahead of ``W_o``.

Where ``cfg.tallies_choices`` (a held range, or zero-compute outputs, or a
block that selects: its two pools ride there anyway) the
step programs count the choices held here and the zero ones, and the pool
rides in a ``kvcache/state.Cache`` that carries the counts out, as
models/hybrid.py's does; elsewhere (Kimi) the pool is passed bare and nothing
is counted.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..kvcache import pages, state
from ..ops import (apply_rope, pallas_dsa, rms_norm, rope_table,
                   sparse_attention)
from ..ops.attention import NEG_INF
from ..ops.rope import yarn_mscale
from . import scopes
from .configs import ModelConfig
from .llama import (_embedded, _ffn_input, _last_logits, _logits,
                    _over_layers)
from .routing import route

Params = dict[str, Any]

def init_params(cfg: ModelConfig, key: jax.Array,
                dtype: jnp.dtype | None = None) -> Params:
    """Random-init parameters: ``dense`` and ``layers`` are the two stacks
    (leading dense layers, expert layers), each with a leading layer axis.
    Norm weights and the selection bias are drawn too, not ones and zeros:
    a run on random weights then sees them."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    D, V, E = cfg.d_model, cfg.vocab_size, cfg.n_experts
    Fm, Fs = cfg.moe_d_ff, cfg.n_shared_experts * cfg.moe_d_ff
    keys = iter(jax.random.split(key, 40))
    # (The indexer draws from keys of its own, so that the blocks without
    # one keep the weights they had.)
    index_keys = iter(jax.random.split(jax.random.fold_in(key, 1), 16))
    gate_keys = iter(jax.random.split(jax.random.fold_in(key, 2), 8))
    window_keys = iter(jax.random.split(jax.random.fold_in(key, 3), 40))

    def w(shape, fan_in, keys=keys):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def norm(shape, keys=keys):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)

    def indexer(L):
        if not cfg.index_topk:
            return {}
        Hi, Di = cfg.index_n_heads, cfg.index_head_dim
        return {
            "wqb_idx": w((L, cfg.q_lora_rank, Hi * Di), cfg.q_lora_rank,
                         index_keys),
            "wk_idx": w((L, D, Di), D, index_keys),
            "k_norm_idx": norm((L, Di), index_keys),
            "k_bias_idx": (0.1 * jax.random.normal(
                next(index_keys), (L, Di), jnp.float32)).astype(dtype),
            "w_idx": w((L, D, Hi), D, index_keys)}

    def attention(L, c=cfg, keys=keys):
        # Where the block scales q or the latent by sqrt(d_model / rank), the
        # up-projection is drawn at the width that factor refers to (variance
        # 1 / d_model: the scale is there to correct exactly that), so q, k
        # and v come out at unit variance as a trained model's do. Drawn
        # fan-in scaled they come out sqrt(d_model / rank) too large, 7 x
        # in the attention logits at the published ranks: attention turns
        # one-hot, and bf16 rounding then flips WHICH row it attends to
        # (chip run, PR 39: 20-50% of max |logit| against the reference).
        # ``c`` is the configuration at the layers' kind (cfg.of_window()).
        H, rq, r = c.n_heads, c.q_lora_rank, c.kv_lora_rank
        dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        query = ({"wqa": w((L, D, rq), D, keys), "q_norm": norm((L, rq), keys),
                  "wqb": w((L, rq, H * (dn + dr)),
                           D if c.mla_scale_q_lora else rq, keys)} if rq
                 else {"wq": w((L, D, H * (dn + dr)), D, keys)})
        # (The gate draws from keys of its own, as the indexer does.)
        gate = ({"wg": w((L, D, H), D, gate_keys)} if c.attn_gate else {})
        return {
            **query,
            **(indexer(L) if c.index_topk else {}),
            **gate,
            "wkva": w((L, D, r + dr), D, keys),
            "kv_norm": norm((L, r), keys),
            "wkvb": w((L, r, H * (dn + dv)),
                      D if c.mla_scale_kv_lora else r, keys),
            "wo": w((L, H * dv, D), H * dv, keys),
            "ln_attn": norm((L, D), keys),
            "ln_mlp": norm((L, D), keys),
        }

    def experts(L, keys=keys):
        Eh = cfg.held_experts[1]
        return {
            "router": w((L, D, E), D, keys),
            # The published bias is what load balancing left behind, of the
            # order of the scores' spread; drawn so that it changes selections.
            "router_bias": (0.1 * jax.random.normal(
                next(keys), (L, E), jnp.float32)),
            # (The experts held here: all of them, or a chip's share.)
            "w1": w((L, Eh, D, Fm), D, keys), "w3": w((L, Eh, D, Fm), D, keys),
            "w2": w((L, Eh, Fm, D), Fm, keys),
            "w1s": w((L, D, Fs), D, keys), "w3s": w((L, D, Fs), D, keys),
            "w2s": w((L, Fs, D), Fs, keys)}

    params = {"embed": w((V, D), D), "final_norm": norm((D,)),
              "lm_head": w((D, V), D)}
    if cfg.attn_sublayers == 2:
        L, Eh, F = cfg.n_layers, cfg.held_experts[1], cfg.d_ff
        width = cfg.router_width
        params["layers"] = {
            **attention(2 * L),
            "w1d": w((2 * L, D, F), D), "w3d": w((2 * L, D, F), D),
            "w2d": w((2 * L, F, D), F),
            "router": w((L, D, width), D),
            # Of the order of the scores' spread, as below: a softmax over
            # `width` outputs spreads its scores by about 1 / width (0.1 here
            # would hand every token the same experts_per_token outputs).
            "router_bias": (0.5 / width) * jax.random.normal(
                next(keys), (L, width), jnp.float32),
            "w1": w((L, Eh, D, Fm), D), "w3": w((L, Eh, D, Fm), D),
            "w2": w((L, Eh, Fm, D), Fm)}
        return params
    Ld, Lw = cfg.first_k_dense, cfg.n_window_layers
    Le = cfg.n_layers - Ld - Lw
    if Ld:
        params["dense"] = {
            **attention(Ld),
            "w1": w((Ld, D, cfg.d_ff), D), "w3": w((Ld, D, cfg.d_ff), D),
            "w2": w((Ld, cfg.d_ff, D), cfg.d_ff)}
    params["layers"] = {**attention(Le), **experts(Le)}
    if Lw:
        # The window layers, expert layers all: a stack of the window kind's
        # shapes, from keys of its own.
        params["window"] = {**attention(Lw, cfg.of_window(), window_keys),
                            **experts(Lw, window_keys)}
    return params


# ---- FFN ----------------------------------------------------------------------


def _swiglu(h, w1, w3, w2, scope: str = "ffn.dense"):
    with scopes.block(scope):
        return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def _ffn(cfg: ModelConfig, lp: Params, h: jnp.ndarray,
         real: jnp.ndarray | None = None
         ) -> tuple[jnp.ndarray, jnp.ndarray | None, jnp.ndarray | None]:
    """A layer's FFN on h [..., D] -- dense or experts, by the pytree -- the
    outputs of the router its tokens chose ([T, k]; None of a dense layer),
    and where ``cfg.tallies_choices`` how many of those choices name an
    expert held here and how many a zero-compute one (int32 [2]; else None)
    -- and third, in the form that reads the chosen experts alone, how many
    held experts' weights the layer read. ``real`` [T] says which rows are
    somebody's (None: all): the others' choices make no expert worth
    reading."""
    if "router" not in lp:
        return _swiglu(h, lp["w1"], lp["w3"], lp["w2"]), None, None
    ht = h.reshape(-1, h.shape[-1])
    with scopes.block("ffn.router"):
        idx, gates = route(cfg, lp, ht)
        # The experts this chip holds: all of them (nothing to tell apart, and
        # the parameters are indexed by the router's own ids), or a range.
        first, count = cfg.held_experts
        here = ((idx >= first) & (idx < first + count) if cfg.tallies_choices
                else None)
        local = idx if here is None else jnp.where(here, idx - first, -1)
    read = []
    # (The grouped form's glue names itself inside.)
    with scopes.block("ffn.experts"):
        if cfg.moe_impl.startswith("grouped"):
            from ..ops.pallas_moe import grouped_experts

            # A choice of no expert held here (another chip's, or a
            # zero-compute one) is sorted behind the last group and adds no
            # row to any.
            y = grouped_experts(lp, ht, idx, gates, count,
                                layer=lp.get("layer"),
                                first=None if here is None else first,
                                interpret=cfg.moe_impl == "grouped_interpret")
        elif cfg.moe_impl.startswith("chosen"):
            from ..ops.pallas_moe import chosen_experts

            # Dense over the held experts that a row of somebody's chose.
            if real is not None:
                local = jnp.where(real[:, None], local, -1)
            y, n_read = chosen_experts(
                lp, ht, local, gates, count, layer=lp.get("layer"),
                interpret=cfg.moe_impl == "chosen_interpret")
            read = [n_read]
        else:
            # Dense over the held experts: each of them for every token,
            # weighted by its gate or by zero (models/llama._moe_ffn's form).
            weights = jnp.einsum(
                "tke,tk->te", jax.nn.one_hot(local, count, dtype=h.dtype),
                gates.astype(h.dtype))
            up = jnp.einsum("td,edf->tef", ht, lp["w1"])
            gate = jnp.einsum("td,edf->tef", ht, lp["w3"])
            out = jnp.einsum("tef,efd->ted", jax.nn.silu(up) * gate, lp["w2"])
            y = jnp.einsum("ted,te->td", out, weights)
    if "w1s" in lp:
        y = y + _swiglu(ht, lp["w1s"], lp["w3s"], lp["w2s"], "ffn.shared")
    if here is None:
        return y.reshape(h.shape), idx, None
    zero = idx >= cfg.n_experts
    if cfg.n_zero_experts:
        # An expert that computes nothing returns the token: the zero
        # choices' gates, summed, times h, beside either form above.
        with scopes.block("ffn.experts"):
            y = y + (jnp.sum(jnp.where(zero, gates, 0.0), axis=-1,
                             keepdims=True)
                     * ht.astype(jnp.float32)).astype(h.dtype)
    with scopes.block("ffn.router"):
        counts = jnp.stack([jnp.sum(here, dtype=jnp.int32),
                            jnp.sum(zero, dtype=jnp.int32), *read])
    return y.reshape(h.shape), idx, counts


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
    [..., r + dr] = [normed latent | rotated key part]; where the block
    selects, the rows carry the indexer's keys behind them ([..., r + dr +
    index_dim]: :func:`_split_rows`) and a fourth value is the indexer's
    (queries, weights) of these tokens."""
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    with scopes.block("attn.proj"):
        if cfg.q_lora_rank:
            c_q = rms_norm(h @ lp["wqa"], lp["q_norm"], cfg.norm_eps)
            q = c_q @ lp["wqb"]
            if cfg.mla_scale_q_lora:
                q = q * (cfg.d_model / cfg.q_lora_rank) ** 0.5
        else:
            q = h @ lp["wq"]
        q = q.reshape(*h.shape[:-1], cfg.n_heads, -1)
        kva = h @ lp["wkva"]
        c = rms_norm(kva[..., :r], lp["kv_norm"], cfg.norm_eps)
        if cfg.mla_scale_kv_lora:
            # Ahead of W_kvb and of the cache: a cached row holds the scaled
            # latent, so both forms of attention read what they should.
            c = c * (cfg.d_model / r) ** 0.5
        q_rope = apply_rope(q[..., dn:], cos, sin)
        k_rope = apply_rope(kva[..., None, r:], cos, sin)[..., 0, :]
        if cfg.index_topk:
            with scopes.block("attn.index"):
                q_idx, k_idx, w_idx = _index_project(cfg, lp, h, c_q, cos,
                                                     sin)
            return (q[..., :dn], q_rope,
                    jnp.concatenate([c, k_rope, k_idx], axis=-1),
                    (q_idx, w_idx))
        return q[..., :dn], q_rope, jnp.concatenate([c, k_rope], axis=-1)


def _index_project(cfg: ModelConfig, lp: Params, h: jnp.ndarray,
                   c_q: jnp.ndarray, cos, sin
                   ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The indexer's side of tokens h [..., D] with query latents c_q: its
    queries [..., Hi, Di] and keys [..., Di], the first d_rope columns of
    each rotated, and its head weights [..., Hi] f32, scaled by ``(Hi x
    Di) ** -0.5``."""
    Hi, Di, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q = (c_q @ lp["wqb_idx"]).reshape(*h.shape[:-1], Hi, Di)
    q = jnp.concatenate([apply_rope(q[..., :dr], cos, sin), q[..., dr:]],
                        axis=-1)
    k = (h @ lp["wk_idx"]).astype(jnp.float32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    k = (k * lp["k_norm_idx"].astype(jnp.float32)
         + lp["k_bias_idx"].astype(jnp.float32)).astype(h.dtype)
    k = jnp.concatenate(
        [apply_rope(k[..., None, :dr], cos, sin)[..., 0, :], k[..., dr:]],
        axis=-1)
    w = jnp.dot(h, lp["w_idx"], preferred_element_type=jnp.float32)
    return q, k, w * (Hi * Di) ** -0.5


def _split_rows(cfg: ModelConfig, rows: jnp.ndarray
                ) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """What :func:`_project` made a token's row, as (latent rows, indexer
    keys or None): what each of the two pools takes. (Behind the keys a
    comparison's program may carry each token's selection out,
    :func:`_with_picked`; :func:`_picked` reads it.)"""
    if not cfg.index_topk:
        return rows, None
    return (rows[..., :cfg.latent_dim],
            rows[..., cfg.latent_dim:cfg.latent_dim + cfg.index_dim])


def _with_picked(want: bool, rows: jnp.ndarray, keep: jnp.ndarray):
    """``rows`` with each token's selection ``keep`` [..., T] behind them
    where ``want``: scripts/compare_dsa_reference.py's second hook into the
    program (``want_routes``), by the one way out of the scan over layers
    that is there; no server asks."""
    return (jnp.concatenate([rows, keep.astype(rows.dtype)], axis=-1)
            if want else rows)


def _picked(cfg: ModelConfig, rows: jnp.ndarray) -> jnp.ndarray:
    return rows[..., cfg.latent_dim + cfg.index_dim:] > 0


def _index_scores(cfg: ModelConfig, q_idx, w_idx, keys) -> jnp.ndarray:
    """ops/sparse_attention.index_scores in the form this program traces
    with (``cfg.index_impl``, the engine's to set as ``moe_impl`` is)."""
    if cfg.index_impl.startswith("kernel"):
        return pallas_dsa.index_scores_pallas(
            q_idx, w_idx, keys, interpret=cfg.index_impl == "kernel_interpret")
    return sparse_attention.index_scores(q_idx, w_idx, keys)


def _selected(cfg: ModelConfig, index, keys: jnp.ndarray,
              mask: jnp.ndarray) -> jnp.ndarray:
    """``mask`` [B, S, T] (the rows a query may see) narrowed to those it
    selected, by its indexer's (queries, weights) ``index`` against ``keys``
    [B, T, Di]. T rows or fewer than index_topk: every row a query may see is
    selected, and nothing is scored."""
    if not cfg.index_topk or keys.shape[1] <= cfg.index_topk:
        return mask
    return sparse_attention.select_top(_index_scores(cfg, *index, keys), mask,
                                       cfg.index_topk)


def _selected_of_lanes(cfg: ModelConfig, q_idx, w_idx, idx_pool, layer,
                       block_tables, seq_lens, cur_key, seen) -> jnp.ndarray:
    """:func:`_selected` for one query a lane (decode): ``seen`` [B, T + 1]
    (the table's T rows and the token's own, last) narrowed to the rows each
    lane's query selected. The cached keys are the key pool's pages under
    ``block_tables``: read by the kernel a page at a time where the programs
    run their kernels, gathered whole for the plain form."""
    if seen.shape[1] <= cfg.index_topk:
        return seen
    own = sparse_attention.index_scores(
        q_idx[:, None], w_idx[:, None],
        cur_key[:, None].astype(q_idx.dtype))[:, 0]               # [B, 1]
    if cfg.index_impl.startswith("kernel"):
        cached = pallas_dsa.index_scores_paged_pallas(
            q_idx, w_idx, idx_pool, layer, block_tables, seq_lens,
            interpret=cfg.index_impl == "kernel_interpret")
    else:
        cached = sparse_attention.index_scores(
            q_idx[:, None], w_idx[:, None],
            pages.read_rows(idx_pool, layer, block_tables))[:, 0]
    return sparse_attention.select_top(
        jnp.concatenate([cached, own], axis=1), seen, cfg.index_topk)


def _scale(cfg: ModelConfig) -> float:
    return ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
            * yarn_mscale(cfg.rope_yarn) ** 2)


def expanded_attention(cfg: ModelConfig, lp: Params, q_nope, q_rope, rows,
                       mask, *, impl: str | None = None,
                       name: str | None = None) -> jnp.ndarray:
    """Queries [B, S, H, .] against cache rows [B, T, r + dr], every row
    carried out to its keys and values; ``mask`` [B, S, T] says which rows a
    query sees. Returns [B, S, H * dv]. Products in the operands' dtype with
    f32 accumulation, the softmax in f32. Where the engine runs its kernels
    (``cfg.expanded_impl``: on a TPU, whether or not the block selects) the
    scores stay in VMEM a tile at a time (ops/pallas_dsa.py), and a tile no
    query of which sees a row (above the diagonal, past the prefix in its
    bucket, outside the selection) is skipped: whole they would be 0.6 GB at
    16 heads for a window over 8k rows, 8.6 GB at 128 heads over 16k. The
    kernel is ``mla_window_attention`` in a device trace, and
    ``dsa_window_attention`` for a block that selects. ``impl`` and ``name``
    are the window layers' to pass: their own form (``cfg.swa_impl``) and
    their name."""
    B, S, H, _ = q_nope.shape
    r = cfg.kv_lora_rank
    with scopes.block("attn.expand"):
        w_uk, w_uv = _split_kvb(cfg, lp["wkvb"])
        c, k_rope = rows[..., :r], rows[..., r:cfg.latent_dim]
    impl = cfg.expanded_impl if impl is None else impl
    if name is None:
        name = ("dsa_window_attention" if cfg.index_topk
                else "mla_window_attention")
    if impl.startswith("kernel"):
        with scopes.block("attn.core"):
            q_nope, q_rope = (jnp.swapaxes(q, 1, 2) for q in (q_nope, q_rope))
        with scopes.block("attn.expand"):
            k_nope = jnp.einsum("btr,rhd->bhtd", c, w_uk)
            v = jnp.einsum("btr,rhd->bhtd", c, w_uv)
        with scopes.block("attn.core"):
            out = pallas_dsa.masked_window_attention_pallas(
                q_nope, q_rope, k_nope, k_rope, v, mask, scale=_scale(cfg),
                interpret=impl == "kernel_interpret", name=name)
            return jnp.swapaxes(out, 1, 2).reshape(B, S, -1)
    with scopes.block("attn.expand"):
        k_nope = jnp.einsum("btr,rhd->bthd", c, w_uk)
        v = jnp.einsum("btr,rhd->bthd", c, w_uv)
    with scopes.block("attn.core"):
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
    with scopes.block("attn.proj"):
        w_uk, w_uv = _split_kvb(cfg, lp["wkvb"])
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w_uk)
    with scopes.block("attn.core"):
        o_lat = attend(jnp.concatenate([q_lat, q_rope], axis=-1), cur_row)
    with scopes.block("attn.proj"):
        out = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv)
        return out.reshape(out.shape[0], -1)


# ---- the stack ------------------------------------------------------------------


# What the double layer's parameters hold once a SUBLAYER (2 L rows, row
# 2 l + i); everything else of ``params["layers"]`` is the expert layer's (L
# rows). The scan over layers closes over the former and reads row 2 l + i
# where it lies: sliced a layer as [2, ...] and then a sublayer, XLA copies
# every weight out of the stack once a layer a step (AOT, PR 39: 0.9 GB of
# temporaries a decode step, none this way).
_SUBLAYER = ("wqa", "q_norm", "wqb", "wkva", "kv_norm", "wkvb", "wo",
             "ln_attn", "ln_mlp", "w1d", "w3d", "w2d")


def _gated(cfg: ModelConfig, lp: Params, h: jnp.ndarray, a: jnp.ndarray
           ) -> jnp.ndarray:
    """The attention's output ``a`` [..., H * dv] gated a head by the
    layer's normed input h: ``o_j *= sigmoid(h W_g)_j`` (f32), where the
    layer's kind has a gate (``cfg`` at that kind)."""
    if not cfg.attn_gate:
        return a
    g = jax.nn.sigmoid(jnp.dot(h, lp["wg"],
                               preferred_element_type=jnp.float32))
    out = a.reshape(*g.shape, -1).astype(jnp.float32) * g[..., None]
    return out.astype(a.dtype).reshape(a.shape)


def _segments(params: Params, cfg: ModelConfig
              ) -> list[tuple[str, int, int, str, int]]:
    """The stacks in layer order: (stack, its layers [lo, hi), their kind,
    the first one's number among its kind's cache layers) a run of like
    layers. Without window layers: the leading dense layers, then the expert
    layers, each whole. With them, by the layer pattern: "W" is the next
    layer of ``params["window"]``, "*" the next of ``dense`` (while the
    leading dense layers last) or of ``layers``."""
    if not cfg.window_attn:
        out, first = [], 0
        for name in ("dense", "layers"):
            if name in params:
                n = params[name]["wo"].shape[0] // cfg.attn_sublayers
                out.append((name, 0, n, "full", first))
                first += n
        return out
    segs: list[list] = []
    used = dict(dense=0, layers=0, window=0)
    caches = dict(full=0, window=0)
    for i, ch in enumerate(cfg.layer_pattern):
        kind = "window" if ch == "W" else "full"
        name = ("window" if ch == "W" else
                "dense" if i < cfg.first_k_dense else "layers")
        if segs and segs[-1][0] == name:
            segs[-1][2] += 1
        else:
            segs.append([name, used[name], used[name] + 1, kind,
                         caches[kind]])
        used[name] += 1
        caches[kind] += 1
    return [tuple(seg) for seg in segs]


def _blocks(params: Params, cfg: ModelConfig, x: jnp.ndarray, attend,
            real: jnp.ndarray | None = None
            ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                       jnp.ndarray | None, jnp.ndarray | None]:
    """x through every block, a scan a run of like layers
    (:func:`_segments`). ``attend(lp, h, layer)`` -> (attention output [..., H
    * dv], the tokens' cache rows); ``layer`` counts the cache layers of the
    layer's kind, as its page pool does. A model with window layers passes a
    function a kind, ``{"full": ..., "window": ...}``. Returns (x, rows
    [n_kv_layers, ...], the router's outputs chosen in every expert layer
    [n_expert_layers, T, k], where ``cfg.tallies_choices`` the counts of
    those that are held here and of those that compute nothing ([2]; [3]
    with the held experts' weights read, in the form that counts them:
    :func:`_ffn`, which ``real`` [T] is for), and the window layers' rows
    [n_window_layers, ...] or None)."""
    attends = attend if isinstance(attend, dict) else {"full": attend}
    rows = dict(full=[], window=[])
    routes, counts = [], None
    double = cfg.attn_sublayers == 2
    for name, lo, hi, kind, first in _segments(params, cfg):
        # Where a kernel serves the routed experts, their weights stay whole
        # beside the scan (models/llama._over_layers, of one stack).
        stack, attend = params[name], attends[kind]
        kcfg = cfg.of_window() if kind == "window" else cfg
        subs = {k: stack[k] for k in _SUBLAYER} if double else {}
        sliced, whole = _over_layers(
            cfg, {k: v for k, v in stack.items() if k not in subs})
        n = hi - lo
        if n != stack["wo"].shape[0] // cfg.attn_sublayers:
            # A run inside a stack (a pattern with more than one period).
            sliced = jax.tree.map(lambda a: a[lo:hi], sliced)

        def body(x, layer_in):
            lp, layer = layer_in
            lp = {**lp, **whole}
            with scopes.block("attn.proj"):
                h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
            a, row = attend(lp, h, layer)
            with scopes.block("attn.proj"):
                x = x + _gated(kcfg, lp, h, a) @ lp["wo"]
            y, chosen, tally = _ffn(cfg, lp, _ffn_input(cfg, lp, x), real)
            return x + y, (row, chosen, tally)

        def double_body(x, layer_in):
            lp, layer = layer_in
            lp = {**lp, **whole}
            made = []
            for i in range(2):
                sub = {k: v[2 * layer + i] for k, v in subs.items()}
                with scopes.block("attn.proj"):
                    h = rms_norm(x, sub["ln_attn"], cfg.norm_eps)
                a, row = attend(sub, h, 2 * layer + i)
                made.append(row)
                with scopes.block("attn.proj"):
                    x = x + a @ sub["wo"]
                h = _ffn_input(cfg, sub, x)
                if i == 0:
                    m, chosen, tally = _ffn(cfg, lp, h, real)
                x = x + _swiglu(h, sub["w1d"], sub["w3d"], sub["w2d"])
            return x + m, (jnp.stack(made), chosen, tally)

        x, (stack_rows, chosen, tally) = jax.lax.scan(
            double_body if double else body, x,
            (sliced, first + jnp.arange(n, dtype=jnp.int32)))
        if double:                    # [n, 2, ...] -> a cache layer a row
            stack_rows = stack_rows.reshape(-1, *stack_rows.shape[2:])
        rows[kind].append(stack_rows)
        if chosen is not None:
            routes.append(chosen)
        if tally is not None:
            tally = tally.sum(axis=0)
            counts = tally if counts is None else counts + tally
    if len(routes) > 1:               # every expert layer's, in layer order
        routes = [jnp.concatenate(routes, axis=0)]
    return (x, jnp.concatenate(rows["full"], axis=0),
            routes[0] if routes else None, counts,
            jnp.concatenate(rows["window"], axis=0) if rows["window"]
            else None)


def _pool_of(k_pages):
    """The latent pool of what a step was handed: the pool itself, or the
    ``state.Cache`` it rides in where the programs count (the module's
    docstring)."""
    return k_pages.k if isinstance(k_pages, state.Cache) else k_pages


def _kept(k_pages, pool: jnp.ndarray, counts: jnp.ndarray | None,
          idx: jnp.ndarray | None = None, win: jnp.ndarray | None = None):
    """What a step hands back in ``k_pages``' place: the pool as the step
    left it (and the indexer's key pool ``idx`` and the window layers' pool
    ``win`` where there is one), the step's counts added where it rides with
    them."""
    if not isinstance(k_pages, state.Cache):
        return pool
    return state.counted(
        dataclasses.replace(k_pages, k=pool, idx=idx, win=win), *counts)


def _window_side(cfg: ModelConfig, positions: jnp.ndarray):
    """What the window layers of a mixed model read beside the other kind's:
    (their configuration, the rotary table of ``positions`` at their base,
    the window in tokens); None without such layers."""
    if not cfg.window_attn:
        return None
    wcfg = cfg.of_window()
    with scopes.block("attn.proj"):
        table = rope_table(positions, wcfg.qk_rope_head_dim, wcfg.rope_theta)
    return wcfg, table, wcfg.window_attn.window


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
    with scopes.block("attn.proj"):
        cos, sin = rope_table(positions, cfg.qk_rope_head_dim, cfg.rope_theta,
                              cfg.rope_yarn)
    mask = positions[:, :, None] >= positions[:, None, :]          # [B, S, S]
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, :]

    def attend(lp, h, layer):
        q_nope, q_rope, rows, *index = _project(cfg, lp, h, cos, sin)
        if not index:
            return expanded_attention(cfg, lp, q_nope, q_rope, rows, mask), rows
        with scopes.block("attn.index"):
            seen = _selected(cfg, *index, _split_rows(cfg, rows)[1], mask)
        return (expanded_attention(cfg, lp, q_nope, q_rope, rows, seen),
                _with_picked(want_routes, rows, seen))

    if cfg.window_attn:
        wcfg, (wcos, wsin), window = _window_side(cfg, positions)
        band = mask & (positions[:, :, None] - positions[:, None, :] < window)

        def attend_window(lp, h, layer):
            q_nope, q_rope, rows = _project(wcfg, lp, h, wcos, wsin)
            return expanded_attention(
                wcfg, lp, q_nope, q_rope, rows, band, impl=cfg.swa_impl,
                name="swa_window_attention"), rows

        attend = dict(full=attend, window=attend_window)

    x, rows, routes, counts, wrows = _blocks(
        params, cfg, _embedded(params, tokens), attend)
    with scopes.block("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    kv = None
    if want_routes and cfg.index_topk:
        routes = (routes, _picked(cfg, rows))
    if want_kv:
        # With counts, the rows go to ``pages.write_sequences`` in the value
        # that hands the counts to the cache as well.
        rows, idx = _split_rows(cfg, rows)
        kv = ((rows if counts is None
               else state.Fresh(rows, None, None, None, *counts[:2],
                                idx=idx, win=wrows)),
              None)
    with scopes.block("head"):
        out = (x if want_hidden
               else x @ params["lm_head"]).astype(jnp.float32)
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
    with scopes.block("attn.proj"):
        cos, sin = rope_table(positions, cfg.qk_rope_head_dim, cfg.rope_theta,
                              cfg.rope_yarn)
    seq_lens = positions + 1
    pool = _pool_of(k_pages)
    idx_pool = k_pages.idx if cfg.index_topk else None
    with scopes.block("kv.write"):
        cur_slots = pages.token_slots(pool, block_tables, positions)

    def attend(lp, h, layer):
        q_nope, q_rope, row, *index = _project(cfg, lp, h, cos, sin)
        chosen = {}
        if index:
            # The lane's cached keys and the token's own, which competes
            # with them; the table's padding and the rows past the lane's
            # length are nobody's to see.
            (q_idx, w_idx), cur_key = index[0], _split_rows(cfg, row)[1]
            T = block_tables.shape[1] * pages.block_size(pool)
            with scopes.block("attn.index"):
                seen = jnp.concatenate(
                    [jnp.arange(T)[None, :] < positions[:, None],
                     jnp.ones((positions.shape[0], 1), bool)], axis=1)
                keep = _selected_of_lanes(
                    cfg, q_idx, w_idx, idx_pool, layer, block_tables,
                    seq_lens, cur_key, seen)
                chosen = dict(keep=keep[:, :T], cur_keep=keep[:, T])
            row = _with_picked(want_routes, row, keep)

        def paged(q, cur_row):
            return attention_fn(q, pool, layer, block_tables, seq_lens,
                                cur_row, value_dim=cfg.kv_lora_rank,
                                scale=_scale(cfg), **chosen)

        return absorbed_attention(cfg, lp, q_nope, q_rope,
                                  _split_rows(cfg, row)[0], paged), row

    win_pool = None
    if cfg.window_attn:
        wcfg, (wcos, wsin), window = _window_side(cfg, positions)
        win_pool, win_tables = k_pages.win, k_pages.wt

        def attend_window(lp, h, layer):
            q_nope, q_rope, row = _project(wcfg, lp, h, wcos, wsin)

            def paged(q, cur_row):
                return pages.window_decode_attention(
                    q, win_pool, layer, win_tables, seq_lens, cur_row,
                    value_dim=wcfg.kv_lora_rank, scale=_scale(wcfg),
                    window=window, impl=cfg.swa_impl)

            return absorbed_attention(wcfg, lp, q_nope, q_rope, row,
                                      paged), row

        attend = dict(full=attend, window=attend_window)

    # A padding lane's choices are nobody's (asked only by the form that reads
    # the chosen experts).
    x, rows, routes, counts, wrows = _blocks(
        params, cfg, _embedded(params, tokens), attend,
        real=(pages.lanes_in_use(block_tables)
              if cfg.moe_impl.startswith("chosen") else None))
    if want_routes and cfg.index_topk:
        routes = (routes, _picked(cfg, rows))
    with scopes.block("kv.write"):
        rows, idx_rows = _split_rows(cfg, rows)
        pool, _ = pages.write(pool, None, rows, None, *cur_slots)
        if idx_rows is not None:
            idx_pool, _ = pages.write(idx_pool, None, idx_rows, None,
                                      *cur_slots)
        if wrows is not None:
            win_pool, _ = pages.write(
                win_pool, None, wrows, None,
                *pages.token_slots(win_pool, win_tables, positions))
    k_pages = _kept(k_pages, pool, counts, idx_pool, win_pool)

    out = (_logits(params, cfg, x, active), k_pages, None)
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
    pool = _pool_of(k_pages)
    idx_pool = k_pages.idx if cfg.index_topk else None
    T = prior_table_row.shape[1] * pages.block_size(pool)

    positions = prefix_len[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    with scopes.block("attn.proj"):
        cos, sin = rope_table(positions, cfg.qk_rope_head_dim, cfg.rope_theta,
                              cfg.rope_yarn)
    prior_pos = jnp.arange(T, dtype=jnp.int32)[None, :]
    kv_pos = jnp.concatenate([prior_pos, positions], axis=1)        # [1, T+S]
    kv_valid = jnp.concatenate(
        [prior_pos < prefix_len[:, None],
         jnp.arange(S)[None, :] < suffix_len[:, None]], axis=1)
    mask = ((positions[:, :, None] >= kv_pos[:, None, :])
            & kv_valid[:, None, :])                                 # [1,S,T+S]

    def attend(lp, h, layer):
        q_nope, q_rope, rows, *index = _project(cfg, lp, h, cos, sin)
        own, own_keys = _split_rows(cfg, rows)
        with scopes.block("attn.expand"):    # the rows it carries out
            prior = pages.read_latent_prefix(pool, layer, prior_table_row,
                                             cfg.latent_dim)
            seen = jnp.concatenate([prior.astype(rows.dtype), own], axis=1)
        chosen = mask
        if index:
            with scopes.block("attn.index"):
                keys = jnp.concatenate(
                    [pages.read_latent_prefix(
                        idx_pool, layer, prior_table_row,
                        cfg.index_dim).astype(rows.dtype), own_keys], axis=1)
                chosen = _selected(cfg, *index, keys, mask)
            rows = _with_picked(want_routes, rows, chosen)
        return expanded_attention(cfg, lp, q_nope, q_rope, seen, chosen), rows

    win_pool = None
    if cfg.window_attn:
        # The window layers: the pages of their own pool that end where this
        # window starts, then its own rows, by the band.
        wcfg, (wcos, wsin), window = _window_side(cfg, positions)
        win_pool, win_table = k_pages.win, k_pages.wt
        ids, near_pos = pages.window_prefix_pages(
            win_table, prefix_len, pages.block_size(win_pool), window)
        near = jnp.concatenate([near_pos, positions], axis=1)
        band = ((positions[:, :, None] >= near[:, None, :])
                & (positions[:, :, None] - near[:, None, :] < window)
                & jnp.concatenate(
                    [near_pos < prefix_len[:, None],
                     jnp.arange(S)[None, :] < suffix_len[:, None]],
                    axis=1)[:, None, :])

        def attend_window(lp, h, layer):
            q_nope, q_rope, rows = _project(wcfg, lp, h, wcos, wsin)
            with scopes.block("attn.expand"):
                prior = pages.read_latent_prefix(win_pool, layer, ids,
                                                 wcfg.latent_dim)
                seen = jnp.concatenate([prior.astype(rows.dtype), rows],
                                       axis=1)
            return expanded_attention(
                wcfg, lp, q_nope, q_rope, seen, band, impl=cfg.swa_impl,
                name="swa_window_attention"), rows

        attend = dict(full=attend, window=attend_window)

    x, rows, routes, counts, wrows = _blocks(
        params, cfg, _embedded(params, tokens), attend)
    if want_routes and cfg.index_topk:
        routes = (routes, _picked(cfg, rows))
    with scopes.block("kv.write"):
        rows, idx_rows = _split_rows(cfg, rows)
        pool, _ = pages.write_sequences(
            pool, None, rows, None, block_table_row, suffix_len,
            start=prefix_len)
        if idx_rows is not None:
            idx_pool, _ = pages.write_sequences(
                idx_pool, None, idx_rows, None, block_table_row, suffix_len,
                start=prefix_len)
        if wrows is not None:
            win_pool, _ = pages.write_sequences(
                win_pool, None, wrows, None, win_table, suffix_len,
                start=prefix_len)
    k_pages = _kept(k_pages, pool, counts, idx_pool, win_pool)

    out = (_last_logits(params, cfg, x, suffix_len), k_pages, None)
    return (*out, routes) if want_routes else out
