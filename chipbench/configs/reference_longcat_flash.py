"""Plain reference for LongCat-Flash's decoder as LongCat-Flash-Omni's language
model publishes it (config.json: `num_layers`, `ffn_hidden_size`,
`expert_ffn_hidden_size`, `moe_topk`, `zero_expert_num`, `attention_method`
MLA; the layer is that of the LongCat-Flash technical report, arXiv:2509.01322):
the forward pass in straightforward jax.numpy and float32 for ONE sequence.
Expanded attention only, a Python loop over layers, sublayers and experts, no
cache, no scan, no kernels; it shares no code with the program.

A layer is a DOUBLE layer (x [S, 6144]; RMSNorm eps 1e-5, pre-norm residuals):

  for i in (0, 1):
      h = RMSNorm(x; ln_attn[i]);  x = x + MLA_i(h) W_o[i]
      h = RMSNorm(x; ln_mlp[i])
      if i == 0:  m = MoE(h)                      the shortcut: from sublayer 0
      x = x + SwiGLU_i(h)                         dense FFN, ffn_hidden_size
  x = x + m                                       after sublayer 1

MLA_i (64 heads at the published size; a low-rank query):
  c_q = RMSNorm(h W_qa; q_norm)                   [q_lora_rank]
  q   = (c_q W_qb) * sqrt(hidden / q_lora_rank)   (mla_scale_q_lora)
        -> a head is [q_nope d_nope | q_rope d_rope]
  h W_kva                    -> [c r | k_rope d_rope], ONE row for all heads
  c <- RMSNorm(c; kv_norm) * sqrt(hidden / kv_lora_rank)  (mla_scale_kv_lora)
  rotary(theta) on q_rope and k_rope only
  c W_kvb                    -> a head is [k_nope d_nope | v d_v]
  scores = (q_nope . k_nope + q_rope . k_rope) / sqrt(d_nope + d_rope)
  causal softmax in f32, out = sum p v, heads side by side, then W_o[i].
  (A cache would hold [c | k_rope] after the norm, the scale and the rotation;
  this file keeps no cache.)

MoE (512 experts and 256 zero-compute experts at the published size, 12 a
token, no shared expert):
  s = softmax(h W_r)         in f32, over all n_experts + zero_expert_num outputs
  chosen = the k outputs with the largest s + b   (b: `router_bias`, the
           published `e_score_correction_bias`: it selects, it does not weigh)
  g_j = routed_scaling_factor * s_j    NOT normalised over the chosen
           (`norm_topk_prob` absent from the published config: false)
  E_e(h) = SwiGLU_e(h) for e < n_experts;  E_e(h) = h for e >= n_experts
           (`zero_expert_type` identity)
  m = sum_j g_j E_chosen_j(h)
The weights hold the experts `first .. first + count` of the n_experts the
router scores (what one of the chips that share a layer holds); what the absent
experts would have added is left out, here as in the program. The zero-compute
experts' term needs no weights and is computed whatever the share.

Departures from the published implementation, each the program's too:
- Rotary pairing: the published code holds the rope columns interleaved and
  un-interleaves them at run time; the parameter tree holds them
  un-interleaved, so the rotation pairs column i with i + d_rope/2. A fixed
  permutation of W_qb's and W_kva's rope columns, the same on both sides of
  every dot product.
- The zero-compute experts' gates are summed in f32 and multiply h once; the
  published loop multiplies and adds a choice at a time.
- The audio and vision encoders and the codec decoder are not built: token
  ids only.

Weights are the program's parameter tree (that layout is the one thing the two
agree on): `embed`, `final_norm`, `lm_head`, and `layers`: the tensors a
sublayer has (`wqa`, `q_norm`, `wqb`, `wkva`, `kv_norm`, `wkvb`, `wo`,
`ln_attn`, `ln_mlp`, and the dense FFN's `w1d`, `w3d`, `w2d`) stacked a
sublayer, 2 L rows, sublayer i of layer l at row 2 l + i; the expert layer's a
layer, L rows: `router` [L, D, outputs], `router_bias` [L, outputs], and the
held experts' `w1`, `w3` [L, count, D, F], `w2`. On a TPU
a float32 matmul runs in lower precision unless told otherwise, so everything
runs under `highest`.

Sizes: attention takes its queries in blocks of `q_block` positions, an
expert's weights are cast to float32 one expert at a time, and `logits` is
given the rows of the hidden states it should carry to the vocabulary.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [S, heads, d]; position s rotates pair (i, i + d/2) by
    s * theta^(-i / (d/2))."""
    s, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def _attention(lp, h, *, n_heads, kv_lora_rank, qk_nope_head_dim,
               qk_rope_head_dim, rope_theta, norm_eps, scale_q, scale_kv,
               q_block):
    s, d_model = h.shape
    r, dn, dr = kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim
    c_q = _rms(h @ lp["wqa"], lp["q_norm"], norm_eps)
    q = c_q @ lp["wqb"]
    if scale_q:
        q = q * (d_model / c_q.shape[-1]) ** 0.5
    q = q.reshape(s, n_heads, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], rope_theta)
    kva = h @ lp["wkva"]
    c = _rms(kva[:, :r], lp["kv_norm"], norm_eps)
    if scale_kv:
        c = c * (d_model / r) ** 0.5
    k_rope = _rope(kva[:, None, r:], rope_theta)[:, 0]            # [S, dr]
    kv = (c @ lp["wkvb"]).reshape(s, n_heads, -1)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    pos = jnp.arange(s)
    outs = []
    for lo in range(0, s, q_block):
        hi = min(lo + q_block, s)
        scores = (jnp.einsum("qhd,khd->hqk", q_nope[lo:hi], k_nope)
                  + jnp.einsum("qhd,kd->hqk", q_rope[lo:hi], k_rope))
        scores = scores / (dn + dr) ** 0.5
        causal = pos[lo:hi, None] >= pos[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(outs).reshape(s, -1) @ lp["wo"]


def experts(lp, expert, first, count, n_experts, h, *, experts_per_token,
            routed_scaling_factor, forced=None, routed=True, zero=True):
    """(m [S, D], the router's outputs chosen [S, k]) of an expert layer whose
    weights hold experts ``first .. first + count`` of the ``n_experts`` the
    router scores ahead of its zero-compute outputs; ``expert(name, e)`` is
    held expert e's weight in float32 (e counted from ``first``). With
    ``forced`` [S, k] those outputs are taken in place of the layer's own
    choice, and the second result is each position's shortfall [S]: how far
    the worst forced output's ``s + b`` lies under this layer's own k-th
    best (0 where the choices agree). ``routed`` False leaves the held
    experts' part out and ``zero`` False the zero-compute experts' (the
    shares of one layer, taken apart)."""
    scores = jax.nn.softmax(h @ lp["router"], axis=-1)          # [S, outputs]
    biased = scores + lp["router_bias"]
    best, idx = jax.lax.top_k(biased, experts_per_token)
    told = idx
    if forced is not None:
        idx = forced
        told = jnp.maximum(best[:, -1] - jnp.min(
            jnp.take_along_axis(biased, idx, axis=-1), axis=-1), 0.0)
    gate = jnp.take_along_axis(scores, idx, axis=-1) * routed_scaling_factor
    m = jnp.zeros_like(h)
    for e in range(count if routed else 0):
        weight = jnp.sum(jnp.where(idx == first + e, gate, 0.0), axis=-1)
        m = m + _swiglu(h, expert("w1", e), expert("w3", e),
                        expert("w2", e)) * weight[:, None]
    if zero:
        weight = jnp.sum(jnp.where(idx >= n_experts, gate, 0.0), axis=-1)
        m = m + h * weight[:, None]
    return m, told


def hidden(params, tokens, *, n_heads: int, kv_lora_rank: int,
           qk_nope_head_dim: int, qk_rope_head_dim: int, rope_theta: float,
           norm_eps: float, experts_per_token: int,
           routed_scaling_factor: float, n_experts: int,
           first_expert: int = 0, scale_q: bool = True, scale_kv: bool = True,
           q_block: int = 512, routes=None):
    """(final-normed hidden states [S, D] in float32, the router's outputs
    each position chose in each layer [layers, S, k]) for one sequence of
    token ids [S]. ``n_experts`` is what the router scores ahead of its
    zero-compute outputs; the weights hold ``first_expert ..`` of them.

    ``routes`` [layers, S, k] forces the choices (the gates stay this file's
    own scores of them): with random weights the experts are unrelated
    functions, so one near-tie that a bf16 program parts the other way moves
    the logits of that position and of all that attend to it by as much as
    the logits themselves. Held to the program's choices the reference
    follows the program's history, what is left is rounding, and the second
    result is instead the shortfall of every forced choice [layers, S]
    (``experts``), which says whether each was a near-tie."""
    attn = dict(n_heads=n_heads, kv_lora_rank=kv_lora_rank,
                qk_nope_head_dim=qk_nope_head_dim,
                qk_rope_head_dim=qk_rope_head_dim, rope_theta=rope_theta,
                norm_eps=norm_eps, scale_q=scale_q, scale_kv=scale_kv,
                q_block=q_block)
    stack = params["layers"]
    held = ("w1", "w2", "w3")
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        told = []
        for layer in range(stack["router"].shape[0]):
            # The held experts' weights are taken and cast one expert at a
            # time (a layer's 16 in f32 are 1.2 GB at the published size).
            lp = {k: _f32(stack[k][layer]) for k in ("router", "router_bias")}
            for i in range(2):
                sub = {k: _f32(v[2 * layer + i]) for k, v in stack.items()
                       if k not in held and k not in lp}
                x = x + _attention(sub, _rms(x, sub["ln_attn"], norm_eps),
                                   **attn)
                h = _rms(x, sub["ln_mlp"], norm_eps)
                if i == 0:
                    m, idx = experts(
                        lp, lambda name, e: _f32(stack[name][layer, e]),
                        first_expert, stack["w1"].shape[1], n_experts, h,
                        experts_per_token=experts_per_token,
                        routed_scaling_factor=routed_scaling_factor,
                        forced=None if routes is None else routes[layer])
                    told.append(idx)
                x = x + _swiglu(h, sub["w1d"], sub["w3d"], sub["w2d"])
            x = x + m
        return _rms(x, _f32(params["final_norm"]), norm_eps), jnp.stack(told)


def logits(params, hidden_rows):
    """Hidden states [n, D] carried to the vocabulary: [n, vocab] float32."""
    head = params["lm_head"]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [_f32(hidden_rows) @ _f32(head[:, lo:lo + 32768])
             for lo in range(0, head.shape[1], 32768)], axis=1)


def forward(params, tokens, **sizes):
    """Logits [S, vocab] in float32 for one sequence of token ids [S]."""
    x, _ = hidden(params, tokens, **sizes)
    return logits(params, x)
