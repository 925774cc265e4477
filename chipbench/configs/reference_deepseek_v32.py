"""Plain reference for DeepSeek-V3.2-Exp's block (config.json, `model_type`
deepseek_v32): the forward pass in straightforward jax.numpy and float32 for
ONE sequence. Dense scores and a selection mask, a Python loop over layers and
over experts, no cache, no scan, no kernels; it shares no code with the
program.

The layer (`h = RMSNorm(x)`, eps 1e-6, pre-norm residual blocks, `x +=
attention(h)` then `x += ffn(RMSNorm(x))`):

Attention (128 heads at the published size).
  c_q = RMSNorm(h W_qa)      (q_lora_rank values, its own weight `q_norm`)
  q = c_q W_qb               -> a head is [q_nope d_nope | q_rope d_rope]
  h W_kva                    -> [c r | k_rope d_rope], ONE row for all heads
  c <- RMSNorm(c)            (`kv_norm`)
  rotary on q_rope and k_rope, YaRN frequencies:
      f_i = theta^(-2i/d);  low = floor(d ln(orig / (beta_fast 2 pi)) / (2 ln theta)),
      high = ceil(d ln(orig / (beta_slow 2 pi)) / (2 ln theta)), clipped to [0, d-1];
      g_i = clip((i - low) / (high - low), 0, 1);  inv_freq_i = f_i (1 - g_i) + f_i / factor g_i
  c W_kvb                    -> a head is [k_nope d_nope | v d_v]
  scores = (q_nope . k_nope + q_rope . k_rope) (d_nope + d_rope)^-0.5 m^2,
      m = 0.1 mscale_all_dim ln(factor) + 1  (cos/sin unscaled)
Indexer (64 heads of 128 at the published size), which says WHICH rows:
  q^I = c_q W_qb^I           -> a head's first d_rope columns rotated (same table)
  k^I = LayerNorm(h W_k^I)   (weight `k_norm_idx`, bias `k_bias_idx`), first d_rope rotated
  w = (h W_w) (heads x head_dim)^-0.5
  I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])     for s <= t
  S_t = the min(index_topk, t + 1) positions s <= t with the largest I[t, s],
      ties to the lower position
  softmax over S_t alone (every other row minus infinity), out = sum p v,
  heads side by side, then W_o.

FFN. The first `first_k_dense` layers (the tree's `dense` stack): SwiGLU. The
others (`layers`):
  s = sigmoid(h W_r)         in f32, one score a router output
  s' = s + b                 (`router_bias`); the outputs lie in n_group
      groups; a group scores the sum of its two largest s'; the topk_group
      best groups stay open; chosen = the k largest s' inside them
  g_i = s_i / sum_chosen s * routed_scaling_factor
  y = sum over chosen experts HELD HERE of g_i SwiGLU_i(h) + SwiGLU_shared(h)
The chip holds experts first_expert .. first_expert + (held count) of the
n_experts the router scores (the tree's expert stacks are that long); what
the others would have added is left out, here as in the program.

Departures from the published implementation, each the program's too:
- Rotary pairing: column i with i + d_rope/2 in the attention AND in the
  indexer (the published code pairs neighbours in the attention and halves in
  the indexer); a fixed permutation of weight columns, the same on both sides
  of each dot product, which a checkpoint converter would apply.
- The published indexer rotates q^I and k^I by a Hadamard matrix and
  quantises them to FP8 with a scale a token. The rotation is orthogonal and
  leaves q . k as it is; neither is done here, nor in the program.
- The multi-token-prediction module is not built.

Weights are the program's parameter tree: `embed`, `final_norm`, `lm_head`,
and two stacks with a leading layer axis, `dense` and `layers`, whose
attention tensors are `wqa`, `q_norm`, `wqb`, `wkva`, `kv_norm`, `wkvb`, `wo`,
`ln_attn`, `ln_mlp`, and the indexer's `wqb_idx`, `wk_idx`, `k_norm_idx`,
`k_bias_idx`, `w_idx`. Everything runs under `highest`.

Sizes: queries in blocks of `q_block` positions, so that scores are [heads,
q_block, S]; `logits` is given the rows it should carry to the vocabulary.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_inv_freq(d: int, theta: float, yarn):
    """The d/2 rotary frequencies; ``yarn`` = (factor, original context,
    beta_fast, beta_slow, mscale_all_dim) or empty for plain rotary."""
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / d)
    if not yarn:
        return f
    factor, orig, fast, slow = yarn[:4]
    low = max(math.floor(d * math.log(orig / (fast * 2 * math.pi))
                         / (2 * math.log(theta))), 0)
    high = min(math.ceil(d * math.log(orig / (slow * 2 * math.pi))
                         / (2 * math.log(theta))), d - 1)
    g = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return f * (1.0 - g) + (f / factor) * g


def _rope(x, inv_freq):
    """x: [S, heads, d]; position s rotates pair (i, i + d/2) by
    s * inv_freq[i]."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(h, w1, w3, w2, rows: int = 2048):
    """In blocks of rows: 16k tokens at a width of 18,432 are 1.2 GB a
    product."""
    return jnp.concatenate([
        (jax.nn.silu(h[lo:lo + rows] @ w1) * (h[lo:lo + rows] @ w3)) @ w2
        for lo in range(0, h.shape[0], rows)])


def selection(scores, seen, k):
    """[Q, S] bool: of the rows ``seen`` [Q, S], the min(k, rows seen) with
    the largest ``scores``, ties to the lower position (a stable descending
    sort)."""
    # (A seen row is ahead of every unseen one whatever it scores.)
    order = jnp.argsort(-jnp.where(seen, jnp.maximum(scores, -3e38),
                                   -jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < k) & seen


def _attention(lp, h, *, n_heads, kv_lora_rank, qk_nope_head_dim,
               qk_rope_head_dim, rope_theta, rope_yarn, norm_eps,
               index_n_heads, index_head_dim, index_topk, q_block,
               picked=None):
    """(the attention's output [S, D], the share of this layer's own
    selection that ``picked`` also holds, or None). ``picked(lo, hi)`` ->
    [hi - lo, S] bool is a selection to attend by in place of this layer's
    own (the program's, see :func:`hidden`)."""
    s = h.shape[0]
    r, dn, dr = kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim
    inv_freq = yarn_inv_freq(dr, rope_theta, rope_yarn)
    c_q = _rms(h @ lp["wqa"], lp["q_norm"], norm_eps)
    q = (c_q @ lp["wqb"]).reshape(s, n_heads, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], inv_freq)
    kva = h @ lp["wkva"]
    c = _rms(kva[:, :r], lp["kv_norm"], norm_eps)
    k_rope = _rope(kva[:, None, r:], inv_freq)[:, 0]              # [S, dr]
    kv = (c @ lp["wkvb"]).reshape(s, n_heads, -1)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = (dn + dr) ** -0.5
    if rope_yarn:
        scale *= (0.1 * rope_yarn[4] * math.log(rope_yarn[0]) + 1.0) ** 2

    # The indexer.
    qi = (c_q @ lp["wqb_idx"]).reshape(s, index_n_heads, index_head_dim)
    qi = jnp.concatenate([_rope(qi[..., :dr], inv_freq), qi[..., dr:]], -1)
    ki = h @ lp["wk_idx"]
    ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
    ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True)
                            + norm_eps)
    ki = ki * lp["k_norm_idx"] + lp["k_bias_idx"]
    ki = jnp.concatenate([_rope(ki[:, None, :dr], inv_freq)[:, 0],
                          ki[:, dr:]], -1)
    wi = (h @ lp["w_idx"]) * (index_n_heads * index_head_dim) ** -0.5

    pos = jnp.arange(s)
    outs, same, own_rows = [], 0.0, 0.0
    for lo in range(0, s, q_block):
        hi = min(lo + q_block, s)
        causal = pos[lo:hi, None] >= pos[None, :]
        index = jnp.einsum("qj,qjk->qk", wi[lo:hi], jax.nn.relu(
            jnp.einsum("qjd,kd->qjk", qi[lo:hi], ki)))
        own = selection(index, causal, index_topk)
        if picked is not None:
            theirs = jnp.asarray(picked(lo, hi))
            same += float(jnp.sum(own & theirs))
            own_rows += float(jnp.sum(own))
            own = theirs
        scores = (jnp.einsum("qhd,khd->hqk", q_nope[lo:hi], k_nope)
                  + jnp.einsum("qhd,kd->hqk", q_rope[lo:hi], k_rope)) * scale
        scores = jnp.where(own[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               jax.nn.softmax(scores, axis=-1), v))
    out = jnp.concatenate(outs).reshape(s, -1) @ lp["wo"]
    return out, (same / own_rows if picked is not None else None)


def route(lp, h, *, experts_per_token, n_group, topk_group):
    """(scores [S, E], the chosen outputs [S, k], the biased scores [S, E]
    with the closed groups' at minus infinity, and how far each output's
    group lies under the last group that stayed open [S, E]: 0 in an open
    group, and everywhere without groups)."""
    scores = jax.nn.sigmoid(h @ lp["router"])
    biased = scores + lp["router_bias"]
    under = jnp.zeros_like(biased)
    if n_group > 1:
        groups = biased.reshape(biased.shape[0], n_group, -1)
        group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
        best, open_groups = jax.lax.top_k(group_score, topk_group)    # [S, g]
        is_open = jnp.any(open_groups[:, :, None]
                          == jnp.arange(n_group)[None, None, :], axis=1)
        biased = jnp.where(is_open[:, :, None], groups,
                           -jnp.inf).reshape(biased.shape)
        under = jnp.repeat(jnp.maximum(best[:, -1:] - group_score, 0.0),
                           groups.shape[-1], axis=-1)
    return (scores, jax.lax.top_k(biased, experts_per_token)[1], biased,
            under)


def _experts(lp, expert, n_held, first_expert, h, *, experts_per_token,
             routed_scaling_factor, n_group, topk_group, forced=None):
    """(y [S, D], the chosen outputs [S, k]) of an expert layer;
    ``expert(name, e)`` is HELD expert e's weight in float32. With ``forced``
    [S, k] those outputs are taken in place of the layer's own choice, and
    the second result is each position's shortfall [S] (0 where the choices
    agree), the larger of two, because a choice is made twice. The groups: a
    forced output in a group this layer closed has that group's score (the
    sum of its two largest ``s + b``) under the last open group's by so much.
    The outputs: with the forced outputs' groups taken as open (and this
    layer's own best groups beside them, up to topk_group), how far the worst
    forced ``s + b`` lies under the k-th best of those groups. (Judged against
    this layer's OWN groups, one group parted the other way at a near-tie
    would count every output that took the lost group's places as far off.)
    Infinite where the forced outputs lie in more than topk_group groups."""
    scores, idx, biased, under = route(
        lp, h, experts_per_token=experts_per_token, n_group=n_group,
        topk_group=topk_group)
    told = idx
    if forced is not None:
        idx = forced
        raw = (scores + lp["router_bias"]).reshape(scores.shape[0], n_group, -1)
        in_theirs = jnp.any((forced // raw.shape[-1])[:, :, None]
                            == jnp.arange(n_group)[None, None, :], axis=1)
        group_score = jnp.sum(jax.lax.top_k(raw, min(2, raw.shape[-1]))[0],
                              axis=-1)
        taken = jax.lax.top_k(jnp.where(in_theirs, jnp.inf, group_score),
                              topk_group)[1]
        is_open = jnp.any(taken[:, :, None]
                          == jnp.arange(n_group)[None, None, :], axis=1)
        among = jnp.where(is_open[:, :, None], raw, -jnp.inf).reshape(
            scores.shape)
        kth = jax.lax.top_k(among, experts_per_token)[0][:, -1]
        worst = jnp.min(jnp.take_along_axis(among, forced, axis=-1), axis=-1)
        told = jnp.maximum(
            jnp.maximum(kth - worst, 0.0),
            jnp.max(jnp.take_along_axis(under, forced, axis=-1), axis=-1))
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gate = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    gate = gate * routed_scaling_factor
    y = _swiglu(h, lp["w1s"], lp["w3s"], lp["w2s"])
    for e in range(n_held):
        weight = jnp.sum(jnp.where(idx == first_expert + e, gate, 0.0),
                         axis=-1)                                    # [S]
        y = y + _swiglu(h, expert("w1", e), expert("w3", e),
                        expert("w2", e)) * weight[:, None]
    return y, told


def hidden(params, tokens, *, n_heads: int, kv_lora_rank: int,
           qk_nope_head_dim: int, qk_rope_head_dim: int, rope_theta: float,
           rope_yarn, norm_eps: float, experts_per_token: int,
           routed_scaling_factor: float, n_group: int, topk_group: int,
           index_n_heads: int, index_head_dim: int, index_topk: int,
           first_expert: int = 0, q_block: int = 512, routes=None,
           picked=None):
    """(final-normed hidden states [S, D] in float32, the outputs each
    position chose in each expert layer [n_expert_layers, S, k], and None)
    for one sequence of token ids [S].

    ``routes`` [n_expert_layers, S, k] forces the router's choices and
    ``picked(layer, lo, hi)`` -> [hi - lo, S] bool the rows attended to, both
    for one reason: with random weights the experts are unrelated functions
    and a row's index score says nothing of its attention weight, so a
    near-tie that a bf16 program parts the other way moves that position's
    logits, and those of all that attend to it, by as much as a different
    model would. Held to the program's choices the reference follows the
    program's history and what is left is rounding; the choices are judged
    for what they are: the second result becomes the shortfall of every
    forced route [n_expert_layers, S] (``_experts``), the third the share of
    each layer's OWN selection that the program's also holds [n_layers]."""
    attn = dict(n_heads=n_heads, kv_lora_rank=kv_lora_rank,
                qk_nope_head_dim=qk_nope_head_dim,
                qk_rope_head_dim=qk_rope_head_dim, rope_theta=rope_theta,
                rope_yarn=tuple(rope_yarn), norm_eps=norm_eps,
                index_n_heads=index_n_heads, index_head_dim=index_head_dim,
                index_topk=index_topk, q_block=q_block)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        told, shared, layer = [], [], 0
        for name in ("dense", "layers"):
            stack = params.get(name)
            if stack is None:
                continue
            routed = "router" in stack
            for i in range(stack["wo"].shape[0]):
                # The routed experts' weights are taken and cast one expert
                # at a time.
                lp = {k: _f32(v[i]) for k, v in stack.items()
                      if not (routed and k in ("w1", "w2", "w3"))}
                a, same = _attention(
                    lp, _rms(x, lp["ln_attn"], norm_eps), **attn,
                    picked=(None if picked is None else
                            (lambda lo, hi, layer=layer: picked(layer, lo, hi))))
                shared.append(same)
                x = x + a
                h = _rms(x, lp["ln_mlp"], norm_eps)
                if routed:
                    y, idx = _experts(
                        lp, lambda name, e: _f32(stack[name][i, e]),
                        stack["w1"].shape[1], first_expert, h,
                        experts_per_token=experts_per_token,
                        routed_scaling_factor=routed_scaling_factor,
                        n_group=n_group, topk_group=topk_group,
                        forced=None if routes is None else routes[len(told)])
                    told.append(idx)
                else:
                    y = _swiglu(h, lp["w1"], lp["w3"], lp["w2"])
                x = x + y
                layer += 1
        return (_rms(x, _f32(params["final_norm"]), norm_eps),
                jnp.stack(told), None if picked is None else shared)


def logits(params, hidden_rows):
    """Hidden states [n, D] carried to the vocabulary: [n, vocab] float32."""
    head = params["lm_head"]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [_f32(hidden_rows) @ _f32(head[:, lo:lo + 32768])
             for lo in range(0, head.shape[1], 32768)], axis=1)


def forward(params, tokens, **sizes):
    """Logits [S, vocab] in float32 for one sequence of token ids [S]."""
    x, _, _ = hidden(params, tokens, **sizes)
    return logits(params, x)
