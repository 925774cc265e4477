"""Plain reference for dots3-note-prev's language model (config.json,
`model_type` dots3_note), cut as `dots3-note-prev-cut.json` cuts it: the
forward pass in straightforward jax.numpy and float32 for ONE sequence. Dense
scores under a mask, a Python loop over layers and over experts, no cache, no
scan, no kernels, no batching; it shares no code with the program.

Every layer is pre-norm (`h = RMSNorm(x)`, eps 1e-5): `x += W_o gate(attention
(h))`, then `x += ffn(RMSNorm(x))`. `layer_types[i]` says which attention.

Full layer (`full_attention`), DeepSeek-V3.2's block with a rescale and a gate:
  c_q = RMSNorm(h W_qa) sqrt(D / q_lora_rank)       (`q_norm`; the rescale, (a))
  q = c_q W_qb               -> a head is [q_nope 128 | q_rope 64], 128 heads
  h W_kva                    -> [c 512 | k_rope 64], ONE row for all heads
  c <- RMSNorm(c) sqrt(D / kv_lora_rank)            (`kv_norm`; (a))
  rotary on q_rope and k_rope, base rope_theta, no scaling
  c W_kvb                    -> a head is [k_nope 128 | v 128]
  scores = (q_nope . k_nope + q_rope . k_rope) 192^-0.5
  Indexer: q^I = c_q W_qb^I (64 heads of 128, the first 64 columns rotated),
      k^I = LayerNorm(h W_k^I) (weight, bias; first 64 rotated), w = (h W_w)
      (64 x 128)^-0.5, I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]);
      S_t = the min(index_topk, t + 1) positions s <= t with the largest I,
      ties to the lower position; softmax over S_t alone.
  (The indexer's query is projected from the RESCALED c_q: the latent the
  query itself is projected from. (a))
Window layer (`sliding_attention`): the same latent attention at the `swa_*`
widths (64 heads of [192 | 64], v 128, both ranks 1,024, base swa_rope_theta),
no indexer; the query at t attends to s with 0 <= t - s <= window - 1
(`sliding_window_size` counts the query's own position, (a)); scale 256^-0.5.
Gate, both kinds (`attention_gate_type` / `swa_attention_gate_type` headwise,
(a)): g = sigmoid(h W_g), one value a head from the layer's normed input;
head j's output is multiplied by g_j before W_o.

FFN. Layer 0 (the tree's `dense` stack): SwiGLU of `intermediate_size`. The
others:
  s = sigmoid(h W_r)         in f32, one score a router output (256)
  chosen = the 8 largest s + b  (`router_bias`; no groups: n_group absent, (a))
  g_i = s_i / sum_chosen s * routed_scaling_factor
  y = sum over chosen experts HELD HERE of g_i SwiGLU_i(h) + SwiGLU_shared(h)
The chip holds experts first_expert .. first_expert + (held count) of the
n_experts the router scores; what the others would have added is left out,
here as in the program.

Departures from the published implementation, each the program's too:
- The vision tower, the audio encoder and the MTP module are not built.
- Rotary pairing: column i with i + d_rope/2 everywhere; a fixed permutation
  of weight columns, the same on both sides of each dot product, which a
  checkpoint converter would apply.
- The published indexer's Hadamard rotation and FP8 quantisation are dropped
  (orthogonal; bf16 keys), as in reference_deepseek_v32.py.
- Weights are served in bf16; this reference upcasts them to float32.

Weights are the program's parameter tree: `embed`, `final_norm`, `lm_head`,
and three stacks with a leading layer axis: `dense` (the leading dense layers,
full attention), `layers` (the other full-attention layers) and `window` (the
window layers), taken in the order `layer_types` gives. Attention tensors:
`wqa`, `q_norm`, `wqb`, `wkva`, `kv_norm`, `wkvb`, `wg`, `wo`, `ln_attn`,
`ln_mlp`, and a full layer's indexer `wqb_idx`, `wk_idx`, `k_norm_idx`,
`k_bias_idx`, `w_idx`. Everything runs under `highest`.

Sizes: queries in blocks of `q_block` positions, so that scores are [heads,
q_block, S]; `logits` is given the rows it should carry to the vocabulary.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def inv_freq(d: int, theta: float):
    """The d/2 rotary frequencies (no scaling: ``rope_scaling`` null)."""
    return theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)


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


def _latent(lp, h, *, n_heads, kv_lora_rank, qk_nope_head_dim,
            qk_rope_head_dim, rope_theta, norm_eps, rescale):
    """A layer's queries, keys and values from its normed input h [S, D]:
    (c_q, q_nope [S, H, dn], q_rope, k_nope, k_rope [S, dr], v, the rotary
    frequencies)."""
    s, d_model = h.shape
    r, dn, dr = kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim
    freq = inv_freq(dr, rope_theta)
    c_q = _rms(h @ lp["wqa"], lp["q_norm"], norm_eps)
    kva = h @ lp["wkva"]
    c = _rms(kva[:, :r], lp["kv_norm"], norm_eps)
    if rescale:
        c_q = c_q * (d_model / c_q.shape[-1]) ** 0.5
        c = c * (d_model / r) ** 0.5
    q = (c_q @ lp["wqb"]).reshape(s, n_heads, dn + dr)
    k_rope = _rope(kva[:, None, r:], freq)[:, 0]                  # [S, dr]
    kv = (c @ lp["wkvb"]).reshape(s, n_heads, -1)
    return (c_q, q[..., :dn], _rope(q[..., dn:], freq), kv[..., :dn], k_rope,
            kv[..., dn:], freq)


def _attend(lp, h, q_nope, q_rope, k_nope, k_rope, v, seen, q_block, gate):
    """softmax over the rows ``seen(lo, hi)`` -> [hi - lo, S] bool says a
    block of queries attends to; the gate a head; then W_o."""
    s = h.shape[0]
    scale = (q_nope.shape[-1] + q_rope.shape[-1]) ** -0.5
    outs = []
    for lo in range(0, s, q_block):
        hi = min(lo + q_block, s)
        scores = (jnp.einsum("qhd,khd->hqk", q_nope[lo:hi], k_nope)
                  + jnp.einsum("qhd,kd->hqk", q_rope[lo:hi], k_rope)) * scale
        scores = jnp.where(seen(lo, hi)[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               jax.nn.softmax(scores, axis=-1), v))
    out = jnp.concatenate(outs)                                   # [S, H, dv]
    if gate:
        out = out * jax.nn.sigmoid(h @ lp["wg"])[:, :, None]
    return out.reshape(s, -1) @ lp["wo"]


def _full_attention(lp, h, *, widths, norm_eps, rescale, gate, index_n_heads,
                    index_head_dim, index_topk, q_block, picked=None):
    """(the attention's output [S, D], the share of this layer's own
    selection that ``picked`` also holds, or None). ``picked(lo, hi)`` ->
    [hi - lo, S] bool is a selection to attend by in place of this layer's
    own (the program's, see :func:`hidden`)."""
    s = h.shape[0]
    dr = widths["qk_rope_head_dim"]
    c_q, q_nope, q_rope, k_nope, k_rope, v, freq = _latent(
        lp, h, **widths, norm_eps=norm_eps, rescale=rescale)
    qi = (c_q @ lp["wqb_idx"]).reshape(s, index_n_heads, index_head_dim)
    qi = jnp.concatenate([_rope(qi[..., :dr], freq), qi[..., dr:]], -1)
    ki = h @ lp["wk_idx"]
    ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
    ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True)
                            + norm_eps)
    ki = ki * lp["k_norm_idx"] + lp["k_bias_idx"]
    ki = jnp.concatenate([_rope(ki[:, None, :dr], freq)[:, 0],
                          ki[:, dr:]], -1)
    wi = (h @ lp["w_idx"]) * (index_n_heads * index_head_dim) ** -0.5
    pos = jnp.arange(s)
    tally = dict(same=0.0, own=0.0)

    def seen(lo, hi):
        causal = pos[lo:hi, None] >= pos[None, :]
        index = jnp.einsum("qj,qjk->qk", wi[lo:hi], jax.nn.relu(
            jnp.einsum("qjd,kd->qjk", qi[lo:hi], ki)))
        own = selection(index, causal, index_topk)
        if picked is None:
            return own
        theirs = jnp.asarray(picked(lo, hi))
        tally["same"] += float(jnp.sum(own & theirs))
        tally["own"] += float(jnp.sum(own))
        return theirs

    out = _attend(lp, h, q_nope, q_rope, k_nope, k_rope, v, seen, q_block,
                  gate)
    return out, (tally["same"] / tally["own"] if picked is not None else None)


def _window_attention(lp, h, *, widths, norm_eps, rescale, gate, window,
                      q_block):
    """A window layer's output [S, D]: the query at t sees s with 0 <= t - s
    <= window - 1."""
    _, q_nope, q_rope, k_nope, k_rope, v, _ = _latent(
        lp, h, **widths, norm_eps=norm_eps, rescale=rescale)
    pos = jnp.arange(h.shape[0])

    def seen(lo, hi):
        behind = pos[lo:hi, None] - pos[None, :]
        return (behind >= 0) & (behind < window)

    return _attend(lp, h, q_nope, q_rope, k_nope, k_rope, v, seen, q_block,
                   gate)


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


def hidden(params, tokens, *, layer_types, full: dict, window: dict,
           sliding_window_size: int, norm_eps: float, rescale: bool,
           gate: bool, window_gate: bool, experts_per_token: int,
           routed_scaling_factor: float, index_n_heads: int,
           index_head_dim: int, index_topk: int, first_expert: int = 0,
           q_block: int = 512, routes=None, picked=None):
    """(final-normed hidden states [S, D] in float32, the outputs each
    position chose in each expert layer [n_expert_layers, S, k], and None)
    for one sequence of token ids [S]. ``layer_types`` is the published list
    as cut; ``full`` and ``window`` are each kind's widths (n_heads,
    kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, rope_theta).

    ``routes`` [n_expert_layers, S, k] forces the router's choices and
    ``picked(full layer, lo, hi)`` -> [hi - lo, S] bool the rows a full layer
    attends to, both for reference_deepseek_v32.py's reason: with random
    weights a near-tie that a bf16 program parts the other way moves that
    position's logits as a different model would. Held to the program's
    choices the reference follows the program's history; the choices are
    judged for what they are: the second result becomes the shortfall of every
    forced route [n_expert_layers, S], the third the share of each full
    layer's OWN selection that the program's also holds."""
    stacks = {k: params.get(k) for k in ("dense", "layers", "window")}
    taken = dict(dense=0, layers=0, window=0)
    n_dense = 0 if stacks["dense"] is None else stacks["dense"]["wo"].shape[0]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        told, shared, n_full = [], [], 0
        for i, kind in enumerate(layer_types):
            name = ("window" if kind == "sliding_attention" else
                    "dense" if i < n_dense else "layers")
            stack, at = stacks[name], taken[name]
            taken[name] += 1
            routed = "router" in stack
            # The routed experts' weights are taken and cast one expert at
            # a time.
            lp = {k: _f32(v[at]) for k, v in stack.items()
                  if not (routed and k in ("w1", "w2", "w3"))}
            h = _rms(x, lp["ln_attn"], norm_eps)
            if kind == "sliding_attention":
                a = _window_attention(
                    lp, h, widths=window, norm_eps=norm_eps, rescale=rescale,
                    gate=window_gate, window=sliding_window_size,
                    q_block=q_block)
            else:
                a, same = _full_attention(
                    lp, h, widths=full, norm_eps=norm_eps, rescale=rescale,
                    gate=gate, index_n_heads=index_n_heads,
                    index_head_dim=index_head_dim, index_topk=index_topk,
                    q_block=q_block,
                    picked=(None if picked is None else
                            (lambda lo, hi, n=n_full: picked(n, lo, hi))))
                shared.append(same)
                n_full += 1
            x = x + a
            h = _rms(x, lp["ln_mlp"], norm_eps)
            if routed:
                y, idx = _experts(
                    lp, lambda w, e, stack=stack, at=at: _f32(stack[w][at, e]),
                    stack["w1"].shape[1], first_expert, h,
                    experts_per_token=experts_per_token,
                    routed_scaling_factor=routed_scaling_factor,
                    n_group=1, topk_group=1,
                    forced=None if routes is None else routes[len(told)])
                told.append(idx)
            else:
                y = _swiglu(h, lp["w1"], lp["w3"], lp["w2"])
            x = x + y
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
