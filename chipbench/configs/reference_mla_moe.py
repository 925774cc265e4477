"""Plain reference for the DeepSeek-V3-family block as Kimi-VL-A3B-Instruct's
language model publishes it (config.json, `text_config`): the forward pass in
straightforward jax.numpy and float32 for ONE sequence. Expanded attention
only, a Python loop over layers and over experts, no cache, no scan, no
kernels; it shares no code with the program.

The layer, as published (`h = RMSNorm(x)`, eps 1e-5, pre-norm residual
blocks, `x += attention(h)` then `x += ffn(RMSNorm(x))`):

Attention (16 heads at the published size).
  q = h W_q                  -> a head is [q_nope d_nope | q_rope d_rope]
  h W_kva                    -> [c r | k_rope d_rope], ONE row for all heads
  c <- RMSNorm(c)            (its own weight, `kv_norm`)
  rotary(theta) on q_rope and k_rope only
  c W_kvb                    -> a head is [k_nope d_nope | v d_v]
  scores = (q_nope . k_nope + q_rope . k_rope) / sqrt(d_nope + d_rope)
  causal softmax in f32, out = sum p v, heads side by side, then W_o.
  (A cache would hold [c | k_rope] after the norm and the rotation, nothing
  else; this file keeps no cache.)

FFN. The first `first_k_dense` layers (the tree's `dense` stack): SwiGLU,
`(silu(h W_1) * (h W_3)) W_2`. The others (the `layers` stack):
  s = sigmoid(h W_r)         in f32, one score an expert
  chosen = the k experts with the largest s + b   (b: `router_bias`; with
           n_group 1 the published group limit is the identity)
  g_i = s_i / sum_chosen s * routed_scaling_factor   (the bias selects, it
           does not weigh; norm_topk_prob true)
  y = sum_i g_i SwiGLU_i(h) + SwiGLU_shared(h)   (shared width = n_shared x
           the expert width, one SwiGLU: `w1s`, `w3s`, `w2s`)

Departures from the published implementation, each the program's too:
- Rotary pairing. The published code holds the rope columns of q and k
  interleaved (pair (2i, 2i+1)) and un-interleaves them at run time before a
  rotate-half. The parameter tree holds them already un-interleaved, so the
  rotation here pairs column i with i + d_rope/2. That is a fixed permutation
  of W_q's and W_kva's rope columns, the same on both sides of the dot
  product, so every score is the same; a checkpoint converter would apply it.
- The embedding and the LM head are separate tensors.
- The vision tower and its projector are not built: token ids only.

Weights are the program's parameter tree (that layout is the one thing the
two agree on): `embed`, `final_norm`, `lm_head`, and two stacks with a
leading layer axis, `dense` and `layers`, whose attention tensors are `wq`,
`wkva`, `kv_norm`, `wkvb`, `wo`, `ln_attn`, `ln_mlp`. On a TPU a float32
matmul runs in lower precision unless told otherwise, so everything runs
under `highest`.

Sizes: `hidden` takes the queries in blocks of `q_block` positions (scores are
[heads, q_block, S]), and `logits` is given the rows of the hidden states it
should carry to the vocabulary, so that 4,096 tokens at the published widths
(a [4096, 163840] f32 logit matrix would be 2.7 GB) fit beside the program.
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
               qk_rope_head_dim, rope_theta, norm_eps, q_block):
    s = h.shape[0]
    r, dn, dr = kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim
    q = (h @ lp["wq"]).reshape(s, n_heads, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], rope_theta)
    kva = h @ lp["wkva"]
    c = _rms(kva[:, :r], lp["kv_norm"], norm_eps)
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


def _experts(lp, expert, n_experts, h, *, experts_per_token,
             routed_scaling_factor, forced=None):
    """(y [S, D], the chosen experts [S, k]) of an expert layer;
    ``expert(name, e)`` is expert e's weight in float32. With ``forced``
    [S, k] those experts are taken in place of the layer's own choice, and
    the second result is each position's shortfall [S]: how far the worst
    forced expert's ``s + b`` lies under this layer's own k-th best (0 where
    the choices agree)."""
    scores = jax.nn.sigmoid(h @ lp["router"])                      # [S, E]
    biased = scores + lp["router_bias"]
    best, idx = jax.lax.top_k(biased, experts_per_token)
    told = idx
    if forced is not None:
        idx = forced
        told = jnp.maximum(best[:, -1] - jnp.min(
            jnp.take_along_axis(biased, idx, axis=-1), axis=-1), 0.0)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gate = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    gate = gate * routed_scaling_factor
    y = _swiglu(h, lp["w1s"], lp["w3s"], lp["w2s"])
    for e in range(n_experts):
        weight = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)   # [S]
        y = y + _swiglu(h, expert("w1", e), expert("w3", e),
                        expert("w2", e)) * weight[:, None]
    return y, told


def hidden(params, tokens, *, n_heads: int, kv_lora_rank: int,
           qk_nope_head_dim: int, qk_rope_head_dim: int, rope_theta: float,
           norm_eps: float, experts_per_token: int,
           routed_scaling_factor: float, q_block: int = 512, routes=None):
    """(final-normed hidden states [S, D] in float32, the experts each
    position chose in each expert layer [n_expert_layers, S, k]) for one
    sequence of token ids [S].

    ``routes`` [n_expert_layers, S, k] forces the experts (the gates stay
    this file's own scores of them): with random weights the experts are
    unrelated functions, so one near-tie that a bf16 program parts the other
    way moves the logits of that position and of all that attend to it by as
    much as the logits themselves. Held to the program's choices the
    reference follows the program's history, what is left is rounding, and
    the second result is instead the shortfall of every forced choice
    [n_expert_layers, S] (``_experts``), which says whether each was a
    near-tie."""
    attn = dict(n_heads=n_heads, kv_lora_rank=kv_lora_rank,
                qk_nope_head_dim=qk_nope_head_dim,
                qk_rope_head_dim=qk_rope_head_dim, rope_theta=rope_theta,
                norm_eps=norm_eps, q_block=q_block)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        told = []
        for name in ("dense", "layers"):
            stack = params.get(name)
            if stack is None:
                continue
            routed = "router" in stack
            for i in range(stack["wq"].shape[0]):
                # The routed experts' weights are taken and cast one expert
                # at a time (a layer of them in f32 is 2.2 GB at the
                # published size).
                lp = {k: _f32(v[i]) for k, v in stack.items()
                      if not (routed and k in ("w1", "w2", "w3"))}
                x = x + _attention(lp, _rms(x, lp["ln_attn"], norm_eps), **attn)
                h = _rms(x, lp["ln_mlp"], norm_eps)
                if routed:
                    y, idx = _experts(
                        lp, lambda name, e: _f32(stack[name][i, e]),
                        stack["w1"].shape[1], h,
                        experts_per_token=experts_per_token,
                        routed_scaling_factor=routed_scaling_factor,
                        forced=None if routes is None else routes[len(told)])
                    told.append(idx)
                else:
                    y = _swiglu(h, lp["w1"], lp["w3"], lp["w2"])
                x = x + y
        return _rms(x, _f32(params["final_norm"]), norm_eps), jnp.stack(told)


def logits(params, hidden_rows):
    """Hidden states [n, D] carried to the vocabulary: [n, vocab] float32."""
    head = params["lm_head"]
    with jax.default_matmul_precision("highest"):
        # The head is cast a slice of the vocabulary at a time (1.3 GB in
        # f32 at the published size).
        return jnp.concatenate(
            [_f32(hidden_rows) @ _f32(head[:, lo:lo + 32768])
             for lo in range(0, head.shape[1], 32768)], axis=1)


def forward(params, tokens, **sizes):
    """Logits [S, vocab] in float32 for one sequence of token ids [S]."""
    x, _ = hidden(params, tokens, **sizes)
    return logits(params, x)
