"""Plain reference for the lfm2_moe block as LiquidAI's LFM2-8B-A1B publishes
it (config.json, `model_type` `lfm2_moe`, and the family's modelling code):
the forward pass in straightforward jax.numpy and float32 for ONE sequence.
The convolution as three shifted products, full causal attention with 8 KV
heads of 64, the router in f32, every expert of a layer for every token, no
cache, no kernels, no batching; it shares no code with the program.

Layer i is a convolution or an attention layer as `layer_types[i]` says.
Every layer:

  h   = x + Mixer(RMSNorm(x; operator_norm))
  out = h + FFN(RMSNorm(h; ffn_norm))

eps 1e-5, no bias anywhere; a final RMSNorm (`embedding_norm`); the head is
the embedding transposed (the family ties them).

Convolution mixer, input n [T, H]:
  [B | C | u] = n W_in                   H -> 3 H, the thirds in that order
  g = B * u
  c_t = sum_(j<3) w[j] * g_(t-2+j)       depth-wise, causal, `conv_L_cache` 3
                                         wide, zeros before the first row
  out = (C * c) W_out
No activation and no recurrent state: what a cache would keep a layer is the
last two rows of g (`want_tail` returns them).

Attention mixer (32 query heads on 8 KV heads of 64):
  q, k, v = n W_q, n W_k, n W_v;  q and k: RMSNorm a head with a learned
  weight of 64, then rotary (theta 1e6, the halves paired: rotate-half);
  causal softmax(q k^T / 8) v;  W_o.

FFN: layers below `num_dense_layers` W_down(silu(W_gate n) * (W_up n)), 7,168
wide; the others 32 routed experts of the same form 1,792 wide, 4 a token, no
shared expert:
  s = sigmoid(n W_r)                     f32
  chosen = the 4 largest of s + expert_bias    (the bias selects only)
  gate_e = s_e / (sum over the chosen of s + 1e-6) * routed_scaling_factor
  out = sum over the chosen of gate_e Expert_e(n)

Departures from the published description, each noted where it matters:
- the 1e-6 in the gates' sum is from memory of the modelling file (listed
  under `assumed` in the configuration file); the PROGRAM leaves it out
  (models/routing.route), a relative 1e-6 of a gate;
- `routes` (below) holds the reference to the program's expert choices.

Weights are the program's parameter tree (that layout is the one thing the
two agree on): `embed`, `final_norm`, and the stacks `conv` (`ln`, `w_in`,
`conv_w` [3, H], `w_out`), `attn` (`ln`, `wq`, `wk`, `wv`, `wo`, `q_norm`,
`k_norm`), `ffn` (`ln_mlp`, `w1` gate, `w3` up, `w2` down: the dense layers)
and `experts` (`ln_mlp`, `router`, `router_bias`, `w1`, `w3`, `w2` with an
experts axis: the others), a row a layer of its kind in layer order. The head
reads `embed`, not the program's `lm_head`: a program whose head were not the
embedding transposed would differ. On a TPU a float32 matmul runs in lower
precision unless told otherwise, so everything runs under `highest`.

Switches that are controls of the comparison, each of which has to fail it
(scripts/compare_lfm2_reference.py): `swap_bc` exchanges B and C, `qk_norm`
False leaves the heads' norms out, `rotary` False leaves q and k unrotated,
`router_bf16` rounds the router's scores to bf16 before the choice,
`bias_in_gates` weighs the chosen experts by s + expert_bias.

`routes` [expert layers, T, 4]: the experts the PROGRAM chose. With random
weights a token's fourth and fifth scores lie a rounding apart often enough
that a bf16 program and an f32 reference part ways somewhere in every
prompt, and from there on they compute different functions. Given `routes`
the reference computes the gates itself, from its own scores, for those
experts, and reports how far under its own choice's threshold the weakest of
them lies (`want_shortfall`): a program that chose by anything else than
s + expert_bias reads far from 0 there.

Sizes: attention takes its queries in blocks of `q_block` positions, an
expert layer casts its weights up an expert at a time, and `logits` is given
the rows of the hidden states it should carry to the vocabulary.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _conv(lp, n, *, swap_bc=False):
    """n [T, H] -> ([T, H], the last two rows of g)."""
    t_len, width = n.shape
    bcu = n @ lp["w_in"]
    b, c, u = bcu[:, :width], bcu[:, width:2 * width], bcu[:, 2 * width:]
    if swap_bc:
        b, c = c, b
    g = b * u
    taps = lp["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, width)), g])
    conv = sum(padded[j:j + t_len] * lp["conv_w"][j] for j in range(taps))
    return (c * conv) @ lp["w_out"], padded[t_len:]


def _rotated(x, theta):
    """x [T, heads, head_dim] rotated by position (halves paired)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half) / half)
    angle = jnp.arange(x.shape[0])[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def _attention(lp, n, *, n_heads, n_kv_heads, head_dim, rope_theta, norm_eps,
               q_block, qk_norm=True, rotary=True):
    s = n.shape[0]
    q = (n @ lp["wq"]).reshape(s, n_heads, head_dim)
    k = (n @ lp["wk"]).reshape(s, n_kv_heads, head_dim)
    if qk_norm:
        q = _rms(q, lp["q_norm"], norm_eps)
        k = _rms(k, lp["k_norm"], norm_eps)
    if rotary:
        q, k = _rotated(q, rope_theta), _rotated(k, rope_theta)
    rep = n_heads // n_kv_heads
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat((n @ lp["wv"]).reshape(s, n_kv_heads, head_dim), rep, axis=1)
    pos = jnp.arange(s)
    outs = []
    for lo in range(0, s, q_block):
        hi = min(lo + q_block, s)
        scores = jnp.einsum("qhd,khd->hqk", q[lo:hi], k) / head_dim ** 0.5
        scores = jnp.where((pos[lo:hi, None] >= pos[None, :])[None], scores,
                           -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(outs).reshape(s, -1) @ lp["wo"]


def _swiglu(n, w1, w3, w2):
    return (jax.nn.silu(n @ w1) * (n @ w3)) @ w2


def _experts(stack, row, n, *, top_k, scaling, routes=None,
             router_bf16=False, bias_in_gates=False):
    """n [T, H] through expert layer ``row`` of the stacked ``experts``:
    ([T, H], how far under its own choice's threshold the weakest forced
    choice lies, 0.0 without ``routes``)."""
    bias = _f32(stack["router_bias"][row])
    scores = jax.nn.sigmoid(n @ _f32(stack["router"][row]))
    if router_bf16:
        scores = scores.astype(jnp.bfloat16).astype(jnp.float32)
    biased = scores + bias
    own_values, chosen = jax.lax.top_k(biased, top_k)
    shortfall = jnp.zeros(())
    if routes is not None:
        chosen = routes
        forced = jnp.take_along_axis(biased, chosen, axis=-1)
        shortfall = jnp.max(jnp.maximum(
            own_values[:, -1] - jnp.min(forced, axis=-1), 0.0))
    weigh = jnp.take_along_axis(biased if bias_in_gates else scores, chosen,
                                axis=-1)
    gates = weigh / (jnp.sum(weigh, axis=-1, keepdims=True) + 1e-6) * scaling
    n_experts = bias.shape[0]
    out = jnp.zeros_like(n)
    for e in range(n_experts):       # every expert, its weights cast up alone
        weight = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
        out = out + weight[:, None] * _swiglu(
            n, *(_f32(stack[w][row, e]) for w in ("w1", "w3", "w2")))
    return out, shortfall


def hidden(params, tokens, *, layer_types, num_dense_layers: int,
           n_heads: int, n_kv_heads: int, head_dim: int, top_k: int,
           rope_theta: float, norm_eps: float, scaling: float = 1.0,
           q_block: int = 512, routes=None, want_tail: bool = False,
           want_shortfall: bool = False, swap_bc: bool = False,
           qk_norm: bool = True, rotary: bool = True,
           router_bf16: bool = False, bias_in_gates: bool = False):
    """Final-normed hidden states [T, H] in float32 for one sequence of token
    ids [T]; ``layer_types`` a sequence of "conv" / "full_attention".
    ``want_tail`` appends the last two rows of g of every convolution layer
    [conv layers, 2, H]; ``want_shortfall`` the largest shortfall of a forced
    choice over the expert layers (:func:`_experts`)."""
    attn = dict(n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
                rope_theta=rope_theta, norm_eps=norm_eps, q_block=q_block,
                qk_norm=qk_norm, rotary=rotary)
    n_attn = n_conv = 0
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        tails, shortfalls = [], []
        for i, kind in enumerate(layer_types):
            if kind == "full_attention":
                lp = {k: _f32(v[n_attn]) for k, v in params["attn"].items()}
                n_attn += 1
                x = x + _attention(lp, _rms(x, lp["ln"], norm_eps), **attn)
            elif kind == "conv":
                lp = {k: _f32(v[n_conv]) for k, v in params["conv"].items()}
                n_conv += 1
                y, tail = _conv(lp, _rms(x, lp["ln"], norm_eps),
                                swap_bc=swap_bc)
                tails.append(tail)
                x = x + y
            else:
                raise ValueError(f"layer_types[{i}] = {kind!r}")
            if i < num_dense_layers:
                lp = {k: _f32(v[i]) for k, v in params["ffn"].items()}
                x = x + _swiglu(_rms(x, lp["ln_mlp"], norm_eps), lp["w1"],
                                lp["w3"], lp["w2"])
            else:
                row = i - num_dense_layers
                stack = params["experts"]
                y, short = _experts(
                    stack, row, _rms(x, _f32(stack["ln_mlp"][row]), norm_eps),
                    top_k=top_k, scaling=scaling,
                    routes=None if routes is None else routes[row],
                    router_bf16=router_bf16, bias_in_gates=bias_in_gates)
                shortfalls.append(short)
                x = x + y
        out = (_rms(x, _f32(params["final_norm"]), norm_eps),)
        if want_tail:
            out += (jnp.stack(tails),)
        if want_shortfall:
            out += (jnp.max(jnp.stack(shortfalls)) if shortfalls
                    else jnp.zeros(()),)
        return out if len(out) > 1 else out[0]


def logits(params, hidden_rows):
    """Hidden states [n, H] carried to the vocabulary through the embedding
    transposed: [n, vocab] float32."""
    embed = params["embed"]
    with jax.default_matmul_precision("highest"):
        # The embedding is cast a slice of the vocabulary at a time.
        return jnp.concatenate(
            [_f32(hidden_rows) @ _f32(embed[lo:lo + 32768]).T
             for lo in range(0, embed.shape[0], 32768)], axis=1)


def forward(params, tokens, **sizes):
    """Logits [T, vocab] in float32 for one sequence of token ids [T]."""
    return logits(params, hidden(params, tokens, **sizes))
