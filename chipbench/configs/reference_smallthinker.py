"""Plain reference for SmallThinker's language model (PowerInfer's
SmallThinker-21BA3B-Instruct, config.json), cut as
`smallthinker-21b-a3b-cut.json` cuts it: the forward pass in straightforward
jax.numpy and float32 for ONE sequence. Dense scores under a mask, a Python
loop over layers and over experts, no cache, no scan, no kernels, no
batching; it shares no code with the program.

Per layer l, token t, hidden x (RMSNorm with eps `rms_norm_eps` and a weight;
no bias anywhere, no norm over q or k, the head untied):

1. a = RMSNorm(x; ln_attn). ROUTER: r = a W_r in f32 (one logit an expert);
   I = the `moe_num_active_primary_experts` largest of r, ties to the lower
   index; g = softmax(r[I]) over those alone
   (`moe_primary_router_apply_softmax` true; `norm_topk_prob` then changes
   nothing). The router reads the ATTENTION's normed input.
2. ATTENTION: q = a W_q (`num_attention_heads` heads of `head_dim`), k = a
   W_k, v = a W_v (`num_key_value_heads` heads; query head h reads KV head h
   // (heads / KV heads)). Where `rope_layout[l]` is 1, q and k are rotated
   at base `rope_theta`; where 0 there is NO rotation at all. Scores q . k /
   sqrt(head_dim), causal; where `sliding_window_layout[l]` is 1 the query at
   t sees s with 0 <= t - s < `sliding_window_size`. x += (softmax . v) W_o.
3. EXPERTS: m = RMSNorm(x; ln_mlp); y = sum over e in I of g_e (relu(m
   W_gate^e) * (m W_up^e)) W_down^e -- routed by step 1's I and g, computed
   on m (ReGLU). x += y.
4. After the last layer: logits = RMSNorm(x; final_norm) W_head.

Departures from the published description, each the program's too:
- Rotary pairing: column i with i + head_dim/2 (rotate-half); a fixed
  permutation of W_q's and W_k's columns, the same on both sides of the dot
  product, which a checkpoint converter would apply.
- What config.json does not say is the configuration file's `assumed`: the
  router reads the NORMED attention input; the window counts the query's own
  position; the top-k is taken before the softmax.
- Weights are served in bf16; this reference upcasts them to float32.

Weights are the program's parameter tree (models/llama.py): `embed`,
`final_norm`, `lm_head`, and `layers` with a leading layer axis: `wq`, `wk`,
`wv`, `wo`, `ln_attn`, `ln_mlp`, `router`, and the experts `w1` (gate), `w3`
(up) [L, E, D, F], `w2` (down) [L, E, F, D]. Everything runs under `highest`.

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


def _rope(x, theta):
    """x: [S, heads, d]; position s rotates pair (i, i + d/2) by
    s * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def route(a, w_r, k: int):
    """(The k experts each row of ``a`` [S, D] chooses, largest logit first
    and ties to the lower index [S, k], their gates [S, k], the logits
    [S, E])."""
    r = a @ w_r
    order = jnp.argsort(-r, axis=-1, stable=True)[:, :k]
    return order, jax.nn.softmax(jnp.take_along_axis(r, order, axis=-1),
                                 axis=-1), r


def _attention(a, lp, *, n_heads, n_kv_heads, head_dim, rotate, theta, window,
               q_block):
    S = a.shape[0]
    q = (a @ lp["wq"]).reshape(S, n_heads, head_dim)
    k = (a @ lp["wk"]).reshape(S, n_kv_heads, head_dim)
    v = (a @ lp["wv"]).reshape(S, n_kv_heads, head_dim)
    if rotate:
        q, k = _rope(q, theta), _rope(k, theta)
    group = n_heads // n_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.arange(S)
    out = []
    for lo in range(0, S, q_block):
        t = s[lo:lo + q_block, None]
        seen = s[None, :] <= t
        if window:
            seen = seen & (t - s[None, :] < window)
        scores = jnp.einsum("qhd,shd->hqs", q[lo:lo + q_block], k) \
            / head_dim ** 0.5
        p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqs,shd->qhd", p, v).reshape(-1,
                                                             n_heads * head_dim))
    return jnp.concatenate(out) @ lp["wo"]


def _experts(m, expert, n_experts: int, chosen, gates):
    """sum over a row's chosen experts of gate x ReGLU expert; ``expert(name,
    e)`` hands expert e's weight in float32, one at a time."""
    y = jnp.zeros_like(m)
    for e in range(n_experts):
        g = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)     # [S]
        h = jax.nn.relu(m @ expert("w1", e)) * (m @ expert("w3", e))
        y = y + g[:, None] * (h @ expert("w2", e))
    return y


def hidden(params, tokens, *, n_heads: int, n_kv_heads: int, head_dim: int,
           rope_theta: float, norm_eps: float, experts_per_token: int,
           rope_layout, sliding_window_layout, sliding_window_size: int,
           q_block: int = 512, routes=None):
    """(Final-normed hidden states [S, D] in float32, the experts each
    position chose in each layer [L, S, k], and how far under its own
    choice's least logit each forced choice's lies [L, S], zeros when
    nothing is forced) for one sequence of token ids [S].

    ``routes`` [L, S, k] forces the router's choices (the gates are then the
    softmax over THOSE logits): with random weights a near-tie that a bf16
    program parts the other way moves that position's logits as a different
    model would; held to the program's choices the reference follows the
    program's history, and the choices are judged for what they are by the
    shortfall."""
    layers = params["layers"]
    n_experts = layers["router"].shape[-1]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        told, short = [], []
        for l in range(layers["wq"].shape[0]):
            lp = {k: _f32(v[l]) for k, v in layers.items()
                  if k not in ("w1", "w2", "w3")}
            a = _rms(x, lp["ln_attn"], norm_eps)
            chosen, gates, r = route(a, lp["router"], experts_per_token)
            if routes is not None:
                own_least = jnp.take_along_axis(r, chosen, axis=-1).min(-1)
                chosen = routes[l]
                forced = jnp.take_along_axis(r, chosen, axis=-1)
                gates = jax.nn.softmax(forced, axis=-1)
                short.append(jnp.maximum(own_least - forced.min(-1), 0.0))
            else:
                short.append(jnp.zeros(x.shape[0]))
            told.append(chosen)
            x = x + _attention(
                a, lp, n_heads=n_heads, n_kv_heads=n_kv_heads,
                head_dim=head_dim, rotate=bool(rope_layout[l]),
                theta=rope_theta,
                window=sliding_window_size if sliding_window_layout[l] else 0,
                q_block=q_block)
            m = _rms(x, lp["ln_mlp"], norm_eps)
            x = x + _experts(
                m, lambda w, e, l=l: _f32(layers[w][l, e]), n_experts,
                chosen, gates)
        return (_rms(x, _f32(params["final_norm"]), norm_eps),
                jnp.stack(told), jnp.stack(short))


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
