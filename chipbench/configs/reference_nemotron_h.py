"""Plain reference for the nemotron_h block as NVIDIA-Nemotron-3-Super-120B-A12B
publishes it (config.json, `model_type` `nemotron_h`): the forward pass in
straightforward jax.numpy and float32 for ONE sequence. The state-space
recurrence token by token, full causal attention, the experts by a Python loop
over the held range, no cache, no chunked form, no kernels; it shares no code
with the program.

`hybrid_override_pattern` gives a layer's kind, one character a layer, and
every layer is ONE mixer under its own residual: `x += mixer(RMSNorm(x))`,
eps 1e-5, no bias anywhere but the convolution; final RMSNorm, untied head.

M, Mamba-2 (128 heads of 64, state 128, 8 groups, convolution 4 wide).
  [z | xBC | dt] = h W_in            widths inner | inner + 2 G N | heads
  xBC_t <- silu(b + sum_(j<4) w[j] * xBC_(t-3+j))   depth-wise, causal
  xBC_t -> x_t [heads, 64], B_t, C_t [G, 128]; head i reads group i // 16
  D_t = softplus(dt_t + dt_bias)     a head
  S_t = exp(D_t A) S_(t-1) + D_t x_t (x) B_t,   A = -exp(A_log) a head
  y_t = S_t C_t + D x_t
  y <- RMSNorm(y * silu(z)) over groups of inner / G (its own weight)
  out = y W_out

*, attention (32 query / 2 KV heads of 128).
  q, k, v = h W_q, h W_k, h W_v;  causal softmax(q k^T / sqrt(128)) v;  W_o.
  NO rotary embedding: the family's convention is that the state-space layers
  carry position (`rope_theta` is in the config and unused).

E, LatentMoE (512 experts of 2688 in a 1024-wide latent space, 22 a token).
  s = sigmoid(h W_r)                 in f32, one score an expert
  chosen = the k experts with the largest s + b   (`n_group` 1)
  g_i = s_i / sum_chosen s * routed_scaling_factor
  u = h W_down                       4096 -> 1024
  r = sum over chosen experts i HELD HERE of g_i relu(u W1_i)^2 W2_i
  out = r W_up + relu(h Ws1)^2 Ws2   (the shared expert, 4096 -> 5376 -> 4096)
An expert is not gated (`mlp_hidden_act` relu2). The weights hold the experts
`first .. first + count` of those the router scores (what one of the chips
that share a layer holds); what the absent experts would have added is left
out, here as in the program.

Not built, here or in the program: the multi-token-prediction module
(`num_nextn_predict_layers` 1), a draft head.

Weights are the program's parameter tree (that layout is the one thing the
two agree on): `embed`, `final_norm`, `lm_head`, and one stack a kind of
layer with a leading axis over that kind's layers -- `ssm` (`ln`, `w_in`,
`conv_w` [4, channels], `conv_b`, `dt_bias`, `A_log`, `D`, `norm`, `w_out`),
`moe` (`ln`, `router`, `router_bias`, `w_down`, `w_up`, `w1`, `w2` [held
experts, ...], `w1s`, `w2s`), `attn` (`ln`, `wq`, `wk`, `wv`, `wo`). On a TPU
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


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _mamba(lp, h, *, heads, head_dim, state, groups, norm_eps):
    """h [S, D] -> ([S, D], the state [heads, head_dim, state] the last token
    left); the recurrence one token at a time."""
    s_len = h.shape[0]
    inner, gn = heads * head_dim, groups * state
    zxbcdt = h @ lp["w_in"]
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * gn],
                  zxbcdt[:, 2 * inner + 2 * gn:])
    width = lp["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(lp["conv_b"] + sum(
        padded[j:j + s_len] * lp["conv_w"][j] for j in range(width)))
    x = xbc[:, :inner].reshape(s_len, heads, head_dim)
    group_of = jnp.arange(heads) // (heads // groups)
    b_mat = xbc[:, inner:inner + gn].reshape(s_len, groups, state)[:, group_of]
    c_mat = xbc[:, inner + gn:].reshape(s_len, groups, state)[:, group_of]
    step = jax.nn.softplus(dt + lp["dt_bias"])                     # [S, heads]
    a = -jnp.exp(lp["A_log"])

    def token(s, inp):
        x_t, b_t, c_t, d_t = inp
        s = (jnp.exp(d_t * a)[:, None, None] * s
             + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    last, y = jax.lax.scan(token, jnp.zeros((heads, head_dim, state)),
                           (x, b_mat, c_mat, step))
    y = (y + lp["D"][:, None] * x).reshape(s_len, inner) * jax.nn.silu(z)
    y = _rms(y.reshape(s_len, groups, -1), 1.0, norm_eps).reshape(s_len, inner)
    return (y * lp["norm"]) @ lp["w_out"], last


def _attention(lp, h, *, n_heads, n_kv_heads, head_dim, q_block):
    s = h.shape[0]
    q = (h @ lp["wq"]).reshape(s, n_heads, head_dim)
    rep = n_heads // n_kv_heads
    k = jnp.repeat((h @ lp["wk"]).reshape(s, n_kv_heads, head_dim), rep, axis=1)
    v = jnp.repeat((h @ lp["wv"]).reshape(s, n_kv_heads, head_dim), rep, axis=1)
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


def _experts(lp, expert, first, count, h, *, experts_per_token,
             routed_scaling_factor, forced=None, routed=True):
    """(y [S, D], the chosen experts [S, k]) of an expert layer whose weights
    hold experts ``first .. first + count``; ``expert(name, e)`` is held
    expert e's weight in float32 (e counted from ``first``). With ``forced``
    [S, k] those experts are taken in place of the layer's own choice, and
    the second result is each position's shortfall [S]: how far the worst
    forced expert's ``s + b`` lies under this layer's own k-th best (0 where
    the choices agree). ``routed`` False leaves the routed part out (a
    control of the comparison)."""
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
    u = h @ lp["w_down"]
    r = jnp.zeros_like(u)
    for e in range(count if routed else 0):
        weight = jnp.sum(jnp.where(idx == first + e, gate, 0.0), axis=-1)
        r = r + (_relu2(u @ expert("w1", e)) @ expert("w2", e)
                 ) * weight[:, None]
    return r @ lp["w_up"] + _relu2(h @ lp["w1s"]) @ lp["w2s"], told


def hidden(params, tokens, *, pattern: str, n_heads: int, n_kv_heads: int,
           head_dim: int, ssm_heads: int, ssm_head_dim: int, ssm_state: int,
           ssm_groups: int, norm_eps: float, experts_per_token: int,
           routed_scaling_factor: float, first_expert: int = 0,
           q_block: int = 512, routes=None, routed: bool = True,
           want_state: bool = False):
    """(final-normed hidden states [S, D] in float32, the experts each
    position chose in each expert layer [expert layers, S, k]) for one
    sequence of token ids [S].

    ``routes`` [expert layers, S, k] forces the experts (the gates stay this
    file's own scores of them): with random weights the experts are unrelated
    functions, so one near-tie that a bf16 program parts the other way moves
    that position's logits, and through the recurrent state every later one's,
    by as much as the logits themselves. Held to the program's choices the
    reference follows the program's history, what is left is rounding, and
    the second result is instead the shortfall of every forced choice
    [expert layers, S] (``_experts``), which says whether each was a
    near-tie. ``want_state`` appends the state every state-space layer is left
    with after the last token [state layers, heads, head_dim, state]."""
    mamba = dict(heads=ssm_heads, head_dim=ssm_head_dim, state=ssm_state,
                 groups=ssm_groups, norm_eps=norm_eps)
    attn = dict(n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
                q_block=q_block)
    stack_of = {"M": "ssm", "E": "moe", "*": "attn"}
    seen = dict.fromkeys(stack_of, 0)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        told, states = [], []
        for kind in pattern:
            stack, i = params[stack_of[kind]], seen[kind]
            seen[kind] += 1
            # The routed experts' weights are taken and cast one expert at a
            # time (a layer's held experts in f32 are 2.8 GB at the
            # published size).
            lp = {k: _f32(v[i]) for k, v in stack.items()
                  if not (kind == "E" and k in ("w1", "w2"))}
            h = _rms(x, lp["ln"], norm_eps)
            if kind == "M":
                y, last = _mamba(lp, h, **mamba)
                states.append(last)
            elif kind == "*":
                y = _attention(lp, h, **attn)
            else:
                y, idx = _experts(
                    lp, lambda name, e: _f32(stack[name][i, e]), first_expert,
                    stack["w1"].shape[1], h,
                    experts_per_token=experts_per_token,
                    routed_scaling_factor=routed_scaling_factor,
                    forced=None if routes is None else routes[len(told)],
                    routed=routed)
                told.append(idx)
            x = x + y
        out = _rms(x, _f32(params["final_norm"]), norm_eps), jnp.stack(told)
        return (*out, jnp.stack(states)) if want_state else out


def logits(params, hidden_rows):
    """Hidden states [n, D] carried to the vocabulary: [n, vocab] float32."""
    head = params["lm_head"]
    with jax.default_matmul_precision("highest"):
        # The head is cast a slice of the vocabulary at a time (2.1 GB in
        # f32 at the published size).
        return jnp.concatenate(
            [_f32(hidden_rows) @ _f32(head[:, lo:lo + 32768])
             for lo in range(0, head.shape[1], 32768)], axis=1)


def forward(params, tokens, **sizes):
    """Logits [S, vocab] in float32 for one sequence of token ids [S]."""
    x, _ = hidden(params, tokens, **sizes)
    return logits(params, x)
