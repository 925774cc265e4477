"""Plain reference for the jamba block as AI21-Jamba2-3B publishes it
(config.json, `model_type` `jamba`): the forward pass in straightforward
jax.numpy and float32 for ONE sequence. The state-space recurrence token by
token (`lax.scan` over positions), full causal attention, no cache, no kernels,
no batching; it shares no code with the program.

Layer i of `num_hidden_layers` is an attention layer where `i %
attn_layer_period == attn_layer_offset` and a Mamba layer otherwise (the
family's convention; the published file does not list the order).
`num_experts` 1: every layer's FFN is the dense one. Every layer:

  h   = x + Mixer(RMSNorm_in(x))
  out = h + W_down(silu(W_gate n) * (W_up n)),  n = RMSNorm_ff(h)

eps 1e-6, no bias but the convolution's and the step size's; a final RMSNorm;
the head is the embedding transposed (`tie_word_embeddings` true).

Mamba mixer (Mamba-1: `mamba_expand` 2 -> d_inner 5120, `mamba_d_state` 16,
`mamba_dt_rank` 160, `mamba_d_conv` 4), input u [T, H]:
  [x | z] = u W_in                       H -> 2 d_inner
  x_t <- silu(b + sum_(j<4) w[j] * x_(t-3+j))    depth-wise, causal, x alone
  [dt_r | B | C] = x W_x                 d_inner -> 160 + 16 + 16
  dt_r, B, C <- RMSNorm each, with a learned weight
  dt = softplus(dt_r W_dt + b_dt)        160 -> d_inner
  A = -exp(A_log)                        a value a channel a state value
  S_t[c, n] = exp(dt_t[c] A[c, n]) S_(t-1)[c, n] + dt_t[c] B_t[n] x_t[c]
  y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] x_t[c]
  out = (y * silu(z)) W_out

Attention mixer (20 query heads of 128 on ONE KV head):
  q, k, v = h W_q, h W_k, h W_v;  causal softmax(q k^T / sqrt(128)) v;  W_o.
  NO rotary embedding and no other position code: the state-space layers
  carry position.

Weights are the program's parameter tree (that layout is the one thing the
two agree on): `embed`, `final_norm`, and the stacks `ssm1` (`ln`, `w_in`,
`conv_w` [4, d_inner], `conv_b`, `w_x`, `dt_norm`, `b_norm`, `c_norm`, `w_dt`,
`dt_bias`, `A_log` [state, d_inner] -- the program keeps A transposed, the
channels minor, as its state pool lies --, `D`, `w_out`), `attn` (`ln`, `wq`,
`wk`, `wv`, `wo`) and `ffn` (`ln_mlp`, `w1` gate, `w3` up, `w2` down; a row
a layer, in layer order). The head reads `embed`, not the program's
`lm_head`: a program whose head were not the embedding transposed would
differ. On a TPU a float32 matmul runs in lower precision unless told
otherwise, so everything runs under `highest`.

Two switches are controls of the comparison, each of which has to fail it
(scripts/compare_jamba_reference.py): `norms` False leaves the three
RMSNorms on dt, B and C out, `rotary` True rotates q and k (theta 10,000).

Sizes: attention takes its queries in blocks of `q_block` positions, and
`logits` is given the rows of the hidden states it should carry to the
vocabulary.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mamba(lp, u, *, state, dt_rank, norm_eps, norms=True):
    """u [T, H] -> ([T, H], the state [d_inner, state] the last token left);
    the recurrence one token at a time."""
    t_len = u.shape[0]
    xz = u @ lp["w_in"]
    inner = xz.shape[1] // 2
    x, z = xz[:, :inner], xz[:, inner:]
    width = lp["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, inner)), x])
    x = jax.nn.silu(lp["conv_b"] + sum(
        padded[j:j + t_len] * lp["conv_w"][j] for j in range(width)))
    dbc = x @ lp["w_x"]
    dt_r, b, c = (dbc[:, :dt_rank], dbc[:, dt_rank:dt_rank + state],
                  dbc[:, dt_rank + state:])
    if norms:
        dt_r = _rms(dt_r, lp["dt_norm"], norm_eps)
        b = _rms(b, lp["b_norm"], norm_eps)
        c = _rms(c, lp["c_norm"], norm_eps)
    dt = jax.nn.softplus(dt_r @ lp["w_dt"] + lp["dt_bias"])        # [T, inner]
    a = -jnp.exp(lp["A_log"]).T                                    # [inner, N]

    def token(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t[:, None] * a) * s
             + (dt_t * x_t)[:, None] * b_t[None, :])
        return s, s @ c_t

    last, y = jax.lax.scan(token, jnp.zeros((inner, state)), (x, b, c, dt))
    return ((y + lp["D"] * x) * jax.nn.silu(z)) @ lp["w_out"], last


def _rotated(x, theta=10_000.0):
    """x [T, heads, head_dim] rotated by position (halves paired)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half) / half)
    angle = jnp.arange(x.shape[0])[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def _attention(lp, h, *, n_heads, n_kv_heads, head_dim, q_block, rotary=False):
    s = h.shape[0]
    q = (h @ lp["wq"]).reshape(s, n_heads, head_dim)
    k = (h @ lp["wk"]).reshape(s, n_kv_heads, head_dim)
    if rotary:
        q, k = _rotated(q), _rotated(k)
    rep = n_heads // n_kv_heads
    k = jnp.repeat(k, rep, axis=1)
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


def hidden(params, tokens, *, n_layers: int, attn_period: int,
           attn_offset: int, n_heads: int, n_kv_heads: int, head_dim: int,
           ssm_state: int, ssm_dt_rank: int, norm_eps: float,
           q_block: int = 512, want_state: bool = False, norms: bool = True,
           rotary: bool = False):
    """Final-normed hidden states [T, H] in float32 for one sequence of
    token ids [T]. ``want_state`` appends the state every Mamba layer is left
    with after the last token [Mamba layers, d_inner, state]."""
    mamba = dict(state=ssm_state, dt_rank=ssm_dt_rank, norm_eps=norm_eps,
                 norms=norms)
    attn = dict(n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
                q_block=q_block, rotary=rotary)
    n_attn = n_mamba = 0
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        states = []
        for i in range(n_layers):
            if i % attn_period == attn_offset:
                lp = {k: _f32(v[n_attn]) for k, v in params["attn"].items()}
                n_attn += 1
                x = x + _attention(lp, _rms(x, lp["ln"], norm_eps), **attn)
            else:
                lp = {k: _f32(v[n_mamba]) for k, v in params["ssm1"].items()}
                n_mamba += 1
                y, last = _mamba(lp, _rms(x, lp["ln"], norm_eps), **mamba)
                states.append(last)
                x = x + y
            lp = {k: _f32(v[i]) for k, v in params["ffn"].items()}
            n = _rms(x, lp["ln_mlp"], norm_eps)
            x = x + (jax.nn.silu(n @ lp["w1"]) * (n @ lp["w3"])) @ lp["w2"]
        out = _rms(x, _f32(params["final_norm"]), norm_eps)
        return (out, jnp.stack(states)) if want_state else out


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
