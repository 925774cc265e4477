"""Plain reference for the Llama-family block (Qwen3-4B, Mixtral-8x7B): the
forward pass in straightforward jax.numpy and float32. No kernels, no cache,
no batching tricks, no scan; it shares no code with the program.

Follows the published descriptions: pre-norm blocks, RMSNorm, rotary
embeddings on rotate-half pairs, grouped-query causal attention, SwiGLU;
Qwen3 adds an RMSNorm over each head of q and k before the rotation; Mixtral
replaces the FFN by 8 experts of which each token takes the 2 with the
largest router logits, weighted by a softmax over those 2. Departure: the
embedding and the LM head are separate tensors, as the program lays them out.

Weights are the program's parameter tree (that layout is the one thing the
two must agree on). On a TPU a float32 matmul runs in lower precision unless
told otherwise, so everything runs under `highest`.

What it is used for: chipbench/tests compare the program's model code with it
at a small size on the CPU. On the chip the served path returns text only, no
token ids or logits, so a logit-level comparison there has to wait for the
program to return them (PERF.md, Open questions).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [S, heads, head_dim]; position s rotates pair (i, i + half) by
    s * theta^(-i/half)."""
    s, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def forward(params, tokens, *, n_heads: int, n_kv_heads: int, head_dim: int,
            rope_theta: float, norm_eps: float, experts_per_token: int = 2):
    """Logits [S, vocab] in float32 for one sequence of token ids [S]."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: jnp.asarray(a, jnp.float32)
        x = f32(params["embed"])[tokens]
        s = x.shape[0]
        causal = jnp.tril(jnp.ones((s, s), bool))
        layers = params["layers"]
        for i in range(layers["wq"].shape[0]):
            lp = {k: f32(v[i]) for k, v in layers.items()}
            h = _rms(x, lp["ln_attn"], norm_eps)
            q = (h @ lp["wq"]).reshape(s, n_heads, head_dim)
            k = (h @ lp["wk"]).reshape(s, n_kv_heads, head_dim)
            v = (h @ lp["wv"]).reshape(s, n_kv_heads, head_dim)
            if "q_norm" in lp:
                q = _rms(q, lp["q_norm"], norm_eps)
                k = _rms(k, lp["k_norm"], norm_eps)
            q, k = _rope(q, rope_theta), _rope(k, rope_theta)
            group = n_heads // n_kv_heads
            k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
            scores = jnp.einsum("qhd,khd->hqk", q, k) / head_dim ** 0.5
            scores = jnp.where(causal[None], scores, -jnp.inf)
            attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
            x = x + attn.reshape(s, -1) @ lp["wo"]
            h = _rms(x, lp["ln_mlp"], norm_eps)
            if "router" in lp:
                logits = h @ lp["router"]                      # [S, E]
                top, idx = jax.lax.top_k(logits, experts_per_token)
                gate = jax.nn.softmax(top, axis=-1)
                y = jnp.zeros_like(x)
                for e in range(lp["w1"].shape[0]):
                    out = (jax.nn.silu(h @ lp["w1"][e]) * (h @ lp["w3"][e])) @ lp["w2"][e]
                    weight = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)
                    y = y + out * weight[:, None]
                x = x + y
            else:
                x = x + (jax.nn.silu(h @ lp["w1"]) * (h @ lp["w3"])) @ lp["w2"]
        x = _rms(x, f32(params["final_norm"]), norm_eps)
        return x @ f32(params["lm_head"])
