"""The nemotron_h family's decoder for the TPU engine: a pattern of layers,
each ONE mixer under its own residual -- a Mamba-2 state-space layer ("M"), a
LatentMoE expert layer ("E") or attention ("*"). NVIDIA's Nemotron-3-Super is
this block; its draft head (multi-token prediction) is not built. The jamba
family (AI21's Jamba2-3B) is two more letters of the same walk: "S" a
Mamba-1 mixer and "A" the same attention, EACH followed by a dense SwiGLU
under a second residual (described below the three).

The module has models/llama.py's four entry points with its signatures
(``init_params``, ``forward``, ``decode_step``, ``prefill_with_prefix``), so
the engine serves it through the same step functions; ``models.family`` picks
the module by the presence of ``cfg.layer_pattern``. Where a signature says
``k_pages, v_pages`` this family hands ``(cache, None)``: pages and recurrent
state as one value (kvcache/state.py), and the "KV" a prefill returns is a
``state.Fresh``. Parameters are stacked per kind (``ssm``, ``moe``, ``attn``)
and walked in the pattern's order, so any pattern serves.

Every layer: ``x <- x + mixer(RMSNorm(x))``; no bias but the convolution's.

**M, Mamba-2.** ``[z | xBC | dt] = h W_in``; a causal depth-wise convolution
``ssm_conv`` wide and a silu over ``xBC``, which then splits into ``x``
(heads x head_dim), ``B`` and ``C`` (groups x state; head i reads group
i // (heads / groups)); ``D_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``
a head; ``S_t = exp(D_t A) S_(t-1) + D_t x_t (x) B_t``; ``y_t = S_t C_t + D x_t``;
``y <- RMSNorm(y * silu(z))`` over groups of inner / groups; ``out = y W_out``.
One mathematics in two forms, chosen by the shape of the step as models/mla.py
chooses its attention's form:

- *scan* (a run of positions a sequence: ``forward``, ``prefill_with_prefix``):
  the chunked matrix form. Inside a chunk of ``ssm_chunk`` positions
  ``y_t = sum_(s<=t) exp(a_t - a_s) (C_t . B_s) D_s x_s`` with ``a`` the running
  sum of ``D A`` -- two matrix products a chunk -- and the chunks are joined by
  a recurrence over their end states. Padded positions get ``D = 0``: no decay
  and no input, so the state a bucket leaves is the state its true last token
  left, and the convolution's tail is gathered at the true length.
- *step* (one position a sequence: ``decode_step``): the recurrence as
  written, on the sequences' rows of the state pool where they lie
  (kvcache/state.recur: in place through ops/pallas_ssm.py's kernel, or
  gathered by slot, as ``cfg.ssm_impl`` says).

The state is float32; the products take their operands in the model's dtype
with float32 accumulation.

**\\*, attention.** GQA, causal, softmax(q k^T / sqrt(head_dim)) v, and NO
rotary embedding (the family's convention: the state-space layers carry
position; ``rope_theta`` is unused). Pages of K and V as models/llama.py's,
one pool layer an attention layer.

**E, LatentMoE.** ``s = sigmoid(h W_r)``; the experts_per_token largest of
``s + bias``; gates the chosen scores normalised, times routed_scaling_factor
(models/routing.py: the DeepSeek-V3 family's router). ``u = h W_down`` into
the latent space; ``r = sum over chosen AND HELD experts of g_i
relu(u W1_i)^2 W2_i`` (not gated); ``out = r W_up + relu(h Ws1)^2 Ws2``. The
chip holds ``cfg.held_experts`` of the experts the router scores -- what
expert parallelism gives one chip -- and what the absent ones would have added
is left out: in the deployment the 1,024-wide latents are exchanged between
``W_down`` and ``W_up``, on one chip there is no exchange and nothing stands
in for it. The routed part runs dense over the held experts, grouped, or
dense over the held experts some row chose (ops/pallas_moe.py), chosen by the
engine per program (``cfg.moe_impl``).

**S, Mamba-1** (``cfg.ssm_dt_rank`` > 0). ``[x | z] = h W_in``; the
convolution (with its bias) and a silu over ``x`` ALONE; ``[dt_r | B | C] = x
W_x`` and an RMSNorm with a learned weight on each of the three; ``dt =
softplus(dt_r W_dt + b_dt)``; ``A = -exp(A_log)``, a value for every channel
and state value, f32; ``S_t[c, n] = exp(dt_t[c] A[c, n]) S_(t-1)[c, n] +
dt_t[c] B_t[n] x_t[c]``; ``y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] x_t[c]``;
``out = (y * silu(z)) W_out``. The decay differs by channel AND state value,
so there is no matrix form: a run of positions goes through the recurrence
in order (*scan*: ops/pallas_ssm.selective_scan, the state tile resident in
VMEM over the window's rows, or ``lax.scan`` over positions, as
``cfg.ssm_scan_impl`` says), one position is the *step* form on the pool's
second layout (``state.recur1``). The state and ``A`` lie ``[state, inner]``,
the channels minor.

**A** is ``*`` with an FFN behind it; **the FFN** of an "S" or "A" layer is
models/llama.py's dense SwiGLU (``ffn`` stack: ``ln_mlp``, ``w1``, ``w3``,
``w2``, a row a layer in layer order). A model's ONE KV head is kept twice a
page (``cfg.kv_heads_kept``: :func:`_qkv`).

The lfm2_moe family (LiquidAI's LFM2-8B-A1B) is two more letters, each with
an FFN behind it under a second residual:

**C, a gated short convolution.** ``[B | C | u] = h W_in`` (d_model -> 3 x
d_model, the thirds in that order); ``g = B * u``; ``c_t = sum_j w[:, j] *
g_(t - (K - 1) + j)``, depth-wise and causal, ``ssm_conv`` (K = 3) wide, no
bias, zeros before the sequence's first row; ``out = (C * c) W_out``. No
activation and NO recurrent state: a sequence keeps the last K - 1 rows of
``g`` a layer, in the model's dtype, in a slot row that is a tail and nothing
else (kvcache/state.py's third kind). The *run* form (:func:`conv_run`) goes
over a window's rows from the slot's carried tail and gathers the new tail
at the true length of a padded window; the *step* form (:func:`conv_step`)
is one row a lane. ``g`` is rounded to the model's dtype before the
convolution in both, as the tail stores it, so a window's first rows see
what the window before it saw; the three products accumulate in f32.

**Q, attention that rotates.** models/llama.py's Qwen3 attention at this
family's widths: an RMSNorm with a learned weight a head on q and on k
(``llama.qk_normed``), rotary embedding (``cfg.rope_theta``, rotate-half),
causal softmax(q k^T / sqrt(head_dim)) v. Heads of 64 lie two a page row
(``cfg.kv_heads_a_row``, kvcache/pages.py): this module hands and takes its
own ``[.., Hkv, 64]`` rows.

**The FFN behind a C or Q mixer**, by the layer's index: the dense SwiGLU
(the ``ffn`` stack) in the first ``cfg.first_k_dense`` layers; from there on
``cfg.n_experts`` routed SwiGLU experts ``cfg.moe_d_ff`` wide and no shared
one (the ``experts`` stack: ``ln_mlp``, ``router``, ``router_bias``, ``w1``,
``w3``, ``w2``): ``s = sigmoid(h W_r)`` in f32, the experts_per_token
largest of ``s + bias`` (the bias selects and does not weigh), the chosen
``s`` over their sum (models/routing.route, ``n_group`` 1; the published
code adds 1e-6 to that sum and this one does not: a relative 1e-6 of a
gate), then models/llama._ffn in the form the program's shape calls for
(``cfg.moe_impl``: grouped from 512 padded tokens, dense over all below).
Every choice names an expert held here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..kvcache import pages, state
from ..ops import causal_attention, rms_norm
from ..ops.rope import apply_rope, rope_table
from . import scopes
from .configs import ModelConfig
from ..ops import pallas_ssm
from .llama import (_embedded, _ffn, _ffn_input, _last_logits, _logits,
                    qk_normed)
from .routing import route

Params = dict[str, Any]

# A layer's letter -> the stack that holds its mixer's parameters (and the
# pool layer it keeps is its index among that stack's layers); "S" and "A"
# carry a dense SwiGLU too, a row of the ``ffn`` stack each.
_STACK = {"M": "ssm", "E": "moe", "*": "attn", "S": "ssm1", "A": "attn",
          "C": "conv", "Q": "attn"}
_WITH_FFN = "SACQ"


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype: jnp.dtype | None = None) -> Params:
    """Random-init parameters, one stack a kind of layer with a leading axis
    over that kind's layers. ``A`` is uniform in [1, 16]; ``dt_bias`` the
    inverse softplus of a log-uniform step in cfg.ssm_dt_range; ``D`` ones;
    norm weights and the selection bias are drawn (models/mla.py), so that a
    run on random weights sees them. (The jamba family's: :func:`_init_jamba`.)"""
    dtype = dtype or jnp.dtype(cfg.dtype)
    D, V = cfg.d_model, cfg.vocab_size
    Lm, Le, La = (cfg.layer_pattern.count(c) for c in "ME*")
    H, G, N, Kc = cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv
    inner, conv_dim = cfg.ssm_inner, cfg.ssm_conv_dim
    E, Eh = cfg.n_experts, cfg.held_experts[1]
    Z, F, Fs = cfg.moe_latent_dim, cfg.moe_d_ff, cfg.shared_d_ff
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 40))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def norm(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)

    def step(shape):
        """A log-uniform step in cfg.ssm_dt_range."""
        dt_min, dt_max, dt_floor = cfg.ssm_dt_range
        return jnp.maximum(jnp.exp(jax.random.uniform(
            next(keys), shape, jnp.float32, jnp.log(dt_min),
            jnp.log(dt_max))), dt_floor)

    dt = step((Lm, H))
    if cfg.ssm_dt_rank:
        return _init_jamba(cfg, dtype, w, norm, step, keys)
    if cfg.conv_mixers:
        return _init_lfm2(cfg, dtype, w, norm, keys)
    return {
        "embed": w((V, D), D), "final_norm": norm((D,)),
        "lm_head": w((D, V), D),
        "ssm": {
            "ln": norm((Lm, D)),
            "w_in": w((Lm, D, inner + conv_dim + H), D),
            "conv_w": w((Lm, Kc, conv_dim), Kc),
            "conv_b": (0.1 * jax.random.normal(
                next(keys), (Lm, conv_dim), jnp.float32)).astype(dtype),
            "dt_bias": _inverse_softplus(dt),
            "A_log": jnp.log(jax.random.uniform(
                next(keys), (Lm, H), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((Lm, H), jnp.float32),
            "norm": norm((Lm, inner)),
            "w_out": w((Lm, inner, D), inner)},
        "moe": {
            "ln": norm((Le, D)),
            "router": w((Le, D, E), D),
            "router_bias": 0.1 * jax.random.normal(
                next(keys), (Le, E), jnp.float32),
            "w_down": w((Le, D, Z), D), "w_up": w((Le, Z, D), Z),
            "w1": w((Le, Eh, Z, F), Z), "w2": w((Le, Eh, F, Z), F),
            "w1s": w((Le, D, Fs), D), "w2s": w((Le, Fs, D), Fs)},
        "attn": {
            "ln": norm((La, D)),
            "wq": w((La, D, Hq * Dh), D), "wk": w((La, D, Hkv * Dh), D),
            "wv": w((La, D, Hkv * Dh), D), "wo": w((La, Hq * Dh, D), Hq * Dh)},
    }


def _inverse_softplus(dt: jnp.ndarray) -> jnp.ndarray:
    return dt + jnp.log(-jnp.expm1(-dt))


def _init_jamba(cfg: ModelConfig, dtype, w, norm, step, keys) -> Params:
    """The jamba family's parameters (:func:`init_params`' helpers): the
    Mamba-1 mixers' stack ``ssm1``, the attention layers' ``attn``, and every
    layer's dense SwiGLU in ``ffn`` (models/llama._ffn's names). ``A`` is
    uniform in [1, 16] a channel a state value, kept as ``A_log`` [layers,
    state, inner] f32 (the channels minor, as the state lies); the step
    size's bias as the Mamba-2 layers'; ``D``, the three norms on dt, B and C
    and the convolution's bias are drawn, so that a run sees them. The head
    is the embedding transposed (tied): two tensors that hold the same
    values."""
    D, V, F = cfg.d_model, cfg.vocab_size, cfg.d_ff
    Ls, La = (cfg.layer_pattern.count(c) for c in "SA")
    N, R, Kc, inner = cfg.ssm_state, cfg.ssm_dt_rank, cfg.ssm_conv, cfg.ssm_inner
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def near(shape, around=0.0):
        return around + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    embed = w((V, D), D)
    return {
        "embed": embed, "final_norm": norm((D,)), "lm_head": embed.T,
        "ssm1": {
            "ln": norm((Ls, D)),
            "w_in": w((Ls, D, 2 * inner), D),
            "conv_w": w((Ls, Kc, inner), Kc),
            "conv_b": near((Ls, inner)).astype(dtype),
            "w_x": w((Ls, inner, R + 2 * N), inner),
            "dt_norm": norm((Ls, R)), "b_norm": norm((Ls, N)),
            "c_norm": norm((Ls, N)),
            "w_dt": w((Ls, R, inner), R),
            "dt_bias": _inverse_softplus(step((Ls, inner))),
            "A_log": jnp.log(jax.random.uniform(
                next(keys), (Ls, N, inner), jnp.float32, 1.0, 16.0)),
            "D": near((Ls, inner), 1.0),
            "w_out": w((Ls, inner, D), inner)},
        "attn": {
            "ln": norm((La, D)),
            "wq": w((La, D, Hq * Dh), D), "wk": w((La, D, Hkv * Dh), D),
            "wv": w((La, D, Hkv * Dh), D), "wo": w((La, Hq * Dh, D), Hq * Dh)},
        "ffn": {
            "ln_mlp": norm((Ls + La, D)),
            "w1": w((Ls + La, D, F), D), "w3": w((Ls + La, D, F), D),
            "w2": w((Ls + La, F, D), F)},
    }


def _init_lfm2(cfg: ModelConfig, dtype, w, norm, keys) -> Params:
    """The lfm2_moe family's parameters (:func:`init_params`' helpers): the
    convolution mixers' stack ``conv`` (``w_in`` [d_model, 3 d_model]: B, C,
    u; ``conv_w`` [taps, d_model]; ``w_out``), the attention layers' ``attn``
    with a norm weight a head for q and for k, the leading layers' dense
    SwiGLU in ``ffn`` and the later layers' routed experts in ``experts``
    (models/llama._ffn's names, a row a layer in layer order each). Every
    norm weight, the convolution's taps and the selection bias are drawn, so
    that a run sees them. The head is the embedding transposed (tied): two
    tensors that hold the same values."""
    D, V, F, Fe = cfg.d_model, cfg.vocab_size, cfg.d_ff, cfg.moe_d_ff
    Lc, La = (cfg.layer_pattern.count(c) for c in "CQ")
    Ld, Le, E = cfg.first_k_dense, cfg.n_expert_layers, cfg.n_experts
    Hq, Hkv, Dh, Kc = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.ssm_conv
    embed = w((V, D), D)
    return {
        "embed": embed, "final_norm": norm((D,)), "lm_head": embed.T,
        "conv": {
            "ln": norm((Lc, D)),
            "w_in": w((Lc, D, 3 * D), D),
            "conv_w": w((Lc, Kc, D), Kc),
            "w_out": w((Lc, D, D), D)},
        "attn": {
            "ln": norm((La, D)),
            "wq": w((La, D, Hq * Dh), D), "wk": w((La, D, Hkv * Dh), D),
            "wv": w((La, D, Hkv * Dh), D), "wo": w((La, Hq * Dh, D), Hq * Dh),
            "q_norm": norm((La, Dh)), "k_norm": norm((La, Dh))},
        "ffn": {
            "ln_mlp": norm((Ld, D)),
            "w1": w((Ld, D, F), D), "w3": w((Ld, D, F), D),
            "w2": w((Ld, F, D), F)},
        "experts": {
            "ln_mlp": norm((Le, D)),
            "router": w((Le, D, E), D),
            "router_bias": 0.1 * jax.random.normal(
                next(keys), (Le, E), jnp.float32),
            "w1": w((Le, E, D, Fe), D), "w3": w((Le, E, D, Fe), D),
            "w2": w((Le, E, Fe, D), Fe)},
    }


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


# ---- E: LatentMoE -------------------------------------------------------------


def latent_moe(cfg: ModelConfig, stack: Params, layer: int, h: jnp.ndarray,
               real: jnp.ndarray | None = None
               ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                          jnp.ndarray | None]:
    """Expert layer ``layer`` of the stacked ``moe`` parameters on h [..., D]:
    (output, the experts every token chose [T, k], how many of those choices
    name an expert held here, and -- in the form that reads the chosen
    experts alone, else None -- how many held experts' weights the layer
    read). ``real`` [T] says which rows are somebody's (None: all): the
    others' choices make no expert worth reading."""
    lp = {n: a[layer] for n, a in stack.items() if n not in ("w1", "w2")}
    first, count = cfg.held_experts
    ht = h.reshape(-1, h.shape[-1])
    with scopes.block("ffn.router"):
        idx, gates = route(cfg, lp, ht)
        here = (idx >= first) & (idx < first + count)
    # The latent's down and up projections are the experts' own: every token
    # pays them for its routed experts, whichever they are. (The grouped
    # form's glue names itself inside.)
    with scopes.block("ffn.experts"):
        u = ht @ lp["w_down"]
        local = jnp.where(here, idx - first, -1)
        read = None
        if cfg.moe_impl.startswith("grouped"):
            from ..ops.pallas_moe import grouped_experts

            # The kernel reads the stacked weights at (layer, expert): a
            # slice of them would reach it as a copy
            # (models/llama._over_layers).
            r = grouped_experts(stack, u, idx, gates, count,
                                layer=jnp.asarray(layer, jnp.int32),
                                first=first, gated=False,
                                interpret=cfg.moe_impl == "grouped_interpret")
        elif cfg.moe_impl.startswith("chosen"):
            from ..ops.pallas_moe import chosen_experts

            # Dense over the held experts that a row of somebody's chose.
            if real is not None:
                local = jnp.where(real[:, None], local, -1)
            r, read = chosen_experts(
                stack, u, local, gates, count,
                layer=jnp.asarray(layer, jnp.int32), gated=False,
                interpret=cfg.moe_impl == "chosen_interpret")
        else:
            # Dense over the held experts: each of them for every token,
            # weighted by its gate or by zero; the gate goes in ahead of the
            # second product, which then sums over experts and width at once.
            weights = jnp.einsum(
                "tke,tk->te", jax.nn.one_hot(local, count, dtype=h.dtype),
                gates.astype(h.dtype))
            act = _relu2(jnp.einsum("tz,ezf->tef", u, stack["w1"][layer]))
            r = jnp.einsum("tef,efz->tz", act * weights[..., None],
                           stack["w2"][layer])
        y = r @ lp["w_up"]
    with scopes.block("ffn.shared"):
        y = y + _relu2(ht @ lp["w1s"]) @ lp["w2s"]
    y = y.reshape(h.shape)
    with scopes.block("ffn.router"):
        held = jnp.sum(here, dtype=jnp.int32)
    return y, idx, held, read


# ---- M: Mamba-2 ---------------------------------------------------------------


def _split_in(cfg: ModelConfig, lp: Params, h: jnp.ndarray):
    """h W_in as (z [..., inner], xBC [..., conv_dim], dt [..., heads])."""
    zxbcdt = h @ lp["w_in"]
    inner, conv_dim = cfg.ssm_inner, cfg.ssm_conv_dim
    return (zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv_dim],
            zxbcdt[..., inner + conv_dim:])


def _step_size(lp: Params, dt: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])


def _split_conv(cfg: ModelConfig, xbc: jnp.ndarray):
    """The convolution's output as x [..., G, R, P] (head g * R + r), B and C
    [..., G, N]."""
    G, N, P = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_head_dim
    inner = cfg.ssm_inner
    lead = xbc.shape[:-1]
    return (xbc[..., :inner].reshape(*lead, G, cfg.ssm_heads // G, P),
            xbc[..., inner:inner + G * N].reshape(*lead, G, N),
            xbc[..., inner + G * N:].reshape(*lead, G, N))


def _gated_out(cfg: ModelConfig, lp: Params, y: jnp.ndarray, z: jnp.ndarray
               ) -> jnp.ndarray:
    """y [..., inner] (f32) gated by z, normed over its groups, through
    W_out."""
    G = cfg.ssm_groups
    y = y * jax.nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(*y.shape[:-1], G, -1)
    var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    y = (grouped * jax.lax.rsqrt(var + cfg.norm_eps)).reshape(y.shape)
    return (y * lp["norm"].astype(jnp.float32)).astype(z.dtype) @ lp["w_out"]


def _conv_run(lp: Params, xbc: jnp.ndarray, tail0: jnp.ndarray,
              lens: jnp.ndarray, Kc: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The causal depth-wise convolution (with its bias where the layer has
    one, before any activation, f32) over a run xbc [B, S, channels] that
    continues ``tail0`` [B, Kc - 1, channels], and the tail as position
    ``lens[b] - 1`` leaves it."""
    S = xbc.shape[1]
    seq = jnp.concatenate([tail0.astype(xbc.dtype), xbc], axis=1)
    # Row t + j of seq is position t - (Kc - 1) + j.
    bias = _conv_bias(lp)
    conv = sum(
        seq[:, j:j + S].astype(jnp.float32) * lp["conv_w"][j].astype(jnp.float32)
        for j in range(Kc))
    if bias is not None:
        conv = bias + conv
    tail = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(s, n, Kc - 1))(
        seq, lens)
    return conv, tail


def _conv_step(lp: Params, xbc: jnp.ndarray, tail0: jnp.ndarray
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`_conv_run` for one position a sequence: xbc [B, channels]
    behind ``tail0`` [B, Kc - 1, channels]; (the convolution's output, the
    Kc rows it ran over: the last Kc - 1 are the new tail)."""
    seq = jnp.concatenate([tail0.astype(xbc.dtype), xbc[:, None]], axis=1)
    bias = _conv_bias(lp)
    conv = jnp.sum(
        seq.astype(jnp.float32) * lp["conv_w"].astype(jnp.float32), axis=1)
    return (conv if bias is None else bias + conv), seq


def _conv_bias(lp: Params) -> jnp.ndarray | None:
    """The convolution's bias in f32; None for a layer without one."""
    return lp["conv_b"].astype(jnp.float32) if "conv_b" in lp else None


def ssm_scan(cfg: ModelConfig, lp: Params, h: jnp.ndarray, lens: jnp.ndarray,
             state0: jnp.ndarray | None = None,
             tail0: jnp.ndarray | None = None
             ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A state-space layer over a run of positions, the chunked form: h
    [B, S, D] (normed), of which the first ``lens[b]`` are real, continuing
    from ``state0`` [B, heads, head_dim, state] f32 and the convolution's
    ``tail0`` [B, conv - 1, channels] (None: a sequence's start, zeros).
    Returns (out [B, S, D], and the state and the tail as position
    ``lens[b] - 1`` left them)."""
    B, S, _ = h.shape
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    R, Kc = H // G, cfg.ssm_conv
    with scopes.block("state.proj"):
        z, xbc, dt = _split_in(cfg, lp, h)
        if tail0 is None:
            tail0 = jnp.zeros((B, Kc - 1, xbc.shape[-1]), xbc.dtype)
        if state0 is None:
            state0 = jnp.zeros((B, H, P, N), jnp.float32)
        conv, tail = _conv_run(lp, xbc, tail0, lens, Kc)
        x, b_mat, c_mat = _split_conv(cfg, jax.nn.silu(conv).astype(h.dtype))

    with scopes.block("state.update"):
        real = jnp.arange(S)[None, :] < lens[:, None]
        dt = jnp.where(real[..., None], _step_size(lp, dt), 0.0)    # [B, S, H]
        a_head = -jnp.exp(lp["A_log"])                              # [H]

        # Chunks of Q positions (a short bucket is one chunk); a run that is no
        # whole number of them is padded with positions that change nothing.
        Q = min(cfg.ssm_chunk, S)
        pad = -S % Q
        if pad:
            x, b_mat, c_mat, dt = (
                jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                for t in (x, b_mat, c_mat, dt))
        nc = (S + pad) // Q
        x = x.reshape(B, nc, Q, G, R, P)
        b_mat, c_mat = (t.reshape(B, nc, Q, G, N) for t in (b_mat, c_mat))
        dt = dt.reshape(B, nc, Q, G, R)
        a = jnp.cumsum(dt * a_head.reshape(G, R), axis=2)           # <= 0
        f32 = dict(preferred_element_type=jnp.float32)
        dtx = (dt[..., None] * x.astype(jnp.float32))               # D_s x_s

        # Inside a chunk: y_q = sum_(s <= q) exp(a_q - a_s) (C_q . B_s) D_s x_s.
        cb = jnp.einsum("bcqgn,bcsgn->bcqsg", c_mat, b_mat, **f32)
        q_at, s_at = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]
        decay = jnp.exp(jnp.where(
            (q_at >= s_at)[None, None, :, :, None, None],
            a[:, :, :, None] - a[:, :, None, :], -jnp.inf))         # [B,c,q,s,G,R]
        y = jnp.einsum("bcqsgr,bcsgrp->bcqgrp",
                       (cb[..., None] * decay).astype(h.dtype),
                       dtx.astype(h.dtype), **f32)

        # What a chunk adds to the state by its end, and the recurrence over
        # chunks: S_c = exp(a_Q) S_(c-1) + sum_s exp(a_Q - a_s) D_s x_s (x) B_s.
        to_end = jnp.exp(a[:, :, -1:] - a)
        added = jnp.einsum("bcsgn,bcsgrp->bcgrpn", b_mat,
                           (dtx * to_end[..., None]).astype(h.dtype), **f32)

        def join(prev, chunk):
            add, keep = chunk
            return prev * keep[..., None, None] + add, prev

        state1, before = jax.lax.scan(
            join, state0.reshape(B, G, R, P, N),
            (jnp.moveaxis(added, 1, 0), jnp.moveaxis(jnp.exp(a[:, :, -1]), 1, 0)))
        # What the state before a chunk gives its positions: exp(a_q) C_q . S.
        y = y + (jnp.einsum("bcqgn,cbgrpn->bcqgrp", c_mat,
                            before.astype(h.dtype), **f32)
                 * jnp.exp(a)[..., None])

        y = y + lp["D"].reshape(G, R, 1) * x.astype(jnp.float32)
        y = y.reshape(B, nc * Q, H * P)[:, :S]
    with scopes.block("state.proj"):
        out = _gated_out(cfg, lp, y, z)
    return out, state1.reshape(B, H, P, N), tail


def ssm_step(cfg: ModelConfig, lp: Params, h: jnp.ndarray,
             cache: state.Cache, layer: int
             ) -> tuple[jnp.ndarray, state.Cache, jnp.ndarray]:
    """A state-space layer for one position a sequence, the recurrence as
    written: h [B, D] (normed), the sequences' states and tails the rows of
    ``cache`` at state layer ``layer``. The recurrence itself runs where the
    states live (``state.recur``, in the form ``cfg.ssm_impl`` names); what
    leads up to it and what follows is here. Returns (out [B, D], the cache
    with the states updated, the new tails [B, conv - 1, channels], which
    the caller writes)."""
    B = h.shape[0]
    H, P, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups
    R = H // G
    with scopes.block("state.proj"):
        z, xbc, dt = _split_in(cfg, lp, h)
        tail0 = state.tail(cache, layer).reshape(B, cfg.ssm_conv - 1,
                                                 cfg.ssm_conv_dim)
        conv, seq = _conv_step(lp, xbc, tail0)
        x, b_mat, c_mat = _split_conv(cfg, jax.nn.silu(conv).astype(h.dtype))
        x, b_mat, c_mat = (t.astype(jnp.float32) for t in (x, b_mat, c_mat))
    with scopes.block("state.update"):
        dt = _step_size(lp, dt).reshape(B, G, R)
        keep = jnp.exp(dt * -jnp.exp(lp["A_log"]).reshape(G, R))
        cache, y = state.recur(
            cache, layer, keep.reshape(B, H), (dt[..., None] * x).reshape(B, H, P),
            b_mat, c_mat, impl=cfg.ssm_impl)
        y = y.reshape(B, G, R, P) + lp["D"].reshape(G, R, 1) * x
    with scopes.block("state.proj"):
        out = _gated_out(cfg, lp, y.reshape(B, H * P), z)
    return out, cache, seq[:, 1:]


# ---- S: Mamba-1 ---------------------------------------------------------------


def _ssm1_in(cfg: ModelConfig, lp: Params, h: jnp.ndarray):
    """h W_in as (x [..., inner], z [..., inner])."""
    xz = h @ lp["w_in"]
    return xz[..., :cfg.ssm_inner], xz[..., cfg.ssm_inner:]


def _ssm1_operands(cfg: ModelConfig, lp: Params, x: jnp.ndarray):
    """What the recurrence takes of the convolution's output x [..., inner]
    (the model's dtype): (dt, the step sizes, and x [..., inner], B and C
    [..., state], all f32). ``[dt_r | B | C] = x W_x``, an RMSNorm on each,
    ``dt = softplus(dt_r W_dt + b_dt)``."""
    R, N = cfg.ssm_dt_rank, cfg.ssm_state
    dbc = x @ lp["w_x"]
    dt_r = rms_norm(dbc[..., :R], lp["dt_norm"], cfg.norm_eps)
    b = rms_norm(dbc[..., R:R + N], lp["b_norm"], cfg.norm_eps)
    c = rms_norm(dbc[..., R + N:], lp["c_norm"], cfg.norm_eps)
    dt = jax.nn.softplus((dt_r @ lp["w_dt"]).astype(jnp.float32)
                         + lp["dt_bias"])
    return dt, *(t.astype(jnp.float32) for t in (x, b, c))


def _ssm1_out(lp: Params, y: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """y [..., inner] (f32) gated by z, through W_out."""
    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype) @ lp["w_out"]


def ssm1_scan(cfg: ModelConfig, lp: Params, h: jnp.ndarray, lens: jnp.ndarray,
              state0: jnp.ndarray | None = None,
              tail0: jnp.ndarray | None = None
              ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A Mamba-1 layer over a run of positions (:func:`ssm_scan`'s arguments
    and results; the state [B, state, inner] f32). There is no matrix form:
    the rows go through the recurrence in order, in the form
    ``cfg.ssm_scan_impl`` names (ops/pallas_ssm.py: one kernel with the state
    resident in VMEM, or ``lax.scan`` over positions). A padded position gets
    a step size of 0: no decay and no input."""
    B, S, _ = h.shape
    Kc = cfg.ssm_conv
    with scopes.block("state.proj"):
        x, z = _ssm1_in(cfg, lp, h)
        if tail0 is None:
            tail0 = jnp.zeros((B, Kc - 1, x.shape[-1]), x.dtype)
        if state0 is None:
            state0 = jnp.zeros((B, cfg.ssm_state, cfg.ssm_inner), jnp.float32)
        conv, tail = _conv_run(lp, x, tail0, lens, Kc)
        dt, x, b, c = _ssm1_operands(cfg, lp,
                                     jax.nn.silu(conv).astype(h.dtype))
    with scopes.block("state.update"):
        real = jnp.arange(S)[None, :] < lens[:, None]
        dt = jnp.where(real[..., None], dt, 0.0)
        a = -jnp.exp(lp["A_log"])
        if cfg.ssm_scan_impl.startswith("kernel"):
            y, state1 = pallas_ssm.selective_scan(
                dt, x, b, c, a, lp["D"], state0,
                interpret=cfg.ssm_scan_impl == "kernel_interpret")
        else:
            y, state1 = pallas_ssm.scan_rows(dt, x, b, c, a, lp["D"], state0)
    with scopes.block("state.proj"):
        out = _ssm1_out(lp, y, z)
    return out, state1, tail


def ssm1_step(cfg: ModelConfig, lp: Params, h: jnp.ndarray,
              cache: state.Cache, layer: int
              ) -> tuple[jnp.ndarray, state.Cache, jnp.ndarray]:
    """A Mamba-1 layer for one position a sequence (:func:`ssm_step`'s
    arguments and results): the recurrence where the states live
    (``state.recur1``, in the form ``cfg.ssm_impl`` names)."""
    B = h.shape[0]
    with scopes.block("state.proj"):
        x, z = _ssm1_in(cfg, lp, h)
        tail0 = state.tail(cache, layer).reshape(B, cfg.ssm_conv - 1,
                                                 cfg.ssm_inner)
        conv, seq = _conv_step(lp, x, tail0)
        dt, x, b, c = _ssm1_operands(cfg, lp,
                                     jax.nn.silu(conv).astype(h.dtype))
    with scopes.block("state.update"):
        cache, y = state.recur1(cache, layer, dt, x, b, c,
                                -jnp.exp(lp["A_log"]), lp["D"],
                                impl=cfg.ssm_impl)
    with scopes.block("state.proj"):
        out = _ssm1_out(lp, y, z)
    return out, cache, seq[:, 1:]


# ---- C: a gated short convolution ------------------------------------------------


def _conv_in(cfg: ModelConfig, lp: Params, h: jnp.ndarray):
    """h W_in as (g = B * u, what the convolution runs over, and C), each
    [..., d_model] in the model's dtype."""
    D = cfg.d_model
    bcu = h @ lp["w_in"]
    return bcu[..., :D] * bcu[..., 2 * D:], bcu[..., D:2 * D]


def _conv_out(lp: Params, c: jnp.ndarray, conv: jnp.ndarray) -> jnp.ndarray:
    """The convolution's output (f32) gated by C, through W_out."""
    return (c.astype(jnp.float32) * conv).astype(c.dtype) @ lp["w_out"]


def conv_run(cfg: ModelConfig, lp: Params, h: jnp.ndarray, lens: jnp.ndarray,
             state0: None = None, tail0: jnp.ndarray | None = None
             ) -> tuple[jnp.ndarray, None, jnp.ndarray]:
    """A gated short convolution over a run of positions (:func:`ssm_scan`'s
    arguments and results, the state None: the layer keeps none): h [B, S, D]
    (normed), the first ``lens[b]`` rows real, continuing from the slot's
    ``tail0`` [B, conv - 1, D] (None: a sequence's start, zeros). A padded
    row lies behind every real one, so it changes no real row's output, and
    the tail is gathered at the true length."""
    B = h.shape[0]
    with scopes.block("state.proj"):
        g, c = _conv_in(cfg, lp, h)
    with scopes.block("state.update"):
        if tail0 is None:
            tail0 = jnp.zeros((B, cfg.ssm_conv - 1, g.shape[-1]), g.dtype)
        conv, tail = _conv_run(lp, g, tail0, lens, cfg.ssm_conv)
    with scopes.block("state.proj"):
        out = _conv_out(lp, c, conv)
    return out, None, tail


def conv_step(cfg: ModelConfig, lp: Params, h: jnp.ndarray,
              cache: state.Cache, layer: int
              ) -> tuple[jnp.ndarray, state.Cache, jnp.ndarray]:
    """A gated short convolution for one position a sequence
    (:func:`ssm_step`'s arguments and results): h [B, D], the sequences'
    tails the rows of ``cache`` at state layer ``layer``; the cache comes
    back as it was (there is no state to update) and the caller writes the
    new tails."""
    B = h.shape[0]
    with scopes.block("state.proj"):
        g, c = _conv_in(cfg, lp, h)
    with scopes.block("state.update"):
        tail0 = state.tail(cache, layer).reshape(B, cfg.ssm_conv - 1,
                                                 cfg.ssm_conv_dim)
        conv, seq = _conv_step(lp, g, tail0)
    with scopes.block("state.proj"):
        out = _conv_out(lp, c, conv)
    return out, cache, seq[:, 1:]


def _expert_ffn(cfg: ModelConfig, stack: Params, i: int, x: jnp.ndarray
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Row ``i`` of the ``experts`` stack behind a mixer, on the residual
    stream x [B, S, D]: (the routed experts' output, the experts every token
    chose [T, k]). The router is models/routing.route on the FFN's normed
    input; the experts are models/llama._ffn's, in the form ``cfg.moe_impl``
    names (the grouped form reads the stacked weights at (layer, expert): a
    slice of them would reach the kernel as a copy)."""
    lp = {n: stack[n][i] for n in ("ln_mlp", "router", "router_bias")}
    h = _ffn_input(cfg, lp, x)
    with scopes.block("ffn.router"):
        idx, gates = route(cfg, lp, h.reshape(-1, h.shape[-1]))
    whole = cfg.moe_impl.startswith("grouped")
    lp.update({n: stack[n] if whole else stack[n][i]
               for n in ("w1", "w3", "w2")})
    if whole:
        lp["layer"] = jnp.asarray(i, jnp.int32)
    chosen = (idx.reshape(*h.shape[:-1], -1), gates.reshape(*h.shape[:-1], -1))
    return _ffn(cfg, lp, h, chosen), idx


# ---- the stack ------------------------------------------------------------------


_NORM_SCOPE = {"M": "state.proj", "*": "attn.proj", "E": "ffn.router",
               "S": "state.proj", "A": "attn.proj", "C": "state.proj",
               "Q": "attn.proj"}
# The letter whose mixer a layer's letter runs: an attention layer with an FFN
# behind it is the attention, a convolution keeps a slot row as the
# state-space mixers do.
_MIXER = {"A": "*", "Q": "*", "C": "S"}


def _walk(params: Params, cfg: ModelConfig, x: jnp.ndarray,
          mixers: dict[str, Callable[[Params, jnp.ndarray, int], jnp.ndarray]],
          real: jnp.ndarray | None = None
          ) -> tuple[jnp.ndarray, jnp.ndarray, list[jnp.ndarray],
                     jnp.ndarray | None]:
    """x through every layer in the pattern's order. ``mixers["M"]``,
    ``mixers["S"]`` (a "C" layer's too) and ``mixers["*"]`` (an "A" or "Q"
    layer's too) are ``(layer's parameters, normed input, index among its
    stack's layers) -> mixer output`` and keep what else they make (states,
    K/V rows) for their caller; the expert layer is the same in every step
    (``real`` is :func:`latent_moe`'s), and so is the FFN behind an "S",
    "A", "C" or "Q" mixer: the dense SwiGLU, or from layer
    ``cfg.first_k_dense`` of a model with experts on, the routed experts
    (:func:`_expert_ffn`). Returns (x, the count of expert choices held here,
    every expert layer's choices, the count of held experts read or None:
    :func:`latent_moe`)."""
    seen = dict.fromkeys(_STACK.values(), 0)
    held, routes, read, n_ffn = jnp.zeros((), jnp.int32), [], None, 0
    for at, kind in enumerate(cfg.layer_pattern):
        stack, i = params[_STACK[kind]], seen[_STACK[kind]]
        seen[_STACK[kind]] += 1
        with scopes.block(_NORM_SCOPE[kind]):   # with what reads it first
            h = rms_norm(x, stack["ln"][i], cfg.norm_eps)
        if kind == "E":
            y, chosen, n, n_read = latent_moe(cfg, stack, i, h, real)
            held, routes = held + n, routes + [chosen]
            if n_read is not None:
                read = n_read if read is None else read + n_read
        else:
            y = mixers[_MIXER.get(kind, kind)](
                {n: a[i] for n, a in stack.items()}, h, i)
        x = x + y
        if kind in _WITH_FFN and cfg.n_experts and at >= cfg.first_k_dense:
            flat = x.ndim == 2      # a decode step's [B, D]
            y, chosen = _expert_ffn(cfg, params["experts"],
                                    at - cfg.first_k_dense,
                                    x[:, None] if flat else x)
            x = x + (y[:, 0] if flat else y)
            # Every expert is held here: every choice counts.
            held, routes = held + chosen.size, routes + [chosen]
        elif kind in _WITH_FFN:
            lp = {n: a[n_ffn] for n, a in params["ffn"].items()}
            n_ffn += 1
            x = x + _ffn(cfg, lp, _ffn_input(cfg, lp, x))
    return x, held, routes, read


def _qkv(cfg: ModelConfig, lp: Params, h: jnp.ndarray,
         positions: jnp.ndarray | None = None):
    """h [..., D] -> q [..., H, Dh], k and v [..., Hkv, Dh]; no rotation,
    but for a layer whose parameters hold a norm a head for q and k (a "Q"
    layer): that norm, then the rotary embedding of ``positions`` [...]
    (None: a run from position 0 on). A
    model's ONE KV head is handed on twice (``cfg.kv_heads_kept``): the pages
    keep it so, and each copy serves half the query heads."""
    lead, Dh = h.shape[:-1], cfg.head_dim
    with scopes.block("attn.proj"):
        q = (h @ lp["wq"]).reshape(*lead, cfg.n_heads, Dh)
        k = (h @ lp["wk"]).reshape(*lead, cfg.n_kv_heads, Dh)
        v = (h @ lp["wv"]).reshape(*lead, cfg.n_kv_heads, Dh)
        if "q_norm" in lp:
            q, k = qk_normed(cfg, lp, q, k)
            if positions is None:
                positions = jnp.arange(h.shape[-2], dtype=jnp.int32)
            cos, sin = rope_table(positions, Dh, cfg.rope_theta)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        if cfg.kv_heads_kept != cfg.n_kv_heads:
            k, v = (jnp.repeat(t, cfg.kv_heads_kept // cfg.n_kv_heads,
                               axis=-2) for t in (k, v))
        return q, k, v


def _out(lp: Params, attn: jnp.ndarray) -> jnp.ndarray:
    """The attention's output [..., H, Dh] through ``wo``."""
    with scopes.block("attn.proj"):
        return attn.reshape(*attn.shape[:-2], -1) @ lp["wo"]


def _scan_of(cfg: ModelConfig):
    """The form for a run of positions of the layers that keep a slot row."""
    if cfg.conv_mixers:
        return conv_run
    return ssm1_scan if cfg.ssm_dt_rank else ssm_scan


def _step_of(cfg: ModelConfig):
    """Their form for one position a sequence."""
    if cfg.conv_mixers:
        return conv_step
    return ssm1_step if cfg.ssm_dt_rank else ssm_step


def _stacked(rows: list[jnp.ndarray], like: tuple[int, ...], dtype
             ) -> jnp.ndarray:
    """A kind's per-layer outputs stacked; a pattern without that kind gives
    an empty stack of the right rank."""
    return jnp.stack(rows) if rows else jnp.zeros((0, *like), dtype)


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,                   # [B, S]
    positions: jnp.ndarray | None = None,  # read by "Q" layers alone
    *,
    want_kv: bool = False,
    want_hidden: bool = False,
    kv_valid: jnp.ndarray | None = None,   # [B, S] padding mask
    mm_embeds: jnp.ndarray | None = None,
    mm_positions: jnp.ndarray | None = None,
    seq_len: jnp.ndarray | None = None,    # [B] true lengths of the bucket
    want_routes: bool = False,
):
    """Full-sequence forward from a sequence's start (a prefill, or a long
    prompt's first window), the state-space layers in the scan form. Returns
    (logits [B, S, V] f32, (``state.Fresh``, None) if want_kv): the K/V rows
    of the attention layers, and the state and tail every state-space layer
    is left with at ``seq_len[b] - 1`` (None: the whole run is real).
    ``want_routes`` appends every expert layer's choices [expert layers, T,
    k], as models/mla.py's does, for scripts/compare_ssm_reference.py."""
    if mm_embeds is not None:
        raise NotImplementedError(
            "this family serves text: multimodal embeddings have nothing to "
            "come from")
    B, S = tokens.shape
    lens = jnp.full((B,), S, jnp.int32) if seq_len is None else seq_len
    ks, vs, ssms, tails = [], [], [], []

    def ssm(lp, h, i):
        out, s, tail = _scan_of(cfg)(cfg, lp, h, lens)
        ssms.append(s), tails.append(tail)
        return out

    def attend(lp, h, i):
        q, k, v = _qkv(cfg, lp, h, positions)
        ks.append(k), vs.append(v)
        with scopes.block("attn.core"):
            out = causal_attention(q, k, v, kv_valid=kv_valid)
        return _out(lp, out)

    x, held, routes, _ = _walk(params, cfg, _embedded(params, tokens),
                               {"M": ssm, "S": ssm, "*": attend})
    with scopes.block("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    kv = None
    if want_kv:
        dt = x.dtype
        kv_like = (B, S, cfg.kv_heads_kept, cfg.head_dim)
        kv = (state.Fresh(
            _stacked(ks, kv_like, dt), _stacked(vs, kv_like, dt),
            # (None: layers that keep a tail and no recurrent state.)
            _stacked(ssms, (B, *cfg.ssm_row), jnp.float32) if cfg.ssm_row
            else None,
            _stacked(tails, (B, cfg.ssm_conv - 1, cfg.ssm_conv_dim), dt),
            held), None)
    with scopes.block("head"):
        out = (x if want_hidden
               else x @ params["lm_head"]).astype(jnp.float32)
    return (out, kv, jnp.stack(routes)) if want_routes else (out, kv)


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,        # [B]
    positions: jnp.ndarray,     # [B]
    k_pages: state.Cache,       # pages and state, the rows' slots in it
    v_pages: None,
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    active: jnp.ndarray | None = None,
    *,
    attention_fn: Callable[..., jnp.ndarray] = pages.decode_attention,
    want_routes: bool = False,
):
    """One decode step, the state-space layers in the step form; returns
    (logits [B, V] f32, cache, None). The attention layers read the stacked
    K/V pools at (layer, page) as models/llama.decode_step's do; every
    state-space layer updates its rows of the state pool at (layer, slot)
    when it runs, and the tails are written back with one scatter
    afterwards."""
    cache = k_pages
    B = tokens.shape[0]
    seq_lens = positions + 1
    with scopes.block("kv.write"):
        cur_slots = pages.token_slots(cache.k, block_tables, positions)
    ks, vs, tails = [], [], []

    def ssm(lp, h, i):
        nonlocal cache
        out, cache, tail = _step_of(cfg)(cfg, lp, h, cache, i)
        tails.append(tail)
        return out

    def attend(lp, h, i):
        q, k, v = _qkv(cfg, lp, h, positions)
        ks.append(k), vs.append(v)
        with scopes.block("attn.core"):
            out = attention_fn(q, cache.k, cache.v, jnp.asarray(i, jnp.int32),
                               block_tables, seq_lens, k, v)
        return _out(lp, out)

    # A padding lane's choices are nobody's (asked only by the form that reads
    # the chosen experts).
    x, held, routes, read = _walk(
        params, cfg, _embedded(params, tokens), {"M": ssm, "S": ssm, "*": attend},
        real=(pages.lanes_in_use(block_tables)
              if cfg.moe_impl.startswith("chosen") else None))
    cache = _written(cache, ks, vs, None, tails, held, cur_slots, read)

    out = (_logits(params, cfg, x, active), cache, None)
    return (*out, jnp.stack(routes)) if want_routes else out


def _written(cache: state.Cache, ks, vs, ssms, tails, held, kv_slots,
             read=None) -> state.Cache:
    """``cache`` after a step: the attention layers' new rows in their pages
    at ``kv_slots`` (block ids, slots in them), the state layers' new rows in
    their slots (``ssms`` None: a decode step, whose states are in the cache
    already), the step's count of held choices added (and, where it counted
    them, of held experts ``read``)."""
    with scopes.block("kv.write"):
        if ks:
            k, v = pages.write(cache.k, cache.v, jnp.stack(ks), jnp.stack(vs),
                               *kv_slots)
            cache = dataclasses.replace(cache, k=k, v=v)
        if tails:
            cache = state.write(cache, ssms, tails)
    return state.counted(cache, held, read=read)


def prefill_with_prefix(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,        # [1, S_bucket] the window's tokens (padded)
    suffix_len: jnp.ndarray,    # [1]
    prefix_len: jnp.ndarray,    # [1] tokens already written
    k_pages: state.Cache,
    v_pages: None,
    block_table_row: jnp.ndarray,               # [1, max_blocks]
    prior_table_row: jnp.ndarray | None = None,  # [1, prefix_bucket]
    *,
    want_routes: bool = False,
):
    """A long prompt's next window: the state-space layers continue in the
    scan form from the slot's state and tail, the attention layers read the
    prompt's earlier K/V back from the pages. Returns (last-token logits
    [1, V] f32, cache, None). It continues the SLOT's state: what a prefix
    cache would hand it is pages of another slot's tokens with no state to
    go with them, which is why an engine that serves this family keeps no
    prefix cache (engine/core.py)."""
    cache = k_pages
    B, S = tokens.shape
    assert B == 1
    if prior_table_row is None:
        prior_table_row = block_table_row
    T = prior_table_row.shape[1] * pages.block_size(cache.k)

    positions = prefix_len[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    prior_pos = jnp.arange(T, dtype=jnp.int32)[None, :]
    kv_positions = jnp.concatenate([prior_pos, positions], axis=1)
    kv_valid = jnp.concatenate(
        [prior_pos < prefix_len[:, None],
         jnp.arange(S)[None, :] < suffix_len[:, None]], axis=1)
    ks, vs, ssms, tails = [], [], [], []

    def ssm(lp, h, i):
        s0, tail0 = state.read(cache, i)
        out, s, tail = _scan_of(cfg)(cfg, lp, h, suffix_len, s0, tail0.reshape(
            B, cfg.ssm_conv - 1, cfg.ssm_conv_dim))
        ssms.append(s), tails.append(tail)
        return out

    def attend(lp, h, i):
        q, k, v = _qkv(cfg, lp, h, positions)
        ks.append(k), vs.append(v)
        with scopes.block("attn.core"):
            k_prior, v_prior = pages.read_prefix(
                cache.k, cache.v, prior_table_row, layer=i,
                heads=k.shape[-2:])
            out = causal_attention(
                q, jnp.concatenate([k_prior, k], axis=1),
                jnp.concatenate([v_prior, v], axis=1), q_positions=positions,
                kv_positions=kv_positions, kv_valid=kv_valid)
        return _out(lp, out)

    x, held, routes, _ = _walk(params, cfg, _embedded(params, tokens),
                               {"M": ssm, "S": ssm, "*": attend})
    # (No states to write where the layers keep a tail alone.)
    cache = _written(cache, ks, vs, ssms if cfg.ssm_row else None, tails,
                     held, pages.sequence_slots(cache.k, block_table_row,
                                                suffix_len, S, prefix_len))

    out = (_last_logits(params, cfg, x, suffix_len), cache, None)
    return (*out, jnp.stack(routes)) if want_routes else out
