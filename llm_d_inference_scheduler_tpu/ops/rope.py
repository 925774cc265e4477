"""Rotary position embeddings (Llama-style, non-interleaved halves)."""

from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_mscale(yarn: tuple[float, ...]) -> float:
    """YaRN's magnitude factor ``0.1 mscale_all_dim ln(factor) + 1`` (1.0
    without YaRN): the DeepSeek family leaves cos/sin unscaled and multiplies
    its softmax scale by the square of it."""
    if not yarn or yarn[0] <= 1.0:
        return 1.0
    return 0.1 * yarn[4] * math.log(yarn[0]) + 1.0


def yarn_frequencies(head_dim: int, theta: float, yarn: tuple[float, ...]
                     ) -> jnp.ndarray:
    """The head_dim/2 inverse frequencies under YaRN, ``yarn`` = (factor,
    original_max_position_embeddings, beta_fast, beta_slow, ...): frequency i
    is ``f_i = theta^(-2i/d)`` below ``low`` (rotations the original context
    already saw whole: kept), ``f_i / factor`` above ``high`` (interpolated),
    and a linear blend between, where ``low`` / ``high`` are the indices
    whose wavelength fits beta_fast / beta_slow times into the original
    context."""
    factor, original, fast, slow = yarn[:4]
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))

    def index_of(rotations: float) -> float:
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(index_of(fast)), 0)
    high = min(math.ceil(index_of(slow)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return freqs * (1.0 - ramp) + (freqs / factor) * ramp


def rope_table(positions: jnp.ndarray, head_dim: int, theta: float,
               yarn: tuple[float, ...] = ()) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for integer positions. positions: [...]. Returns
    [..., head_dim//2]. ``yarn``: :func:`yarn_frequencies`' in place of the
    plain ones."""
    half = head_dim // 2
    if yarn:
        freqs = yarn_frequencies(head_dim, theta, yarn)
    else:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., half]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate pairs (x1, x2) = (x[..:half], x[half:]).

    x: [..., n_heads, head_dim]; cos/sin: broadcastable to [..., 1, head_dim//2].
    """
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = xf[..., :half], xf[..., half:]
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)
