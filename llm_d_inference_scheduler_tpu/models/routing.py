"""The sigmoid-score router of the DeepSeek-V3 and nemotron_h families, where
models/mla.py and models/hybrid.py both find it."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .configs import ModelConfig


def route(cfg: ModelConfig, lp: dict[str, Any], h: jnp.ndarray
          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(experts [T, k] int32, gates [T, k] f32) of tokens h [T, D]: scores
    ``sigmoid(h W_r)``, the experts_per_token largest of score plus the
    selection bias (which selects and does not weigh), gates the chosen
    scores normalised and scaled. Scores in f32 straight from the product (a
    score rounded to bf16 sends near-ties to other experts:
    ops/pallas_moe.py has the same note)."""
    scores = jax.nn.sigmoid(jnp.dot(h, lp["router"],
                                    preferred_element_type=jnp.float32))
    _, idx = jax.lax.top_k(scores + lp["router_bias"].astype(jnp.float32),
                           cfg.experts_per_token)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx, gates * cfg.routed_scaling_factor
