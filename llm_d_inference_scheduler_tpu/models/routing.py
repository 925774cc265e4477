"""The routers of the expert layers, where models/mla.py and models/hybrid.py
both find them. Which one a layer has is the configuration's to say
(``ModelConfig.router_scoring``), not the caller's:

- ``"sigmoid"`` (the DeepSeek-V3 and nemotron_h families): a score an expert
  ``sigmoid(h W_r)``; the gates are the chosen scores normalised over the
  chosen, times ``routed_scaling_factor``.
- ``"softmax"`` (LongCat-Flash): scores ``softmax(h W_r)`` over ALL the
  router's outputs, its experts and its zero-compute experts alike; the gates
  are the chosen scores times ``routed_scaling_factor``, NOT normalised over
  the chosen (a token that spends choices on zero-compute experts keeps what
  they weigh).

Either selects the experts_per_token largest of score plus the selection bias,
which selects and does not weigh. Where the configuration groups the router's
outputs (``n_group`` > 1, DeepSeek-V3's group-limited selection) the choice is
made inside the ``topk_group`` groups whose two largest biased scores sum
highest; ``n_group`` 1 is the plain choice and traces as it always did."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .configs import ModelConfig

_SCORING = ("sigmoid", "softmax")


def route(cfg: ModelConfig, lp: dict[str, Any], h: jnp.ndarray
          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(outputs chosen [T, k] int32, gates [T, k] f32) of tokens h [T, D], by
    the router ``cfg.router_scoring`` names (the module's docstring). Scores
    in f32 straight from the product (a score rounded to bf16 sends near-ties
    to other experts: ops/pallas_moe.py has the same note)."""
    if cfg.router_scoring not in _SCORING:
        raise ValueError(f"{cfg.name}: router_scoring "
                         f"{cfg.router_scoring!r} is none of {_SCORING}")
    over_all = cfg.router_scoring == "softmax"
    logits = jnp.dot(h, lp["router"], preferred_element_type=jnp.float32)
    scores = (jax.nn.softmax(logits, axis=-1) if over_all
              else jax.nn.sigmoid(logits))
    biased = scores + lp["router_bias"].astype(jnp.float32)
    if cfg.n_group > 1:
        groups = biased.reshape(*biased.shape[:-1], cfg.n_group, -1)
        best = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)    # [T, n_group]
        _, kept = jax.lax.top_k(best, cfg.topk_group)
        open_ = jnp.any(kept[..., None] == jnp.arange(cfg.n_group), axis=-2)
        biased = jnp.where(open_[..., None], groups,
                           -jnp.inf).reshape(biased.shape)
    _, idx = jax.lax.top_k(biased, cfg.experts_per_token)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = (chosen if over_all
             else chosen / jnp.sum(chosen, axis=-1, keepdims=True))
    return idx, gates * cfg.routed_scaling_factor
