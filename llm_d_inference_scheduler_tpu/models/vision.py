"""Vision encoder for E/P/D multimodal serving (the E/P/D deployment:
CPU/TPU encode workers producing embeddings for TPU prefill).

The reference routes multimodal requests to encode workers but the towers
live in the external engines (SURVEY §2.10 connector_epd_shared_storage.go);
this module provides the TPU-native tower: a compact ViT — patch embedding as
a reshape+matmul (MXU-shaped, no conv primitive needed), pre-norm transformer
blocks run under ``lax.scan`` over stacked layer weights (one traced body,
layer-count-free compiles), and a projection to the language model's
embedding width so outputs splice directly into prefill embeddings.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops import rms_norm


@dataclasses.dataclass(frozen=True)
class VisionConfig:
    name: str = "vit-tiny"
    image_size: int = 32          # square input, pixels
    patch_size: int = 8
    channels: int = 3
    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 4
    d_ff: int = 128
    out_dim: int = 128            # language model d_model to project into
    norm_eps: float = 1e-5
    dtype: str = "float32"        # encode runs fine in f32 on CPU workers

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def patch_dim(self) -> int:
        return self.patch_size * self.patch_size * self.channels

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


VIT_TINY = VisionConfig()


def init_vision_params(cfg: VisionConfig, key: jax.Array):
    dtype = jnp.dtype(cfg.dtype)
    L, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 8)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5).astype(dtype)

    return {
        "patch_embed": w(ks[0], (cfg.patch_dim, D), cfg.patch_dim),
        "pos_embed": w(ks[1], (cfg.n_patches, D), D),
        "layers": {
            "wqkv": w(ks[2], (L, D, 3 * D), D),
            "wo": w(ks[3], (L, D, D), D),
            "w1": w(ks[4], (L, D, F), D),
            "w2": w(ks[5], (L, F, D), F),
            "ln_attn": jnp.ones((L, D), dtype),
            "ln_mlp": jnp.ones((L, D), dtype),
        },
        "final_norm": jnp.ones((D,), dtype),
        "proj": w(ks[6], (D, cfg.out_dim), D),
    }


def _patchify(cfg: VisionConfig, pixels: jnp.ndarray) -> jnp.ndarray:
    """[B, H, W, C] → [B, n_patches, patch_dim] without a conv primitive."""
    B = pixels.shape[0]
    P = cfg.patch_size
    n = cfg.image_size // P
    x = pixels.reshape(B, n, P, n, P, cfg.channels)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # [B, n, n, P, P, C]
    return x.reshape(B, n * n, cfg.patch_dim)


def encode_image(params, cfg: VisionConfig, pixels: jnp.ndarray) -> jnp.ndarray:
    """pixels [B, H, W, C] float → embeddings [B, n_patches, out_dim]."""
    x = _patchify(cfg, pixels.astype(jnp.dtype(cfg.dtype)))
    x = x @ params["patch_embed"] + params["pos_embed"][None]
    B, S, D = x.shape
    Hd = cfg.head_dim

    def body(x, lp):
        h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
        qkv = (h @ lp["wqkv"]).reshape(B, S, 3, cfg.n_heads, Hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (Hd ** 0.5)
        attn = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", attn, v).reshape(B, S, D)
        x = x + out @ lp["wo"]
        h = rms_norm(x, lp["ln_mlp"], cfg.norm_eps)
        x = x + jax.nn.gelu(h @ lp["w1"]) @ lp["w2"]
        return x, None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x @ params["proj"]
