"""The program's model code against the plain reference, on seeded random
weights at a small size on the CPU: dense with QK-norm (Qwen3's block) and
sparse experts (Mixtral's)."""

import importlib.util
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _reference():
    path = os.path.join(os.path.dirname(HERE), "configs",
                        "reference_llama_family.py")
    spec = importlib.util.spec_from_file_location("reference_llama_family", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("preset", ["tiny-qwen", "tiny-moe"])
def test_program_forward_matches_plain_reference(preset):
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from llm_d_inference_scheduler_tpu.models import llama
    from llm_d_inference_scheduler_tpu.models.configs import get_config

    cfg = get_config(preset)
    params = llama.init_params(cfg, jax.random.key(7), dtype=jnp.float32)
    if cfg.qk_norm:
        # The init sets every norm weight to one; make the check see them.
        k1, k2 = jax.random.split(jax.random.key(8))
        params["layers"]["q_norm"] = 1 + 0.1 * jax.random.normal(
            k1, params["layers"]["q_norm"].shape)
        params["layers"]["k_norm"] = 1 + 0.1 * jax.random.normal(
            k2, params["layers"]["k_norm"].shape)
    tokens = jax.random.randint(jax.random.key(9), (2, 24), 0, cfg.vocab_size)
    ours, _ = llama.forward(params, cfg, tokens)
    ref = _reference()
    for row in range(2):
        want = ref.forward(params, tokens[row], n_heads=cfg.n_heads,
                           n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                           rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                           experts_per_token=cfg.experts_per_token)
        # float32 on both sides, different summation order: 1e-4 of logits
        # that are O(1). bf16 anywhere would miss by 1e-2.
        np.testing.assert_allclose(np.asarray(ours[row]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
