"""Numerics tests for the Llama engine model: decode path == prefill path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.kvcache import pages
from llm_d_inference_scheduler_tpu.models import TINY, llama


@pytest.fixture(scope="module")
def params():
    return llama.init_params(TINY, jax.random.key(0), dtype=jnp.float32)


def test_forward_shapes(params):
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, TINY.vocab_size)
    logits, kv = llama.forward(params, TINY, tokens, want_kv=True)
    assert logits.shape == (2, 8, TINY.vocab_size)
    k, v = kv
    assert k.shape == (TINY.n_layers, 2, 8, TINY.n_kv_heads, TINY.head_dim)
    assert not np.isnan(np.asarray(logits)).any()


def test_causality(params):
    """Changing a later token must not change earlier logits."""
    t1 = jax.random.randint(jax.random.key(2), (1, 8), 0, TINY.vocab_size)
    t2 = t1.at[0, 5].set((t1[0, 5] + 1) % TINY.vocab_size)
    l1, _ = llama.forward(params, TINY, t1)
    l2, _ = llama.forward(params, TINY, t2)
    np.testing.assert_allclose(np.asarray(l1[0, :5]), np.asarray(l2[0, :5]), rtol=1e-5)
    assert not np.allclose(np.asarray(l1[0, 5:]), np.asarray(l2[0, 5:]))


def test_paged_decode_matches_full_forward(params):
    """Prefill + paged decode must reproduce full-sequence forward logits."""
    cfg = TINY
    B, prompt_len, gen = 2, 7, 5
    total = prompt_len + gen
    block = cfg.kv_block_size
    max_blocks = -(-cfg.max_seq_len // block)
    n_blocks = 1 + B * max_blocks  # block 0 = trash

    key = jax.random.key(3)
    tokens = jax.random.randint(key, (B, total), 0, cfg.vocab_size)

    # Reference: full forward over the whole sequence.
    ref_logits, _ = llama.forward(params, cfg, tokens)

    # Paged path: prefill prompt, then decode token by token.
    k_pages, v_pages = pages.alloc(
        pages.PageGeometry.for_model(cfg, n_blocks, dtype="float32"))
    block_tables = jnp.arange(1, 1 + B * max_blocks, dtype=jnp.int32).reshape(B, max_blocks)

    prefill_logits, (k_new, v_new) = llama.forward(params, cfg, tokens[:, :prompt_len], want_kv=True)
    seq_lens = jnp.full((B,), prompt_len, jnp.int32)
    k_pages, v_pages = pages.write_sequences(k_pages, v_pages, k_new, v_new, block_tables, seq_lens)
    np.testing.assert_allclose(
        np.asarray(prefill_logits), np.asarray(ref_logits[:, :prompt_len]), rtol=2e-4, atol=2e-4
    )

    for i in range(gen):
        pos = jnp.full((B,), prompt_len + i, jnp.int32)
        step_logits, k_pages, v_pages = llama.decode_step(
            params, cfg, tokens[:, prompt_len + i], pos, k_pages, v_pages, block_tables
        )
        np.testing.assert_allclose(
            np.asarray(step_logits),
            np.asarray(ref_logits[:, prompt_len + i]),
            rtol=2e-4,
            atol=2e-4,
        )


def test_decode_crosses_block_boundary(params):
    """Decode positions that span multiple KV blocks stay consistent."""
    cfg = TINY
    B = 1
    block = cfg.kv_block_size
    total = block + 4  # forces a second block
    tokens = jax.random.randint(jax.random.key(4), (B, total), 0, cfg.vocab_size)
    ref_logits, _ = llama.forward(params, cfg, tokens)

    max_blocks = 4
    n_blocks = 1 + max_blocks
    k_pages, v_pages = pages.alloc(
        pages.PageGeometry.for_model(cfg, n_blocks, dtype="float32"))
    block_tables = jnp.arange(1, 1 + max_blocks, dtype=jnp.int32).reshape(1, max_blocks)

    prompt_len = 2
    _, (k_new, v_new) = llama.forward(params, cfg, tokens[:, :prompt_len], want_kv=True)
    k_pages, v_pages = pages.write_sequences(
        k_pages, v_pages, k_new, v_new, block_tables, jnp.array([prompt_len], jnp.int32)
    )
    for i in range(prompt_len, total):
        pos = jnp.array([i], jnp.int32)
        step_logits, k_pages, v_pages = llama.decode_step(
            params, cfg, tokens[:, i], pos, k_pages, v_pages, block_tables
        )
    np.testing.assert_allclose(
        np.asarray(step_logits), np.asarray(ref_logits[:, -1]), rtol=2e-4, atol=2e-4
    )
