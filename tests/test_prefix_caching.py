"""Automatic prefix caching: block reuse correctness and eviction."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np

from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
from llm_d_inference_scheduler_tpu.engine.blocks import PrefixCachingAllocator
from llm_d_inference_scheduler_tpu.kvcache import pages
from llm_d_inference_scheduler_tpu.models import TINY, llama


def test_prefill_with_prefix_matches_full_forward():
    """Prefill of [prefix in cache] + suffix == full-forward logits."""
    cfg = TINY
    block = cfg.kv_block_size
    prompt_len = 3 * block + 5  # 2 cacheable blocks + partial
    prefix_blocks = 2
    prefix_len = prefix_blocks * block

    params = llama.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(1), (1, prompt_len), 0,
                                cfg.vocab_size)
    ref_logits, _ = llama.forward(params, cfg, tokens)

    max_blocks = 8
    n_blocks = 1 + max_blocks
    k_pages, v_pages = pages.alloc(
        pages.PageGeometry.for_model(cfg, n_blocks, dtype="float32"))
    table = jnp.arange(1, 1 + max_blocks, dtype=jnp.int32).reshape(1, max_blocks)

    # Stage 1: prefill ONLY the prefix into the pages (simulating cached blocks).
    _, (k_new, v_new) = llama.forward(params, cfg, tokens[:, :prefix_len],
                                      want_kv=True)
    k_pages, v_pages = pages.write_sequences(
        k_pages, v_pages, k_new, v_new, table,
        jnp.array([prefix_len], jnp.int32))

    # Stage 2: prefill the suffix continuing from the cached prefix.
    suffix = tokens[:, prefix_len:]
    pad = 16 - (suffix.shape[1] % 16) if suffix.shape[1] % 16 else 0
    suffix_padded = jnp.pad(suffix, ((0, 0), (0, pad)))
    logits, k_pages, v_pages = llama.prefill_with_prefix(
        params, cfg, suffix_padded,
        jnp.array([suffix.shape[1]], jnp.int32),
        jnp.array([prefix_len], jnp.int32),
        k_pages, v_pages, table)
    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(ref_logits[0, -1]),
                               rtol=2e-4, atol=2e-4)

    # The pages must now hold the SAME KV as a full prefill would produce.
    _, (k_full, v_full) = llama.forward(params, cfg, tokens, want_kv=True)
    for t in range(prompt_len):
        blk, slot = 1 + t // block, t % block
        np.testing.assert_allclose(np.asarray(k_pages[:, blk, slot]),
                                   np.asarray(k_full[:, 0, t]),
                                   rtol=2e-4, atol=2e-4)


def test_allocator_prefix_reuse_and_eviction():
    a = PrefixCachingAllocator(n_blocks=6, block_size=16)  # 5 usable
    b1 = a.alloc(3)
    a.commit_hashes(b1[:2], [101, 102])
    assert a.match_prefix([101, 102]) == b1[:2]
    assert a.match_prefix([999]) == []
    a.release(b1)
    # 2 parked (hash-committed) + 1 freed + 2 never allocated
    assert a.cached_block_count == 2 and a.free_blocks == 3

    # Reuse: acquire cached, allocate the rest.
    m = a.match_prefix([101, 102, 103])
    assert m == b1[:2]
    a.acquire_cached(m)
    extra = a.alloc(3)  # 1 free + evicts nothing further? 5 usable: 2 held + 3
    assert not set(extra) & set(m)
    a.release(m)
    a.release(extra)

    # Eviction under pressure: allocate everything; parked blocks get evicted
    # and their hashes reported.
    big = a.alloc(5)
    assert 101 in a.last_evicted_hashes or 102 in a.last_evicted_hashes
    assert a.match_prefix([101, 102]) == [] or len(a.match_prefix([101, 102])) < 2
    a.release(big)


def test_engine_prefix_cache_hit_and_consistency():
    """Second identical prompt: cached_tokens > 0 and identical greedy tokens."""
    async def body():
        from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

        eng = TpuEngine(EngineConfig(model="tiny", backend="tpu", max_batch=2,
                                     max_model_len=256))
        await eng.start()
        try:
            prompt = [1] + list(range(100, 100 + 40))  # 41 tokens: 2 full blocks

            async def gen(rid):
                out = eng.submit(EngineRequest(request_id=rid,
                                               prompt_token_ids=prompt,
                                               max_tokens=6, ignore_eos=True))
                toks, cached = [], 0
                while True:
                    ev = await asyncio.wait_for(out.get(), timeout=60)
                    cached = max(cached, ev.cached_tokens)
                    if ev.token_id is not None:
                        toks.append(ev.token_id)
                    if ev.finish_reason is not None:
                        return toks, cached

            t1, c1 = await gen("first")
            assert c1 == 0
            t2, c2 = await gen("second")
            assert c2 == 32  # two cached blocks reused
            assert t2 == t1  # numerically consistent continuation

            # A different prompt must not hit the cache.
            out = eng.submit(EngineRequest(
                request_id="other", prompt_token_ids=[1] + list(range(500, 540)),
                max_tokens=2, ignore_eos=True))
            cached = 0
            while True:
                ev = await asyncio.wait_for(out.get(), timeout=60)
                cached = max(cached, ev.cached_tokens)
                if ev.finish_reason is not None:
                    break
            assert cached == 0
        finally:
            await eng.stop()

    asyncio.run(body())


def test_engine_cache_eviction_under_pressure():
    """Tiny block budget: cache blocks evict instead of wedging admission."""
    async def body():
        from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

        eng = TpuEngine(EngineConfig(model="tiny", backend="tpu", max_batch=1,
                                     max_model_len=128, hbm_kv_blocks=9))
        await eng.start()
        try:
            async def gen(prompt):
                out = eng.submit(EngineRequest(
                    request_id=f"r{prompt[1]}", prompt_token_ids=prompt,
                    max_tokens=2, ignore_eos=True))
                while True:
                    ev = await asyncio.wait_for(out.get(), timeout=60)
                    if ev.finish_reason is not None:
                        return ev

            # Distinct 3-block prompts; budget of 8 usable blocks forces LRU
            # eviction of parked cache blocks across iterations.
            for base in (100, 200, 300, 400):
                ev = await gen([1] + list(range(base, base + 40)))
                assert ev.finish_reason.value in ("length", "stop")
        finally:
            await eng.stop()

    asyncio.run(body())
