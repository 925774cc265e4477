"""Automatic prefix caching: block reuse correctness and eviction."""

import asyncio
import copy
import pathlib
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
from llm_d_inference_scheduler_tpu.engine.blocks import (
    BlockAllocator, PrefixCachingAllocator, table_groups)
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


def _as_taken(cls):
    """``cls`` with the sort taken out of ``alloc``: the allocator as it was
    before a table's order was made ascending."""
    return type("AsTaken" + cls.__name__, (cls,),
                {"alloc": lambda self, n: self._take(n)})


@pytest.mark.parametrize("cls", [BlockAllocator, PrefixCachingAllocator])
def test_alloc_returns_ascending_ids_and_takes_what_it_took(cls):
    """A random course of allocations and releases; at every allocation a
    copy of the allocator without the sort allocates too: ``alloc`` returns
    the copy's blocks in ascending order, and the free list left, what stays
    parked, what is evicted and ``last_evicted_hashes`` (in eviction order)
    are the copy's."""
    rng = random.Random(3)
    a = cls(64, 16)
    caching = cls is PrefixCachingAllocator
    held, next_hash, evicted_some, reordered = [], 0, False, False
    for _ in range(400):
        room = a.reusable_blocks if caching else a.free_blocks
        if held and (rng.random() < 0.45 or room < 12):
            a.free(held.pop(rng.randrange(len(held))))
            continue
        n = rng.randint(1, 12)
        twin = copy.deepcopy(a)
        twin.__class__ = _as_taken(cls)
        mine, theirs = a.alloc(n), twin.alloc(n)
        assert mine == sorted(theirs) and len(set(mine)) == n
        reordered |= mine != theirs
        assert a._free == twin._free
        if caching:
            assert a.last_evicted_hashes == twin.last_evicted_hashes
            evicted_some |= len(a.last_evicted_hashes) > 1
            assert list(a._cached_lru) == list(twin._cached_lru)
            assert a._by_hash == twin._by_hash and a._ref == twin._ref
            whole = mine[:rng.randint(0, n)]
            a.commit_hashes(whole, range(next_hash, next_hash + len(whole)))
            next_hash += len(whole)
        held.append(mine)
    assert reordered and (evicted_some or not caching)


def test_a_prefix_hits_blocks_lead_the_table_and_the_rest_ascend():
    a = PrefixCachingAllocator(n_blocks=40, block_size=16)
    first = a.alloc(6)
    a.commit_hashes(first[:4], [11, 12, 13, 14])
    other = a.alloc(5)
    a.release(first)
    a.release(other)            # the free list now pops 5 ... 1-ish order
    matched = a.match_prefix([11, 12, 13, 99])
    assert matched == first[:3]
    a.acquire_cached(matched)
    rest = a.alloc(9)
    assert rest == sorted(rest) and not set(rest) & set(matched)
    # The borrowed head splits the first group of four; the rest are runs.
    assert matched + rest == [1, 2, 3] + list(range(5, 14))
    assert table_groups(matched + rest, 4) == (2, 1)


@pytest.mark.parametrize("table,group,want", [
    ([], 8, (0, 0)),
    ([5, 6, 7, 8, 9, 10, 11, 12], 8, (1, 0)),
    ([5, 6, 7, 8, 9, 10, 11, 12, 13], 8, (1, 1)),      # a short last group
    ([12, 11, 10, 9, 8, 7, 6, 5], 8, (0, 1)),          # descending
    ([5, 6, 7, 8, 10, 11, 12, 13], 4, (2, 0)),
    ([5, 6, 7, 8, 10, 11, 12, 13], 8, (0, 1)),
    ([0, 0, 0, 0], 4, (0, 1)),                         # the trash page
    ([3, 4, 5], 4, (0, 1)),
])
def test_table_groups_counts_runs_as_the_kernels_take_them(table, group, want):
    assert table_groups(table, group) == want


@pytest.mark.parametrize("sort,bounds", [(True, (80, 100)), (False, (0, 60))])
def test_sorted_tables_hold_runs_under_the_long_context_cells_churn(
        sort, bounds):
    """Why ``alloc`` sorts: the allocator under longctx-reason's shapes (32
    lanes of up to 18,432 tokens, prompts log-uniform 4,096-16,384, outputs
    512-1,536, every complete prompt block parked in the prefix cache at
    release; scripts/microbench_decode.churned_tables: the 500 turnovers
    after the first 100). Of the groups of 8 table entries that the latent
    kernels fetch, 86% name 8 adjacent blocks where a table ascends, and 54%
    as the allocator takes them (a LIFO free list, an LRU evicted block by
    block). Both fall as the pool ages (PERF.md section 7 (63))."""
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "scripts"))
    from microbench_decode import churned_tables

    handed = churned_tables(
        32, 18432, seed=1, allocator=(PrefixCachingAllocator if sort
                   else _as_taken(PrefixCachingAllocator)))
    runs, splits = map(sum, zip(*(table_groups(t, 8) for t in handed)))
    assert len(handed) == 500 and runs + splits > 30_000
    assert bounds[0] <= 100 * runs / (runs + splits) < bounds[1]


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
