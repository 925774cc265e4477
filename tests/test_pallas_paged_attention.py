"""Pallas paged attention (interpret mode) vs the XLA reference formulation.

Both ops take every layer's pool stacked, [L, N, block, Hkv, D], and a layer
index; the reference here is handed ONE layer's pool (``pool[layer][None]``,
layer 0), so an op that ignores the index fails.
"""

import functools

import jax
import jax.numpy as jnp
import latent_table_cases as table_cases
import numpy as np
import pytest

from llm_d_inference_scheduler_tpu.kvcache import pages
from llm_d_inference_scheduler_tpu.models import llama
from llm_d_inference_scheduler_tpu.models.configs import (
    MIXTRAL_8X7B,
    QWEN3_4B,
    ModelConfig,
)
from llm_d_inference_scheduler_tpu.ops import (apply_rope,
                                               pallas_paged_attention,
                                               rms_norm, rope_table)
from llm_d_inference_scheduler_tpu.ops.attention import paged_decode_attention
from llm_d_inference_scheduler_tpu.ops.pallas_latent_attention import (
    run_pages,
    table_runs,
)
from llm_d_inference_scheduler_tpu.ops.pallas_paged_attention import (
    STAGE_VMEM_BYTES,
    kv_window_prefill_attention,
    paged_decode_attention_pallas,
    pages_per_stage,
    stage_vmem_bytes,
)


# Heads and table of the first cases: 8 Q / 2 KV heads of 32, a table of 4
# pages, which a stage's P clamps to. Of the staged cases: the cells' KV
# heads (8 of 128), f32 pages, so P = 8 falls out of the VMEM budget, and a
# table three stages wide (or narrower than one).
_SMALL = dict(H=8, Hkv=2, D=32, maxB=4)
_STAGED = dict(H=16, Hkv=8, D=128, maxB=24)
_NARROW = dict(H=16, Hkv=8, D=128, maxB=3)


def _stage_tokens(dims, block=16):
    return block * pages_per_stage(block, dims["Hkv"], dims["D"], 4,
                                   dims["maxB"])


@pytest.mark.parametrize("dims,seq_lens_of,layer", [
    *[pytest.param(_SMALL, lambda s, spec=spec: spec, layer,
                   id=f"{name}-seq_lens_spec{i}")
      for layer, name in enumerate(["first", "middle", "last"])
      for i, spec in enumerate([[5], [17, 3], [33, 1, 16]])],
    # seq_lens count the current token: the pages hold one row fewer.
    pytest.param(_STAGED, lambda s: [s + 1], 1, id="one-whole-stage"),
    pytest.param(_STAGED, lambda s: [s + 2, s], 2,
                 id="one-row-into-the-second-stage-and-one-short-of-it"),
    pytest.param(_STAGED, lambda s: [3 * s - 5, 1, 2], 0,
                 id="three-stages-beside-an-empty-lane-and-a-one-token-lane"),
    pytest.param(_STAGED, lambda s: [2 * s + 1, s + 17, 40], 1,
                 id="last-stages-of-zero-one-and-three-pages"),
    pytest.param(_NARROW, lambda s: [48, 33, 7], 2,
                 id="stage-clamped-by-a-three-page-table"),
])
def test_pallas_matches_xla_reference(dims, seq_lens_of, layer):
    H, Hkv, D, maxB = dims["H"], dims["Hkv"], dims["D"], dims["maxB"]
    L, block = 3, 16
    seq_lens_spec = seq_lens_of(_stage_tokens(dims, block))
    assert max(seq_lens_spec) - 1 <= maxB * block
    B = len(seq_lens_spec)
    N = 1 + B * maxB
    key = jax.random.key(0)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    # Every layer's pages are their own draw.
    k_pages = jax.random.normal(ks[1], (L, N, block, Hkv, D), jnp.float32)
    v_pages = jax.random.normal(ks[2], (L, N, block, Hkv, D), jnp.float32)
    cur_k = jax.random.normal(ks[3], (B, Hkv, D), jnp.float32)
    cur_v = jax.random.normal(ks[4], (B, Hkv, D), jnp.float32)
    block_tables = jnp.arange(1, 1 + B * maxB, dtype=jnp.int32).reshape(B, maxB)
    seq_lens = jnp.array(seq_lens_spec, jnp.int32)

    ref = paged_decode_attention(q, k_pages[layer][None], v_pages[layer][None],
                                 0, block_tables, seq_lens,
                                 cur_k=cur_k, cur_v=cur_v)
    xla = paged_decode_attention(q, k_pages, v_pages, layer, block_tables,
                                 seq_lens, cur_k=cur_k, cur_v=cur_v)
    np.testing.assert_array_equal(np.asarray(xla), np.asarray(ref))
    out = paged_decode_attention_pallas(q, k_pages, v_pages, layer,
                                        block_tables, seq_lens, cur_k, cur_v,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n_kv", [4, 8])
@pytest.mark.parametrize("case", table_cases.CASES)
def test_kernel_fetches_runs_of_adjacent_pages_as_one_copy(case, n_kv,
                                                           monkeypatch):
    """The K/V walk against the gather over every kind of table
    (tests/latent_table_cases.py), 7 query heads a KV head: which groups it
    takes as one copy is what was counted by hand, the result is the
    gather's either way, and it is the same to the last bit when the same
    rows lie at shuffled pages where every group is a copy a page, as every
    page was before the runs: a copy is a copy. Rows past a lane's length
    and pages it does not own hold large values: they weigh nothing."""
    c, D = table_cases, 32
    assert c.shrink_kv_stage(monkeypatch, pallas_paged_attention, n_kv,
                             D) == c.STAGE
    group = run_pages(c.STAGE)
    tables, lens = c.tables(case, group)
    runs = np.asarray(table_runs(jnp.asarray(tables), jnp.asarray(lens),
                                 c.BLOCK, group))
    np.testing.assert_array_equal(runs, c.runs_by_hand(tables, lens, group))
    assert runs.sum(axis=1).tolist() == c.RUNS[case]
    pools = c.kv_pools_under(tables, lens, n_kv, D, seed=31)
    keys = jax.random.split(jax.random.key(31), 3)
    q = jax.random.normal(keys[0], (len(lens), 7 * n_kv, D), jnp.float32)
    cur_k, cur_v = (jax.random.normal(k, (len(lens), n_kv, D), jnp.float32)
                    for k in keys[1:])
    # (The gather reads a lane of length 0 as one that holds its own row.)
    want = paged_decode_attention(q, *pools, 1, jnp.asarray(tables),
                                  jnp.maximum(jnp.asarray(lens), 1),
                                  cur_k=cur_k, cur_v=cur_v)
    got = paged_decode_attention_pallas(
        q, *pools, 1, jnp.asarray(tables), jnp.asarray(lens), cur_k, cur_v,
        interpret=True)
    assert np.abs(np.asarray(want)).max() < 10
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    far_tables, far_pools = c.moved(tables, pools, seed=3)
    assert not np.asarray(table_runs(jnp.asarray(far_tables),
                                     jnp.asarray(lens), c.BLOCK, group)).any()
    np.testing.assert_array_equal(
        np.asarray(paged_decode_attention_pallas(
            q, *far_pools, 1, jnp.asarray(far_tables), jnp.asarray(lens),
            cur_k, cur_v, interpret=True)), np.asarray(got))


# The cells' query heads on their KV heads: SmallThinker, Qwen3-4B and
# Mixtral, Jamba (its one head kept twice), Nemotron; and four a group on two.
_CELL_HEADS = [(28, 4), (32, 8), (20, 2), (32, 2), (8, 2)]
_BF16_STAGE = 4          # pages a stage: 64 rows
_BF16_WINDOW = 70


@pytest.mark.parametrize("window", [0, _BF16_WINDOW], ids=["full", "window"])
@pytest.mark.parametrize("heads,n_kv", _CELL_HEADS,
                         ids=[f"{h}on{g}" for h, g in _CELL_HEADS])
def test_walks_over_bf16_pools_are_banded_attentions_arithmetic(
        heads, n_kv, window, monkeypatch):
    """Both walks as a chip runs them: bf16 pools, heads of 128, a KV head's
    rows against its own query heads, the products in the pool's dtype with
    f32 accumulation and the probabilities rounded for the second. The
    reference is ops/attention.banded_attention (whose text states that
    arithmetic) over the rows ops/attention's own table forms gather, the
    softmax's sums in another order. Stages of 4 pages under a table of 32:
    a lane with no cached page, lanes that end inside a first and inside a
    later stage, one that ends on a stage's edge; of a window of 70 rows,
    one that starts two rows into the walk's first stage and one whose walk
    starts 117 rows before it, a whole masked stage and most of the next.
    Rows past a lane's length and pages nobody owns hold large values."""
    from llm_d_inference_scheduler_tpu.ops.attention import (banded_attention,
                                                             window_table)
    from llm_d_inference_scheduler_tpu.ops.pallas_paged_attention import (
        swa_paged_decode_attention_kernel)

    block, D, maxB, L, layer = 16, 128, 32, 2, 1
    monkeypatch.setattr(
        pallas_paged_attention, "STAGE_VMEM_BYTES",
        _BF16_STAGE * stage_vmem_bytes(1, block, n_kv, D, 2))
    lens = [1, 30, 315, 200] if window else [1, 100, 129, 250]
    B = len(lens)
    rng = np.random.default_rng([heads, n_kv, window])
    tables = 1 + rng.permutation(B * maxB).reshape(B, maxB).astype(np.int32)
    pools = []
    for _ in range(2):
        pool = np.full((L, 1 + B * maxB, block, n_kv, D), 1e4, np.float32)
        for lane, n in enumerate(lens):
            rows = np.full((maxB * block, n_kv, D), 1e4, np.float32)
            rows[:n - 1] = rng.standard_normal((n - 1, n_kv, D))
            pool[layer, tables[lane]] = rows.reshape(maxB, block, n_kv, D)
        pools.append(jnp.asarray(pool, jnp.bfloat16))
    q = jnp.asarray(rng.standard_normal((B, heads, D)), jnp.bfloat16)
    cur_k, cur_v = (jnp.asarray(rng.standard_normal((B, n_kv, D)),
                                jnp.bfloat16) for _ in range(2))
    tables, seq_lens = jnp.asarray(tables), jnp.asarray(lens, jnp.int32)

    # (P is read where the wrapper is traced: no trace of another budget.)
    jitted, more = ((swa_paged_decode_attention_kernel, dict(window=window))
                    if window else (paged_decode_attention_pallas, {}))
    jitted.clear_cache()
    got = jitted(q, *pools, layer, tables, seq_lens, cur_k, cur_v,
                 interpret=True, **more)
    assert got.shape == q.shape and got.dtype == q.dtype

    # The rows a lane's table names (a window's: those in reach), then its
    # own; which of them it sees.
    if window:
        at, upto, skip = window_table(tables, seq_lens, block, window)
        assert np.asarray(skip).tolist() == [0, 0, 5, 2]
    else:
        at, upto, skip = tables, seq_lens, jnp.zeros((B,), jnp.int32)
    col = jnp.arange(at.shape[1] * block)[None, :]
    seen = jnp.concatenate(
        [(col >= skip[:, None]) & (col < (upto - 1)[:, None]),
         jnp.ones((B, 1), bool)], axis=1)
    k, v = (jnp.concatenate(
        [pool[layer, at].reshape(B, -1, n_kv, D), cur[:, None]], axis=1)
        for pool, cur in zip(pools, (cur_k, cur_v)))
    nowhere = jnp.zeros(seen.shape, jnp.int32)
    want = banded_attention(q[:, None], k, v, q_positions=nowhere[:, :1],
                            kv_positions=nowhere, kv_valid=seen)[:, 0]
    want = np.asarray(want, np.float32)
    assert np.abs(want).max() < 10
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2e-2, atol=2e-2)
    # The lane with no cached page sees its own token alone.
    np.testing.assert_array_equal(
        np.asarray(got[0], np.float32),
        np.repeat(np.asarray(cur_v[0], np.float32), heads // n_kv, axis=0))
    jitted.clear_cache()


def test_a_bf16_pool_of_one_kv_head_is_refused_where_the_kernel_is_traced():
    """A 16-bit pool's heads leave a page two at a time (a 32-bit word holds
    a row of each): an odd number of them has no such pairs, and the trace
    says so with the tile's shape. (models/hybrid.py keeps a lone head
    twice, ``ModelConfig.kv_heads_kept``; an f32 pool takes any number.)"""
    B, H, D, block, maxB = 2, 4, 128, 16, 2
    q = jnp.ones((B, H, D), jnp.bfloat16)
    tables = jnp.arange(1, 1 + B * maxB, dtype=jnp.int32).reshape(B, maxB)
    lens = jnp.array([20, 3], jnp.int32)

    def walk(n_kv, dtype):
        pool = jnp.ones((1, 1 + B * maxB, block, n_kv, D), dtype)
        cur = jnp.ones((B, n_kv, D), dtype)
        return paged_decode_attention_pallas(q, pool, pool, 0, tables, lens,
                                             cur, cur, interpret=True)

    with pytest.raises(AssertionError, match=r"pairs.*\(2, 2, 16, 1, 128\)"):
        walk(1, jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(walk(1, jnp.float32), np.float32),
                               1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(walk(2, jnp.bfloat16), np.float32),
                               1.0, rtol=1e-2)


def test_staged_cases_cross_a_stage_and_the_narrow_one_is_clamped():
    """What the cases above lean on: at their shapes a stage is 8 pages of a
    24-page table, and 2 pages of a 3-page one. (P is what it was while an
    f32 copy of each tile was made and counted: an f32 pool's rows laid out
    a KV head apart take the bytes those copies took.)"""
    assert _stage_tokens(_STAGED) == 8 * 16
    assert _stage_tokens(_NARROW) == 2 * 16
    assert _stage_tokens(_SMALL) == 4 * 16


@pytest.mark.parametrize("m", [QWEN3_4B, MIXTRAL_8X7B],
                         ids=lambda m: m.name)
def test_pages_per_stage_at_the_cells_shapes(m):
    """Both cells serve --max-model-len 2048: bf16 pages of 16 tokens, 8 KV
    heads of 128, a table 128 wide. P is what the kernel's gain rests on
    (one page a step was 7-9% of the roofline), and its tiles must fit the
    budget stated beside it, well inside the 16 MiB a kernel may hold. P is
    16 here, 32 at SmallThinker's 4 KV heads and 64 at the 2 of Nemotron's
    and Jamba's pools, as before the kernel stopped making an f32 copy of
    each tile: what a stage holds besides its tiles is now its rows a KV
    head apart in the pool's dtype, and a stage twice as long read slower
    on the chip at every head count (PERF.md section 6, PR 53)."""
    itemsize = jnp.dtype(m.dtype).itemsize
    table_width = 2048 // m.kv_block_size
    assert (m.kv_block_size, m.n_kv_heads, m.head_dim, itemsize) == (16, 8, 128, 2)
    pages = pages_per_stage(m.kv_block_size, m.n_kv_heads, m.head_dim,
                            itemsize, table_width)
    assert pages == 16
    tile = pages * m.kv_block_size * m.n_kv_heads * m.head_dim
    # K and V, two slots each, and the computed stage's rows of each a KV
    # head apart, all as stored: nothing of a tile's size is f32.
    held = 6 * tile * itemsize
    assert held == stage_vmem_bytes(pages, m.kv_block_size, m.n_kv_heads,
                                    m.head_dim, itemsize)
    assert held <= STAGE_VMEM_BYTES <= 16 * 1024 * 1024 // 2
    assert [pages_per_stage(16, n_kv, 128, 2, 1024) for n_kv in (4, 2)] == [
        32, 64]
    # A stage twice as long would not fit; a narrower table clamps it.
    assert stage_vmem_bytes(2 * pages, m.kv_block_size, m.n_kv_heads,
                            m.head_dim, itemsize) > STAGE_VMEM_BYTES
    assert pages_per_stage(m.kv_block_size, m.n_kv_heads, m.head_dim,
                           itemsize, 5) == 4


def test_pallas_trash_block_slots_isolated():
    """Padding slots (seq_len=1, table all trash) only see their cur_k column."""
    B, H, Hkv, D, block, maxB = 2, 4, 2, 32, 16, 2
    N = 1 + B * maxB
    key = jax.random.key(1)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k_pages = jax.random.normal(ks[1], (1, N, block, Hkv, D), jnp.float32)
    v_pages = jax.random.normal(ks[2], (1, N, block, Hkv, D), jnp.float32)
    cur_k = jax.random.normal(ks[3], (B, Hkv, D), jnp.float32)
    cur_v = jax.random.normal(ks[4], (B, Hkv, D), jnp.float32)
    block_tables = jnp.array([[1, 2], [0, 0]], jnp.int32)  # row 1: trash
    seq_lens = jnp.array([20, 1], jnp.int32)

    out = paged_decode_attention_pallas(q, k_pages, v_pages, 0, block_tables,
                                        seq_lens, cur_k, cur_v, interpret=True)
    # Row 1 attends only to its own token -> output == cur_v broadcast per group
    expect = jnp.repeat(cur_v[1], H // Hkv, axis=0)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def _decode_step_by_layer_loop(params, cfg, tokens, positions, k_pages,
                               v_pages, block_tables, attend):
    """decode_step as it was before the pools stayed stacked: a Python loop
    over the layers that slices ``pool[l]`` out and hands attention that one
    layer's pool."""
    B = tokens.shape[0]
    block = k_pages.shape[2]
    Dh = cfg.head_dim
    cos, sin = rope_table(positions, Dh, cfg.rope_theta)
    seq_lens = positions + 1
    blk_idx = block_tables[jnp.arange(B), positions // block]
    slot = positions % block
    x = params["embed"][tokens]
    k_cur, v_cur = [], []
    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        h = rms_norm(x, lp["ln_attn"], cfg.norm_eps)
        q = (h @ lp["wq"]).reshape(B, cfg.n_heads, Dh)
        k = (h @ lp["wk"]).reshape(B, cfg.n_kv_heads, Dh)
        v = (h @ lp["wv"]).reshape(B, cfg.n_kv_heads, Dh)
        q, k = llama.qk_normed(cfg, lp, q, k)
        q = apply_rope(q[:, None], cos[:, None], sin[:, None])[:, 0]
        k = apply_rope(k[:, None], cos[:, None], sin[:, None])[:, 0]
        attn = attend(q, k_pages[l][None], v_pages[l][None], 0, block_tables,
                      seq_lens, k, v)
        x = x + attn.reshape(B, -1) @ lp["wo"]
        h = rms_norm(x, lp["ln_mlp"], cfg.norm_eps)
        x = x + llama._ffn(cfg, lp, h)
        k_cur.append(k)
        v_cur.append(v)
    k_pages = k_pages.at[:, blk_idx, slot].set(jnp.stack(k_cur).astype(k_pages.dtype))
    v_pages = v_pages.at[:, blk_idx, slot].set(jnp.stack(v_cur).astype(v_pages.dtype))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return (x @ params["lm_head"]).astype(jnp.float32), k_pages, v_pages


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_decode_step_reads_each_layers_own_pages(use_pallas):
    """The stacked-pool scan against the per-layer loop, every layer's pages
    filled differently: same logits, same pages back."""
    cfg = ModelConfig(name="t", vocab_size=64, d_model=64, n_layers=3,
                      n_heads=4, n_kv_heads=2, head_dim_override=32, d_ff=128,
                      dtype="float32", kv_block_size=16, qk_norm=True)
    B, maxB = 3, 3
    N = 1 + B * maxB
    ks = jax.random.split(jax.random.key(2), 3)
    params = llama.init_params(cfg, ks[0])
    shape = (cfg.n_layers, N, cfg.kv_block_size, cfg.n_kv_heads, cfg.head_dim)
    k_pages = jax.random.normal(ks[1], shape, jnp.float32)
    v_pages = jax.random.normal(ks[2], shape, jnp.float32)
    block_tables = jnp.arange(1, N, dtype=jnp.int32).reshape(B, maxB)
    tokens = jnp.array([3, 17, 42], jnp.int32)
    positions = jnp.array([40, 0, 15], jnp.int32)  # 3 pages, none, 1 page

    # Both ops take (q, k_pages, v_pages, layer, tables, seq_lens, cur_k, cur_v).
    attend = (functools.partial(paged_decode_attention_pallas, interpret=True)
              if use_pallas else paged_decode_attention)
    want = _decode_step_by_layer_loop(params, cfg, tokens, positions, k_pages,
                                      v_pages, block_tables, attend)
    got = llama.decode_step(
        params, cfg, tokens, positions, k_pages, v_pages, block_tables,
        attention_fn=functools.partial(pages.decode_attention,
                                       kernel=use_pallas, interpret=True))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)
    # The step wrote one row a layer and lane, and nothing else.
    assert int((np.asarray(got[1]) != np.asarray(k_pages)).any(axis=(3, 4)).sum()) \
        == cfg.n_layers * B


def test_engine_pallas_branch_matches_default():
    """The engine's kernel decode branch (interpreted) generates the same
    greedy tokens as the XLA path."""
    import asyncio
    from llm_d_inference_scheduler_tpu.engine import EngineConfig, EngineRequest
    from llm_d_inference_scheduler_tpu.engine.core import TpuEngine

    async def gen(cfg):
        eng = TpuEngine(cfg)
        await eng.start()
        try:
            out = eng.submit(EngineRequest(request_id="r",
                                           prompt_token_ids=[1, 7, 8, 9] * 3,
                                           max_tokens=5, stop_token_ids=(-1,)))
            toks = []
            while True:
                ev = await asyncio.wait_for(out.get(), timeout=60)
                if ev.token_id is not None:
                    toks.append(ev.token_id)
                if ev.finish_reason is not None:
                    return toks
        finally:
            await eng.stop()

    base = dict(model="tiny", backend="tpu", max_batch=2, max_model_len=128)
    t_default = asyncio.run(gen(EngineConfig(**base)))
    t_pallas = asyncio.run(gen(EngineConfig(**base, pallas_attention=True,
                                            pallas_interpret=True)))
    assert t_pallas == t_default


# ---------- a continuation window's queries over the pages before it ----------

# 7 query heads a KV head, pages of 16 rows, stages of 4 pages (so a table
# holds several), 96 queries in tiles of 32: three programs, whose bands
# start in different stages.
_PF = dict(H=14, Hkv=2, D=32, block=16, S=96, stage=4, tile=32, width=64,
           n_blocks=200, layers=3, layer=1)
_BAND = 150                   # a window layer's: it starts inside a page
_STAGE_ROWS = _PF["stage"] * _PF["block"]


def _prefill_case(kind, prefix_len, suffix_len, table_kind, dtype, seed=0):
    """The operands of ``pages.prefill_attention`` for one continuation
    window of a layer of ``kind``: (q, k_new, v_new, pools, near_pools,
    is_near, layer, table [1, W], near_table [1, 10], near_first_pos [1],
    prefix_len [1], suffix_len [1]). BOTH pool pairs hold random rows at the
    pages the sequence's table names up to ``prefix_len`` in the layer that
    is read, each its own, and large values everywhere else (the other
    layers, the rest of the last page, pages nobody owns, the trash page),
    in float32; ``dtype`` is cast last."""
    p = _PF
    rng = np.random.default_rng([seed, prefix_len])
    n_pages = -(-prefix_len // p["block"])
    logical = {"ascending": 5 + np.arange(p["width"]),
               "shuffled": 1 + rng.permutation(p["n_blocks"] - 1)[:p["width"]],
               }.get(table_kind.split("+")[0])
    # Entries past the prompt's pages: the engine's table holds the trash
    # block there; "own" leaves pages the sequence will write next.
    table_row = np.where(np.arange(p["width"]) < n_pages, logical,
                         logical if table_kind.endswith("+own") else 0)
    pools = []
    for i in range(4):       # K and V of the one kind, K and V of the other
        pool = np.full((p["layers"], p["n_blocks"], p["block"], p["Hkv"],
                        p["D"]), 1e4, np.float32)
        rows = rng.standard_normal((n_pages * p["block"], p["Hkv"], p["D"]),
                                   np.float32)
        rows[prefix_len:] = 1e4
        pool[p["layer"], table_row[:n_pages]] = rows.reshape(
            n_pages, p["block"], p["Hkv"], p["D"])
        pools.append(jnp.asarray(pool, dtype))
    keys = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(keys[0], (1, p["S"], p["H"], p["D"]), dtype)
    k_new, v_new = (jax.random.normal(k, (1, p["S"], p["Hkv"], p["D"]), dtype)
                    for k in keys[1:])
    prefix = jnp.asarray([prefix_len], jnp.int32)
    table = jnp.asarray(table_row[None], jnp.int32)
    near_table, pos = pages.window_prefix_pages(table, prefix, p["block"],
                                                _BAND)
    return (q, k_new, v_new, tuple(pools[:2]), tuple(pools[2:]),
            jnp.asarray(kind == "window"), jnp.int32(p["layer"]), table,
            near_table, pos[:, 0], prefix,
            jnp.asarray([suffix_len], jnp.int32))


def _attend(case, impl):
    return pages.prefill_attention(*case, window=_BAND, impl=impl)


@pytest.fixture
def shrunk_prefill(monkeypatch):
    """Stages of ``_PF['stage']`` pages and tiles of ``_PF['tile']`` queries
    (read where the wrapper is traced: its cache is emptied around them)."""
    p = _PF
    monkeypatch.setattr(
        pallas_paged_attention, "STAGE_VMEM_BYTES",
        p["stage"] * stage_vmem_bytes(1, p["block"], p["Hkv"], p["D"], 4))
    monkeypatch.setattr(pallas_paged_attention, "QUERY_TILE", p["tile"])
    kv_window_prefill_attention.clear_cache()
    yield
    kv_window_prefill_attention.clear_cache()


@pytest.mark.parametrize("kind, prefix_len, suffix_len, table_kind, dtype", [
    # The prefix: one row, one page, short of a stage's multiple by a page,
    # whole stages, one page past them; both kinds of layer.
    *[(kind, n, _PF["S"], "shuffled", "float32")
      for kind in ("full", "window")
      for n in (1, 16, 1008, 4 * _STAGE_ROWS, 4 * _STAGE_ROWS + 16)],
    # A prefix that ends inside a page; a window shorter than the tile, and
    # one whose last tile is all padding.
    ("full", 1003, 96, "shuffled", "float32"),
    ("full", 1008, 70, "shuffled", "float32"),
    ("window", 1008, 33, "shuffled", "float32"),
    ("window", 16, 5, "ascending", "float32"),
    # An ascending table (every whole group a run), one that holds pages of
    # the sequence's own past the prefix, one the trash block there.
    ("full", 1008, 96, "ascending", "float32"),
    ("full", 528, 96, "ascending+own", "float32"),
    ("window", 1008, 96, "ascending", "float32"),
    ("window", 272, 96, "ascending+own", "float32"),
    ("full", 272, 96, "shuffled+own", "float32"),
    # The pools as a chip holds them.
    ("full", 1008, 96, "shuffled", "bfloat16"),
    ("full", 528, 80, "ascending", "bfloat16"),
    ("window", 1008, 96, "ascending", "bfloat16"),
    ("window", 16, 96, "shuffled", "bfloat16"),
])
def test_window_prefill_kernel_is_banded_attention_over_the_gathered_rows(
        kind, prefix_len, suffix_len, table_kind, dtype, shrunk_prefill):
    """ONE kernel for the layers of both kinds, which walks the pages,
    against the plain form, which gathers the kind's table's rows whole and
    bands them in XLA: the same to rounding for every real query (the padded
    ones are nobody's), with large values wherever the walk must not look
    (the other kind's pools hold other rows at the same pages)."""
    case = _prefill_case(kind, prefix_len, suffix_len, table_kind,
                         jnp.dtype(dtype))
    q, table, near_table, near_first = case[0], *case[7:10]
    assert table.shape[1] == _PF["width"]
    # A window layer's pages end where the window starts; its band starts
    # inside one (150 rows back from a multiple of 16).
    assert near_table.shape[1] == -(-(_BAND - 1) // _PF["block"]) == 10
    assert (_BAND - 1) % _PF["block"]
    if kind == "full" and table_kind.startswith("ascending"):
        runs = np.asarray(table_runs(table, case[10] + 1, _PF["block"],
                                     run_pages(_PF["stage"])))
        assert runs.sum() == -(-prefix_len // _PF["block"]) // 4 > 0
    want = _attend(case, "xla")
    got = _attend(case, "kernel_interpret")
    assert got.shape == q.shape and got.dtype == q.dtype
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == "float32" else dict(
        rtol=2e-2, atol=2e-2)
    want = np.asarray(want, np.float32)[0, :suffix_len]
    assert np.abs(want).max() < 10
    np.testing.assert_allclose(np.asarray(got, np.float32)[0, :suffix_len],
                               want, **tol)
    # The other kind of layer over the same operands reads the other pools.
    flipped = (*case[:5], jnp.logical_not(case[5]), *case[6:])
    assert np.abs(np.asarray(_attend(flipped, "kernel_interpret"), np.float32)
                  [0, :suffix_len] - want).max() > 1e-2


@pytest.mark.parametrize("kind", ["full", "window"])
def test_window_prefill_kernel_reads_what_the_prompt_holds_not_the_bucket(
        kind, shrunk_prefill):
    """Pages a table names past ``prefix_len`` are never fetched, nor is
    anything of the other kind's pools: filled with NaN (where the plain
    form's 0 x NaN spoils every query), the kernel's output is what it was,
    to the last bit."""
    case = _prefill_case(kind, 272, _PF["S"], "ascending+own",
                         jnp.dtype("float32"))
    pools, near_pools, table = case[3], case[4], case[7]
    # The sequence's own pages past the prefix, and the trash page (which
    # ``window_prefix_pages`` names there).
    past = np.concatenate([np.asarray(table)[0, 272 // 16:], [0]])
    assert len(past) > 1 and (past < _PF["n_blocks"]).all()
    mine, other = (near_pools, pools) if kind == "window" else (pools,
                                                               near_pools)
    spoiled = dict(
        mine=[pool.at[:, past].set(jnp.nan) for pool in mine],
        other=[jnp.full_like(pool, jnp.nan) for pool in other])
    if kind == "window":
        spoiled = (tuple(spoiled["other"]), tuple(spoiled["mine"]))
    else:
        spoiled = (tuple(spoiled["mine"]), tuple(spoiled["other"]))
    bad = (*case[:3], *spoiled, *case[5:])
    got = np.asarray(_attend(bad, "kernel_interpret"))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(
        got, np.asarray(_attend(case, "kernel_interpret")))
    if kind == "full":
        assert np.isnan(np.asarray(_attend(bad, "xla"))).all()
