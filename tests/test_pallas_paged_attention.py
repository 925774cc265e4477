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


def test_staged_cases_cross_a_stage_and_the_narrow_one_is_clamped():
    """What the cases above lean on: at their shapes a stage is 8 pages of a
    24-page table, and 2 pages of a 3-page one."""
    assert _stage_tokens(_STAGED) == 8 * 16
    assert _stage_tokens(_NARROW) == 2 * 16
    assert _stage_tokens(_SMALL) == 4 * 16


@pytest.mark.parametrize("m", [QWEN3_4B, MIXTRAL_8X7B],
                         ids=lambda m: m.name)
def test_pages_per_stage_at_the_cells_shapes(m):
    """Both cells serve --max-model-len 2048: bf16 pages of 16 tokens, 8 KV
    heads of 128, a table 128 wide. P is what the kernel's gain rests on
    (one page a step was 7-9% of the roofline), and its tiles must fit the
    budget stated beside it, well inside the 16 MiB a kernel may hold."""
    itemsize = jnp.dtype(m.dtype).itemsize
    table_width = 2048 // m.kv_block_size
    assert (m.kv_block_size, m.n_kv_heads, m.head_dim, itemsize) == (16, 8, 128, 2)
    pages = pages_per_stage(m.kv_block_size, m.n_kv_heads, m.head_dim,
                            itemsize, table_width)
    assert pages == 16
    tile = pages * m.kv_block_size * m.n_kv_heads * m.head_dim
    # K and V, two slots each, as stored; and the f32 copy of each.
    held = 4 * tile * itemsize + 2 * tile * 4
    assert held == stage_vmem_bytes(pages, m.kv_block_size, m.n_kv_heads,
                                    m.head_dim, itemsize)
    assert held <= STAGE_VMEM_BYTES <= 16 * 1024 * 1024 // 2
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
